"""Checkpoint/resume for the DP trainer (SURVEY.md §6 "Checkpoint / resume").

The reference keeps no checkpoint state of its own — allreduce rounds are
stateless beyond the round window, and model save/load lives in its BIDMach
dependency. For capability parity of "resume after dropout" (BASELINE.json
config 5) the TPU build provides the trainer-layer equivalent: Orbax
checkpoints of ``{params, opt_state, step}``, plus a zero-copy in-memory
snapshot used by the elastic re-mesh path (SURVEY.md §8.4 — "checkpoint-in-HBM
→ reinit mesh → resume").

``orbax.checkpoint`` is NOT imported with this module: it is 12-13 s of a
process start on the chip's host (11-12 of them ``google.cloud.logging``;
PERF.md section 6, PR 38) that only :class:`TrainerCheckpointer` and its
async subclass use, and every trainer enters through
``akka_allreduce_tpu.train``. :func:`_orbax` loads it, once a
process, when the first of them is CONSTRUCTED — not at the first ``save``,
which must not stall by those seconds and whose writer thread must find the
library loaded. The load runs under the span ``checkpoint.import_orbax`` and
leaves its seconds in the gauge ``checkpoint.orbax_import_s`` (absent in a
process that built no Orbax checkpointer). The delta store, ``Snapshot`` and
the capture / placement helpers never touch it.
"""

from __future__ import annotations

import dataclasses
import threading
from pathlib import Path
from typing import Any

import jax
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.obs import metrics as obs_metrics
from akka_allreduce_tpu.obs import trace as obs_trace

_ocp = None
_ocp_lock = threading.Lock()


def _orbax():
    """``orbax.checkpoint``, imported by the first call of a process (module
    docstring)."""
    global _ocp
    with _ocp_lock:
        if _ocp is None:
            with obs_trace.span("checkpoint.import_orbax") as s:
                import orbax.checkpoint as ocp
            obs_metrics.gauge("checkpoint.orbax_import_s").set(s.dur)
            _ocp = ocp
    return _ocp


def state_shardings(trainer) -> tuple[Any, Any]:
    """(param_shardings, opt_shardings) for placing restored state.

    Sharded trainers (TP / EP / PP — anything exposing ``_param_specs`` /
    ``_opt_specs`` PartitionSpec trees) get per-leaf NamedShardings over
    their CURRENT mesh; plain DP trainers fall back to the replicated
    sharding. Either way, restore works across a re-mesh: leaves are placed
    fresh onto whatever mesh the trainer has now.
    """
    mesh = getattr(trainer, "mesh", None)
    is_spec = lambda x: isinstance(x, P)  # noqa: E731

    def tree_of(specs):
        return jax.tree.map(
            lambda s: NamedSharding(mesh, s), specs, is_leaf=is_spec
        )

    pspecs = getattr(trainer, "_param_specs", None)
    ospecs = getattr(trainer, "_opt_specs", None)
    p_sh = (
        tree_of(pspecs)
        if mesh is not None and pspecs is not None
        else trainer._replicated
    )
    o_sh = (
        tree_of(ospecs)
        if mesh is not None and ospecs is not None
        else trainer._replicated
    )
    return p_sh, o_sh


def place_on(tree, sharding) -> Any:
    """Device-put every array leaf of ``tree`` onto ``sharding`` (a single
    sharding for all leaves, or a matching tree of per-leaf shardings).

    jax.Array leaves reshard on device (a no-op when already placed — the
    Orbax restore target usually carries the right sharding, so no
    full-model host round trip); numpy leaves (Snapshot data) upload.
    """

    def put(x, s):
        if isinstance(x, (jax.Array, np.ndarray)):
            return jax.device_put(x, s)
        return x

    if isinstance(sharding, jax.sharding.Sharding):
        return jax.tree.map(lambda x: put(x, sharding), tree)
    return jax.tree.map(put, tree, sharding)


def _restore_ef(trainer, ef) -> None:
    """Place a restored error-feedback residual, redistributing across a
    re-mesh: the residual is per-device withheld gradient mass, so when the
    device count changed we preserve its SUM (what the collective is still
    owed) by splitting it evenly over the new devices."""
    ef = np.asarray(ef, np.float32)
    n = trainer.n_devices
    if ef.shape[0] != n:
        ef = np.tile(ef.sum(axis=0, keepdims=True) / n, (n, 1))
    trainer._ef = jax.device_put(ef, trainer._data_sharding)


def capture_state(trainer) -> tuple[dict, bool]:
    """``(state, custom)``: the serializable state tree of ``trainer`` under
    either checkpoint protocol — the ONE place that knows what trainer
    state consists of. ``custom=True`` means the tree came from the
    trainer-defined ``checkpoint_state()`` (ZeRO-1 / FSDP / Pipeline, host
    numpy, mesh-size-independent); otherwise it is the live
    ``{params, opt_state[, ef]}`` device pytree. Every checkpoint path
    (sync / async / delta / Snapshot) captures through here, so a new
    piece of trainer state is added exactly once."""
    if hasattr(trainer, "checkpoint_state"):
        return dict(trainer.checkpoint_state()), True
    state = {"params": trainer.params, "opt_state": trainer.opt_state}
    if getattr(trainer, "_ef", None) is not None:
        # error-feedback residual is training state: dropping it on
        # restart would permanently lose every withheld gradient
        state["ef"] = trainer._ef
    return state, False


_POD_MESH_MSG = (
    "DeltaCheckpointer is a per-host store; state sharded over a mesh "
    "that spans OS processes cannot be host-gathered here — use "
    "TrainerCheckpointer (Orbax coordinates cross-process saves) on pod "
    "meshes"
)


def _fully_addressable(tree) -> bool:
    """True when every jax.Array leaf is visible to THIS process — the
    precondition for host-side capture without Orbax's cross-process
    coordination."""
    return all(
        x.is_fully_addressable
        for x in jax.tree.leaves(tree)
        if isinstance(x, jax.Array)
    )


def _copy_tree_async(tree):
    """Donation-proof on-device copy with device-to-host transfers
    launched: new buffers with the same shardings, so the training loop's
    donated originals can die while the copy's transfer is still in
    flight. The background writer's ``np.asarray`` then merely joins the
    transfer instead of starting it."""
    import jax.numpy as jnp

    def copy_leaf(x):
        if isinstance(x, jax.Array):
            y = jnp.copy(x)
            y.copy_to_host_async()
            return y
        return x

    return jax.tree.map(copy_leaf, tree)


def async_capture(trainer):
    """``(captured, assemble, custom)`` for the non-stalling checkpoint
    paths, or ``None`` when the state is not fully addressable from this
    process (pod meshes — the Orbax caller falls back to its
    multihost-aware synchronous save; the per-host delta store raises).

    Trainers exposing the shard-local protocol
    (``checkpoint_capture``/``checkpoint_assemble`` — ZeRO-1, FSDP,
    Pipeline) capture as on-device copies of their OWN shards, no gather
    (VERDICT r4 #1); ``assemble`` converts the host tree into the
    serialized form on the writer thread. Pytree-state trainers capture
    ``{params, opt_state[, ef]}`` the same way with ``assemble=None``
    (the host tree IS the serialized form). Custom-protocol trainers
    WITHOUT the shard-local seam pay a synchronous ``checkpoint_state()``
    gather here and hand the host tree to the writer. ``custom`` mirrors
    :func:`capture_state`'s flag (the delta manifest records it)."""
    if hasattr(trainer, "checkpoint_capture"):
        live = dict(trainer.checkpoint_capture())
        if not _fully_addressable(live):
            return None
        return _copy_tree_async(live), trainer.checkpoint_assemble, True
    state, custom = capture_state(trainer)
    if custom:
        # the gather inside checkpoint_state was the synchronous part;
        # the tree is already host numpy
        return state, None, True
    if not _fully_addressable(state):
        return None
    return _copy_tree_async(state), None, False


class _BackgroundWriter:
    """One-save-in-flight background machinery shared by the async
    checkpointers. Subclasses call :meth:`_writer_init` in ``__init__``
    and :meth:`_launch` with the write closure; a background failure is
    re-raised on the next ``busy``/``save``/``restore``/``close``."""

    def _writer_init(self) -> None:
        self._lock = threading.Lock()  # serializes store access
        self._inflight: threading.Thread | None = None
        self._errors: list = []

    def _launch(self, write, name: str) -> None:
        def guarded():
            try:
                write()
            except Exception as e:  # surfaced on the next save/drain
                self._errors.append(e)

        t = threading.Thread(target=guarded, name=name, daemon=True)
        self._inflight = t
        t.start()

    def _drain(self) -> None:
        t = self._inflight
        if t is not None:
            t.join()
            self._inflight = None
        if self._errors:
            err = self._errors[:]
            self._errors.clear()
            raise RuntimeError(f"background checkpoint save failed: {err[0]}")

    def busy(self) -> bool:
        t = self._inflight
        if t is not None and not t.is_alive():
            self._drain()  # reap + surface any background error
        return self._inflight is not None

    def wait_until_finished(self) -> None:
        """Block until the in-flight save (if any) is durable; re-raise a
        background failure."""
        self._drain()


@dataclasses.dataclass
class Snapshot:
    """In-memory (host RAM) snapshot of trainer state for fast re-mesh resume.

    Held as numpy so it survives the death of the device mesh it came from:
    during elastic reconfiguration the old mesh's devices may be gone by the
    time we restore.

    Trainers with the trainer-defined checkpoint protocol
    (``checkpoint_state``/``restore_checkpoint_state`` — ZeRO-1, FSDP)
    snapshot through it: their serialized form is mesh-size-INDEPENDENT, so
    the same snapshot restores onto a mesh with a different device count —
    exactly what the elastic re-mesh needs (VERDICT r3 #3). Pytree-state
    trainers use the params/opt_state capture as before.
    """

    params: Any  # pytree of np.ndarray (pytree-state trainers)
    opt_state: Any  # pytree of np.ndarray / leaves
    step: int
    ef: Any = None  # error-feedback residual (n_devices, params) or None
    custom: dict | None = None  # trainer-defined checkpoint_state() payload

    @classmethod
    def capture(cls, trainer) -> "Snapshot":
        state, custom = capture_state(trainer)
        host = jax.tree.map(np.asarray, state)
        if custom:
            return cls(
                params=None,
                opt_state=None,
                step=trainer.step_num,
                custom=host,
            )
        return cls(
            params=host["params"],
            opt_state=host["opt_state"],
            step=trainer.step_num,
            ef=host.get("ef"),
        )

    def restore_into(self, trainer) -> None:
        """Place this snapshot into ``trainer``, honoring its sharding layout
        (replicated for plain DP; per-leaf specs for TP/EP/PP trainers;
        the trainer-defined reshard for ZeRO-1/FSDP)."""
        if self.custom is not None:
            if not hasattr(trainer, "restore_checkpoint_state"):
                raise TypeError(
                    "snapshot was captured through a trainer-defined "
                    "checkpoint protocol; the restore target has none"
                )
            # restore may mutate the dict (zero1 pops format_version) and
            # the snapshot may be restored more than once — hand over a
            # shallow copy
            trainer.restore_checkpoint_state(dict(self.custom))
            trainer.step_num = self.step
            return
        p_sh, o_sh = state_shardings(trainer)
        trainer.params = place_on(self.params, p_sh)
        trainer.opt_state = place_on(self.opt_state, o_sh)
        trainer.step_num = self.step
        if getattr(trainer, "_ef", None) is not None:
            if self.ef is not None:
                _restore_ef(trainer, self.ef)
            else:
                # snapshot carries no residual: a stale live one would
                # re-inject the PRE-restore trajectory's withheld mass
                # (ADVICE r4) — zero it so restore fully determines state
                _restore_ef(
                    trainer, np.zeros(trainer._ef.shape, np.float32)
                )


class TrainerCheckpointer:
    """Durable on-disk checkpoints of trainer state via Orbax.

    Usage::

        ckpt = TrainerCheckpointer(dir)
        ckpt.save(trainer)                  # every k steps
        step = ckpt.restore(trainer)        # after restart / re-mesh
    """

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3) -> None:
        self.directory = Path(directory).absolute()
        ocp = _orbax()
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=max_to_keep, create=True
            ),
        )

    def save(self, trainer, *, force: bool = False, block: bool = True) -> bool:
        # ``block`` exists for signature parity with the async subclass —
        # this save is synchronous regardless
        if trainer.step_num in self._mgr.all_steps():
            return False  # this step is already durable; nothing to do
        state, _ = capture_state(trainer)
        state["step"] = trainer.step_num
        saved = self._mgr.save(
            trainer.step_num,
            args=_orbax().args.StandardSave(state),
            force=force,
        )
        self._mgr.wait_until_finished()
        return bool(saved)

    def latest_step(self) -> int | None:
        return self._mgr.latest_step()

    def _saved_top_keys(self, step: int) -> set | None:
        """Top-level keys of the saved tree (None if metadata unavailable).
        Lets restore reconcile OPTIONAL template keys with what the
        checkpoint actually carries (ADVICE r2: an EF/non-EF or version-key
        difference must not surface as a generic Orbax tree mismatch)."""
        try:
            return set(self._mgr.item_metadata(step).keys())
        except Exception:
            return None

    def restore(self, trainer, step: int | None = None) -> int:
        """Restore trainer state in place; returns the restored step number."""
        step = self.latest_step() if step is None else step
        if step is None:
            raise FileNotFoundError(f"no checkpoint under {self.directory}")
        if hasattr(trainer, "checkpoint_state"):
            # prefer the abstract template (shape/dtype only): building the
            # target must not gather the throwaway fresh state to host
            template_fn = getattr(
                trainer, "checkpoint_template", trainer.checkpoint_state
            )
            target = dict(template_fn())
            target["step"] = trainer.step_num
            saved = self._saved_top_keys(step)
            optional = getattr(trainer, "checkpoint_optional_keys", frozenset())
            if saved is not None:
                for k in optional:
                    # keys newer builds always write (format_version, the
                    # always-present ef_sum) may be absent from older
                    # checkpoints; drop them from the target rather than
                    # fail the whole restore on tree structure
                    if k in target and k not in saved:
                        target.pop(k)
            try:
                restored = self._mgr.restore(
                    step, args=_orbax().args.StandardRestore(target)
                )
            except Exception as e:
                if (
                    "format_version" in optional
                    and saved is not None
                    and "format_version" not in saved
                ):
                    raise ValueError(
                        f"checkpoint step {step} under {self.directory} "
                        "predates this trainer's serialized format (no "
                        "format_version key — e.g. the round-1 padded "
                        "per-mesh ZeRO-1 layout) and cannot be loaded; "
                        "re-save it from the build that wrote it"
                    ) from e
                raise
            trainer.step_num = int(restored.pop("step"))
            trainer.restore_checkpoint_state(restored)
            return trainer.step_num
        # Use the trainer's live state as the abstract target so leaves come
        # back with the right dtypes/shardings for its current mesh.
        target = {
            "params": trainer.params,
            "opt_state": trainer.opt_state,
            "step": trainer.step_num,
        }
        has_ef = getattr(trainer, "_ef", None) is not None
        if has_ef:
            target["ef"] = trainer._ef
        restored = self._mgr.restore(
            step, args=_orbax().args.StandardRestore(target)
        )
        # Orbax may hand back single-device arrays; re-place onto the
        # trainer's CURRENT layout — replicated for plain DP, per-leaf
        # shardings for TP/EP/PP trainers (this is also what makes
        # restore-into-a-different-mesh work after an elastic re-mesh).
        p_sh, o_sh = state_shardings(trainer)
        trainer.params = place_on(restored["params"], p_sh)
        trainer.opt_state = place_on(restored["opt_state"], o_sh)
        trainer.step_num = int(restored["step"])
        if has_ef and "ef" in restored:
            _restore_ef(trainer, restored["ef"])
        return trainer.step_num

    def close(self) -> None:
        self._mgr.close()

    def __enter__(self) -> "TrainerCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class DeltaCheckpointer:
    """Per-leaf, content-addressed checkpoints: a save writes only the
    leaves whose bytes CHANGED since any kept checkpoint (VERDICT r3
    next-round #2, "optional per-leaf delta saves" — size checkpoints to
    the link).

    Layout: ``blobs/<sha256>.npy`` holds each distinct leaf content once;
    ``manifest_<step>.json`` maps leaf paths to blob hashes. Unchanged
    leaves (frozen embeddings, converged moments, a quiet EF residual, the
    weights themselves when saving more often than they change) cost one
    hash, zero bytes on the wire/disk. Pruning drops manifests beyond
    ``max_to_keep`` and any blob no kept manifest references.

    Works with both state protocols (the params/opt_state pytree and the
    trainer-defined ``checkpoint_state``); restore places leaves through
    the same machinery as :class:`TrainerCheckpointer`.
    """

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3) -> None:
        if max_to_keep < 1:
            # sorted(manifests)[:-0] would be an empty slice — pruning
            # silently off and the store growing unboundedly (ADVICE r4)
            raise ValueError(f"max_to_keep must be >= 1, got {max_to_keep}")
        self.directory = Path(directory).absolute()
        self.blobs = self.directory / "blobs"
        self.blobs.mkdir(parents=True, exist_ok=True)
        self.max_to_keep = max_to_keep

    # -- tree <-> {path: leaf} -----------------------------------------------

    @staticmethod
    def _flatten(state) -> dict:
        import jax.tree_util as jtu

        return {
            jtu.keystr(path): leaf
            for path, leaf in jtu.tree_leaves_with_path(state)
        }

    def _capture(self, trainer) -> tuple[dict, bool]:
        state, custom = capture_state(trainer)
        if not _fully_addressable(state):
            raise NotImplementedError(_POD_MESH_MSG)
        return jax.tree.map(np.asarray, state), custom

    # -- save ----------------------------------------------------------------

    def _manifests(self) -> dict[int, Path]:
        out = {}
        for f in self.directory.glob("manifest_*.json"):
            try:
                out[int(f.stem.split("_", 1)[1])] = f
            except ValueError:
                continue
        return out

    def latest_step(self) -> int | None:
        steps = self._manifests()
        return max(steps) if steps else None

    def save(self, trainer, *, force: bool = False, block: bool = True) -> dict:
        """Write a delta checkpoint; returns ``{written_bytes,
        reused_bytes, written_leaves, reused_leaves}`` so callers can see
        the delta actually saving bytes. ``force``/``block`` exist for
        signature parity with the Orbax checkpointers (this save is
        synchronous — :class:`AsyncDeltaCheckpointer` moves the hash/write
        off-thread — and never step-deduped: an identical re-save just
        reuses every blob)."""
        host, custom = self._capture(trainer)
        return self._write_delta(host, custom, int(trainer.step_num))

    def _write_delta(self, host: dict, custom: bool, step: int) -> dict:
        """Hash every leaf, write the new blobs + manifest, prune. Pure
        host-side work on an already-host tree — the half a background
        writer thread can run.

        Durability order matters: every blob is fsynced before its atomic
        rename, and the manifest is fsynced before ITS rename — a crash
        mid-save must leave the old manifests intact and can never publish
        a manifest that names truncated chunk files (the page-cache-loss
        corruption class; regression-pinned in tests/test_checkpoint.py)."""
        import json
        import os as _os

        from akka_allreduce_tpu.control.statetransfer import (
            fsync_write,
            leaf_sha,
            publish_file,
        )

        flat = self._flatten(host)
        manifest = {
            "step": step,
            "custom": custom,
            "leaves": {},
        }
        stats = dict(
            written_bytes=0, reused_bytes=0, written_leaves=0, reused_leaves=0
        )
        for key, leaf in flat.items():
            arr = np.asarray(leaf)
            # ONE definition of the content hash (statetransfer.leaf_sha):
            # the peer chunk transfer verifies fetched blobs against these
            # names, so the hash here and the verifier must never diverge
            sha = leaf_sha(arr)
            blob = self.blobs / f"{sha}.npy"
            if blob.exists():
                stats["reused_bytes"] += arr.nbytes
                stats["reused_leaves"] += 1
            else:
                tmp = blob.with_suffix(".tmp")
                with open(tmp, "wb") as f:  # np.save(path) appends .npy
                    np.save(f, arr, allow_pickle=False)
                    f.flush()
                    _os.fsync(f.fileno())
                publish_file(tmp, blob)  # atomic + directory fsync
                stats["written_bytes"] += arr.nbytes
                stats["written_leaves"] += 1
            manifest["leaves"][key] = sha
        tmp = self.directory / f".manifest_{step}.tmp"
        # fsync BEFORE the atomic rename (statetransfer.fsync_write — one
        # definition of the durability recipe): a crash mid-save leaves old
        # manifests + maybe some orphan blobs, never a torn manifest or one
        # whose blobs' bytes were still in the page cache
        fsync_write(tmp, json.dumps(manifest).encode())
        publish_file(tmp, self.directory / f"manifest_{step}.json")
        self._prune()
        return stats

    def _prune(self) -> None:
        import json

        manifests = self._manifests()
        for step in sorted(manifests)[: -self.max_to_keep]:
            manifests.pop(step).unlink()
        live = set()
        for f in manifests.values():
            live.update(json.loads(f.read_text())["leaves"].values())
        for blob in self.blobs.glob("*.npy"):
            if blob.stem not in live:
                blob.unlink()
        # a crash between tmp write and atomic publish leaves orphans a
        # normal prune would never reclaim — sweep them here (the current
        # save has already published its blobs by the time prune runs)
        for stale in self.blobs.glob("*.tmp"):
            stale.unlink()
        for stale in self.directory.glob(".manifest_*.tmp"):
            stale.unlink()

    # -- restore -------------------------------------------------------------

    def restore(self, trainer, step: int | None = None) -> int:
        import json

        import jax.tree_util as jtu

        manifests = self._manifests()
        step = max(manifests) if step is None and manifests else step
        if step is None or step not in manifests:
            raise FileNotFoundError(
                f"no delta checkpoint for step {step} under {self.directory}"
            )
        manifest = json.loads(manifests[step].read_text())
        leaves = manifest["leaves"]

        def load(path, _template):
            key = jtu.keystr(path)
            if key not in leaves:
                raise KeyError(
                    f"checkpoint at step {step} has no leaf {key!r} (trainer "
                    "structure mismatch)"
                )
            return np.load(self.blobs / f"{leaves[key]}.npy", allow_pickle=False)

        if manifest["custom"]:
            if not hasattr(trainer, "restore_checkpoint_state"):
                raise TypeError(
                    "delta checkpoint was captured through a trainer-defined "
                    "checkpoint protocol; the restore target has none"
                )
            template_fn = getattr(
                trainer, "checkpoint_template", trainer.checkpoint_state
            )
            state = jtu.tree_map_with_path(load, dict(template_fn()))
            trainer.restore_checkpoint_state(state)
        else:
            target = {"params": trainer.params, "opt_state": trainer.opt_state}
            has_ef = getattr(trainer, "_ef", None) is not None
            if has_ef and any(k.startswith("['ef']") for k in leaves):
                target["ef"] = trainer._ef
            state = jtu.tree_map_with_path(load, target)
            p_sh, o_sh = state_shardings(trainer)
            trainer.params = place_on(state["params"], p_sh)
            trainer.opt_state = place_on(state["opt_state"], o_sh)
            if "ef" in state:
                _restore_ef(trainer, state["ef"])
            elif has_ef:
                # the checkpoint carries no residual: keeping the live
                # (possibly nonzero, stale) one would make post-restore
                # state not purely the saved state (ADVICE r4) — zero it
                _restore_ef(trainer, np.zeros(trainer._ef.shape, np.float32))
        trainer.step_num = int(manifest["step"])
        return trainer.step_num

    def close(self) -> None:
        """Nothing to flush (saves are synchronous); CLI-loop parity."""

    def __enter__(self) -> "DeltaCheckpointer":
        return self

    def __exit__(self, *exc) -> None:
        self.close()


class AsyncTrainerCheckpointer(_BackgroundWriter, TrainerCheckpointer):
    """Checkpoints that do not stall the step loop (VERDICT r3 next-round
    #2: "checkpoint cost is part of the recovery story").

    ``save`` splits into a cheap capture phase in the step gap and a
    background phase off-thread (see :func:`async_capture`):

    - **pytree-state trainers** (DP / MoE / LongContext): capture = ONE
      on-device copy of the state (HBM-to-HBM, microseconds to
      milliseconds) + launching ``copy_to_host_async`` on every leaf.
      The training loop resumes immediately and keeps donating its own
      buffers — the copy is independent — while the device-to-host
      transfer of the state (4.8 GB for the flagship) overlaps the
      subsequent steps. The background thread blocks on
      the transfers and then runs the Orbax write.
    - **trainers with the shard-local protocol** (ZeRO-1, FSDP, Pipeline —
      ``checkpoint_capture``/``checkpoint_assemble``): same on-device copy
      of each shard, NO gather in the capture phase (VERDICT r4 #1); the
      writer thread drains the shards and runs the trainer's pure-host
      ``checkpoint_assemble`` (unshard / unpad / re-order) before the
      Orbax write.

    Crash safety: the background write goes through the same Orbax
    manager, which finalizes each step directory atomically — a crash
    mid-save leaves the previous checkpoint as ``latest_step`` and the
    partial step invisible to restore (tested by SIGKILLing a writer
    mid-save in tests/test_checkpoint.py).

    One save is in flight at a time: a ``save`` while busy returns False
    (callers keep training and retry next interval) unless ``block=True``.
    Restores and ``close`` drain the in-flight save first.
    """

    def __init__(self, directory, *, max_to_keep: int = 3) -> None:
        super().__init__(directory, max_to_keep=max_to_keep)
        self._writer_init()

    def save(self, trainer, *, force: bool = False, block: bool = False) -> bool:
        if self.busy():
            if not block:
                return False
            self._drain()
        with self._lock:
            if trainer.step_num in self._mgr.all_steps():
                return False
        step = trainer.step_num
        cap = async_capture(trainer)
        if cap is None:
            # a mesh spanning OS processes: Orbax's cross-process save
            # coordinates ALL processes, and per-process background threads
            # can disagree on busy-skip (one process skips while another
            # enters the barrier — deadlock). Take the multihost-aware
            # synchronous path instead; async capture stays a
            # single-controller optimization.
            return super().save(trainer, force=force)
        captured, assemble, _ = cap

        def write():
            host = jax.tree.map(
                lambda x: np.asarray(x)
                if isinstance(x, (jax.Array, np.ndarray))
                else x,
                captured,
            )
            state = assemble(host) if assemble is not None else host
            state["step"] = step
            with self._lock:
                self._mgr.save(
                    step, args=_orbax().args.StandardSave(state), force=force
                )
                self._mgr.wait_until_finished()

        self._launch(write, f"ckpt-save-{step}")
        if block:
            self._drain()
        return True

    def restore(self, trainer, step: int | None = None) -> int:
        self._drain()  # a restore must see the freshest durable step
        return super().restore(trainer, step)

    def latest_step(self) -> int | None:
        with self._lock:
            return self._mgr.latest_step()

    def close(self) -> None:
        try:
            self._drain()
        finally:
            super().close()


class AsyncDeltaCheckpointer(_BackgroundWriter, DeltaCheckpointer):
    """Delta checkpoints whose hashing and blob writes run off-thread —
    link-sized saves AND non-stalling saves at once (VERDICT r4 #1: the
    round-4 store made them mutually exclusive).

    Capture is the same non-gathering phase as
    :class:`AsyncTrainerCheckpointer` (on-device copies, shard-local for
    the ZeRO-1/FSDP/Pipeline protocol); the writer thread drains, runs the
    trainer's ``checkpoint_assemble``, then hashes leaves and writes only
    the changed blobs. ``save`` returns True when a background save was
    launched (False while one is still in flight); the per-save byte
    stats land in :attr:`last_stats` once it completes (``busy()`` →
    False, or after ``wait_until_finished``). Still a per-host store:
    non-fully-addressable state raises, as in the sync class."""

    def __init__(self, directory: str | Path, *, max_to_keep: int = 3) -> None:
        super().__init__(directory, max_to_keep=max_to_keep)
        self._writer_init()
        #: stats dict of the most recently COMPLETED save (None before any)
        self.last_stats: dict | None = None

    def save(
        self, trainer, *, force: bool = False, block: bool = False
    ) -> bool:
        if self.busy():
            if not block:
                return False
            self._drain()
        step = int(trainer.step_num)
        cap = async_capture(trainer)
        if cap is None:
            raise NotImplementedError(_POD_MESH_MSG)
        captured, assemble, custom = cap

        def write():
            host = jax.tree.map(
                lambda x: np.asarray(x)
                if isinstance(x, (jax.Array, np.ndarray))
                else x,
                captured,
            )
            state = assemble(host) if assemble is not None else host
            with self._lock:
                self.last_stats = self._write_delta(state, custom, step)

        self._launch(write, f"delta-save-{step}")
        if block:
            self._drain()
        return True

    def latest_step(self) -> int | None:
        with self._lock:
            return super().latest_step()

    def restore(self, trainer, step: int | None = None) -> int:
        self._drain()  # a restore must see the freshest durable step
        return super().restore(trainer, step)

    def close(self) -> None:
        try:
            self._drain()
        finally:
            # DeltaCheckpointer.close() is a no-op today, but a drain failure
            # must never skip whatever cleanup it grows (ADVICE r5)
            super().close()

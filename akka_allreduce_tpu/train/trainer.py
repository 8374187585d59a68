"""DP trainer: per-step threshold-masked gradient allreduce inside one jitted
SPMD step (the pure-TPU form of the reference's grad-sync configs,
BASELINE.json:9-10 / SURVEY.md §4.4).

Design: batch sharded over the mesh's data axes, params/optimizer state
replicated; forward + backward run per device; the gradient pytree is
flattened and goes through ONE fused masked psum (optionally bucketed at
``max_chunk_size`` granularity — the reference's chunked buffer); the
optimizer applies the partial-average gradient. Invalid devices (mask 0) still
compute — XLA collectives are all-or-nothing — but contribute nothing, exactly
the threshold-contribution semantics of SURVEY.md §8.1 step 3.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.flatten_util import ravel_pytree
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.binder.api import flatten_pytree
from akka_allreduce_tpu.comm.allreduce import (
    backward_psum_sync,
    backward_ring_sync,
    backward_sync_ef,
    expand_counts,
    masked_psum,
    ring_allreduce_sum,
    ring_ef_residual,
)


@dataclasses.dataclass
class TrainStepMetrics:
    step: int
    loss: float
    contributors: float


def ef_fold(flat: jax.Array, ef) -> jax.Array:
    """Fold the EF residual into this step's contribution: ``c = g + e``."""
    return flat if ef is None else flat + ef.reshape(-1)


def ef_residual(
    c: jax.Array,
    v: jax.Array,
    ef,
    *,
    compress: str = "bf16",
) -> jax.Array:
    """``e' = c - sent``; all of ``c`` carries forward when the device was
    masked out.

    ``compress="bf16"``: ``sent`` mirrors masked_psum's mask-then-cast
    EXACTLY (what the bf16 collective actually summed from this device).
    The bf16 cast error is entirely local, so this residual is the
    complete compensation. The int8 ring no longer routes through here:
    its residual comes from the ring itself
    (``ring_allreduce_sum(..., return_residual=True)`` — per-hop
    accounting including partial-sum requantization, VERDICT r4 #4c).
    """
    if compress != "bf16":
        raise ValueError(
            f"ef_residual is the bf16 mask-then-cast mirror; int8 uses the "
            f"ring's per-hop residual (got compress={compress!r})"
        )
    m = c * v
    sent = m.astype(jnp.bfloat16).astype(jnp.float32)
    return (c - sent).reshape(ef.shape)


def default_classification_loss():
    """Mean softmax cross-entropy over integer labels (the trainers' default)."""
    return lambda logits, y: optax.softmax_cross_entropy_with_integer_labels(
        logits, y
    ).mean()


def normalize_valid(valid: Sequence[float] | None, n: int) -> np.ndarray:
    """Contributor mask -> validated (n,) float32 array (shared by trainers)."""
    if valid is None:
        return np.ones((n,), np.float32)
    arr = np.asarray(valid, np.float32)
    if arr.shape != (n,):
        raise ValueError(f"valid must have shape ({n},), got {arr.shape}")
    return arr


def run_chain_cached(
    trainer,
    sampler,
    steps: int,
    rows: int,
    build,
    valid: Sequence[float] | None,
    n_valid: int,
    valid_sharding,
    seed: int,
    fetch_metrics: bool = True,
    extra_state: tuple = (),
) -> tuple:
    """Shared ``train_chain`` scaffolding for every trainer.

    - chain cache keyed on the shape config ``(steps, rows)`` with the
      sampler object pinned by IDENTITY in the entry: ``id()`` alone could
      match a new sampler allocated at a recycled address after the old one
      was garbage-collected, silently reusing a chain compiled against the
      old closure;
    - contributor mask normalized to ``(n_valid,)`` and placed;
    - the PRNG key folds in ``step_num`` so consecutive chain calls continue
      the data stream instead of replaying the same batches.

    The built chain must have signature ``(params, opt_state, *extras, key,
    valid) -> (params, opt_state, *new_extras, *metric_arrays)``, where
    ``extra_state`` names the trainer attributes holding the extras (e.g.
    ``("_ef",)`` for the error-feedback residual); new state is swapped into
    the trainer here and the stacked metric arrays are returned as host numpy.
    """
    cache_key = (steps, rows)
    entry = trainer._chains.get(cache_key)
    if entry is None or entry[0] is not sampler:
        trainer._chains[cache_key] = (sampler, build())
    vd = jax.device_put(normalize_valid(valid, n_valid), valid_sharding)
    key = jax.device_put(
        jax.random.fold_in(jax.random.PRNGKey(seed), trainer.step_num),
        trainer._replicated,
    )
    extras = tuple(getattr(trainer, name) for name in extra_state)
    out = trainer._chains[cache_key][1](
        trainer.params, trainer.opt_state, *extras, key, vd
    )
    trainer.params, trainer.opt_state = out[0], out[1]
    for i, name in enumerate(extra_state):
        setattr(trainer, name, out[2 + i])
    out = out[:2] + out[2 + len(extra_state):]
    if not fetch_metrics:
        # raw device arrays: benchmarks time the chain without the O(steps)
        # metric fetch (the device_get payload grows linearly with steps and
        # would ride on the timing slope instead of cancelling)
        return out[2:]
    return tuple(np.asarray(jax.device_get(o)) for o in out[2:])


def place_batch(x, y, n_devices: int, data_sharding):
    """Validate divisibility and place an (x, y) batch on the mesh.

    Single-process: ``x``/``y`` are the GLOBAL batch. Multiprocess (pod
    runtime — the sharding's mesh spans OS processes): each process passes
    its HOST-LOCAL rows and they assemble into one global sharded batch via
    ``parallel.multihost.host_local_to_global`` — the pod form of the
    reference's per-worker dataSource pull (SURVEY.md §4.4). train_step,
    accuracy, and ``train_step_accum`` (which builds its
    (devices·accum, micro, ...) layout host-locally per process) all ride
    this seam.
    """
    if not data_sharding.is_fully_addressable:
        # the mesh spans OS processes (a fully-local mesh — e.g. a
        # single-device oracle inside a pod run — takes the plain path)
        from akka_allreduce_tpu.parallel import multihost

        mesh, spec = data_sharding.mesh, data_sharding.spec
        pid = jax.process_index()
        local_share = sum(
            1 for d in mesh.devices.flat if d.process_index == pid
        )
        if local_share == 0 or x.shape[0] % local_share:
            raise ValueError(
                f"host-local batch {x.shape[0]} not divisible by this "
                f"process's {local_share} mesh devices"
            )
        return (
            multihost.host_local_to_global(
                np.asarray(x, np.float32), mesh, spec
            ),
            multihost.host_local_to_global(
                np.asarray(y, np.int32), mesh, spec
            ),
        )
    if x.shape[0] % n_devices:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by {n_devices}"
        )
    x = jax.device_put(np.asarray(x, np.float32), data_sharding)
    y = jax.device_put(np.asarray(y, np.int32), data_sharding)
    return x, y


def place_tokens(x, y, data_sharding, *, seq_len: int, dp: int):
    """Token-LM twin of :func:`place_batch` (both arrays int32, batch rows
    over the ``dp`` row shards of the spec's first dim, the seq dim over any
    seq axis in it).

    Single-process: ``x``/``y`` are the GLOBAL (batch, seq_len) arrays.
    Pod runtime (the sharding's mesh spans OS processes): each process
    passes the HOST-LOCAL slice matching its devices' block of the
    sharding — for the (data, seq) layouts used here, its DP rows' full
    sequences when its devices cover whole replica rows.
    """
    if x.shape[1] != seq_len:
        raise ValueError(f"sequence length {x.shape[1]} != {seq_len}")
    if not data_sharding.is_fully_addressable:
        from akka_allreduce_tpu.parallel import multihost

        mesh, spec = data_sharding.mesh, data_sharding.spec
        return (
            multihost.host_local_to_global(
                np.asarray(x, np.int32), mesh, spec
            ),
            multihost.host_local_to_global(
                np.asarray(y, np.int32), mesh, spec
            ),
        )
    if x.shape[0] % dp:
        raise ValueError(
            f"global batch {x.shape[0]} not divisible by its {dp} row shards"
        )
    return (
        jax.device_put(np.asarray(x, np.int32), data_sharding),
        jax.device_put(np.asarray(y, np.int32), data_sharding),
    )


def place_mask(valid_arr: np.ndarray, data_sharding):
    """Place the GLOBAL per-device contributor mask on the mesh.

    The mask is control-plane state every process agrees on (the membership
    view), so callers always pass the full (n_devices,) array; on a pod
    each process extracts the rows its local devices own before the
    host-local -> global assembly.
    """
    if data_sharding.is_fully_addressable:
        return jax.device_put(valid_arr, data_sharding)
    from akka_allreduce_tpu.parallel import multihost

    arr = np.asarray(valid_arr)
    # the sharding's own index map says which mask ROWS this process's
    # devices hold (NOT one entry per device: on a multi-axis mesh several
    # devices share a data row, and the mask length is the data extent)
    pid = jax.process_index()
    imap = data_sharding.devices_indices_map(arr.shape)
    starts = [
        idx[0].start or 0
        for d, idx in imap.items()
        if d.process_index == pid
    ]
    stops = [
        idx[0].stop if idx[0].stop is not None else arr.shape[0]
        for d, idx in imap.items()
        if d.process_index == pid
    ]
    if not starts:
        # a clean error beats min()-of-empty followed by peers hanging in
        # the collective (same contract as place_batch's 0-device message)
        raise ValueError(
            "this process owns no devices in the training mesh; a "
            "zero-device participant cannot feed the pod collective"
        )
    return multihost.host_local_to_global(
        arr[min(starts) : max(stops)], data_sharding.mesh, data_sharding.spec
    )


class DPTrainer:
    """Data-parallel trainer over every axis of ``mesh``.

    Args:
      model: a flax module with ``init``/``apply``.
      mesh: device mesh; the batch is sharded across ALL its axes jointly
        (a 2D mesh gives the butterfly-grid layout of BASELINE.json:8).
      example_input: one device's worth of input used for ``init``.
      optimizer: optax transform (default: SGD).
      bucket_size: gradient bucket size in elements (None = single fused psum).
      compress: None | "bf16" | "int8" — gradient wire compression. bf16
        runs the psum collective at half width; int8 rides the explicit
        ring schedule with per-segment max-abs scales at a quarter (one
        mesh axis only; the ring segments by device count, so
        ``bucket_size`` does not set its wire chunking). Counts and the
        optimizer state stay float32 either way. Forces the
        explicit-collective path (one bucket when ``bucket_size`` is None).
      error_feedback: carry each device's quantization residual into its
        next contribution (EF-SGD): ``c = g + e; send cast(c·v);
        e' = c − sent`` — what compression withholds this step is re-sent
        the next, making the lossy sync unbiased over time. A masked-out
        device (v=0) sends nothing, so its ENTIRE contribution carries
        forward — threshold dropout loses no gradient signal, only delays
        it. Requires ``compress``. Works on train_step, train_step_accum
        (residual of the accumulated mean gradient) and train_chain (the
        residual rides the scan carry).
      overlap: issue ONE masked collective per param leaf INSIDE the
        backward pass (``comm.allreduce.backward_psum_sync``) instead of a
        single fused psum at the end. Leaf k's collective then depends only
        on leaf k's backward subgraph, so the latency-hiding scheduler
        (TPU async all-reduce pairs) can hide it behind the remaining
        backward compute — SURVEY.md §8.4's overlap story. Composes with
        ``compress`` (bf16 psums; int8 = one per-leaf ring,
        ``backward_ring_sync``) AND ``error_feedback`` (the new residual
        rides the same autodiff pass as each leaf's e-cotangent —
        VERDICT r4 #4a); mutually exclusive only with ``bucket_size``
        (leaf granularity IS the bucketing).
    """

    def __init__(
        self,
        model,
        mesh: Mesh,
        example_input: np.ndarray,
        *,
        optimizer: optax.GradientTransformation | None = None,
        learning_rate: float = 0.1,
        bucket_size: int | None = None,
        loss_fn: Callable | None = None,
        seed: int = 0,
        compress: str | None = None,
        error_feedback: bool = False,
        overlap: bool = False,
    ) -> None:
        if overlap and bucket_size is not None:
            raise ValueError(
                "overlap issues ONE collective per param leaf inside the "
                "backward pass — leaf granularity IS its bucketing; "
                "bucket_size does not compose with it"
            )
        if compress not in (None, "bf16", "int8"):
            raise ValueError(
                f"compress must be None, 'bf16' or 'int8', got {compress!r}"
            )
        if compress == "int8" and len(mesh.axis_names) != 1:
            raise ValueError(
                "int8 grad sync rides the explicit ring schedule, which "
                f"reduces over ONE mesh axis; got axes {mesh.axis_names}"
            )
        if error_feedback and compress not in ("bf16", "int8"):
            raise ValueError(
                "error_feedback requires compress='bf16' or 'int8' "
                "(lossless sync has no residual to carry). bf16's cast "
                "error is exactly local (ef_residual); int8 EF is per-hop: "
                "the ring returns every quantization error this device "
                "injected — its own contribution's first hop AND its "
                "requantization of relayed partial sums — and the full "
                "amount is re-sent next step (VERDICT r4 #4c)"
            )
        self.model = model
        self.mesh = mesh
        self.axis_names = tuple(mesh.axis_names)
        self.n_devices = int(np.prod([mesh.shape[a] for a in self.axis_names]))
        self.tx = optimizer or optax.sgd(learning_rate)
        self.bucket_size = bucket_size
        self.compress = compress
        self.error_feedback = error_feedback
        self.overlap = overlap
        # how many independent data streams train_chain samples (one per
        # device here; the long-context trainer has one per DP replica row)
        self.data_shards = self.n_devices
        self._loss = loss_fn or default_classification_loss()

        key = jax.random.PRNGKey(seed)
        self.params = model.init(key, jnp.asarray(example_input))
        self.opt_state = self.tx.init(self.params)
        self.param_count = int(
            sum(np.prod(p.shape) for p in jax.tree.leaves(self.params))
        )
        self.step_num = 0

        data_spec = P(
            self.axis_names if len(self.axis_names) > 1 else self.axis_names[0]
        )
        self._data_spec = data_spec
        self._data_sharding = NamedSharding(mesh, data_spec)
        self._replicated = NamedSharding(mesh, P())
        axis_names = self.axis_names
        bucket = bucket_size
        model_apply = model.apply
        loss_impl = self._loss
        tx = self.tx
        wire_bf16 = compress == "bf16"
        n_devices_static = self.n_devices

        def explicit_step(params, opt_state, x, y, v, ef):
            """Explicit bucketed collective (the reference's chunked buffer):
            make params device-varying first so grads stay LOCAL (no implicit
            psum), then run the bucketed masked collective ourselves — in
            bfloat16 on the wire when compressing, with an optional
            error-feedback residual folded in and carried out."""
            scalar_cnt = lax.psum(v, axis_names)
            denom = jnp.maximum(scalar_cnt, 1.0)
            params_local = jax.tree.map(
                lambda p: lax.pcast(p, axis_names, to="varying"), params
            )

            def local_loss(p):
                logits = model_apply(p, x)
                return loss_impl(logits, y)

            loss, grads = jax.value_and_grad(local_loss)(params_local)
            flat, unravel = ravel_pytree(grads)
            c = ef_fold(flat, ef)
            b = bucket if bucket is not None else flat.shape[0]
            n_buckets = -(-flat.shape[0] // b)
            if compress == "int8":
                # quarter-width wire: the explicit ring carries int8 hops
                # with per-segment max-abs scales (comm/allreduce.py); the
                # ring segments by DEVICE COUNT, so bucket_size only sets
                # count granularity here, not wire chunking. Counts reuse
                # the scalar psum already computed above — no extra
                # collective on the hot path. With EF, the ring also
                # returns this device's PER-HOP injected quantization error
                # (partial-sum requantization included — VERDICT r4 #4c),
                # which becomes next step's residual: e' = c·(1−v) + hops.
                if ef is None:
                    gsum = ring_allreduce_sum(
                        c * v.astype(c.dtype),
                        axis_names[0],
                        n_devices_static,
                        compress="int8",
                    )
                    new_ef = None
                else:
                    gsum, hop_err = ring_allreduce_sum(
                        c * v.astype(c.dtype),
                        axis_names[0],
                        n_devices_static,
                        compress="int8",
                        return_residual=True,
                    )
                    new_ef = ring_ef_residual(c, v, hop_err).reshape(ef.shape)
                cnt = jnp.full((n_buckets,), scalar_cnt, jnp.float32)
            else:
                # bf16 wire: masked_psum runs the payload collective at half
                # width; counts stay float32 (exact at any mesh size)
                gsum, cnt = masked_psum(
                    c,
                    jnp.full((n_buckets,), v),
                    axis_names,
                    bucket_size=b,
                    wire_dtype=jnp.bfloat16 if wire_bf16 else None,
                )
                new_ef = None if ef is None else ef_residual(
                    c, v, ef, compress=compress
                )
            denom_el = jnp.maximum(expand_counts(cnt, flat.shape[0], b), 1.0)
            gavg = unravel(gsum / denom_el)
            loss_avg = lax.psum(loss * v, axis_names) / denom
            updates, new_opt = tx.update(gavg, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt, new_ef, loss_avg, scalar_cnt

        if overlap:
            wire = jnp.bfloat16 if wire_bf16 else None
            if compress == "int8":
                # per-leaf int8 ring inside the backward (VERDICT r4 #4a)
                grad_sync = backward_ring_sync(
                    axis_names[0], n_devices_static, compress="int8"
                )
                grad_sync_ef = backward_ring_sync(
                    axis_names[0], n_devices_static, compress="int8",
                    error_feedback=True,
                ) if error_feedback else None
            else:
                grad_sync = backward_psum_sync(axis_names, wire)
                grad_sync_ef = (
                    backward_sync_ef(axis_names, wire)
                    if error_feedback
                    else None
                )

            def overlapped_step(params, opt_state, x, y, v, ef=None):
                """Per-leaf collectives issued INSIDE the backward pass:
                leaf k's psum (or int8 ring) depends only on leaf k's
                backward subgraph, so the latency-hiding scheduler can run
                it behind the rest of the backward (SURVEY.md §8.4;
                backward_psum_sync / backward_ring_sync). With EF, the
                flat residual is unraveled into param-shaped leaves, each
                leaf's sync folds its residual into the cotangent, and the
                NEW residual comes back as the e-cotangent of the same
                autodiff pass — e' = ravel of those leaves."""
                scalar_cnt = lax.psum(v, axis_names)
                denom = jnp.maximum(scalar_cnt, 1.0)
                params_local = jax.tree.map(
                    lambda p: lax.pcast(p, axis_names, to="varying"), params
                )
                if ef is None:

                    def local_loss(pt):
                        ps = jax.tree.map(lambda p: grad_sync(p, v), pt)
                        return loss_impl(model_apply(ps, x), y)

                    loss, gsum = jax.value_and_grad(local_loss)(params_local)
                    new_ef = None
                else:
                    _, unravel_p = ravel_pytree(params_local)
                    ef_tree = unravel_p(ef.reshape(-1))

                    def local_loss_ef(pt, et):
                        ps = jax.tree.map(
                            lambda p, e: grad_sync_ef(p, e, v), pt, et
                        )
                        return loss_impl(model_apply(ps, x), y)

                    loss, (gsum, new_ef_tree) = jax.value_and_grad(
                        local_loss_ef, argnums=(0, 1)
                    )(params_local, ef_tree)
                    new_ef = ravel_pytree(new_ef_tree)[0].reshape(ef.shape)
                gavg = jax.tree.map(lambda g: g / denom, gsum)
                loss_avg = lax.psum(loss * v, axis_names) / denom
                updates, new_opt = tx.update(gavg, opt_state, params)
                new_params = optax.apply_updates(params, updates)
                if ef is None:
                    return new_params, new_opt, loss_avg, scalar_cnt
                return new_params, new_opt, new_ef, loss_avg, scalar_cnt

        def step(params, opt_state, x, y, valid):
            v = valid.reshape(())
            if overlap:
                return overlapped_step(params, opt_state, x, y, v)
            if bucket is not None or compress is not None:
                out = explicit_step(params, opt_state, x, y, v, None)
                return out[0], out[1], out[3], out[4]
            # Differentiating the v-weighted local loss w.r.t. REPLICATED
            # params makes JAX's shard_map autodiff insert the cross-device
            # psum itself (the transpose of the params broadcast), so the
            # gradient that comes back is already sum_d(v_d * g_d) in ONE
            # fused collective — the masked allreduce with zero extra code.
            scalar_cnt = lax.psum(v, axis_names)
            denom = jnp.maximum(scalar_cnt, 1.0)

            def global_masked_loss(p):
                logits = model_apply(p, x)
                return loss_impl(logits, y) * v

            lsum, gsum_tree = jax.value_and_grad(global_masked_loss)(params)
            gavg = jax.tree.map(lambda g: g / denom, gsum_tree)
            loss_avg = lax.psum(lsum, axis_names) / denom
            updates, new_opt = tx.update(gavg, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt, loss_avg, scalar_cnt

        mapped = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(P(), P(), data_spec, data_spec, data_spec),
            out_specs=(P(), P(), P(), P()),
            # the int8 ring's all-gather result IS replicated and the overlap
            # custom_vjp's psum erases vma typing, but the static varying-axes
            # check cannot see either (same caveat as the comm layer's ring
            # schedules); the f32-equivalence tests are the oracle
            check_vma=(compress != "int8" and not overlap),
        )
        self._step = jax.jit(mapped, donate_argnums=(0, 1))
        self._raw_step = step  # reused by train_chain's on-device loop

        if error_feedback:
            # per-device float32 residual of the compressed grad sync,
            # device-varying (each device carries ITS OWN withheld error)
            self._ef = jax.device_put(
                np.zeros((self.n_devices, self.param_count), np.float32),
                self._data_sharding,
            )

            def step_ef(params, opt_state, ef, x, y, valid):
                v = valid.reshape(())
                if overlap:
                    return overlapped_step(params, opt_state, x, y, v, ef)
                return explicit_step(params, opt_state, x, y, v, ef)

            self._raw_step_ef = step_ef  # reused by train_chain's EF loop
            self._step_ef = jax.jit(
                jax.shard_map(
                    step_ef,
                    mesh=mesh,
                    in_specs=(
                        P(), P(), data_spec, data_spec, data_spec, data_spec
                    ),
                    out_specs=(P(), P(), data_spec, P(), P()),
                    # the int8 ring's ppermute loop and the overlap
                    # custom_vjp erase varying-axes typing (same relaxation
                    # as the non-EF step above)
                    check_vma=(compress != "int8" and not overlap),
                ),
                donate_argnums=(0, 1, 2),
            )
        self._chains: dict = {}
        self._accum_steps_fns: dict = {}

        def eval_correct(params, x, y):
            logits = model_apply(params, x)
            hits = jnp.sum(jnp.argmax(logits, -1) == y)
            return lax.psum(hits, axis_names)

        self._eval = jax.jit(
            jax.shard_map(
                eval_correct,
                mesh=mesh,
                in_specs=(P(), data_spec, data_spec),
                out_specs=P(),
            )
        )

    # -- stepping ------------------------------------------------------------

    def _normalize_valid(self, valid: Sequence[float] | None) -> np.ndarray:
        return normalize_valid(valid, self.n_devices)

    def _place_batch(self, x, y):
        return place_batch(x, y, self.n_devices, self._data_sharding)

    def train_step(
        self, x: np.ndarray, y: np.ndarray, valid: Sequence[float] | None = None
    ) -> TrainStepMetrics:
        """One DP step. Single-process: ``x``/``y`` are the GLOBAL batch
        (first dim divisible by n_devices). Pod runtime (mesh spans OS
        processes): each process passes its HOST-LOCAL rows — see
        ``place_batch``; ``valid`` stays GLOBAL (n_devices,) either way."""
        valid_arr = self._normalize_valid(valid)
        xd, yd = self._place_batch(x, y)
        vd = place_mask(valid_arr, self._data_sharding)
        if self.error_feedback:
            self.params, self.opt_state, self._ef, loss, cnt = self._step_ef(
                self.params, self.opt_state, self._ef, xd, yd, vd
            )
            self.step_num += 1
            return TrainStepMetrics(
                step=self.step_num, loss=float(loss), contributors=float(cnt)
            )
        self.params, self.opt_state, loss, cnt = self._step(
            self.params, self.opt_state, xd, yd, vd
        )
        self.step_num += 1
        return TrainStepMetrics(
            step=self.step_num,
            loss=float(loss),
            contributors=float(cnt),
        )

    def train(
        self, batches: Iterable, valid_schedule: Callable[[int], Sequence[float]] | None = None
    ) -> list[TrainStepMetrics]:
        history = []
        for x, y in batches:
            valid = valid_schedule(self.step_num) if valid_schedule else None
            history.append(self.train_step(x, y, valid))
        return history

    def accuracy(self, x: np.ndarray, y: np.ndarray) -> float:
        xd, yd = self._place_batch(x, y)
        hits = self._eval(self.params, xd, yd)
        # the hit count is psummed over ALL devices, so the denominator is
        # the GLOBAL row count (xd is the assembled global array — on a pod
        # x.shape[0] would be only this process's rows)
        return float(hits) / xd.shape[0]

    # -- gradient accumulation (microbatching) -------------------------------

    def _build_accum_step(self, accum_steps: int):
        """One optimizer step over ``accum_steps`` microbatches: grads are
        accumulated per device across a ``lax.scan`` and synced with ONE
        masked psum at the end — bigger effective batches in fixed memory,
        and one collective per effective batch instead of per microbatch.
        Exactly equivalent to a single step on the concatenated batch (the
        mean of equal-size microbatch mean-gradients IS the full-batch mean).
        """
        axis_names = self.axis_names
        model_apply = self.model.apply
        loss_impl = self._loss
        tx = self.tx
        bucket = self.bucket_size
        ef_enabled = self.error_feedback

        def compute(params, opt_state, ef, x, y, valid):
            # x: (accum, micro, ...) per-device block; ef: residual or None
            v = valid.reshape(())
            scalar_cnt = lax.psum(v, axis_names)
            denom = jnp.maximum(scalar_cnt, 1.0)
            params_local = jax.tree.map(
                lambda p: lax.pcast(p, axis_names, to="varying"), params
            )

            def micro(carry, xy):
                g_acc, l_acc = carry
                xm, ym = xy

                def local_loss(p):
                    return loss_impl(model_apply(p, xm), ym)

                loss, grads = jax.value_and_grad(local_loss)(params_local)
                return (
                    jax.tree.map(jnp.add, g_acc, grads),
                    l_acc + loss,
                ), None

            zeros = jax.tree.map(jnp.zeros_like, params_local)
            # the loss carry must enter the scan device-varying like the
            # losses that accumulate into it
            l0 = lax.pcast(jnp.float32(0.0), axis_names, to="varying")
            (gsum, lsum), _ = lax.scan(micro, (zeros, l0), (x, y))
            # local mean over microbatches, then the SAME single fused (or
            # bucketed) masked collective the plain step uses — never one
            # psum per parameter leaf
            flat, unravel = ravel_pytree(
                jax.tree.map(lambda g: g / accum_steps, gsum)
            )
            # EF (train_step semantics on the accumulated mean gradient)
            c = ef_fold(flat, ef)
            wire = jnp.bfloat16 if self.compress == "bf16" else None
            if self.compress == "int8":
                # quarter-width wire at scan end: ONE int8 ring pass over
                # the accumulated mean gradient — the same explicit
                # collective the plain step uses, amortized over the whole
                # accumulation (VERDICT r3 #5a). Counts reuse the scalar
                # psum. EF composes per-hop exactly as in the plain step
                # (VERDICT r4 #4c): e' = c·(1−v) + ring hop errors.
                if ef is None:
                    total = ring_allreduce_sum(
                        c * v.astype(c.dtype),
                        axis_names[0],
                        self.n_devices,
                        compress="int8",
                    )
                    new_ef = None
                else:
                    total, hop_err = ring_allreduce_sum(
                        c * v.astype(c.dtype),
                        axis_names[0],
                        self.n_devices,
                        compress="int8",
                        return_residual=True,
                    )
                    new_ef = ring_ef_residual(c, v, hop_err).reshape(ef.shape)
                denom_el = denom  # per-element == scalar count (one ring)
            elif bucket is None:
                total, cnt = masked_psum(c, v, axis_names, wire_dtype=wire)
                denom_el = jnp.maximum(cnt, 1.0)
                new_ef = None if ef is None else ef_residual(
                    c, v, ef, compress=self.compress
                )
            else:
                n_buckets = -(-flat.shape[0] // bucket)
                total, cnt = masked_psum(
                    c,
                    jnp.full((n_buckets,), v),
                    axis_names,
                    bucket_size=bucket,
                    wire_dtype=wire,
                )
                denom_el = jnp.maximum(
                    expand_counts(cnt, flat.shape[0], bucket), 1.0
                )
                new_ef = None if ef is None else ef_residual(
                    c, v, ef, compress=self.compress
                )
            gavg = unravel(total / denom_el)
            loss_avg = lax.psum(lsum * v / accum_steps, axis_names) / denom
            updates, new_opt = tx.update(gavg, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            if ef is None:
                return new_params, new_opt, loss_avg, scalar_cnt
            return new_params, new_opt, new_ef, loss_avg, scalar_cnt

        data_spec = self._data_spec
        # the int8 ring's ppermute loop erases varying-axes typing (same
        # caveat as the comm layer's ring schedules); the f32-equivalence
        # test is the oracle there
        check_vma = self.compress != "int8"
        if ef_enabled:
            # compute already has the exact (params, opt, ef, x, y, valid)
            # signature; only the non-EF branch needs a wrapper to bind None
            mapped = jax.shard_map(
                compute,
                mesh=self.mesh,
                in_specs=(P(), P(), data_spec, data_spec, data_spec, data_spec),
                out_specs=(P(), P(), data_spec, P(), P()),
                check_vma=check_vma,
            )
            return jax.jit(mapped, donate_argnums=(0, 1, 2))

        def step(params, opt_state, x, y, valid):
            return compute(params, opt_state, None, x, y, valid)

        mapped = jax.shard_map(
            step,
            mesh=self.mesh,
            in_specs=(P(), P(), data_spec, data_spec, data_spec),
            out_specs=(P(), P(), P(), P()),
            check_vma=check_vma,
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def train_step_accum(
        self,
        x: np.ndarray,
        y: np.ndarray,
        accum_steps: int,
        valid: Sequence[float] | None = None,
    ) -> TrainStepMetrics:
        """One optimizer step over a GLOBAL batch split into ``accum_steps``
        microbatches per device (batch divisible by n_devices * accum_steps).
        """
        if accum_steps < 1:
            raise ValueError(f"accum_steps must be >= 1, got {accum_steps}")
        if accum_steps == 1:  # identical math; reuse the already-built step
            return self.train_step(x, y, valid)
        if self.overlap:
            raise NotImplementedError(
                "overlap is pointless under gradient accumulation: every "
                "leaf's gradient depends on the WHOLE accumulation scan, so "
                "per-leaf collectives could never run behind the backward; "
                "use the accumulation path without overlap"
            )
        if accum_steps not in self._accum_steps_fns:
            self._accum_steps_fns[accum_steps] = self._build_accum_step(
                accum_steps
            )
        sh = self._data_sharding
        valid_arr = self._normalize_valid(valid)
        if sh.is_fully_addressable:
            n = self.n_devices * accum_steps
            if x.shape[0] % n:
                raise ValueError(
                    f"global batch {x.shape[0]} not divisible by "
                    f"{self.n_devices} devices x {accum_steps} accumulation "
                    "steps"
                )
            micro = x.shape[0] // n
            # (global_batch, ...) -> (n_dev*accum, micro, ...): the data
            # sharding splits the leading axis, so device d gets its
            # contiguous (accum, micro, ...) block — the same rows
            # train_step would give it
            def rearrange(a, dt):
                a = np.asarray(a, dt)
                return a.reshape(n, micro, *a.shape[1:])

            xd = jax.device_put(rearrange(x, np.float32), sh)
            yd = jax.device_put(rearrange(y, np.int32), sh)
        else:
            # pod runtime (VERDICT r3 next-round #3): each process passes
            # its HOST-LOCAL rows; the (local_devices*accum, micro, ...)
            # layout is built locally and assembled into the global
            # microbatch array along the sharded leading axis —
            # jax.devices() is process-contiguous, so the assembly gives
            # every device the same contiguous block the single-controller
            # rearrange would
            from akka_allreduce_tpu.parallel import multihost

            mesh, spec = sh.mesh, sh.spec
            pid = jax.process_index()
            local_share = sum(
                1 for d in mesh.devices.flat if d.process_index == pid
            )
            ln = local_share * accum_steps
            if local_share == 0 or x.shape[0] % ln:
                raise ValueError(
                    f"host-local batch {x.shape[0]} not divisible by this "
                    f"process's {local_share} mesh devices x {accum_steps} "
                    "accumulation steps"
                )
            micro = x.shape[0] // ln

            def rearrange_local(a, dt):
                a = np.asarray(a, dt)
                return a.reshape(ln, micro, *a.shape[1:])

            xd = multihost.host_local_to_global(
                rearrange_local(x, np.float32), mesh, spec
            )
            yd = multihost.host_local_to_global(
                rearrange_local(y, np.int32), mesh, spec
            )
        vd = place_mask(valid_arr, sh)
        fn = self._accum_steps_fns[accum_steps]
        if self.error_feedback:
            self.params, self.opt_state, self._ef, loss, cnt = fn(
                self.params, self.opt_state, self._ef, xd, yd, vd
            )
        else:
            self.params, self.opt_state, loss, cnt = fn(
                self.params, self.opt_state, xd, yd, vd
            )
        self.step_num += 1
        return TrainStepMetrics(
            step=self.step_num, loss=float(loss), contributors=float(cnt)
        )

    # -- on-device training chain (data-loader path, no host I/O per step) ---

    def _build_chain(self, sampler, steps: int, batch_per_device: int):
        axis_names = self.axis_names

        def device_key(key):
            # independent per-device stream: fold the device's mesh
            # coordinates into the key (this IS the DP data shard)
            for a in axis_names:
                key = jax.random.fold_in(key, lax.axis_index(a))
            return key

        if self.error_feedback:
            raw_step_ef = self._raw_step_ef

            def chain_ef(params, opt_state, ef, key, valid):
                dkey = device_key(key)

                def body(carry, i):
                    p, o, e = carry
                    k = jax.random.fold_in(dkey, i)
                    x, y = sampler(k, batch_per_device)
                    p, o, e, loss, cnt = raw_step_ef(p, o, e, x, y, valid)
                    return (p, o, e), (loss, cnt)

                (params, opt_state, ef), (losses, cnts) = lax.scan(
                    body, (params, opt_state, ef), jnp.arange(steps)
                )
                return params, opt_state, ef, losses, cnts

            mapped = jax.shard_map(
                chain_ef,
                mesh=self.mesh,
                in_specs=(P(), P(), self._data_spec, P(), self._data_spec),
                out_specs=(P(), P(), self._data_spec, P(), P()),
                # same relaxations as _step_ef's shard_map: the int8
                # ring's ppermute loop and the overlap custom_vjp both
                # erase varying-axes typing (overlap composes with EF
                # since VERDICT r4 #4a)
                check_vma=(self.compress != "int8" and not self.overlap),
            )
            return jax.jit(mapped, donate_argnums=(0, 1, 2))

        raw_step = self._raw_step

        def chain(params, opt_state, key, valid):
            dkey = device_key(key)

            def body(carry, i):
                p, o = carry
                k = jax.random.fold_in(dkey, i)
                x, y = sampler(k, batch_per_device)
                p, o, loss, cnt = raw_step(p, o, x, y, valid)
                return (p, o), (loss, cnt)

            (params, opt_state), (losses, cnts) = lax.scan(
                body, (params, opt_state), jnp.arange(steps)
            )
            return params, opt_state, losses, cnts

        mapped = jax.shard_map(
            chain,
            mesh=self.mesh,
            in_specs=(P(), P(), P(), self._data_spec),
            out_specs=(P(), P(), P(), P()),
            # same int8-ring / overlap caveat as the step's shard_map
            check_vma=(self.compress != "int8" and not self.overlap),
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def train_chain(
        self,
        sampler,
        steps: int,
        batch_per_device: int,
        *,
        valid: Sequence[float] | None = None,
        seed: int = 0,
        fetch_metrics: bool = True,
    ) -> list[TrainStepMetrics] | tuple:
        """Run ``steps`` DP steps entirely on device in ONE dispatch.

        ``sampler`` is a traced ``(key, batch_size) -> (x, y)`` (e.g.
        ``SyntheticClassification.device_sampler``); each device draws its own
        batch shard per step, so no host->device transfer happens inside the
        loop — the data-loader discipline for steps short enough that a
        per-step host round trip would cost more than the step itself.

        ``fetch_metrics=False`` returns the raw ``(losses, counts)`` device
        arrays instead of a metrics list — for benchmarks that must keep the
        O(steps) host fetch/conversion out of their timed window.
        """
        result = run_chain_cached(
            self,
            sampler,
            steps,
            batch_per_device,
            lambda: self._build_chain(sampler, steps, batch_per_device),
            valid,
            self.n_devices,
            self._data_sharding,
            seed,
            fetch_metrics=fetch_metrics,
            # the EF residual rides the scan carry and comes back as state
            extra_state=("_ef",) if self.error_feedback else (),
        )
        if not fetch_metrics:
            self.step_num += steps  # keep the data stream advancing
            return result
        losses, cnts = result
        out = []
        for loss, cnt in zip(losses, cnts):
            self.step_num += 1
            out.append(
                TrainStepMetrics(
                    step=self.step_num,
                    loss=float(loss),
                    contributors=float(cnt),
                )
            )
        return out

    # -- weights as a flat buffer (binder/checkpoint seam) -------------------

    def get_flat_params(self) -> np.ndarray:
        flat, _ = flatten_pytree(self.params)
        return flat

    def set_flat_params(self, vec: np.ndarray) -> None:
        _, unravel = ravel_pytree(self.params)
        self.params = jax.device_put(
            unravel(jnp.asarray(vec, jnp.float32)), self._replicated
        )

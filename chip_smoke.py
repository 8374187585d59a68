#!/usr/bin/env python3
"""The quickest proof that akka_allreduce_tpu still starts, compiles and gives
right answers on the accelerator, through the entry points a user calls.

    python chip_smoke.py               # one chip: phases 1-6 below
    python chip_smoke.py --multichip   # four chips: the collectives only

Default run (one chip, a few minutes cold), one JSON object per phase on
earlier lines, then the verdict as the LAST stdout line:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

1. device      — ``jax.devices()[0].platform == "tpu"`` or exit non-zero at
                 once; versions, ``device_kind``, the compile-cache directory
                 in force and whether it was empty at start.
2. threshold_reduce — the fused threshold-reduce + elastic-average kernels
                 (``ops.elastic_average_step`` / ``ops.masked_average``) at
                 BASELINE config 2's size, K=8 workers x 8M floats with
                 worker 3 masked, COMPILED (``tpu_custom_call`` in the HLO),
                 against the plain ``jax.numpy`` masked mean. Count == 7.
3. local_demo  — ``local-demo --nodes 4 --size 1000000`` (BASELINE config 1,
                 the host control plane) after a forced rebuild of the host
                 engine from the committed C++ sources; says native|numpy.
4. flash_attention — ``ops.local_attention``'s kernel branch (the library's
                 Pallas splash kernel, grouped K/V compact), values and
                 gradients, against the dense oracle at the two attention
                 shapes of the benchmark's training cells.
   lm_*        — the 404M flagship (``train-lm`` / ``LongContextTrainer``:
                 d2048 x 16 heads x 8 layers x seq 2048 x batch 8, bf16, no
                 remat, dp=sp=1): the compiled step holds the Pallas attention
                 kernels and fits the chip, three host-loop steps and one
                 3-step on-device chain give finite losses and a numeric MFU.
5. mlp_train   — ``train-mlp`` (BASELINE config 3, ``DPTrainer``): 20 steps,
                 loss falls, the per-step metrics JSONL appears.
6. second_process — the flagship step compiled again in a FRESH process
                 under the same cache directory: cold vs warm compile
                 seconds, and the compile must be a cache hit.

A chip belongs to one process at a time, so this file's parent never
imports JAX: it runs phases 1-5 in one child and phase 6 in a second, each
under a time limit, and every phase inside a child under its own.
``--multichip`` runs ONLY the four-chip phases (every allreduce schedule
over real ICI with one device masked, then the masked DP steps) in one
child that drives all four chips; its last line says ``"count": 4``.

Any failed check or exception ends the run at that phase: exit code != 0
and ``{"ok": false, ...}`` as the last line. There is no CPU fallback.
Everything written goes under ``chiprun_out/chip_smoke/`` and the cache
directory (plus the host engine's ``.so``, built by phase 3).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
SEED = 0
HBM_BYTES = 16e9  # one v5e chip

# the flagship the repo claims: ~404M parameters
FLAGSHIP = dict(
    d_model=2048, heads=16, layers=8, seq_len=2048, batch=8, vocab=256
)
# Three steps of Adam with no warm-up do not make the loss fall at this
# width (chip runs of PR 21: train-lm's default 3e-3 gives 5.99, 6.72,
# 10.15; 1e-4 gives 5.99, 6.38, 6.16), so the flagship phases assert finite
# losses only; a falling loss is asserted where 20 steps show one (phase 5)
FLAGSHIP_LR = 1e-4
REDUCE_WORKERS = 8
REDUCE_FLOATS_PER_WORKER = 8 * 1024 * 1024  # 64M floats in all
ALLREDUCE_FLOATS = 64 * 1024 * 1024  # per device, BASELINE config 2
ALLREDUCE_SMALL_FLOATS = 1024 * 1024  # the numpy-on-host comparison

# (schedule, compress, mesh): the Pallas remote-DMA ring goes last — it has
# never run compiled, and a hang there must not cost the others' results
SCHEDULES = (
    ("psum", None, "line"),
    ("ring", None, "line"),
    ("butterfly", None, "grid"),
    ("psum", "bf16", "line"),
    ("pallas_ring", None, "line"),
    ("pallas_ring", "int8", "line"),
)
# max |got - want| / max |want|, per wire precision: f32 differs from the
# reference by summation order only; bf16 / int8 are the quantization
# classes tests/test_pallas_ring.py holds the same schedules to
REL_TOL = {None: 1e-6, "bf16": 2e-2, "int8": 8e-2}
COLLECTIVE_IN_HLO = {
    "psum": "all-reduce",
    "butterfly": "all-reduce",
    "ring": "collective-permute",
    "pallas_ring": "tpu_custom_call",
}

# seconds: the parent's limit on each child keeps the whole run inside the
# driver's 1200 s; a child's phases each have their own (a hung kernel
# fails ITS phase by name instead of hanging the call)
CHILD_LIMIT_S = {"main": 900, "second_process": 240, "multichip": 1500}


def children_for(multichip: bool) -> list[str]:
    """Which children the parent runs, in order (no JAX needed to ask)."""
    return ["multichip"] if multichip else ["main", "second_process"]


def parse_args(argv: list[str] | None) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument(
        "--multichip", action="store_true",
        help="run ONLY the four-chip collective phases (needs four chips)",
    )
    # internal: the parent re-invokes this file once per child process
    p.add_argument("--child", choices=sorted(CHILD_LIMIT_S),
                   help=argparse.SUPPRESS)
    return p.parse_args(argv)


# -- parent: no JAX here -------------------------------------------------------


def _run_child(name: str) -> tuple[int, list[dict]]:
    """Run one child under its time limit, echoing its stdout; returns its
    exit code and the phase records it printed."""
    import signal
    import subprocess
    import threading

    proc = subprocess.Popen(
        [sys.executable, "-u", os.path.abspath(__file__), "--child", name],
        stdout=subprocess.PIPE, text=True, start_new_session=True,
    )

    def kill() -> None:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass

    timed_out = threading.Event()
    timer = threading.Timer(
        CHILD_LIMIT_S[name], lambda: (timed_out.set(), kill())
    )
    timer.start()
    records = []
    try:
        for line in proc.stdout:
            sys.stdout.write(line)
            sys.stdout.flush()
            if line.startswith('{"phase"'):
                records.append(json.loads(line))
        rc = proc.wait()
    finally:
        timer.cancel()
        kill()  # whatever the child left running goes with it
    if timed_out.is_set():
        records.append({
            "phase": name, "ok": False,
            "error": f"child exceeded its {CHILD_LIMIT_S[name]} s limit",
        })
    return rc, records


def _verdict(ok: bool, **fields) -> int:
    print(json.dumps({"ok": ok, **fields}), flush=True)
    return 0 if ok else 1


def _second_process_record(records: list[dict]) -> dict:
    """Phase 6's verdict: cold (child 1) vs warm (child 2) compile of the
    same flagship step under one cache directory."""
    cold, warm = (
        r for r in records if r["phase"] == "lm_compiled_step"
    )
    device = next(r for r in records if r["phase"] == "device")
    ratio = warm["compile_s"] / max(cold["compile_s"], 1e-9)
    checks = {"warm_compile_was_cache_hit": warm["cache_hits"] >= 1}
    if device["cache_empty_at_start"]:
        checks["warm_under_half_of_cold"] = ratio < 0.5
    return {
        "phase": "second_process",
        "ok": all(checks.values()),
        "program": "flagship LongContextTrainer step",
        "cache_dir": device["cache_dir"],
        "cache_empty_at_start": device["cache_empty_at_start"],
        "cold_compile_s": cold["compile_s"],
        "warm_compile_s": warm["compile_s"],
        "warm_over_cold": round(ratio, 4),
        "failed_checks": [k for k, v in checks.items() if not v],
    }


def main(argv: list[str] | None = None) -> int:
    args = parse_args(argv)
    if args.child:
        return _child_main(args.child)
    records: list[dict] = []
    for name in children_for(args.multichip):
        rc, recs = _run_child(name)
        records += recs
        bad = [r for r in recs if not r.get("ok")]
        if bad:
            return _verdict(
                False, failed=bad[-1]["phase"],
                error=bad[-1].get("error") or bad[-1].get("failed_checks"),
            )
        if rc != 0:
            return _verdict(
                False, failed=name, error=f"child {name!r} exited {rc}"
            )
    if not args.multichip:
        rec = _second_process_record(records)
        print(json.dumps(rec), flush=True)
        if not rec["ok"]:
            return _verdict(
                False, failed=rec["phase"], error=rec["failed_checks"]
            )
    device = [r for r in records if r["phase"] == "device"][-1]
    return _verdict(
        True,
        device={k: device[k] for k in ("platform", "kind", "count")},
    )


# -- child: phase plumbing -----------------------------------------------------


class _TimeLimit:
    """Fail the named phase — and the process — when it overruns. A thread,
    not SIGALRM: a main thread stuck inside a device wait never returns to
    the interpreter to run a signal handler."""

    def __init__(self, phase: str, seconds: float, program: str) -> None:
        import threading

        self._phase, self._seconds, self._program = phase, seconds, program
        self._done = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)

    def _watch(self) -> None:
        if self._done.wait(self._seconds):
            return
        line = json.dumps({
            "phase": self._phase, "ok": False, "program": self._program,
            "error": f"no result within {self._seconds} s (hang?)",
        })
        os.write(1, (line + "\n").encode())
        os._exit(3)

    def __enter__(self) -> "_TimeLimit":
        self._thread.start()
        return self

    def close(self) -> None:
        self._done.set()
        self._thread.join()

    def __exit__(self, *exc) -> None:
        self.close()


def _phase(name: str, limit_s: float, fn, *args, program: str = "") -> dict:
    """Run one phase: print its record, stop the child if it failed."""
    import traceback

    t0 = time.perf_counter()
    try:
        with _TimeLimit(name, limit_s, program or name):
            rec = fn(*args)
    except Exception as e:  # the boundary: report, then stop — never walk past
        traceback.print_exc()
        print(json.dumps({"phase": name, "ok": False, "error": repr(e)}),
              flush=True)
        raise SystemExit(1) from None
    checks = rec.pop("checks")
    rec = {
        "phase": name,
        "ok": all(checks.values()),
        "seconds": round(time.perf_counter() - t0, 2),
        **rec,
        "failed_checks": [k for k, v in checks.items() if not v],
    }
    print(json.dumps(rec), flush=True)
    if not rec["ok"]:
        raise SystemExit(1)
    return rec


def _read_jsonl(path: str) -> list[dict]:
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def _fresh(path: str) -> str:
    """A path under the output directory with no stale file at it (the
    metrics logger appends)."""
    os.makedirs(os.path.dirname(path), exist_ok=True)
    if os.path.exists(path):
        os.remove(path)
    return path


def _finite(values) -> bool:
    import math

    return bool(values) and all(
        isinstance(v, (int, float)) and math.isfinite(v) for v in values
    )


class _CacheHits:
    """Counts JAX's persistent-compile-cache hits in this process."""

    EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self) -> None:
        import jax

        self.n = 0
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **kw) -> None:
        if event == self.EVENT:
            self.n += 1


# -- phases, one chip ----------------------------------------------------------


def phase_device(want_count: int) -> dict:
    """Phase 1: the accelerator or nothing; then place the compile cache."""
    import importlib.metadata as md

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"no accelerator: jax.devices()[0].platform is {dev.platform!r}, "
            "not 'tpu' — chip_smoke.py has no CPU fallback"
        )
    from akka_allreduce_tpu.utils import enable_compile_cache

    cache_dir = enable_compile_cache()
    count = len(jax.devices())
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": count,
        "jax": jax.__version__,
        "jaxlib": md.version("jaxlib"),
        "libtpu": md.version("libtpu"),
        "cache_dir": cache_dir,
        "cache_from_env": bool(os.environ.get("JAX_COMPILATION_CACHE_DIR")),
        "cache_empty_at_start": not (
            os.path.isdir(cache_dir) and os.listdir(cache_dir)
        ),
        "checks": {f"device_count_is_{want_count}": count == want_count},
    }


def phase_threshold_reduce(
    workers: int = REDUCE_WORKERS, per_worker: int = REDUCE_FLOATS_PER_WORKER
) -> dict:
    """Phase 2: fused Pallas threshold-reduce vs plain jax.numpy."""
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import elastic_average_step, masked_average

    alpha = 0.125
    x = jax.jit(
        lambda key: jax.random.normal(key, (workers, per_worker), jnp.float32)
    )(jax.random.PRNGKey(SEED))
    valid = jnp.ones((workers,), jnp.float32).at[3].set(0.0)

    def fused(x, v):
        avg, count = masked_average(x, v)
        return elastic_average_step(x, v, alpha), avg, count

    @jax.jit
    def reference(x, v):  # unfused jax.numpy
        c = jnp.maximum(v.sum(), 1.0)
        avg = (x * v[:, None]).sum(0) / c
        return (1.0 - alpha) * x + alpha * avg[None], avg, v.sum()

    @jax.jit
    def max_errs(got, want):
        return [jnp.max(jnp.abs(g - w)) for g, w in zip(got, want)]

    t0 = time.perf_counter()
    compiled = jax.jit(fused).lower(x, valid).compile()
    compile_s = time.perf_counter() - t0
    kernels = compiled.as_text().count("tpu_custom_call")
    got = compiled(x, valid)
    want = reference(x, valid)
    err_update, err_avg, _ = (float(e) for e in max_errs(got, want))
    scale = float(jnp.max(jnp.abs(want[0])))
    count = float(got[2])
    return {
        "compared": "ops.elastic_average_step + ops.masked_average "
        "(compiled Pallas) vs jax.numpy masked mean, same seeded inputs",
        "workers": workers,
        "floats_per_worker": per_worker,
        "masked_worker": 3,
        "compile_s": round(compile_s, 2),
        "kernels_in_hlo": kernels,
        "count": count,
        "max_abs_err": max(err_update, err_avg),
        "max_rel_err": max(err_update, err_avg) / scale,
        "checks": {
            "compiled_not_interpreted": kernels >= 2,
            "count_is_workers_minus_one": count == workers - 1,
            "matches_reference_f32": max(err_update, err_avg) <= 1e-5,
        },
    }


def phase_local_demo(size: int = 1_000_000) -> dict:
    """Phase 3: the host control plane, on a host engine rebuilt from the
    committed sources (a stale ``.so`` must not ride along)."""
    import contextlib
    import io
    import re

    from akka_allreduce_tpu import native
    from akka_allreduce_tpu.__main__ import main as cli

    built = native.build()
    out = io.StringIO()
    argv = sys.argv
    try:
        with contextlib.redirect_stdout(out):
            rc = cli(["local-demo", "--nodes", "4", "--size", str(size)])
    finally:
        sys.argv = argv  # local-demo parses sys.argv
    text = out.getvalue()
    sys.stdout.write(text)
    rounds = re.search(r"rounds_completed=(\d+)", text)
    engine = re.search(r"engine=(\w+)", text)
    return {
        "command": f"local-demo --nodes 4 --size {size}",
        "native_build": "built" if built else "unavailable (numpy engine)",
        "engine": engine.group(1) if engine else None,
        "rounds_completed": int(rounds.group(1)) if rounds else None,
        "checks": {
            "exit_code_0": rc == 0,
            "all_20_rounds_completed": bool(rounds)
            and int(rounds.group(1)) == 20,
            "engine_matches_build": bool(engine)
            and engine.group(1) == ("native" if built else "numpy"),
        },
    }


#: (B, T, H, H_kv, D) of the attention call in the benchmark's training
#: cells: StarCoder2-3B at batch 2 x 4096, LFM2-24B-A2B at 1 x 8192
ATTENTION_SHAPES = ((2, 4096, 24, 2, 128), (1, 8192, 32, 8, 64))


def phase_flash_attention(shapes=ATTENTION_SHAPES) -> dict:
    """Phase 4, the kernel alone: ``local_attention``'s kernel branch (the
    library's splash kernel, K/V at their own head count) against the dense
    oracle, values AND gradients, at the shapes the benchmark's cells run —
    tests/test_local_attention.py's check, on the chip (a miscompiled kernel
    still gives finite losses)."""
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import attention_reference, local_attention
    from akka_allreduce_tpu.ops.ring_attention import repeat_kv

    def value_and_grads(attention):
        def loss(q, k, v):
            out = attention(q, k, v, causal=True).astype(jnp.float32)
            return (out ** 2).sum(), out

        def run(q, k, v):
            (_, out), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2), has_aux=True
            )(q, k, v)
            return (out, *grads)

        return run

    def oracle(q, k, v):
        """Dense attention one (batch, K/V head) at a time, so that the
        (group, T, T) f32 scores and what their gradient keeps fit the chip;
        the loss is a sum over heads, so each group's gradients are its own."""
        b, t, h, d = q.shape
        h_kv = k.shape[2]

        def groups(x):  # (B, T, H_kv * n, D) -> (B * H_kv, 1, T, n, D)
            x = x.reshape(b, t, h_kv, -1, d).transpose(0, 2, 1, 3, 4)
            return x.reshape(b * h_kv, 1, t, -1, d)

        dense = value_and_grads(
            lambda q, k, v, causal: attention_reference(
                q, repeat_kv(k, q.shape[2]), repeat_kv(v, q.shape[2]),
                causal=causal,
            )
        )
        parts = jax.lax.map(
            lambda qkv: dense(*qkv), (groups(q), groups(k), groups(v))
        )
        return tuple(
            x.reshape(b, h_kv, t, -1, d).transpose(0, 2, 1, 3, 4)
            .reshape(b, t, -1, d)
            for x in parts
        )

    @jax.jit
    def rel_errs(got, want):
        return [
            jnp.max(jnp.abs(g.astype(jnp.float32) - w.astype(jnp.float32)))
            / jnp.max(jnp.abs(w.astype(jnp.float32)))
            for g, w in zip(got, want)
        ]

    readings, checks = [], {}
    for b, t, h, h_kv, d in shapes:
        keys = jax.random.split(jax.random.PRNGKey(SEED), 3)
        q = jax.random.normal(keys[0], (b, t, h, d), jnp.bfloat16)
        k = jax.random.normal(keys[1], (b, t, h_kv, d), jnp.bfloat16)
        v = jax.random.normal(keys[2], (b, t, h_kv, d), jnp.bfloat16)
        compiled = (
            jax.jit(value_and_grads(local_attention)).lower(q, k, v).compile()
        )
        text = compiled.as_text()
        got = compiled(q, k, v)
        errs = dict(zip(
            ("out", "dq", "dk", "dv"),
            (float(e) for e in rel_errs(got, jax.jit(oracle)(q, k, v))),
        ))
        name = f"b{b}_t{t}_h{h}_kv{h_kv}_d{d}"
        readings.append({
            "shape_bthkd": [b, t, h, h_kv, d],
            "kernels_in_hlo": text.count("tpu_custom_call"),
            "max_err_over_max_ref": errs,
        })
        checks[f"{name}_splash_kernels_compiled"] = (
            text.count("tpu_custom_call") >= 2 and "splash_m" in text
        )
        checks[f"{name}_grads_at_kv_heads"] = (
            got[2].shape == k.shape and got[3].shape == v.shape
        )
        # bf16 inputs and outputs: 2^-8 relative rounding per value
        checks[f"{name}_matches_dense_oracle"] = max(errs.values()) <= 2e-2
    return {
        "compared": "ops.local_attention (Pallas splash, forward + fused "
        "backward, grouped K/V compact) vs ops.attention_reference over the "
        "expanded K/V, same seeded bf16 inputs",
        "readings": readings,
        "checks": checks,
    }


def _flagship_trainer(cfg: dict, dp: int = 1):
    import jax.numpy as jnp

    from akka_allreduce_tpu.parallel import data_seq_mesh
    from akka_allreduce_tpu.train import LongContextTrainer

    return LongContextTrainer(
        data_seq_mesh(dp, 1),
        vocab=cfg["vocab"], d_model=cfg["d_model"], n_heads=cfg["heads"],
        n_layers=cfg["layers"], seq_len=cfg["seq_len"],
        compute_dtype=jnp.bfloat16, remat=False, learning_rate=FLAGSHIP_LR,
        seed=SEED,
    )


def _compile_lm_step(trainer, cfg: dict, valid=None):
    """AOT-compile the trainer's jitted step on its real state; returns
    ``(compiled, placed_args, compile_seconds)``."""
    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.train.trainer import normalize_valid, place_mask

    x, y = next(iter(
        data.lm_copy_task(cfg["seq_len"], vocab=cfg["vocab"], seed=SEED)
        .batches(cfg["batch"], 1)
    ))
    xd, yd = trainer._place(x, y)
    vd = place_mask(
        normalize_valid(valid, trainer.dp), trainer._valid_sharding
    )
    lowered = trainer._step.lower(
        trainer.params, trainer.opt_state, xd, yd, vd
    )
    t0 = time.perf_counter()
    compiled = lowered.compile()
    return compiled, (xd, yd, vd), time.perf_counter() - t0


def _memory_record(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {
        "argument_gb": round(mem.argument_size_in_bytes / 1e9, 3),
        "temp_gb": round(mem.temp_size_in_bytes / 1e9, 3),
        "output_gb": round(mem.output_size_in_bytes / 1e9, 3),
        "alias_gb": round(mem.alias_size_in_bytes / 1e9, 3),
        "code_gb": round(mem.generated_code_size_in_bytes / 1e9, 3),
        "argument_plus_temp_gb": round(
            (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 3
        ),
        "chip_hbm_gb": HBM_BYTES / 1e9,
    }


def phase_lm_compiled_step(hits: _CacheHits, cfg: dict = FLAGSHIP) -> dict:
    """Phase 4a (and phase 6 in the second process): the flagship step's
    compile seconds, that the flash kernel is IN it, what it needs of the
    chip's memory, one step's loss, and whether ``block_until_ready``
    fences on this machine."""
    import jax

    trainer = _flagship_trainer(cfg)
    hits_before = hits.n
    compiled, (xd, yd, vd), compile_s = _compile_lm_step(trainer, cfg)
    cache_hits = hits.n - hits_before
    text = compiled.as_text()
    kernels = text.count("tpu_custom_call")
    memory = _memory_record(compiled)

    # one step through the executable just compiled, timed three ways: the
    # dispatch returns at once; block_until_ready must take the step's time;
    # a 4-byte fetch after it must then be immediate. (State is donated, so
    # the new state is swapped back into the trainer.)
    t0 = time.perf_counter()
    trainer.params, trainer.opt_state, loss, count = compiled(
        trainer.params, trainer.opt_state, xd, yd, vd
    )
    dispatch_s = time.perf_counter() - t0
    jax.block_until_ready((trainer.params, loss))
    ready_s = time.perf_counter() - t0
    loss_value = float(jax.device_get(loss))
    fetched_s = time.perf_counter() - t0
    fenced = (fetched_s - ready_s) < 0.25 * ready_s
    return {
        "program": "LongContextTrainer step, "
        + " ".join(f"{k}={v}" for k, v in cfg.items())
        + " bf16 no-remat dp=sp=1",
        "params_m": round(trainer.param_count / 1e6, 1),
        "compile_s": round(compile_s, 2),
        "cache_hits": cache_hits,
        "flash_kernels_in_hlo": kernels,
        "memory": memory,
        "loss": loss_value,
        "contributors": float(count),
        "fence": {
            "dispatch_returned_s": round(dispatch_s, 4),
            "block_until_ready_returned_s": round(ready_s, 4),
            "device_get_after_returned_s": round(fetched_s, 4),
            "block_until_ready_fences": fenced,
        },
        "checks": {
            "flash_kernel_in_compiled_step": kernels >= 2 * cfg["layers"],
            "fits_chip_memory": memory["argument_plus_temp_gb"] * 1e9
            < HBM_BYTES,
            "loss_finite": _finite([loss_value]),
        },
    }


def _train_lm_argv(cfg: dict, metrics: str) -> list[str]:
    return [
        "train-lm", "--d-model", str(cfg["d_model"]),
        "--heads", str(cfg["heads"]), "--layers", str(cfg["layers"]),
        "--seq-len", str(cfg["seq_len"]), "--batch", str(cfg["batch"]),
        "--vocab", str(cfg["vocab"]), "--bf16", "--dp", "1", "--sp", "1",
        "--lr", str(FLAGSHIP_LR), "--steps", "3", "--metrics-out", metrics,
    ]


def phase_lm_train(device_data: bool, cfg: dict = FLAGSHIP) -> dict:
    """Phases 4b / 4c: ``train-lm`` through the CLI — three host-loop steps,
    or one 3-step on-device chain (``--device-data``)."""
    import gc

    from akka_allreduce_tpu.__main__ import main as cli

    gc.collect()  # the previous phase's 4.8 GB of trainer state must be gone
    name = "lm_chain" if device_data else "lm_host"
    metrics = _fresh(os.path.join(OUT_DIR, f"{name}.jsonl"))
    argv = _train_lm_argv(cfg, metrics)
    if device_data:
        argv.append("--device-data")
    rc = cli(argv)
    events = _read_jsonl(metrics)
    steps = [e for e in events if e.get("kind") == "train_step"]
    summary = [e for e in events if e.get("kind") == "train_summary"]
    losses = [e["loss"] for e in steps]
    # the host loop logs an MFU per step; the chain logs one for the run
    mfus = [e.get("mfu") for e in (summary if device_data else steps)]
    rec = {
        "command": " ".join(argv),
        "losses": losses,
        "mfu": mfus,
        "checks": {
            "exit_code_0": rc == 0,
            "three_steps_logged": len(steps) == 3,
            "losses_finite": _finite(losses),
            "mfu_is_a_number": _finite(mfus),
        },
    }
    if not device_data:
        rec["step_time_s"] = [e["step_time_s"] for e in steps]
    return rec


def phase_mlp_train() -> dict:
    """Phase 5: ``train-mlp`` — the gradient-sync path of the paper."""
    from akka_allreduce_tpu.__main__ import main as cli

    metrics = _fresh(os.path.join(OUT_DIR, "mlp.jsonl"))
    argv = ["train-mlp", "--steps", "20", "--metrics-out", metrics]
    rc = cli(argv)
    steps = [
        e for e in _read_jsonl(metrics) if e.get("kind") == "train_step"
    ]
    losses = [e["loss"] for e in steps]
    return {
        "command": " ".join(argv),
        "loss_first": losses[0] if losses else None,
        "loss_last": losses[-1] if losses else None,
        "contributors": sorted({e["contributors"] for e in steps}),
        "checks": {
            "exit_code_0": rc == 0,
            "twenty_steps_in_metrics_jsonl": len(steps) == 20,
            "losses_finite": _finite(losses),
            "loss_falls": len(losses) == 20 and losses[-1] < losses[0],
        },
    }


# -- phases, four chips --------------------------------------------------------


def _device_ids(array) -> list[int]:
    return sorted({s.device.id for s in array.addressable_shards})


def _smoke_mesh(kind: str):
    from jax.sharding import PartitionSpec as P

    from akka_allreduce_tpu.parallel import grid_mesh, line_mesh

    if kind == "grid":
        return grid_mesh(2, 2), P(("rows", "cols"))
    return line_mesh(4), P("line")


def phase_allreduce(
    schedule: str, compress: str | None, mesh_kind: str, psum_total: dict,
    floats: int = ALLREDUCE_FLOATS, small: int = ALLREDUCE_SMALL_FLOATS,
) -> dict:
    """One schedule of ``build_threshold_allreduce`` over four chips with
    device 2 masked: against the jax.numpy masked sum (psum f32) or the
    psum schedule's result (the rest) at full size, and against numpy on
    the host at ``small`` floats."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from akka_allreduce_tpu.comm.allreduce import (
        build_threshold_allreduce,
        threshold_allreduce,
    )

    mesh, spec = _smoke_mesh(mesh_kind)
    sharded = NamedSharding(mesh, spec)
    mask = np.array([1.0, 1.0, 0.0, 1.0], np.float32)

    def seeded_row(key):  # row r is the same on either mesh: keyed on r
        row = lax.axis_index(spec[0])
        return jax.random.normal(
            jax.random.fold_in(key, row), (1, floats), jnp.float32
        )

    xs = jax.jit(
        jax.shard_map(seeded_row, mesh=mesh, in_specs=P(), out_specs=spec)
    )(jax.random.PRNGKey(SEED))
    valid = jax.device_put(mask, sharded)

    fn = build_threshold_allreduce(
        mesh, schedule=schedule, compress=compress, donate=False
    )
    t0 = time.perf_counter()
    compiled = fn.lower(xs, valid).compile()
    compile_s = time.perf_counter() - t0
    text = compiled.as_text()
    t0 = time.perf_counter()
    total, count = jax.block_until_ready(compiled(xs, valid))
    run_s = time.perf_counter() - t0

    replicated = NamedSharding(mesh, P())
    if schedule == "psum" and compress is None:
        want = jax.jit(
            lambda x, v: (x * v[:, None]).sum(0), out_shardings=replicated
        )(xs, valid)
        compared = "jax.numpy masked sum of the same inputs"
        psum_total["total"] = total
    else:
        want = jax.device_put(psum_total["total"], replicated)
        compared = "the psum schedule's result on the same inputs"

    @jax.jit
    def stats(total, want, count):
        return (
            jnp.max(jnp.abs(total - want)), jnp.max(jnp.abs(want)),
            jnp.min(count), jnp.max(count),
        )

    err, scale, cmin, cmax = (float(v) for v in stats(total, want, count))

    # the same schedule at a size the host can check with numpy
    host_x = np.random.default_rng(SEED).standard_normal(
        (4, small), dtype=np.float32
    )
    res = threshold_allreduce(
        mesh, host_x, mask, schedule=schedule, compress=compress
    )
    host_want = (host_x * mask[:, None]).sum(0)
    small_err = float(np.max(np.abs(np.asarray(res.sum) - host_want)))
    small_scale = float(np.max(np.abs(host_want)))
    small_counts = np.asarray(res.count)

    tol = REL_TOL[compress]
    return {
        "schedule": schedule,
        "compress": compress or "f32",
        "mesh": dict(mesh.shape),
        "floats_per_device": floats,
        "masked_device": 2,
        "compared": compared + f"; and numpy on the host at {small} floats",
        "compile_s": round(compile_s, 2),
        "first_run_s": round(run_s, 3),
        "collective_in_hlo": COLLECTIVE_IN_HLO[schedule],
        "input_device_ids": _device_ids(xs),
        "output_device_ids": _device_ids(total),
        "count": [cmin, cmax],
        "max_abs_err": err,
        "max_rel_err": err / scale,
        "small_max_abs_err": small_err,
        "small_max_rel_err": small_err / small_scale,
        "rel_tol": tol,
        "checks": {
            "mesh_has_four_devices": mesh.devices.size == 4,
            "input_on_four_devices": len(_device_ids(xs)) == 4,
            "output_on_four_devices": len(_device_ids(total)) == 4,
            "collective_in_compiled_text": COLLECTIVE_IN_HLO[schedule]
            in text,
            "count_is_exactly_3": cmin == cmax == 3.0
            and bool((small_counts == 3.0).all()),
            "matches_reference": err / scale <= tol,
            "matches_numpy_on_host": small_err / small_scale <= tol,
        },
    }


MASK_STEP_2 = (None, [1.0, 1.0, 0.0, 1.0], None)  # device 2 out on step 2


def _masked_steps(trainer, batches) -> dict:
    """Three steps with device 2 masked on the second; the replica check of
    utils/verify.py right after the masked step and again at the end."""
    import jax

    from akka_allreduce_tpu.utils import assert_replica_consistent

    losses, contributors, pairs = [], [], []
    for (x, y), valid in zip(batches, MASK_STEP_2):
        m = trainer.train_step(x, y, valid)
        losses.append(m.loss)
        contributors.append(m.contributors)
        if valid is not None or len(losses) == len(MASK_STEP_2):
            pairs.append(
                assert_replica_consistent(trainer.params, name="params")
            )
    param_ids = _device_ids(jax.tree.leaves(trainer.params)[0])
    return {
        "losses": losses,
        "contributors": contributors,
        "replica_pairs_compared_equal": pairs,
        "param_device_ids": param_ids,
        "checks": {
            "losses_finite": _finite(losses),
            "contributors_4_3_4": contributors == [4.0, 3.0, 4.0],
            "four_param_copies_equal": bool(pairs)
            and all(p > 0 for p in pairs),
            "params_on_four_devices": len(param_ids) == 4,
        },
    }


def phase_dp_mlp() -> dict:
    """``DPTrainer`` (MLP) over ``line_mesh(4)``, masked step included."""
    import numpy as np

    from akka_allreduce_tpu.models import MLP, data
    from akka_allreduce_tpu.parallel import line_mesh
    from akka_allreduce_tpu.train import DPTrainer

    trainer = DPTrainer(
        MLP(hidden=(128,), classes=10), line_mesh(4),
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        learning_rate=0.1, seed=SEED,
    )
    rec = _masked_steps(trainer, data.mnist_like(seed=SEED).batches(64, 3))
    rec["trainer"] = "DPTrainer(MLP 128) over line_mesh(4), batch 64"
    return rec


def phase_dp_lm(cfg: dict = FLAGSHIP) -> dict:
    """``LongContextTrainer --dp 4`` at the flagship width, masked step
    included; the compiled step must hold both the cross-chip all-reduce
    and the flash kernel."""
    from akka_allreduce_tpu.models import data

    trainer = _flagship_trainer(cfg, dp=4)
    compiled, _, compile_s = _compile_lm_step(trainer, cfg)
    text = compiled.as_text()
    rec = _masked_steps(
        trainer,
        data.lm_copy_task(cfg["seq_len"], vocab=cfg["vocab"], seed=SEED)
        .batches(cfg["batch"], 3),
    )
    rec["trainer"] = (
        "LongContextTrainer dp=4 sp=1, "
        + " ".join(f"{k}={v}" for k, v in cfg.items()) + " bf16"
    )
    rec["compile_s"] = round(compile_s, 2)
    rec["memory_per_device"] = _memory_record(compiled)
    rec["checks"]["all_reduce_in_compiled_step"] = "all-reduce" in text
    rec["checks"]["flash_kernel_in_compiled_step"] = (
        text.count("tpu_custom_call") >= 2 * cfg["layers"]
    )
    return rec


# -- children ------------------------------------------------------------------


def _child_main(name: str) -> int:
    flagship = "flagship LongContextTrainer step"
    if name == "multichip":
        _phase("device", 180, phase_device, 4)
        psum_total: dict = {}
        for schedule, compress, mesh_kind in SCHEDULES:
            label = f"allreduce_{schedule}_{compress or 'f32'}"
            _phase(
                label, 150, phase_allreduce, schedule, compress, mesh_kind,
                psum_total,
                program=f"build_threshold_allreduce(schedule={schedule!r}, "
                f"compress={compress!r}) over a {mesh_kind} mesh of 4",
            )
        _phase("dp_mlp_masked_steps", 150, phase_dp_mlp)
        _phase("dp_lm_masked_steps", 480, phase_dp_lm, program=flagship)
        return 0
    _phase("device", 180, phase_device, 1)
    hits = _CacheHits()
    if name == "second_process":
        _phase("lm_compiled_step", 200, phase_lm_compiled_step, hits,
               program=flagship)
        return 0
    _phase("threshold_reduce", 120, phase_threshold_reduce)
    _phase("local_demo", 240, phase_local_demo)
    _phase("flash_attention", 180, phase_flash_attention)
    _phase("lm_compiled_step", 300, phase_lm_compiled_step, hits,
           program=flagship)
    _phase("lm_train_host_loop", 200, phase_lm_train, False,
           program="train-lm, 3 host-loop steps")
    _phase("lm_train_device_chain", 300, phase_lm_train, True,
           program="train-lm --device-data, one 3-step chain")
    _phase("mlp_train", 120, phase_mlp_train)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

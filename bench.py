"""Benchmark entrypoint — prints ONE JSON line:
    {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ...}

Measures the primary BASELINE metric (allreduce bus bandwidth, BASELINE.md):
the threshold-masked allreduce over a 64M-float buffer (config 2's size,
BASELINE.json:8) across every visible device.

- n devices >= 2: bus bandwidth 2*(n-1)/n * bytes / t of the ICI collective.
- n == 1 (the single-chip CI reality): a 1-device psum folds to a no-op, so we
  measure the round's actual reduction work instead — K=8 virtual workers'
  payloads threshold-reduced and elastic-averaged on-chip via the fused
  Pallas kernel (ops/local_reduce.py: one HBM pass instead of XLA's two),
  with the buffer updated every iteration so nothing hoists out of the
  timing loop. This is the direct analog of the reference's local-worker
  configs (BASELINE.json:7: "4 local JVM workers" reducing inside one JVM);
  value is input bytes reduced per second. Set BENCH_XLA=1 to time the
  unfused XLA lowering of the same op for comparison.

Timing discipline (left as is for the benchmark issue, ROADMAP Design 4):
- benchmark data is generated ON DEVICE;
- sync is a 4-byte ``device_get`` of the result;
- the collective is iterated inside one jitted ``fori_loop`` with a *traced*
  trip count, and per-iteration time is the slope between a short and a long
  run: ``(t(inner_hi) - t(inner_lo)) / (inner_hi - inner_lo)``, so constant
  dispatch overhead cancels in the difference;
- the reported value is the MEDIAN of per-pair slopes over several
  interleaved reps. The JSON line carries ``spread_pct`` (IQR/median of the
  slope samples) and the metric name gains a ``_NOISY`` suffix when it
  exceeds BENCH_MAX_SPREAD_PCT (default 15) — a loud flag, still valid JSON.

The backend is whatever ``jax.devices()`` finds (``JAX_PLATFORMS`` from the
environment decides); if it cannot initialize, the run dies with JAX's error.

vs_baseline: the reference's data plane is JVM float chunks over Netty TCP
(SURVEY.md §3); its hard ceiling is 10 GbE wire speed = 1.25 GB/s, used as the
nominal reference value since the reference publishes no numbers
(BASELINE.json:13 "published": {}).
"""

from __future__ import annotations

import json
import os
import sys
import time

REFERENCE_GBPS = 1.25  # 10 GbE ceiling of the reference's Netty data plane


def _adapt_trail() -> dict | None:
    """Per-round policy trail of the adaptive controller, read from the
    obs registry (``adapt.*`` + ``wire.*`` error counters) when anything
    in-process drove it — so an A/B pair of BENCH json lines can
    attribute a throughput shift to degradation mode changes. None (field
    omitted) when no controller ran: the common bench path is unchanged."""
    try:
        from akka_allreduce_tpu.obs.metrics import REGISTRY
    except Exception:
        return None
    snap = REGISTRY.snapshot()
    trail = {
        k.split(".", 1)[1]: v
        for k, v in snap.items()
        if k.startswith("adapt.") and not isinstance(v, dict)
    }
    if not any(trail.values()):
        return None
    for k in ("wire.f16_clipped", "wire.int8_residual_l1"):
        if snap.get(k):
            trail[k] = round(snap[k], 3) if isinstance(snap[k], float) else snap[k]
    return trail


def _emit(metric: str, value: float, **extra) -> None:
    adapt = _adapt_trail()
    if adapt is not None:
        extra["adapt"] = adapt
    print(
        json.dumps(
            {
                "metric": metric,
                "value": round(value, 3),
                "unit": "GB/s",
                "vs_baseline": round(value / REFERENCE_GBPS, 3),
                **extra,
            }
        ),
        flush=True,
    )


def main() -> None:
    num_floats = int(os.environ.get("BENCH_FLOATS", 64 * 1024 * 1024))
    inner_lo = int(os.environ.get("BENCH_INNER_LO", 5))
    inner_hi = int(os.environ.get("BENCH_INNER_HI", 405))
    outer = int(os.environ.get("BENCH_OUTER", 8))
    max_spread = float(os.environ.get("BENCH_MAX_SPREAD_PCT", 15.0))
    mfloat = num_floats // (1024 * 1024)

    from akka_allreduce_tpu.utils import enable_compile_cache

    enable_compile_cache()

    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from akka_allreduce_tpu.comm.allreduce import masked_psum
    from akka_allreduce_tpu.parallel import line_mesh

    devices = jax.devices()
    n = len(devices)
    print(
        f"devices={n} ({devices[0].platform}), floats={num_floats}, "
        f"inner={inner_lo}/{inner_hi}",
        file=sys.stderr,
    )

    def sync(x) -> None:
        # 4-byte forced round trip: fetch one element of one local shard
        shard = x.addressable_shards[0].data
        jax.device_get(jnp.ravel(shard)[:1])

    if n >= 2:
        mesh = line_mesh(n)
        spec = P("line")
        per_dev = num_floats

        @jax.jit
        def init():
            xs = jax.random.normal(
                jax.random.PRNGKey(0), (n, per_dev), jnp.float32
            )
            return (
                jax.device_put(xs, NamedSharding(mesh, spec)),
                jax.device_put(jnp.ones((n,)), NamedSharding(mesh, spec)),
            )

        def kernel(x, valid, trips):
            v = valid.reshape(())

            def body(_, carry):
                s, c = masked_psum(carry, v, ("line",))
                avg = s / jnp.maximum(c, 1.0)
                return lax.pcast(avg, "line", to="varying")

            return lax.fori_loop(
                0, trips.reshape(()), body, x.reshape(x.shape[-1])
            )[None]

        fn = jax.jit(
            jax.shard_map(
                kernel,
                mesh=mesh,
                in_specs=(spec, spec, P()),
                out_specs=spec,
            )
        )
        metric = f"allreduce_bus_bw_{mfloat}Mfloat"
        scale = 2.0 * (n - 1) / n * num_floats * 4
    else:
        K = 8  # virtual local workers reduced on the one chip
        per_worker = num_floats // K

        @jax.jit
        def init():
            return (
                jax.random.normal(
                    jax.random.PRNGKey(0), (K, per_worker), jnp.float32
                ),
                jnp.ones((K,)),
            )

        use_xla = os.environ.get("BENCH_XLA", "0") == "1"
        alpha = jnp.float32(0.125)

        if use_xla:

            def kernel(X, V, trips):
                c = jnp.maximum(V.sum(), 1.0)

                def body(_, X):
                    avg = (X * V[:, None]).sum(0) / c
                    return (1.0 - alpha) * X + alpha * avg[None]

                return lax.fori_loop(0, trips, body, X)

        else:
            from akka_allreduce_tpu.ops import (
                elastic_average_step,
                pack_tiles,
                unpack_tiles,
            )

            def kernel(X, V, trips):
                # carry the PRE-TILED form through the loop: reshaping inside
                # the body defeats the kernel's input/output aliasing across
                # the fori_loop carry (3x slower, ops/local_reduce.py)
                def body(_, Xt):
                    return elastic_average_step(Xt, V, alpha)

                out = lax.fori_loop(0, trips, body, pack_tiles(X))
                return unpack_tiles(out, X.shape[1])

        fn = jax.jit(kernel)
        metric = f"local_threshold_reduce_bw_{mfloat}Mfloat"
        scale = K * per_worker * 4

    args = init()
    sync(args[0])

    def run(trips: int) -> float:
        t0 = time.perf_counter()
        out = fn(*args, jnp.int32(trips))
        sync(out)
        return time.perf_counter() - t0

    from akka_allreduce_tpu.utils.benchmarking import median_slope

    def timed(trips: int) -> float:
        t = run(trips)
        print(f"t({trips})={t * 1e3:.1f}ms", file=sys.stderr)
        return t

    est = median_slope(timed, inner_lo, inner_hi, outer=outer)
    dt = est.seconds_per_iter

    if dt <= 0:
        _emit(f"allreduce_bench_UNMEASURABLE_{mfloat}Mfloat", 0.0)
        return
    if est.noisy(max_spread):
        metric += "_NOISY"  # loud flag: estimate unstable beyond tolerance
    _emit(
        metric,
        scale / dt / 1e9,
        spread_pct=est.spread_pct,
        n_samples=est.n_samples,
    )


if __name__ == "__main__":
    main()

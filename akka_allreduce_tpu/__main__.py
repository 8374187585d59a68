"""CLI bootstrap — the reference's per-role ``main`` classes + run scripts
(SURVEY.md §2 L4, §3 "Bootstrap mains + scripts") as one argparse entrypoint:

    python -m akka_allreduce_tpu local-demo   --nodes 4 --size 1000000
    python -m akka_allreduce_tpu cluster-master --port 7070 --nodes 2 --rounds 20
    python -m akka_allreduce_tpu cluster-node --seed 127.0.0.1:7070
    python -m akka_allreduce_tpu train-mlp    --steps 100 --batch 64
    python -m akka_allreduce_tpu train-resnet --steps 5 --bucket 262144
    python -m akka_allreduce_tpu train-lm     --steps 30 --seq-len 256 --impl ring
    python -m akka_allreduce_tpu elastic-demo --steps 30 --drop-at 10 --rejoin-at 20

``local-demo`` is the reference's single-process N-worker fixture (BASELINE
config 1) on the host engine; the rest run the XLA data plane on whatever
devices are visible (TPU chips, or a virtual CPU mesh via
``XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu``).
"""

from __future__ import annotations

import argparse
import logging
import sys
import time


def _add_obs_flags(p: argparse.ArgumentParser) -> None:
    """Observability flags shared by the cluster roles (obs/ — OBSERVABILITY.md)."""
    p.add_argument(
        "--trace-out",
        default=None,
        metavar="FILE",
        help="write this process's spans as Chrome/Perfetto trace_event "
        "JSON on exit (merge multiple processes' files with "
        "`obs merge-trace`)",
    )
    p.add_argument(
        "--flight-dir",
        default=None,
        metavar="DIR",
        help="arm the flight recorder: dump a post-mortem JSONL here on "
        "unhandled crash or SIGUSR1 (SIGUSR1 dumps, then kills the "
        "process — kill-with-post-mortem); AKKA_OBS_DIR is the env "
        "equivalent",
    )


def _install_obs(args) -> None:
    if getattr(args, "flight_dir", None):
        from akka_allreduce_tpu.obs import flight

        flight.install(args.flight_dir, signal_exit=True)


def _write_trace(args) -> None:
    if getattr(args, "trace_out", None):
        from akka_allreduce_tpu.obs import trace as obs_trace

        path = obs_trace.write_chrome_trace(args.trace_out)
        print(f"trace written to {path}", flush=True)


def _add_profile_flag(p: argparse.ArgumentParser) -> None:
    p.add_argument(
        "--profile-dir",
        default=None,
        help="capture a jax.profiler trace of the step loop (SURVEY.md §6); "
        "view with tensorboard or xprof. train_step's spans (trainer.step*) "
        "sit in its host plane beside the device's ops",
    )


def _profile(args):
    """The step loop's profiler session (``--profile-dir``), or nothing."""
    import contextlib

    if getattr(args, "profile_dir", None):
        import jax

        return jax.profiler.trace(args.profile_dir)
    return contextlib.nullcontext()


def _add_chaos_flags(p: argparse.ArgumentParser) -> None:
    """Chaos + retry-policy flags for the cluster master roles. The chaos
    spec is distributed to every node via Welcome (like every other knob),
    so ONE master flag arms the whole cluster with the same seed; the
    retry policy travels the same way (RESILIENCE.md)."""
    p.add_argument(
        "--chaos-seed", type=int, default=0,
        help="seed of the deterministic chaos schedule (same seed -> same "
        "per-process event log)",
    )
    p.add_argument(
        "--chaos-spec", default="",
        metavar="SPEC",
        help="fault spec, e.g. 'drop:p=0.05;delay:ms=20;corrupt:p=0.01;"
        "partition:groups=m+0|1,at=round10,heal=5s' (empty = chaos off)",
    )
    p.add_argument(
        "--chaos-log", default=None, metavar="FILE",
        help="write this process's chaos event log (JSONL, deterministic "
        "per seed) here on exit",
    )
    p.add_argument(
        "--send-retries", type=int, default=1,
        help="transport reconnect-resend budget per failure burst "
        "(exponential backoff + full jitter; 0 = fail fast)",
    )
    p.add_argument(
        "--send-backoff-base", type=float, default=0.05,
        help="base backoff seconds (doubles per retry, capped)",
    )
    p.add_argument(
        "--send-backoff-max", type=float, default=2.0,
        help="backoff cap in seconds",
    )


def _add_gossip_flags(p: argparse.ArgumentParser) -> None:
    """SWIM gossip membership (control/gossip.py, RESILIENCE.md 'Tier 6').
    Master-role flags: the section rides Welcome, so one flag switches the
    whole cluster from hub heartbeats to decentralized probing."""
    p.add_argument(
        "--gossip", action="store_true",
        help="decentralized membership: nodes probe each other (SWIM "
        "ping / ping-req / suspicion) instead of all heartbeating into "
        "the master's phi detector; the master consumes the gossip view",
    )
    p.add_argument(
        "--gossip-interval", type=float, default=0.5, metavar="S",
        help="gossip probe period in seconds (ack timeout is 0.3x this; "
        "suspicion confirms after 4 unrefuted periods)",
    )


def _gossip_config_from(args):
    import math

    from akka_allreduce_tpu.config import GossipConfig

    if not getattr(args, "gossip", False):
        return GossipConfig()
    interval = getattr(args, "gossip_interval", 0.5)
    return GossipConfig(
        enabled=True,
        probe_interval_s=interval,
        probe_timeout_s=interval * 0.3,
        # keep the suspicion window >= ~2s regardless of the probe
        # cadence: a short interval should mean fast PROBING, not a
        # hair-trigger conviction — a loaded host can stall a healthy
        # process past 1s (GIL, checkpoint fsync), and refutation needs
        # time to travel
        suspicion_periods=max(4, math.ceil(2.0 / interval)),
        seed=getattr(args, "chaos_seed", 0),
    )


def _add_adapt_flags(p: argparse.ArgumentParser) -> None:
    """Closed-loop adaptive degradation (control/adapt.py, RESILIENCE.md
    'Tier 5'): the leader's per-round controller. Master-role flags only —
    workers need no config, the policy rides every Prepare/Start."""
    p.add_argument(
        "--adapt", action="store_true",
        help="enable the per-round adaptive controller: degrade th_reduce "
        "and wire precision (f16 -> int8) when straggler evidence grows, "
        "restore when the tail recovers",
    )
    p.add_argument(
        "--adapt-floor", type=float, default=0.5,
        help="th_reduce never degrades below this fraction",
    )
    p.add_argument(
        "--adapt-window", type=int, default=8,
        help="round completions per controller decision",
    )
    p.add_argument(
        "--adapt-dwell", type=int, default=16,
        help="minimum rounds at a level before the next transition "
        "(the anti-flap hysteresis dwell)",
    )
    p.add_argument(
        "--adapt-lag", type=int, default=12,
        help="worker contribution lag (rounds) that triggers a degrade; "
        "restore requires lag back under a third of this (min 1)",
    )
    p.add_argument(
        "--adapt-log", default=None, metavar="FILE",
        help="write the controller's decision log (JSONL, logical fields "
        "only — same evidence replays the same bytes) here on exit",
    )


def _adapt_config_from(args):
    from akka_allreduce_tpu.config import AdaptConfig

    if not getattr(args, "adapt", False):
        return AdaptConfig()
    lag = max(2, args.adapt_lag)
    return AdaptConfig(
        enabled=True,
        floor_th_reduce=args.adapt_floor,
        window=args.adapt_window,
        min_dwell=args.adapt_dwell,
        lag_degrade=lag,
        lag_restore=max(1, lag // 3),
    )


def _add_wire_dtype_flag(p: argparse.ArgumentParser) -> None:
    """TCP wire compression for the host data plane (cluster masters only —
    the knob is distributed to every node via Welcome)."""
    p.add_argument(
        "--wire-dtype",
        choices=("f32", "f16"),
        default="f32",
        help="float width of Scatter/ReduceBlock payloads on the TCP wire; "
        "f16 halves the network bytes (accumulation stays f32)",
    )


def _add_data_plane_flags(p: argparse.ArgumentParser) -> None:
    """Host data-plane sharding knobs (cluster masters only — distributed
    to every node via Welcome, like --wire-dtype)."""
    p.add_argument(
        "--streams", type=int, default=1,
        help="parallel TCP sockets per peer endpoint: stream 0 carries "
        "control (ordering preserved, byte-identical legacy wire), "
        "payload frames stripe across streams 1..N-1 by chunk id, each "
        "drained by a dedicated sender thread running deferred "
        "encode/checksum/sendmmsg off the event loop "
        "(BENCHMARKS.md round 8); 1 = the legacy single-socket plane",
    )
    p.add_argument(
        "--pump-pool", type=int, default=0,
        help="worker threads for INBOUND decode offload of >=4MB bodies "
        "(0 = auto: streams x endpoints, capped at 8)",
    )
    # the data plane v3 levers (BENCHMARKS.md round 9) — each independently
    # gated, defaulting off, riding Welcome like every knob above
    p.add_argument(
        "--uring", action="store_true",
        help="drain sender-thread bursts through io_uring (one ring "
        "submission per burst; runtime-probed — kernels without it fall "
        "back to the sendmmsg/sendmsg path, byte-identical)",
    )
    p.add_argument(
        "--intra-chunk", type=int, default=0, metavar="BYTES",
        dest="intra_chunk",
        help="split payload frames at/above this many encoded bytes into "
        "sub-frames striped across the payload streams (needs --streams "
        ">= 3 to actually split; 0 = off) — a one-chunk round stops "
        "serializing onto one socket",
    )
    p.add_argument(
        "--congestion", action="store_true",
        help="congestion-aware stripe scheduling: per-stream drain "
        "evidence shifts assignment weight away from a persistently slow "
        "stream (deficit-weighted, hysteresis both edges)",
    )


def _add_sharded_compress_flag(p: argparse.ArgumentParser) -> None:
    """--compress/--overlap for the sharded-param trainers (train-lm/-moe/-pp)."""
    p.add_argument(
        "--compress",
        choices=("bf16", "int8"),
        default=None,
        help="gradient wire compression: bf16 runs each sharding class's "
        "grouped psum at half width; int8 rides the explicit ring "
        "(per-segment scales) over each class's reduce axes at a quarter",
    )
    p.add_argument(
        "--overlap",
        action="store_true",
        help="issue one grad collective per param leaf INSIDE the backward "
        "pass (each over the leaf's replication axes) so the latency-hiding "
        "scheduler can run comm behind compute; composes with --compress",
    )


def _add_mesh_flags(p: argparse.ArgumentParser) -> None:
    p.add_argument("--devices", type=int, default=None, help="mesh size (default: all)")
    p.add_argument(
        "--mesh",
        choices=("line", "grid"),
        default="line",
        help="1D line or 2D butterfly grid (SURVEY.md §4.3)",
    )


def _make_mesh(args):
    import jax

    from akka_allreduce_tpu.parallel import grid_mesh, line_mesh

    if args.mesh == "grid":
        devs = None if args.devices is None else jax.devices()[: args.devices]
        return grid_mesh(devices=devs)
    return line_mesh(args.devices)


def _cmd_local_demo(argv: list[str]) -> int:
    from akka_allreduce_tpu.control.local import _main

    sys.argv = ["local-demo", *argv]
    _main()
    return 0


def _basic_train_flags(p: argparse.ArgumentParser) -> None:
    """The shared core every DP training CLI carries — train-zero1 uses
    exactly this subset, so its defaults can never drift from train-mlp's
    (the advertised numerical equivalence depends on them)."""
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=64, help="global batch size")
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--metrics-out", default=None, help="JSONL metrics path")
    _checkpoint_flags(p)


def _checkpoint_flags(p: argparse.ArgumentParser) -> None:
    """--checkpoint-* flags — ONE definition so every training CLI gets
    the same set (including --async-checkpoint)."""
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument("--checkpoint-every", type=int, default=0)
    p.add_argument(
        "--async-checkpoint",
        action="store_true",
        help="save checkpoints WITHOUT stalling the step loop: capture is "
        "an on-device copy + async device-to-host launch (shard-local for "
        "ZeRO-1/FSDP/PP — no gather), serialization runs off-thread (a "
        "save still in flight at the next interval is skipped, not queued)",
    )
    p.add_argument(
        "--delta-checkpoint",
        action="store_true",
        help="per-leaf content-addressed store instead of Orbax: a save "
        "writes only leaves whose bytes changed since any kept checkpoint "
        "(unchanged leaves cost one hash, zero bytes — size saves to a "
        "slow link); composes with --async-checkpoint for non-stalling "
        "link-sized saves",
    )


def _make_checkpointer(args):
    """The checkpointer the --checkpoint-* flags ask for."""
    from akka_allreduce_tpu.train import (
        AsyncDeltaCheckpointer,
        AsyncTrainerCheckpointer,
        DeltaCheckpointer,
        TrainerCheckpointer,
    )

    is_async = getattr(args, "async_checkpoint", False)
    if getattr(args, "delta_checkpoint", False):
        cls = AsyncDeltaCheckpointer if is_async else DeltaCheckpointer
    else:
        cls = AsyncTrainerCheckpointer if is_async else TrainerCheckpointer
    return cls(args.checkpoint_dir)


def _train_flags(p: argparse.ArgumentParser) -> None:
    _add_mesh_flags(p)
    _basic_train_flags(p)
    p.add_argument("--bucket", type=int, default=None, help="grad bucket (elements)")
    _add_profile_flag(p)
    p.add_argument(
        "--device-data",
        action="store_true",
        help="sample batches ON DEVICE inside one jitted chain (no host I/O "
        "per step — the right mode over a slow host<->device link)",
    )
    p.add_argument(
        "--bf16",
        action="store_true",
        help="bfloat16 activations/matmuls, fp32 params (MXU-native dtype)",
    )
    p.add_argument(
        "--accum",
        type=int,
        default=1,
        help="gradient-accumulation microbatches per step: one collective "
        "per effective batch, bigger batches in fixed memory",
    )
    p.add_argument(
        "--compress",
        choices=("bf16", "int8"),
        default=None,
        help="gradient wire compression: bf16 halves the collective bytes "
        "(psum); int8 quarters them (explicit ring, 1D mesh only; "
        "optimizer state stays fp32 either way)",
    )
    p.add_argument(
        "--error-feedback",
        action="store_true",
        help="carry each device's compression residual into its next "
        "contribution (EF-SGD): lossy sync becomes unbiased over time and a "
        "threshold-dropped device's gradient is delayed, not lost "
        "(requires --compress)",
    )
    p.add_argument(
        "--overlap",
        action="store_true",
        help="issue one grad collective per param leaf INSIDE the backward "
        "pass so the latency-hiding scheduler can run comm behind compute "
        "(SURVEY.md §8.4; composes with --compress bf16; excludes --bucket, "
        "int8, --error-feedback)",
    )


def _mfu_fields(flops_per_step, sec_per_step, n_devices: int = 1) -> dict:
    """tflops/mfu JSONL+print fields (empty off-TPU or without a FLOP model).

    MFU convention: GLOBAL model FLOPs (no remat recompute) over the mesh's
    aggregate dense bf16 peak — utils/benchmarking.py's conventions
    (VERDICT r2 #1).
    """
    from akka_allreduce_tpu.utils.benchmarking import device_peak_flops, mfu

    if not flops_per_step or not sec_per_step or sec_per_step <= 0:
        return {}
    out = {"tflops_per_s": round(flops_per_step / sec_per_step / 1e12, 2)}
    u = mfu(
        flops_per_step, sec_per_step, device_peak_flops(),
        n_devices=n_devices,
    )
    if u is not None:
        out["mfu"] = round(u, 4)
    return out


def _mfu_note(fields: dict) -> str:
    if "mfu" in fields:
        return f"; {fields['tflops_per_s']} TFLOP/s, MFU {fields['mfu']:.1%}"
    if fields.get("tflops_per_s", 0) >= 0.01:
        return f"; {fields['tflops_per_s']} TFLOP/s"
    return ""


def _run_training_chain(trainer, ds, args, *, label: str, flops_per_step=None) -> int:
    """On-device block training: steps run in jitted blocks with no per-step
    host I/O. Honors the same checkpoint/profile/metrics flags as the host
    loop (checkpoints land between blocks of ``--checkpoint-every`` steps)."""
    import numpy as np

    from akka_allreduce_tpu.utils.metrics import MetricsLogger

    shards = trainer.data_shards
    if args.batch % shards:
        raise SystemExit(
            f"global batch {args.batch} not divisible by {shards} data shards"
        )
    if getattr(args, "accum", 1) != 1:
        raise SystemExit(
            "--accum is not supported with --device-data (the on-device "
            "chain samples fixed per-device batches); drop one of the flags"
        )
    profile = _profile(args)
    ckpt = None
    if args.checkpoint_dir:
        ckpt = _make_checkpointer(args)
        if ckpt.latest_step() is not None:
            step = ckpt.restore(trainer)
            print(f"resumed from step {step}")

    logger = MetricsLogger(args.metrics_out)
    sampler = ds.device_sampler()
    if ckpt and getattr(sampler, "diverges_from_host_stream", False):
        print(
            "warning: this dataset's device sampler regenerates templates on "
            "device; a checkpoint from the host loop continues on a "
            "DIFFERENT synthetic task"
        )
    per_dev = args.batch // shards
    block = (
        args.checkpoint_every
        if ckpt and args.checkpoint_every
        else args.steps
    )
    history = []
    t0 = time.perf_counter()
    with profile:
        remaining = args.steps
        while remaining > 0:
            n = min(block, remaining)
            history.extend(trainer.train_chain(sampler, n, per_dev))
            remaining -= n
            if ckpt and remaining > 0:
                ckpt.save(trainer)
    total = time.perf_counter() - t0
    if ckpt:
        ckpt.save(trainer, force=True, block=True)
        ckpt.close()
    for m in history:
        logger.log_event(
            kind="train_step", workload=label, step=m.step, loss=m.loss,
            contributors=m.contributors,
        )
    losses = [m.loss for m in history]
    # amortized time still includes compile, so this MFU is a LOWER bound
    perf = _mfu_fields(
        flops_per_step, total / max(len(losses), 1), trainer.n_devices
    )
    if perf:
        logger.log_event(
            kind="train_summary", workload=label, steps=len(losses),
            amortized_incl_compile=True, **perf,
        )
    logger.close()
    trend = (
        f"loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f}"
        if losses
        else "no steps taken"
    )
    print(
        f"{label}: {len(losses)} on-device steps on {trainer.n_devices} "
        f"devices in {total:.2f}s incl. compile "
        f"({total / max(len(losses), 1) * 1e3:.1f} ms/step amortized)"
        f"{_mfu_note(perf)}; {trend}"
    )
    return 0


def _run_training(trainer, ds, args, *, label: str, flops_per_step=None) -> int:
    import numpy as np

    from akka_allreduce_tpu.utils.metrics import MetricsLogger

    if getattr(args, "device_data", False):
        return _run_training_chain(
            trainer, ds, args, label=label, flops_per_step=flops_per_step
        )

    profile = _profile(args)

    logger = MetricsLogger(args.metrics_out)
    ckpt = None
    if args.checkpoint_dir:
        ckpt = _make_checkpointer(args)
        if ckpt.latest_step() is not None:
            step = ckpt.restore(trainer)
            print(f"resumed from step {step}")
    accum = getattr(args, "accum", 1)
    if accum < 1:
        raise SystemExit(f"--accum must be >= 1, got {accum}")
    # step count, tokens, last loss and step time reach the process registry
    # from inside the sharded-LM trainers' train_step; MFU from here, at the end
    from akka_allreduce_tpu.obs.metrics import REGISTRY

    t0 = time.perf_counter()
    losses = []
    with profile:
        for x, y in ds.batches(args.batch, args.steps):
            st = time.perf_counter()
            if accum > 1:
                m = trainer.train_step_accum(x, y, accum)
            else:
                m = trainer.train_step(x, y)
            dt = time.perf_counter() - st
            losses.append(m.loss)
            logger.log_event(
                kind="train_step", workload=label, step=m.step, loss=m.loss,
                contributors=m.contributors, step_time_s=round(dt, 6),
                **_mfu_fields(flops_per_step, dt, trainer.n_devices),
            )
            if ckpt and args.checkpoint_every and m.step % args.checkpoint_every == 0:
                ckpt.save(trainer)
    total = time.perf_counter() - t0
    if ckpt:
        ckpt.save(trainer, force=True, block=True)
        ckpt.close()
    # host-loop step time includes per-step host<->device I/O, so this MFU
    # is a floor; --device-data measures the on-device figure
    perf = _mfu_fields(
        flops_per_step, total / max(len(losses), 1), trainer.n_devices
    )
    if perf:
        if "mfu" in perf:
            REGISTRY.gauge("trainer.mfu").set(perf["mfu"])
        REGISTRY.gauge("trainer.tflops_per_s").set(perf["tflops_per_s"])
        logger.log_event(
            kind="train_summary", workload=label, steps=len(losses),
            host_loop=True, **perf,
        )
    logger.log_snapshot(REGISTRY, workload=label)
    logger.close()
    trend = (
        f"loss {losses[0]:.4f} -> {np.mean(losses[-5:]):.4f}"
        if losses
        else "no steps taken"
    )
    print(
        f"{label}: {len(losses)} steps on {trainer.n_devices} devices in "
        f"{total:.2f}s ({total / max(len(losses), 1) * 1e3:.1f} ms/step)"
        f"{_mfu_note(perf)}; {trend}"
    )
    return 0


def _cmd_train_zero1(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-zero1",
        description="MLP/MNIST DP-SGD with ZeRO-1 sharded optimizer state "
        "(optimizer memory / n_devices; numerically identical to train-mlp "
        "with the same optimizer — tests/test_zero1.py)",
    )
    p.add_argument("--devices", type=int, default=None, help="1D mesh size")
    _basic_train_flags(p)
    p.add_argument("--hidden", type=int, nargs="+", default=[128])
    p.add_argument(
        "--compress",
        choices=("bf16",),
        default=None,
        help="bf16 wire on the gradient reduce-scatter (weights' all_gather "
        "stays f32)",
    )
    p.add_argument(
        "--error-feedback",
        action="store_true",
        help="carry the bf16 cast residual into the next contribution "
        "(requires --compress bf16; costs no extra collective here)",
    )
    args = p.parse_args(argv)

    import numpy as np
    import optax

    from akka_allreduce_tpu.models import MLP, data
    from akka_allreduce_tpu.parallel import line_mesh
    from akka_allreduce_tpu.train import Zero1DPTrainer

    trainer = Zero1DPTrainer(
        MLP(hidden=tuple(args.hidden), classes=10),
        line_mesh(args.devices),
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        # SGD to match train-mlp's default (the trainer's own default is
        # adam, which the CLI's lr=0.1 default would destabilize) — this is
        # what makes the advertised train-mlp equivalence hold
        optimizer=optax.sgd(args.lr),
        compress=args.compress,
        error_feedback=args.error_feedback,
    )
    print(
        f"ZeRO-1: {trainer.param_count / 1e3:.1f}K params, optimizer shard "
        f"{trainer.optimizer_shard_elems} elems/device on "
        f"{trainer.n_devices} devices"
    )
    from akka_allreduce_tpu.utils.benchmarking import dense_train_flops

    return _run_training(
        trainer, data.mnist_like(), args, label="zero1_mnist",
        flops_per_step=dense_train_flops(trainer.param_count, args.batch),
    )


def _cmd_train_fsdp(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-fsdp",
        description="FSDP / ZeRO-3 Transformer LM: trunk params AND "
        "optimizer state sharded 1/n over the data mesh, one layer gathered "
        "at a time inside the scan (train/fsdp.py; numerics match the dense "
        "model — tests/test_fsdp.py)",
    )
    p.add_argument("--devices", type=int, default=None, help="1D mesh size")
    _basic_train_flags(p)
    p.set_defaults(lr=1e-2)  # adam on an LM: the MLP-SGD default 0.1 diverges
    p.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel shards (FSDP x SP over a (data, seq) mesh; "
        "params still shard over the WHOLE mesh)",
    )
    p.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel shards (FSDP x TP over a (data, model[, seq]) "
        "mesh: attention heads / MLP hidden split Megatron-style over "
        "`model` while each shard's slice still FSDP-shards 1/(dp*sp))",
    )
    p.add_argument(
        "--impl", choices=("ring", "ulysses"), default="ring",
        help="attention schedule over the seq axis (with --sp > 1)",
    )
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument(
        "--kv-heads", type=int, default=None,
        help="grouped-query attention: K/V heads (divides --heads; 1 = MQA)",
    )
    p.add_argument("--layers", type=int, default=2)
    p.add_argument(
        "--remat",
        nargs="?",
        const="full",
        default=False,
        choices=("full", "params"),
        help="'full' (also bare --remat): recompute each layer on backward "
        "— one layer's activations AND one layer's gathered params live at "
        "a time, the full FSDP memory profile. 'params': drop the gathered "
        "layers and re-gather on backward — matmul activations stay saved, "
        "no matmul recompute (the ZeRO-3 sweet spot when activations fit)",
    )
    p.add_argument(
        "--compress",
        choices=("bf16", "int8"),
        default=None,
        help="per-layer collective compression: bf16 halves FSDP's "
        "collective bytes (gather + reduce-scatter transpose); int8 "
        "quarters them — one quantization per shard on the forward "
        "gather, sequential per-axis per-hop-scaled ring reduce-scatters "
        "on backward (composes with --sp; master params/moments stay "
        "f32 either way)",
    )
    p.add_argument(
        "--prefetch",
        action="store_true",
        help="software-pipeline the gathers: layer k+1's all_gather issues "
        "before layer k's compute so the latency-hiding scheduler can "
        "overlap them (same math, one extra gathered layer live). With "
        "--remat params the trunk unrolls so BACKWARD re-gathers overlap "
        "too; excludes --remat full",
    )
    p.add_argument(
        "--device-data",
        action="store_true",
        help="sample token batches ON DEVICE inside one jitted chain "
        "(no host I/O per step)",
    )
    args = p.parse_args(argv)

    import jax

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.parallel import data_seq_mesh, line_mesh
    from akka_allreduce_tpu.train import FSDPLMTrainer

    n = args.devices or len(jax.devices())
    if n % (args.sp * args.tp):
        p.error(
            f"--sp {args.sp} x --tp {args.tp} does not divide the device "
            f"count {n}; devices would be silently idled"
        )
    if args.tp > 1 and args.sp > 1:
        # the canonical 3-axis layout (model innermost: TP's per-layer
        # psums are the most latency-sensitive collectives)
        from akka_allreduce_tpu.parallel import data_seq_model_mesh

        mesh = data_seq_model_mesh(
            n // (args.sp * args.tp), args.sp, args.tp
        )
    elif args.tp > 1:
        mesh = jax.make_mesh(
            (n // args.tp, args.tp), ("data", "model"),
            devices=jax.devices()[:n],
        )
    elif args.sp > 1:
        mesh = data_seq_mesh(n // args.sp, args.sp)
    else:
        mesh = line_mesh(args.devices)
    trainer = FSDPLMTrainer(
        mesh,
        vocab=args.vocab,
        d_model=args.d_model,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        n_layers=args.layers,
        seq_len=args.seq_len,
        seq_impl=args.impl,
        learning_rate=args.lr,
        remat=args.remat,
        compress=args.compress,
        prefetch=args.prefetch,
    )
    print(
        f"FSDP: {trainer.param_count / 1e3:.1f}K params, trunk shard "
        f"{trainer.trunk_shard_elems} elems/device, mesh "
        f"dp={trainer.dp} x tp={trainer.tp} x sp={trainer.sp}"
    )
    ds = data.lm_copy_task(args.seq_len, vocab=args.vocab)
    from akka_allreduce_tpu.utils.benchmarking import transformer_train_flops

    flops = transformer_train_flops(
        n_params=trainer.param_count, batch=args.batch, seq=args.seq_len,
        d_model=args.d_model, n_layers=args.layers,
    )
    return _run_training(
        trainer, ds, args, label="fsdp_lm", flops_per_step=flops
    )


def _cmd_train_mlp(argv: list[str]) -> int:
    p = argparse.ArgumentParser("train-mlp", description="MLP/MNIST DP-SGD (config 3)")
    _train_flags(p)
    p.add_argument("--hidden", type=int, nargs="+", default=[128])
    args = p.parse_args(argv)

    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models import MLP, data
    from akka_allreduce_tpu.train import DPTrainer

    trainer = DPTrainer(
        MLP(
            hidden=tuple(args.hidden),
            classes=10,
            compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        ),
        _make_mesh(args),
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        learning_rate=args.lr,
        bucket_size=args.bucket,
        compress=args.compress,
        error_feedback=args.error_feedback,
        overlap=args.overlap,
    )
    from akka_allreduce_tpu.utils.benchmarking import dense_train_flops

    return _run_training(
        trainer, data.mnist_like(), args, label="mlp_mnist",
        flops_per_step=dense_train_flops(trainer.param_count, args.batch),
    )


def _cmd_train_resnet(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-resnet", description="ResNet-50 DP grad sync (config 4)"
    )
    _train_flags(p)
    p.add_argument("--image-size", type=int, default=32)
    p.add_argument("--classes", type=int, default=10)
    args = p.parse_args(argv)

    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models import ResNet50, data
    from akka_allreduce_tpu.train import DPTrainer

    trainer = DPTrainer(
        ResNet50(
            classes=args.classes,
            compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        ),
        _make_mesh(args),
        example_input=np.zeros(
            (1, args.image_size, args.image_size, 3), np.float32
        ),
        learning_rate=args.lr,
        # the reference's chunk geometry by default; --overlap drops only
        # the DEFAULT (an explicit --bucket still reaches the trainer's
        # conflicting-flags guard, same contract as train-mlp)
        bucket_size=(
            args.bucket
            if args.bucket is not None
            else (None if args.overlap else 262_144)
        ),
        compress=args.compress,
        error_feedback=args.error_feedback,
        overlap=args.overlap,
    )
    print(f"ResNet params: {trainer.param_count / 1e6:.1f}M")
    ds = data.SyntheticClassification(
        (args.image_size, args.image_size, 3), args.classes, seed=0
    )
    # conv FLOPs from the analytic architecture mirror (the 6N rule
    # undercounts convs), x3 for fwd + bwd
    from akka_allreduce_tpu.models.resnet import resnet_fwd_flops

    fwd = resnet_fwd_flops(trainer.model, args.image_size, args.batch)
    return _run_training(
        trainer, ds, args, label="resnet50", flops_per_step=3 * fwd
    )


def _cmd_train_lm(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-lm",
        description="long-context Transformer LM, DP x SP with ring attention "
        "or Ulysses (no analog in the reference — SURVEY.md §6)",
    )
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8, help="global batch size")
    p.add_argument("--lr", type=float, default=3e-3)
    p.add_argument("--seq-len", type=int, default=256, help="GLOBAL sequence length")
    p.add_argument("--dp", type=int, default=None, help="data-parallel rows")
    p.add_argument("--sp", type=int, default=None, help="sequence shards")
    p.add_argument(
        "--tp",
        type=int,
        default=1,
        help="tensor-parallel shards (Megatron-style heads/hidden split "
        "over a third mesh axis; needs --dp and --sp too)",
    )
    p.add_argument("--impl", choices=("ring", "ulysses"), default="ring")
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument(
        "--kv-heads", type=int, default=None,
        help="grouped-query attention: K/V heads (divides --heads; 1 = "
        "MQA). Under ring/Ulysses SP the compact K/V form crosses the "
        "wire, shrinking per-step ICI bytes by heads/kv_heads",
    )
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--metrics-out", default=None, help="JSONL metrics path")
    p.add_argument(
        "--device-data",
        action="store_true",
        help="sample token batches ON DEVICE inside one jitted chain",
    )
    p.add_argument(
        "--bf16",
        action="store_true",
        help="bfloat16 activations/matmuls (params and logits stay fp32) — "
        "the MXU-native dtype",
    )
    p.add_argument(
        "--remat",
        action="store_true",
        help="rematerialize each block on backward (jax.checkpoint): "
        "O(layers) activation memory for one extra forward of FLOPs — "
        "the long-sequence memory knob",
    )
    _checkpoint_flags(p)
    _add_sharded_compress_flag(p)
    _add_profile_flag(p)
    _add_obs_flags(p)
    args = p.parse_args(argv)
    _install_obs(args)

    import jax.numpy as jnp

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.parallel import data_seq_mesh, data_seq_model_mesh
    from akka_allreduce_tpu.train import LongContextTrainer

    if args.tp > 1:
        if not (args.dp and args.sp):
            p.error("--tp requires explicit --dp and --sp")
        mesh = data_seq_model_mesh(args.dp, args.sp, args.tp)
    else:
        mesh = data_seq_mesh(args.dp, args.sp)
    trainer = LongContextTrainer(
        mesh,
        vocab=args.vocab,
        d_model=args.d_model,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        n_layers=args.layers,
        seq_len=args.seq_len,
        seq_impl=args.impl,
        learning_rate=args.lr,
        compute_dtype=jnp.bfloat16 if args.bf16 else jnp.float32,
        remat=args.remat,
        compress=args.compress,
        overlap=args.overlap,
    )
    print(
        f"LM params: {trainer.param_count / 1e6:.2f}M, mesh "
        f"dp={trainer.dp} x sp={trainer.sp} x tp={trainer.tp}, "
        f"seq_len={args.seq_len} ({args.impl})"
    )
    ds = data.lm_copy_task(args.seq_len, vocab=args.vocab)
    from akka_allreduce_tpu.utils.benchmarking import transformer_train_flops

    flops = transformer_train_flops(
        n_params=trainer.param_count, batch=args.batch, seq=args.seq_len,
        d_model=args.d_model, n_layers=args.layers,
    )
    # --device-data is handled inside _run_training via _run_training_chain
    # (trainer.data_shards tells it rows are per DP replica, not per device)
    rc = _run_training(
        trainer, ds, args, label=f"lm_{args.impl}", flops_per_step=flops
    )
    _write_trace(args)
    return rc


def _cmd_cluster_master(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "cluster-master",
        description="seed/master role: membership + round scheduling over TCP "
        "(the reference's master main, SURVEY.md §4.1)",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--nodes", type=int, default=2, help="nodes before organizing")
    p.add_argument("--dims", type=int, default=1, choices=(1, 2))
    p.add_argument("--size", type=int, default=1_000_000)
    p.add_argument("--chunk", type=int, default=262_144)
    p.add_argument("--rounds", type=int, default=20, help="-1 = run forever")
    p.add_argument(
        "--round-window", type=int, default=2,
        help="line rounds in flight (max 4 = the workers' out-of-order "
        "buffer window): deeper windows overlap the per-round "
        "master<->node RTT chain (the latency-bound share of the pair "
        "wall — BENCHMARKS.md round 4)",
    )
    p.add_argument("--th", type=float, default=1.0, help="all three thresholds")
    p.add_argument("--heartbeat", type=float, default=1.0, help="interval (s)")
    p.add_argument(
        "--line-shards", type=int, default=1,
        help="dims-1 round-scheduling shards: split the membership into "
        "up to N LineMasters, each owning (and reducing within) a "
        "contiguous worker subset (RESILIENCE.md 'Tier 6')",
    )
    p.add_argument(
        "--grid", default="", metavar="RxC",
        help="pod-grid coordinate bootstrap (RESILIENCE.md 'Scale'): "
        "anchor node ids to an RxC layout (nodes derive theirs from "
        "--process-index / the pod env), so shard membership and dims-2 "
        "row/column lines follow the pod layout instead of join order",
    )
    p.add_argument("--metrics-out", default=None, help="per-round JSONL path")
    p.add_argument(
        "--round-deadline", type=float, default=0.0,
        help="stall watchdog: a round in flight longer than this many "
        "seconds dumps the flight recorder (0 = off)",
    )
    _add_wire_dtype_flag(p)
    _add_data_plane_flags(p)
    _add_chaos_flags(p)
    _add_adapt_flags(p)
    _add_gossip_flags(p)
    _add_obs_flags(p)
    args = p.parse_args(argv)
    from akka_allreduce_tpu.config import WorkerConfig

    worker_window = WorkerConfig().round_window
    if not 1 <= args.round_window <= worker_window:
        # past the workers' bounded out-of-order buffer, fast-forwarding
        # silently corrupts round accounting (measured collapse at 8)
        p.error(
            f"--round-window must be in [1, {worker_window}] (the "
            f"workers' out-of-order buffer window), got {args.round_window}"
        )
    return _run_cluster_master(args)


def _run_cluster_master(args) -> int:
    """Shared master bootstrap for cluster-master / train-cluster-master."""
    logging.basicConfig(level=logging.INFO, format="%(message)s")

    import asyncio

    from akka_allreduce_tpu.config import (
        AllreduceConfig,
        ChaosConfig,
        DataPlaneConfig,
        LineMasterConfig,
        MasterConfig,
        MetaDataConfig,
        RetryPolicy,
        ThresholdConfig,
        WorkerConfig,
    )
    from akka_allreduce_tpu.control.bootstrap import MasterProcess
    from akka_allreduce_tpu.utils.metrics import MetricsLogger

    chaos_spec = getattr(args, "chaos_spec", "")
    if chaos_spec:
        # fail fast on a malformed spec — before any process is spawned
        from akka_allreduce_tpu.control.chaos import parse_spec

        parse_spec(chaos_spec)
    grid_rows = grid_cols = 0
    if getattr(args, "grid", ""):
        from akka_allreduce_tpu.control.pod import parse_grid

        grid_rows, grid_cols = parse_grid(args.grid)
    cfg = AllreduceConfig(
        threshold=ThresholdConfig(args.th, args.th, args.th),
        metadata=MetaDataConfig(
            data_size=args.size,
            max_chunk_size=args.chunk,
            wire_dtype=getattr(args, "wire_dtype", "f32"),
        ),
        line_master=LineMasterConfig(
            round_window=args.round_window, max_rounds=args.rounds
        ),
        master=MasterConfig(
            node_num=args.nodes,
            dimensions=args.dims,
            line_shards=getattr(args, "line_shards", 1),
            grid_rows=grid_rows,
            grid_cols=grid_cols,
            heartbeat_interval_s=args.heartbeat,
            round_deadline_s=getattr(args, "round_deadline", 0.0),
            retry=RetryPolicy(
                max_retries=getattr(args, "send_retries", 1),
                backoff_base_s=getattr(args, "send_backoff_base", 0.05),
                backoff_max_s=getattr(args, "send_backoff_max", 2.0),
            ),
        ),
        # both CLI node roles publish snapshots (fixed demo arrays / weights
        # replaced by reference), so the zero-copy scatter path is sound
        worker=WorkerConfig(zero_copy_scatter=True),
        chaos=ChaosConfig(
            seed=getattr(args, "chaos_seed", 0), spec=chaos_spec
        ),
        adapt=_adapt_config_from(args),
        data_plane=DataPlaneConfig(
            streams=getattr(args, "streams", 1),
            pump_pool=getattr(args, "pump_pool", 0),
            uring=getattr(args, "uring", False),
            intra_chunk_min_bytes=getattr(args, "intra_chunk", 0),
            congestion=getattr(args, "congestion", False),
        ),
        gossip=_gossip_config_from(args),
    )
    _install_obs(args)

    async def run() -> None:
        metrics = MetricsLogger(args.metrics_out) if args.metrics_out else None
        master = MasterProcess(
            cfg, args.host, args.port, metrics=metrics,
            # a real OS process: the chaos `crash:node=m` fault may
            # os._exit here (the chaos-failover drill's leader kill) —
            # and the injector flushes the chaos log on its way down
            allow_crash=True,
            chaos_log=getattr(args, "chaos_log", None),
        )
        ep = await master.start()
        print(f"master listening on {ep}", flush=True)
        # SIGTERM ends an open-ended (--rounds -1) run GRACEFULLY: nodes get
        # a Shutdown broadcast and every process flushes its metrics/chaos
        # logs — the chaos runner's --duration mode depends on this
        import signal as _signal

        from akka_allreduce_tpu.control.remote import observed_task

        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(
                _signal.SIGTERM,
                lambda: observed_task(
                    master.shutdown("sigterm"), name="sigterm-shutdown"
                ),
            )
        except (NotImplementedError, RuntimeError):  # pragma: no cover
            pass  # non-Unix event loops: SIGTERM stays abrupt
        try:
            t0, c0 = time.perf_counter(), time.process_time()
            await master.run_until_done()
            print(
                f"master done: {master.rounds_completed} line-rounds "
                f"completed (wall {time.perf_counter() - t0:.2f}s, own cpu "
                f"{time.process_time() - c0:.2f}s over the round window)",
                flush=True,
            )
            await asyncio.sleep(2 * args.heartbeat)  # let Shutdown flush
        finally:
            await master.stop()
            if getattr(args, "chaos_log", None) and master.transport.chaos:
                path = master.transport.chaos.write_log(args.chaos_log)
                print(f"chaos event log: {path}", flush=True)
            if getattr(args, "adapt_log", None) and master.adapt is not None:
                path = master.adapt.write_log(args.adapt_log)
                print(f"adapt decision log: {path}", flush=True)
            if metrics is not None:
                from akka_allreduce_tpu.obs.metrics import REGISTRY

                metrics.log_snapshot(REGISTRY, role="master")
                metrics.close()

    asyncio.run(run())
    _write_trace(args)
    return 0


def _cmd_cluster_node(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "cluster-node",
        description="worker-node role: joins the seed, serves one worker per "
        "grid dimension (the reference's worker main, SURVEY.md §4.1)",
    )
    p.add_argument("--seed", required=True, help="master host:port")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--node-id", type=int, default=-1, help="-1 = master assigns")
    p.add_argument(
        "--grid", default="", metavar="RxC",
        help="pod-grid coordinate bootstrap (RESILIENCE.md 'Scale'): "
        "derive this node's id from its process index, row-major over "
        "the RxC layout (SNIPPETS.md [2]'s multi-controller pattern — "
        "process_index/local_devices as grid coordinates), so shard "
        "membership follows the pod layout instead of join order",
    )
    p.add_argument(
        "--process-index", type=int, default=-1,
        help="this process's pod index for --grid (-1 = resolve from "
        "AKKA_PROCESS_INDEX & friends, then a live jax.distributed)",
    )
    p.add_argument("--data-seed", type=int, default=None, help="payload RNG seed")
    p.add_argument(
        "--metrics-out", default=None,
        help="JSONL path for the node's per-stage protocol timing "
        "(fields encode/socket_write/decode/handler as wall spans, plus "
        "cpu_s/wall_s — the on-cpu/off-cpu partition of the round "
        "window)",
    )
    p.add_argument(
        "--chaos-log", default=None, metavar="FILE",
        help="write this node's chaos event log (JSONL) on exit; the "
        "chaos spec itself arrives from the master via Welcome",
    )
    p.add_argument(
        "--state-dir", default=None, metavar="DIR",
        help="arm peer state transfer (RESILIENCE.md 'Recovery'): "
        "delta-checkpoint this node's running state here, replicate the "
        "chunks to peers after every save, and on (re)join restore from "
        "disk — or, when this directory is gone, pull the chunks back "
        "from live peers",
    )
    p.add_argument(
        "--state-every", type=int, default=5,
        help="save + replicate state every N flushed rounds",
    )
    p.add_argument(
        "--replicas", type=int, default=2,
        help="how many peers each checkpoint is pushed to (K)",
    )
    p.add_argument(
        "--uniform-check", action="store_true",
        help="assert-quality accounting for drills: with every node running "
        "the SAME --data-seed, each round's reduced average must equal the "
        "payload regardless of how many contributors made it — track the "
        "max deviation (the wire-compression + EF error) and report it as "
        "max_err= in the shutdown line (chaos-adapt's error-budget check)",
    )
    _add_obs_flags(p)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    if args.grid:
        # grid-coordinate bootstrap: the node id IS the pod coordinate
        # (row-major), never the join order — which is what anchors
        # shard membership to the layout (control/pod.py)
        from akka_allreduce_tpu.control import pod as _pod

        rows, cols = _pod.parse_grid(args.grid)
        idx = _pod.resolve_process_index(
            args.process_index if args.process_index >= 0 else None
        )
        row, col = _pod.grid_coords(idx, rows, cols)
        if args.node_id >= 0 and args.node_id != idx:
            p.error(
                f"--node-id {args.node_id} contradicts the grid "
                f"coordinate {idx} ({row},{col}); drop one of them"
            )
        args.node_id = idx
        print(
            f"pod grid {rows}x{cols}: process {idx} -> coords "
            f"({row},{col}), node id {idx}",
            flush=True,
        )
    _install_obs(args)

    import asyncio
    import json

    import numpy as np

    from akka_allreduce_tpu.control.bootstrap import NodeProcess
    from akka_allreduce_tpu.control.cluster import Endpoint
    from akka_allreduce_tpu.control.remote import observed_task
    from akka_allreduce_tpu.protocol import AllReduceInput

    state = {"payload": None, "flushes": 0, "t0": None, "node": None,
             "save_task": None, "step_base": 0, "save_enabled": False,
             "last_flush_round": -1, "dup_flushes": 0, "max_err": 0.0}

    def source(req):
        if state["payload"] is None:
            raise RuntimeError("source called before Welcome sized the payload")
        return AllReduceInput(state["payload"])

    def sink(out):
        state["flushes"] += 1
        # flushed round ids are strictly increasing BY CONSTRUCTION (the
        # worker abandons older rounds on completion, and the cross-epoch
        # floor survives rejoins) — a non-increasing flush means a round
        # was applied twice. The chaos-failover drill asserts this stays 0
        # across a master failover.
        if out.iteration <= state["last_flush_round"]:
            state["dup_flushes"] += 1
        else:
            state["last_flush_round"] = out.iteration
        if args.uniform_check and state["payload"] is not None:
            # identical payloads on every node => the true average IS the
            # payload wherever at least one contribution landed; any
            # deviation is wire-compression error (f16 rounding / int8
            # quantization net of the EF carry) — the budget chaos-adapt
            # asserts. O(size) numpy per flush, drill-scale only.
            got = out.average()
            mask = out.count > 0
            if mask.any():
                err = float(
                    np.max(np.abs(got[mask] - state["payload"][mask]))
                )
                state["max_err"] = max(state["max_err"], err)
        node = state["node"]
        n = state["flushes"]
        if (
            node is None
            or node.state is None
            or not state["save_enabled"]
            or not args.state_every
            or n % args.state_every
        ):
            # saves stay gated until the startup restore DECIDED: a reborn
            # node writing fresh saves into its emptied store mid-restore
            # would shadow the peer state it is trying to recover
            return
        prev = state["save_task"]
        if prev is not None and not prev.done():
            return  # bounded: at most one save+replicate cycle in flight
        snap = {
            "payload": state["payload"],
            # the reduced view aliases a recycled recv buffer — snapshot it
            "reduced": np.array(out.data, dtype=np.float32, copy=True),
        }
        step = state["step_base"] + n
        state["save_task"] = observed_task(
            node.save_state(step, snap), name=f"state-save-{step}"
        )

    async def run() -> int:
        node = NodeProcess(
            Endpoint.parse(args.seed),
            source,
            sink,
            args.host,
            args.port,
            preferred_node_id=args.node_id,
            # real OS process: the chaos `crash` fault may os._exit here
            allow_crash=True,
            chaos_log=args.chaos_log,
            state_dir=args.state_dir,
            replicas=args.replicas,
        )
        state["node"] = node
        await node.start()
        nid = await node.wait_welcomed()
        size = node.config.metadata.data_size
        seed = args.data_seed if args.data_seed is not None else nid
        state["payload"] = (
            np.random.default_rng(seed).standard_normal(size).astype(np.float32)
        )
        if args.state_dir:
            # the rejoin restore path: disk when it is current, else a
            # parallel chunk pull from live peer holders (statetransfer).
            # give_up: rounds flush through THIS loop while the restore
            # coroutine waits its turn — once a couple of save periods
            # have gone by with the master still answering "nothing
            # known", more blind patience only pushes the first
            # checkpoint past an early seeded crash (the chaos-recover
            # flake under load); an active chunk pull is never capped
            flushes0 = state["flushes"]
            # one save period of our own rounds: the whole pipeline behind
            # the gate (save -> replicate -> peers verify -> advert) needs
            # its own rounds of margin before a seeded early crash, so the
            # blind window must not eat a second period
            budget = max(1, args.state_every or 1)
            rest = await node.restore_state(
                give_up=lambda: state["flushes"] - flushes0 >= budget
            )
            if rest is not None and rest.get("complete"):
                try:
                    step, saved = node.state.store.load_state()
                except (FileNotFoundError, ValueError) as e:
                    print(f"state restore unreadable: {e}", flush=True)
                else:
                    payload = saved.get("payload")
                    if payload is not None and payload.size == size:
                        state["payload"] = np.ascontiguousarray(
                            payload, dtype=np.float32
                        )
                    # continue the save-step numbering where it left off so
                    # post-restore adverts stay monotonic (flushes itself
                    # keeps counting only THIS process's rounds)
                    state["step_base"] = int(step)
            state["save_enabled"] = True
            print(
                "RESTORE "
                + json.dumps(rest if rest is not None else {"source": "none"}),
                flush=True,
            )
        state["t0"] = time.perf_counter()
        cpu0 = time.process_time()
        print(f"node {nid} joined {args.seed}", flush=True)
        try:
            reason = await node.run_until_shutdown()
        finally:
            await node.stop()
            if args.chaos_log and node.transport.chaos is not None:
                node.transport.chaos.write_log(args.chaos_log)
        dt = time.perf_counter() - state["t0"]
        cpu = time.process_time() - cpu0
        mbs = state["flushes"] * size * 4 / max(dt, 1e-9) / 1e6
        stages = dict(node.transport.stage_seconds)
        accounted = sum(stages.values())
        stage_note = ", ".join(
            f"{k}={v:.3f}s" for k, v in stages.items()
        )
        # provenance for the recorded number: which wire codec ran (the C++
        # hot loop vs the struct/numpy fallback) — same flag the engine
        # kernels use, so one bool covers both hot paths. loaded(), not
        # available(): the latter may block on a compile and then describe
        # a library the finished run never used
        from akka_allreduce_tpu import native as _native

        wire_path = "native" if _native.loaded() else "python"
        err_note = (
            f", max_err={state['max_err']:.6f}" if args.uniform_check else ""
        )
        print(
            f"node {nid} shut down ({reason}): {state['flushes']} rounds, "
            f"{mbs:.1f} MB/s reduced, dup_flushes={state['dup_flushes']}"
            f"{err_note}",
            flush=True,
        )
        # wall decomposition (VERDICT r3 #9). Two views, different units:
        # the PARTITION of wall is own-cpu vs off-cpu (process_time —
        # off-cpu = the OS ran someone else, e.g. the peer/master on a
        # shared core, or the socket was idle); the stage timers are
        # WALL SPANS (they include awaits and any preemption inside a
        # stage), an overlay for locating where time passes, not a
        # disjoint part of the partition.
        print(
            f"node {nid} stage times over {dt:.2f}s wall: {stage_note} "
            f"(wall spans, {accounted:.2f}s total; partition: own cpu "
            f"{cpu:.2f}s, off-cpu {max(dt - cpu, 0.0):.2f}s = "
            f"peer/master scheduled or socket idle; wire={wire_path})",
            flush=True,
        )
        if args.metrics_out:
            from akka_allreduce_tpu.obs.metrics import REGISTRY
            from akka_allreduce_tpu.utils.metrics import MetricsLogger

            m = MetricsLogger(args.metrics_out)
            m.log_event(
                kind="node_stage_times", node=nid, wall_s=round(dt, 3),
                cpu_s=round(cpu, 3),
                rounds=state["flushes"], mb_per_s=round(mbs, 1),
                dup_flushes=state["dup_flushes"],
                wire=wire_path,
                **{k: round(v, 4) for k, v in stages.items()},
            )
            m.log_snapshot(REGISTRY, role="node", node=nid)
            m.close()
        return 0

    rc = asyncio.run(run())
    _write_trace(args)
    return rc


def _cmd_cluster_standby(argv: list[str]) -> int:
    """Warm-standby master role (RESILIENCE.md 'Tier 4'): registers with
    the leader, absorbs the replicated state-digest stream, and takes over
    — bumping the leadership epoch — when its lease on the leader expires.
    Nodes walk the standby list distributed via Welcome/AddressBook and
    re-join here; the round budget then completes under the new epoch."""
    p = argparse.ArgumentParser(
        "cluster-standby",
        description="warm-standby master: replicate the leader's control-"
        "plane state and take over on leader loss (epoch-fenced failover)",
    )
    p.add_argument("--seed", required=True, help="leader master host:port")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument(
        "--heartbeat", type=float, default=1.0,
        help="lease tick + expected digest cadence (s); match the "
        "leader's --heartbeat",
    )
    p.add_argument(
        "--phi", type=float, default=8.0,
        help="phi-accrual threshold of the leader lease (lower = faster, "
        "riskier takeover)",
    )
    p.add_argument("--metrics-out", default=None, help="per-round JSONL path")
    _add_obs_flags(p)
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")
    _install_obs(args)

    import asyncio
    import json

    from akka_allreduce_tpu.config import AllreduceConfig
    from akka_allreduce_tpu.control.bootstrap import MasterProcess
    from akka_allreduce_tpu.control.cluster import Endpoint
    from akka_allreduce_tpu.config import MasterConfig
    from akka_allreduce_tpu.utils.metrics import MetricsLogger

    async def run() -> int:
        metrics = MetricsLogger(args.metrics_out) if args.metrics_out else None
        # placeholder config: everything that matters (thresholds, chaos,
        # retry, round budget) is ADOPTED from the leader's digest at
        # takeover — only the lease cadence is ours to configure
        cfg = AllreduceConfig(
            master=MasterConfig(heartbeat_interval_s=args.heartbeat)
        )
        master = MasterProcess(
            cfg, args.host, args.port,
            standby_of=Endpoint.parse(args.seed),
            phi_threshold=args.phi,
            metrics=metrics,
            allow_crash=True,
        )

        def on_takeover(m: MasterProcess) -> None:
            # machine-readable line the chaos-failover drill gates on
            print(
                "TAKEOVER "
                + json.dumps(
                    {
                        "epoch": m.epoch,
                        "members": sorted(m.grid.nodes),
                        "resume_round": m.grid.resume_round,
                        "completed_carried": m.grid._completed_before_reorg,
                        "ckpt_origins": sorted(m._ckpt),
                    }
                ),
                flush=True,
            )

        master.on_takeover = on_takeover
        ep = await master.start()
        print(f"standby listening on {ep} (leader {args.seed})", flush=True)
        import signal as _signal

        from akka_allreduce_tpu.control.remote import observed_task

        loop = asyncio.get_running_loop()
        try:
            loop.add_signal_handler(
                _signal.SIGTERM,
                lambda: observed_task(
                    master.shutdown("sigterm"), name="sigterm-shutdown"
                ),
            )
        except (NotImplementedError, RuntimeError):  # pragma: no cover
            pass
        try:
            t0 = time.perf_counter()
            await master.run_until_done()
            if master.active:
                print(
                    f"master done: {master.rounds_completed} line-rounds "
                    f"completed (epoch {master.epoch}, wall "
                    f"{time.perf_counter() - t0:.2f}s since standby start)",
                    flush=True,
                )
                await asyncio.sleep(2 * args.heartbeat)  # let Shutdown flush
            else:
                print(
                    f"standby released ({master.shutdown_reason})",
                    flush=True,
                )
        finally:
            await master.stop()
            if metrics is not None:
                from akka_allreduce_tpu.obs.metrics import REGISTRY

                metrics.log_snapshot(REGISTRY, role="standby")
                metrics.close()
        return 0

    rc = asyncio.run(run())
    _write_trace(args)
    return rc


def _mlp_trainer(hidden, lr, seed=0):
    import numpy as np

    from akka_allreduce_tpu.models import MLP
    from akka_allreduce_tpu.parallel import line_mesh
    from akka_allreduce_tpu.train import DPTrainer

    return DPTrainer(
        MLP(hidden=tuple(hidden), classes=10),
        line_mesh(1),  # local learner: one device per node process
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        learning_rate=lr,
        seed=seed,
    )


def _cluster_model_flags(p) -> None:
    """Model-selection flags shared by the train-cluster master and nodes —
    every process must be started with the SAME model flags (the master
    derives the cluster's data_size from them)."""
    p.add_argument(
        "--model", choices=("mlp", "lm"), default="mlp",
        help="mlp = MLP/MNIST (reference workload); lm = Transformer LM",
    )
    p.add_argument("--hidden", type=int, nargs="+", default=[32])
    p.add_argument("--seq-len", type=int, default=128)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=64)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--layers", type=int, default=2)


def _cluster_trainer(args, lr: float, seed: int = 17):
    """The node-local learner for the distributed cluster, per --model."""
    if args.model == "lm":
        import jax

        from akka_allreduce_tpu.parallel import data_seq_mesh
        from akka_allreduce_tpu.train import LongContextTrainer

        return LongContextTrainer(
            data_seq_mesh(1, 1, devices=jax.devices()[:1]),
            vocab=args.vocab,
            d_model=args.d_model,
            n_heads=args.heads,
            n_layers=args.layers,
            seq_len=args.seq_len,
            learning_rate=lr,
            seed=seed,
        )
    return _mlp_trainer(args.hidden, lr, seed=seed)


def _cluster_batches(args, data_seed: int):
    from akka_allreduce_tpu.models import data

    if args.model == "lm":
        ds = data.lm_copy_task(args.seq_len, vocab=args.vocab, seed=data_seed)
        return iter(ds.batches(args.batch, args.steps))
    return iter(
        data.mnist_like(seed=data_seed).batches(args.batch, args.steps)
    )


def _cmd_train_cluster_master(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-cluster-master",
        description="master for distributed elastic-averaging training "
        "(the reference's multi-JVM training deployment, SURVEY.md §4.4); "
        "data_size is derived from the model so start nodes with the SAME "
        "model flags",
    )
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--nodes", type=int, default=2)
    _cluster_model_flags(p)
    p.add_argument("--rounds", type=int, default=30, help="-1 = run forever")
    p.add_argument("--chunk", type=int, default=65536)
    p.add_argument("--th", type=float, default=1.0, help="all three thresholds")
    p.add_argument("--heartbeat", type=float, default=0.5, help="interval (s)")
    p.add_argument("--metrics-out", default=None, help="per-round JSONL path")
    _add_wire_dtype_flag(p)
    args = p.parse_args(argv)
    args.size = _cluster_trainer(args, 0.1).param_count
    print(f"model: {args.size} params -> data_size {args.size}", flush=True)
    args.dims = 1
    return _run_cluster_master(args)


def _claim_accelerator(role: str):
    """One process per chip: an accelerator belongs to the first process
    that initializes it, and a second one then fails deep inside backend
    start-up or hangs. Before this process touches JAX, take a host-wide
    advisory lock (keyed on the chips it was told it may see) and refuse
    with a clear message if another node process holds it. No-op when the
    environment pins the CPU platform (the test box, CPU clusters).
    Returns the open lock file — the caller keeps it for its lifetime."""
    import os

    if os.environ.get("JAX_PLATFORMS", "").split(",")[0].strip() == "cpu":
        return None
    import fcntl
    import tempfile

    chips = os.environ.get("TPU_VISIBLE_CHIPS", "all").replace(",", "-")
    path = os.path.join(
        tempfile.gettempdir(), f"akka_allreduce_tpu.chip-{chips}.lock"
    )
    lock = open(path, "w")
    try:
        fcntl.flock(lock, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except BlockingIOError:
        lock.close()
        raise SystemExit(
            f"{role}: another node process on this host already holds the "
            f"accelerator (lock {path}). Legal shapes: ONE node process per "
            "chip (give each its own TPU_VISIBLE_CHIPS on a multi-chip "
            "host), or JAX_PLATFORMS=cpu for a CPU cluster."
        ) from None
    return lock


def _cmd_train_cluster_node(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-cluster-node",
        description="training node: local SGD on its own data shard + "
        "asynchronous elastic-averaging weight sync over the cluster",
    )
    p.add_argument("--seed", required=True, help="master host:port")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument("--node-id", type=int, default=-1, help="-1 = master assigns")
    _cluster_model_flags(p)
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--lr", type=float, default=0.1)
    p.add_argument("--elastic-rate", type=float, default=0.5)
    p.add_argument("--data-seed", type=int, default=None, help="shard RNG seed")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")

    import asyncio

    from akka_allreduce_tpu.control.cluster import Endpoint
    from akka_allreduce_tpu.train import ElasticClusterNode

    chip_lock = _claim_accelerator("train-cluster-node")  # noqa: F841

    async def run() -> int:
        trainer = _cluster_trainer(args, args.lr, seed=17)
        node = ElasticClusterNode(
            Endpoint.parse(args.seed),
            trainer,
            _cluster_batches(
                args, args.data_seed if args.data_seed is not None else 0
            ),
            elastic_rate=args.elastic_rate,
            host=args.host,
            port=args.port,
            preferred_node_id=args.node_id,
        )
        t0 = time.perf_counter()
        steps = await node.run(args.steps)
        dt = time.perf_counter() - t0
        losses = node.losses
        trend = (
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
            if losses
            else "no steps taken"
        )
        print(
            f"trained {steps} steps in {dt:.1f}s "
            f"({node.rounds_applied} sync rounds applied); {trend}",
            flush=True,
        )
        return 0

    return asyncio.run(run())


def _cmd_elastic_demo(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "elastic-demo",
        description="config-5 dropout + late-joiner recovery, end to end",
    )
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch-per-device", type=int, default=8)
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--drop-at", type=int, default=10, help="step the last node dies")
    p.add_argument("--rejoin-at", type=int, default=20, help="step it comes back")
    p.add_argument(
        "--family",
        choices=("dp", "moe", "pp", "lc"),
        default="dp",
        help="which elastic trainer rides the cycle: dp = MLP DPTrainer; "
        "moe / pp / lc = the round-4 families whose expert / pipe / seq "
        "mesh axes RE-SHAPE with membership (the same experts "
        "redistribute, the same logical layers re-chunk, sequences "
        "re-split)",
    )
    args = p.parse_args(argv)

    import jax
    import numpy as np

    from akka_allreduce_tpu.models import MLP, data
    from akka_allreduce_tpu.train import (
        ElasticDPTrainer,
        ElasticLongContextTrainer,
        ElasticMoETrainer,
        ElasticPipelineTrainer,
    )

    devices = jax.devices()
    per = max(1, len(devices) // args.nodes)
    assignment = {
        n: devices[n * per : (n + 1) * per] for n in range(args.nodes)
    }
    now = {"t": 0.0}
    seq_len = 32
    fam_kw = dict(
        vocab=16, d_model=32, n_heads=2, learning_rate=1e-2, seed=0,
        clock=lambda: now["t"],
    )
    if args.family == "dp":
        trainer = ElasticDPTrainer(
            MLP(hidden=(32,), classes=10),
            assignment,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            clock=lambda: now["t"],
        )
        ds = data.mnist_like()
        batch_rows = lambda t: args.batch_per_device * t.n_devices  # noqa: E731
        shape_of = lambda t: f"{t.n_devices} devices"  # noqa: E731
    elif args.family == "moe":
        trainer = ElasticMoETrainer(
            assignment, n_experts=4, n_layers=1, seq_len=seq_len,
            capacity_factor=4.0, **fam_kw,
        )
        ds = data.lm_copy_task(seq_len, vocab=16)
        batch_rows = lambda t: t.dp * t.ep * args.batch_per_device  # noqa: E731
        shape_of = lambda t: f"dp{t.dp} x ep{t.ep}"  # noqa: E731
    elif args.family == "pp":
        trainer = ElasticPipelineTrainer(
            assignment, n_layers=4, microbatches=2, seq_len=seq_len,
            **fam_kw,
        )
        ds = data.lm_copy_task(seq_len, vocab=16)
        batch_rows = (  # noqa: E731
            lambda t: t.dp * t.microbatches * args.batch_per_device
        )
        shape_of = lambda t: f"dp{t.dp} x pp{t.stages}"  # noqa: E731
    else:  # lc
        trainer = ElasticLongContextTrainer(
            assignment, seq_len=seq_len, max_sp=4, n_layers=1, **fam_kw,
        )
        ds = data.lm_copy_task(seq_len, vocab=16)
        batch_rows = lambda t: t.dp * args.batch_per_device  # noqa: E731
        shape_of = lambda t: f"dp{t.dp} x sp{t.sp}"  # noqa: E731
    dead = args.nodes - 1
    for step in range(args.steps):
        live = set(trainer.member_nodes)
        if step == args.rejoin_at:
            trainer.heartbeat(dead)  # late joiner
        for n in range(args.nodes):
            if n == dead and args.drop_at <= step < args.rejoin_at:
                continue
            if n in trainer.devices_by_node:
                trainer.heartbeat(n)
        now["t"] += 1.0
        if trainer.poll():
            print(
                f"step {step}: re-meshed to {trainer.n_nodes} nodes / "
                f"{shape_of(trainer.trainer)} "
                f"(generation {trainer.generation})"
            )
        x, y = next(iter(ds.batches(batch_rows(trainer.trainer), 1,
                                    seed_offset=step)))
        m = trainer.train_step(x, y)
        if step % 5 == 0 or set(trainer.member_nodes) != live:
            print(
                f"step {m.step}: loss={m.loss:.4f} "
                f"contributors={m.contributors:.0f}"
            )
    print(f"done: {args.steps} steps, final generation {trainer.generation}")
    return 0


def _cmd_train_moe(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-moe",
        description="MoE LM with expert parallelism: DP x EP over a "
        "(data, expert) mesh, or DP x SP x EP with --sp (no analog in the "
        "reference — SURVEY.md §3)",
    )
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8, help="global batch size")
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--dp", type=int, default=None, help="data-parallel rows")
    p.add_argument("--ep", type=int, default=1, help="expert-parallel shards")
    p.add_argument(
        "--sp", type=int, default=1,
        help="sequence-parallel shards (3-axis data x seq x expert mesh)",
    )
    p.add_argument(
        "--impl", choices=("ring", "ulysses"), default="ring",
        help="attention schedule over the seq axis (with --sp > 1)",
    )
    p.add_argument("--experts", type=int, default=4)
    p.add_argument("--capacity-factor", type=float, default=1.25)
    p.add_argument(
        "--mu-bf16",
        action="store_true",
        help="adam first moment in bf16: halves the biggest traffic "
        "stream of the all-expert optimizer update (BENCHMARKS.md round "
        "4); the variance stays f32",
    )
    p.add_argument(
        "--topk", type=int, choices=(1, 2), default=1,
        help="router: 1 = Switch top-1, 2 = GShard top-2",
    )
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument(
        "--kv-heads", type=int, default=None,
        help="grouped-query attention: K/V heads (divides --heads; 1 = MQA)",
    )
    p.add_argument("--layers", type=int, default=2)
    p.add_argument(
        "--dispatch", choices=("auto", "einsum", "scatter"), default="auto",
        help="token->expert data movement: one-hot einsums or "
        "scatter/gather (auto: scatter past ~4M one-hot elements)",
    )
    p.add_argument(
        "--device-data",
        action="store_true",
        help="sample batches ON DEVICE inside one jitted chain (no host "
        "I/O per step)",
    )
    p.add_argument(
        "--config", default=None, metavar="JSON",
        help="build the model from a config.json in the dialect its keys "
        "are of: lfm2_moe's (conv/attention hybrid) or DeepSeek-V3's, as "
        "joyai_llm_flash has them (latent attention, a shared expert, a "
        "multi-token-prediction module); gated experts, dropless sigmoid top-k routing "
        "over the experts it says are held here, in place of the size "
        "flags; its \"program\" group, if any, gives compute_dtype",
    )
    _add_sharded_compress_flag(p)
    _add_profile_flag(p)
    _add_obs_flags(p)
    args = p.parse_args(argv)
    _install_obs(args)

    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.parallel import data_seq_model_mesh
    from akka_allreduce_tpu.train import MoETrainer

    if args.config:
        return _train_moe_from_config(args)
    devs = jax.devices()
    dp = args.dp or max(1, len(devs) // (args.ep * args.sp))
    if args.sp > 1:
        mesh = data_seq_model_mesh(
            dp, args.sp, args.ep, axes=("data", "seq", "expert")
        )
    elif args.ep > 1:
        mesh = jax.make_mesh(
            (dp, args.ep), ("data", "expert"), devices=devs[: dp * args.ep]
        )
    else:
        mesh = jax.make_mesh((dp,), ("data",), devices=devs[:dp])
    trainer = MoETrainer(
        mesh,
        vocab=args.vocab,
        d_model=args.d_model,
        n_heads=args.heads,
        n_kv_heads=args.kv_heads,
        n_layers=args.layers,
        n_experts=args.experts,
        seq_len=args.seq_len,
        capacity_factor=args.capacity_factor,
        router_topk=args.topk,
        seq_impl=args.impl,
        learning_rate=args.lr,
        compress=args.compress,
        overlap=args.overlap,
        dispatch_impl=args.dispatch,
        mu_dtype=jnp.bfloat16 if args.mu_bf16 else None,
    )
    print(
        f"MoE params: {trainer.param_count / 1e6:.2f}M "
        f"({args.experts} experts), mesh dp={trainer.dp} x sp={trainer.sp} "
        f"x ep={trainer.ep}"
    )
    if args.steps <= 0:
        return 0
    ds = data.lm_copy_task(args.seq_len, vocab=args.vocab)
    import time

    t0 = time.perf_counter()
    if args.device_data:
        # the chain draws one stream per (data, expert) COORDINATE — seq
        # shards of a coordinate share its rows — so the global batch
        # divides by dp*ep, not n_devices
        coords = trainer.dp * trainer.ep
        rows = max(1, args.batch // coords)
        eff_batch = rows * coords
        if eff_batch != args.batch:
            print(
                f"--device-data: global batch rounded {args.batch} -> "
                f"{eff_batch} ({rows} rows per data x expert coordinate)"
            )
        hist = trainer.train_chain(
            ds.device_sampler(), args.steps, rows_per_device=rows
        )
    else:
        with _profile(args):
            hist = [
                trainer.train_step(x, y)
                for x, y in ds.batches(args.batch, args.steps)
            ]
    dt = time.perf_counter() - t0
    _write_trace(args)
    mode = "on-device " if args.device_data else ""
    from akka_allreduce_tpu.utils.benchmarking import (
        moe_active_params,
        transformer_train_flops,
    )

    eff = rows * trainer.dp * trainer.ep if args.device_data else args.batch
    perf = _mfu_fields(
        transformer_train_flops(
            n_params=moe_active_params(
                trainer.params, args.topk, args.experts
            ),
            batch=eff, seq=args.seq_len,
            d_model=args.d_model, n_layers=args.layers,
        ),
        dt / args.steps,
        trainer.n_devices,
    )
    print(
        f"moe: {args.steps} {mode}steps on {trainer.n_devices} devices in "
        f"{dt:.2f}s ({dt / args.steps * 1e3:.1f} ms/step)"
        f"{_mfu_note(perf)}; "
        f"loss {hist[0].loss:.4f} -> {hist[-1].loss:.4f} "
        f"(aux {hist[-1].aux_loss:.3f}, dropped {hist[-1].dropped:.1%})"
    )
    return 0


def _train_moe_from_config(args) -> int:
    """``train-moe --config``: a configuration-built decoder through the
    same ``MoETrainer``; dp over all devices, no expert exchange (the
    configuration says which experts are held here)."""
    import json
    import time

    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM
    from akka_allreduce_tpu.train import MoETrainer

    if args.ep != 1 or args.sp != 1 or args.device_data:
        raise SystemExit("--config runs with --ep 1 --sp 1 and host batches")
    with open(args.config, encoding="utf-8") as f:
        cfg = json.load(f)
    dtype = jnp.dtype(cfg.get("program", {}).get("compute_dtype", "float32"))
    model = HybridDecoderLM.from_config(cfg, compute_dtype=dtype)
    devs = jax.devices()
    dp = args.dp or len(devs)
    trainer = MoETrainer(
        jax.make_mesh((dp,), ("data",), devices=devs[:dp]),
        model=model, vocab=model.vocab, seq_len=args.seq_len,
        learning_rate=args.lr, compress=args.compress, overlap=args.overlap,
        mu_dtype=jnp.bfloat16 if args.mu_bf16 else None,
    )
    print(
        f"MoE params: {trainer.param_count / 1e6:.2f}M (experts "
        f"{model.held_first}-{model.held_first + model.held_count - 1} of "
        f"{model.num_experts} held, top-{model.experts_per_token}), "
        f"layers {'/'.join(k[:4] for k in model.layer_types)}, "
        f"mesh dp={trainer.dp}"
    )
    if args.steps <= 0:
        return 0
    ds = data.lm_copy_task(args.seq_len, vocab=model.vocab)
    t0 = time.perf_counter()
    with _profile(args):
        hist = [
            trainer.train_step(x, y)
            for x, y in ds.batches(args.batch, args.steps)
        ]
    dt = time.perf_counter() - t0
    _write_trace(args)
    rows = hist[-1].expert_rows
    fullest = rows.sum(axis=1).argmax()
    mtp = "" if hist[-1].mtp_loss is None else (
        f", mtp loss {hist[0].mtp_loss:.4f} -> {hist[-1].mtp_loss:.4f}"
    )
    if hist[-1].indexer_loss is not None:  # attention under a learned mask
        mtp += (
            f", indexer loss {hist[0].indexer_loss:.4f} -> "
            f"{hist[-1].indexer_loss:.4f} ({int(hist[-1].selected_pairs.sum())} "
            f"pairs kept of {len(model.layer_types) * args.batch * args.seq_len * (args.seq_len + 1) // 2})"
        )
    if hist[-1].state_rms is not None:  # linear-attention layers
        mtp += (
            f", log decay {hist[-1].log_decay_mean:.4f}, "
            f"state rms {hist[0].state_rms:.4f} -> {hist[-1].state_rms:.4f}"
        )
    print(
        f"moe: {args.steps} steps on {trainer.n_devices} devices in "
        f"{dt:.2f}s ({dt / args.steps * 1e3:.1f} ms/step); "
        f"loss {hist[0].loss:.4f} -> {hist[-1].loss:.4f}{mtp} "
        f"(dropped {hist[-1].dropped:.1%}; rows per held expert, last "
        f"step, fullest layer: {rows[fullest].astype(int).tolist()} in a "
        f"row buffer of {int(hist[-1].buffer_rows[fullest])})"
    )
    return 0


def _cmd_train_pp(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "train-pp",
        description="pipeline-parallel Transformer LM: DP x PP over a "
        "(data, pipe) mesh, GPipe microbatching in one jitted SPMD program "
        "(no analog in the reference — SURVEY.md §3)",
    )
    p.add_argument("--steps", type=int, default=30)
    p.add_argument("--batch", type=int, default=8, help="global batch size")
    p.add_argument("--lr", type=float, default=1e-2)
    p.add_argument("--seq-len", type=int, default=64)
    p.add_argument("--dp", type=int, default=None, help="data-parallel rows")
    p.add_argument("--pp", type=int, default=2, help="pipeline stages")
    p.add_argument("--layers-per-stage", type=int, default=1)
    p.add_argument("--microbatches", type=int, default=2)
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument(
        "--device-data",
        action="store_true",
        help="sample batches ON DEVICE inside one jitted chain (no host "
        "I/O per step)",
    )
    p.add_argument(
        "--remat",
        action="store_true",
        help="rematerialize each layer on backward (jax.checkpoint): "
        "stage activation memory drops from layers_per_stage to 1 layer",
    )
    p.add_argument(
        "--schedule",
        choices=("gpipe", "1f1b", "interleaved"),
        default="gpipe",
        help="pipeline schedule: gpipe holds O(microbatches) activations "
        "in flight (AD through the tick scan); 1f1b interleaves each "
        "micro's backward right behind its forward, holding O(stages) — "
        "same numerics (tests/test_pipeline.py), the standard memory fix; "
        "interleaved adds --virtual chunks per stage (Megatron virtual "
        "pipeline) so the fill/drain bubble is paid in 1/virtual-sized "
        "chunk ticks",
    )
    p.add_argument(
        "--virtual", type=int, default=1,
        help="virtual chunks per stage for --schedule interleaved "
        "(layers-per-stage must divide by it)",
    )
    _add_sharded_compress_flag(p)
    args = p.parse_args(argv)
    import jax

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.train import PipelineLMTrainer

    devs = jax.devices()
    dp = args.dp or max(1, len(devs) // args.pp)
    mesh = jax.make_mesh(
        (dp, args.pp), ("data", "pipe"), devices=devs[: dp * args.pp]
    )
    try:
        # pure flag validation only — internal construction errors keep
        # their tracebacks; flag mistakes become argparse usage errors
        PipelineLMTrainer.validate_flags(
            schedule=args.schedule,
            virtual_chunks=args.virtual,
            layers_per_stage=args.layers_per_stage,
            overlap=args.overlap,
        )
    except ValueError as e:
        p.error(str(e))
    trainer = PipelineLMTrainer(
        mesh,
        vocab=args.vocab,
        d_model=args.d_model,
        n_heads=args.heads,
        layers_per_stage=args.layers_per_stage,
        microbatches=args.microbatches,
        seq_len=args.seq_len,
        learning_rate=args.lr,
        remat=args.remat,
        compress=args.compress,
        overlap=args.overlap,
        schedule=args.schedule,
        virtual_chunks=args.virtual,
    )
    sched = args.schedule + (
        f" v={args.virtual}" if args.schedule == "interleaved" else ""
    )
    print(
        f"PP params: {trainer.param_count / 1e6:.2f}M "
        f"({trainer.n_layers} layers), mesh dp={trainer.dp} x "
        f"pp={trainer.stages}, {args.microbatches} microbatches "
        f"({sched})"
    )
    if args.steps <= 0:
        return 0
    ds = data.lm_copy_task(args.seq_len, vocab=args.vocab)
    import time

    t0 = time.perf_counter()
    if args.device_data:
        # round rows per replica UP to a whole number of microbatches
        rows = max(1, args.batch // trainer.dp)
        rows = -(-rows // args.microbatches) * args.microbatches
        eff_batch = rows * trainer.dp
        if eff_batch != args.batch:
            print(
                f"--device-data: global batch rounded {args.batch} -> "
                f"{eff_batch} ({rows} rows/replica, whole microbatches)"
            )
        hist = trainer.train_chain(
            ds.device_sampler(), args.steps, rows_per_replica=rows
        )
    else:
        hist = [
            trainer.train_step(x, y)
            for x, y in ds.batches(args.batch, args.steps)
        ]
    dt = time.perf_counter() - t0
    mode = "on-device " if args.device_data else ""
    from akka_allreduce_tpu.utils.benchmarking import transformer_train_flops

    eff = rows * trainer.dp if args.device_data else args.batch
    perf = _mfu_fields(
        transformer_train_flops(
            n_params=trainer.param_count, batch=eff, seq=args.seq_len,
            d_model=args.d_model, n_layers=trainer.n_layers,
        ),
        dt / args.steps,
        trainer.n_devices,
    )
    print(
        f"pp: {args.steps} {mode}steps on {trainer.n_devices} devices in "
        f"{dt:.2f}s ({dt / args.steps * 1e3:.1f} ms/step)"
        f"{_mfu_note(perf)}; "
        f"loss {hist[0].loss:.4f} -> {hist[-1].loss:.4f}"
    )
    return 0


def _cmd_lm_generate(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "lm-generate",
        description="KV-cache autoregressive decoding (models/generate.py): "
        "optionally train on the copy task, then generate and report "
        "decode tokens/s (slope between two generation lengths)",
    )
    p.add_argument("--vocab", type=int, default=64)
    p.add_argument("--d-model", type=int, default=128)
    p.add_argument("--heads", type=int, default=4)
    p.add_argument(
        "--kv-heads", type=int, default=None,
        help="GQA: shrink the KV cache (B, L, H_kv, D) by heads/kv_heads",
    )
    p.add_argument("--layers", type=int, default=2)
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument(
        "--gen", type=int, default=64,
        help="tokens to generate (>= 2: the slope timing needs two lengths)",
    )
    p.add_argument(
        "--train-steps", type=int, default=0,
        help="on-device copy-task training steps before decoding "
        "(0 = random params; >0 shows real text completion)",
    )
    p.add_argument("--seq-len", type=int, default=64, help="training seq len")
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--bf16", action="store_true")
    p.add_argument(
        "--cache-quant", choices=("int8",), default=None,
        help="quantize the KV cache to int8 + per-row scales (4x fewer "
        "cache bytes than f32; ~0.4%% per-element error)",
    )
    p.add_argument(
        "--sp", type=int, default=1,
        help="sequence-sharded decode: shard the KV cache's SLOT dim over "
        "an sp-device 'seq' mesh axis (split-K partial-softmax merge — "
        "caches larger than one device)",
    )
    p.add_argument(
        "--tp", type=int, default=1,
        help="tensor-parallel decode over a 'model' mesh axis (composes "
        "with --sp)",
    )
    args = p.parse_args(argv)
    if args.gen < 2:
        p.error("--gen must be >= 2 (the slope timing needs two lengths)")
    if args.sp < 1 or args.tp < 1:
        p.error("--sp and --tp must be >= 1")

    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from akka_allreduce_tpu.models import LMGenerator, TransformerLM
    from akka_allreduce_tpu.models.data import SyntheticCopyLM

    dtype = jnp.bfloat16 if args.bf16 else jnp.float32
    model = TransformerLM(
        vocab=args.vocab, d_model=args.d_model, n_heads=args.heads,
        n_kv_heads=args.kv_heads, n_layers=args.layers, compute_dtype=dtype,
    )
    ds = SyntheticCopyLM(args.seq_len, vocab=args.vocab)
    if args.train_steps > 0:
        import optax

        from akka_allreduce_tpu.parallel import data_seq_mesh
        from akka_allreduce_tpu.train import LongContextTrainer

        trainer = LongContextTrainer(
            data_seq_mesh(1, 1), vocab=args.vocab, d_model=args.d_model,
            n_heads=args.heads, n_kv_heads=args.kv_heads,
            n_layers=args.layers, seq_len=args.seq_len,
            compute_dtype=dtype, optimizer=optax.adam(3e-3),
        )
        hist = trainer.train_chain(
            ds.device_sampler(), args.train_steps, args.batch
        )
        print(
            f"trained {args.train_steps} steps: loss "
            f"{hist[0].loss:.3f} -> {hist[-1].loss:.3f}"
        )
        params = jax.device_get(trainer.params)
    else:
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((1, args.prompt_len), jnp.int32),
        )

    mesh = None
    if args.sp > 1 or args.tp > 1:
        shape, names = (), ()
        if args.sp > 1:
            shape, names = shape + (args.sp,), names + ("seq",)
        if args.tp > 1:
            shape, names = shape + (args.tp,), names + ("model",)
        mesh = jax.make_mesh(
            shape, names, devices=jax.devices()[: args.sp * args.tp]
        )
    max_len = args.prompt_len + args.gen
    max_len = -(-max_len // args.sp) * args.sp  # whole slots per seq shard
    gen = LMGenerator(
        model, max_len=max_len, cache_quant=args.cache_quant, mesh=mesh,
    )
    if mesh is not None:
        params = gen.place_params(params)
    x, _ = next(ds.batches(args.batch, 1, seed_offset=123))
    prompt = jnp.asarray(x[:, : args.prompt_len])

    # decode throughput: slope between a short and the full generation so
    # prefill + dispatch overhead cancels
    import statistics

    lo = max(1, args.gen // 4)
    gen.generate(params, prompt, lo, temperature=args.temperature)
    out = gen.generate(
        params, prompt, args.gen, temperature=args.temperature
    )  # compile both

    def timed(steps: int) -> float:
        t0 = time.perf_counter()
        o = gen.generate(
            params, prompt, steps, temperature=args.temperature,
            seed=int(t0 * 1e6) % (1 << 30),  # vary the input per call
        )
        jax.device_get(o[:1, -1])  # fence: a small fetch of the result
        return time.perf_counter() - t0

    slopes = [
        (timed(args.gen) - timed(lo)) / (args.gen - lo) for _ in range(5)
    ]
    ms_per_tok = statistics.median(slopes) * 1e3
    out_np = np.asarray(out)
    print(f"prompt : {np.asarray(prompt)[0].tolist()}")
    print(f"decoded: {out_np[0].tolist()}")
    half = args.seq_len // 2
    if args.train_steps > 0 and args.prompt_len == half + 1:
        # prompt ends at position half, so greedy decode should emit the
        # copy x[1:half] (the copy task repeats [0, half) at [half, 2half))
        want = x[0, 1:half][: out_np.shape[1]]
        got = out_np[0][: len(want)]
        acc = float((got == want).mean()) if len(want) else 0.0
        print(f"copy accuracy vs source: {acc:.1%}")
    if ms_per_tok > 1e-3:
        rate = f"{args.batch * 1e3 / ms_per_tok:.0f} tokens/s"
    else:
        rate = "n/a (noise-dominated at this size)"
    qnote = f" {args.cache_quant}-quantized" if args.cache_quant else ""
    print(
        f"decode: {ms_per_tok:.2f} ms/token, {rate} "
        f"(batch {args.batch}, cache (B,{gen.max_len},"
        f"{args.kv_heads or args.heads},{args.d_model // args.heads})"
        f"{qnote})"
    )
    return 0


def _cmd_soak(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        "soak",
        description="the everything-on endurance run (VERDICT r4 #3): "
        "flagship FSDP LM + elastic membership churn + async "
        "checkpointing + a mid-run restore, unattended; prints per-event "
        "lines and one summary JSON",
    )
    p.add_argument("--steps", type=int, default=1000)
    p.add_argument("--nodes", type=int, default=4)
    p.add_argument("--d-model", type=int, default=2048)
    p.add_argument("--layers", type=int, default=8)
    p.add_argument("--heads", type=int, default=None, help="default d/128")
    p.add_argument("--seq-len", type=int, default=2048)
    p.add_argument("--vocab", type=int, default=256)
    p.add_argument("--batch-per-replica", type=int, default=2)
    p.add_argument("--f32", action="store_true", help="disable bf16 compute")
    p.add_argument(
        "--remat", choices=("full", "params", "none"), default="params"
    )
    p.add_argument("--no-prefetch", action="store_true")
    p.add_argument(
        "--compress", choices=("bf16", "int8", "none"), default="int8"
    )
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--drop-at", type=int, default=None)
    p.add_argument("--rejoin-at", type=int, default=None)
    p.add_argument("--restore-at", type=int, default=None)
    p.add_argument(
        "--chaos", type=int, default=None, metavar="SEED",
        help="seeded membership chaos: replace the single scripted "
        "drop/rejoin with deterministic random silence windows per node "
        "(node 0 never flaps); the same seed replays the same churn",
    )
    p.add_argument("--checkpoint-every", type=int, default=100)
    p.add_argument("--checkpoint-dir", default=None)
    p.add_argument(
        "--delta-checkpoint", action="store_true",
        help="async delta store instead of async Orbax",
    )
    p.add_argument(
        "--peer-restore", action="store_true",
        help="requires --delta-checkpoint: replicate every completed delta "
        "save into a replica chunk store, WIPE the local store at the "
        "mid-run restore (disk loss), and rebuild it chunk-verified from "
        "the replica — the report's restore.source reads 'peer' and the "
        "disk-vs-peer A/B is one JSON field (RESILIENCE.md 'Recovery')",
    )
    p.add_argument("--metrics-out", default=None)
    args = p.parse_args(argv)
    if args.peer_restore and not args.delta_checkpoint:
        p.error("--peer-restore replicates delta chunks; add --delta-checkpoint")
    if args.remat == "full" and not args.no_prefetch:
        p.error(
            "--remat full excludes prefetch (the prefetched layer rides "
            "the scan carry remat exists to drop): add --no-prefetch"
        )

    import json

    from akka_allreduce_tpu.soak import run_soak

    report = run_soak(
        steps=args.steps,
        nodes=args.nodes,
        vocab=args.vocab,
        d_model=args.d_model,
        n_heads=args.heads,
        n_layers=args.layers,
        seq_len=args.seq_len,
        batch_per_replica=args.batch_per_replica,
        bf16=not args.f32,
        remat=False if args.remat == "none" else args.remat,
        prefetch=not args.no_prefetch,
        compress=None if args.compress == "none" else args.compress,
        learning_rate=args.lr,
        drop_at=args.drop_at,
        rejoin_at=args.rejoin_at,
        restore_at=args.restore_at,
        chaos_seed=args.chaos,
        checkpoint_every=args.checkpoint_every,
        checkpoint_dir=args.checkpoint_dir,
        delta=args.delta_checkpoint,
        peer_restore=args.peer_restore,
        metrics_out=args.metrics_out,
    )
    print(json.dumps(report.as_dict()))
    return 0


def _drill_spawn(env):
    """Subprocess factory shared by the chaos drills — ONE parent python
    owns every role (separate shell jobs may land in isolated sandbox
    network namespaces and never reach each other's loopback ports)."""
    import subprocess

    def spawn(*cli):
        return subprocess.Popen(
            [sys.executable, "-m", "akka_allreduce_tpu", *cli],
            env=env, stdout=subprocess.PIPE, text=True,
        )

    return spawn


def _drill_pump(proc, into: list):
    """Drain a drill subprocess's stdout into ``into`` from a daemon
    thread (shared by the drills that watch for marker lines — TAKEOVER,
    RESTORE — while the process keeps running)."""
    import threading

    t = threading.Thread(target=lambda: into.extend(proc.stdout), daemon=True)
    t.start()
    return t


def _add_drill_gossip_flags(p: argparse.ArgumentParser) -> None:
    """Every chaos drill can run its cluster under SWIM gossip membership
    instead of hub heartbeats (the Makefile pins --gossip on all of them,
    like --streams 2): the drills then prove their scenario survives the
    decentralized detector too."""
    p.add_argument(
        "--gossip", action="store_true",
        help="arm SWIM gossip membership on the drill's cluster "
        "(distributed via Welcome, RESILIENCE.md 'Tier 6')",
    )
    p.add_argument(
        "--gossip-interval", type=float, default=0.25, metavar="S",
        help="gossip probe period for the drill cluster",
    )


def _drill_gossip_args(args) -> list[str]:
    """Extra cluster-master CLI args for a drill's master spawn."""
    if not getattr(args, "gossip", False):
        return []
    return [
        "--gossip", "--gossip-interval",
        str(getattr(args, "gossip_interval", 0.25)),
    ]


def _add_drill_lever_flags(p: argparse.ArgumentParser) -> None:
    """Every chaos drill can arm the data plane v3 levers on its cluster
    (the Makefile pins all three, like --streams 2 and --gossip): the
    drills then prove their scenario survives the levered plane too. With
    --streams 2 the intra-chunk split is inert by construction (one
    payload stream — nothing to split across), but the knob distribution,
    scheduler, and uring probe/fallback paths all run."""
    p.add_argument(
        "--uring", action="store_true",
        help="arm io_uring burst submission on the drill's cluster",
    )
    p.add_argument(
        "--intra-chunk", type=int, default=0, metavar="BYTES",
        dest="intra_chunk",
        help="arm intra-chunk striping at this byte bar (0 = off)",
    )
    p.add_argument(
        "--congestion", action="store_true",
        help="arm congestion-aware stripe scheduling",
    )


def _drill_lever_args(args) -> list[str]:
    """Extra cluster-master CLI args arming the v3 levers for a drill."""
    out: list[str] = []
    if getattr(args, "uring", False):
        out.append("--uring")
    bar = getattr(args, "intra_chunk", 0)
    if bar:
        out += ["--intra-chunk", str(bar)]
    if getattr(args, "congestion", False):
        out.append("--congestion")
    return out


def _drill_jsonl_records(path):
    """Records of a (possibly live) metrics JSONL — the ONE torn-tolerant
    reader every drill scan goes through: blank lines and the in-progress
    writer's torn last line are skipped, never a traceback."""
    import json
    import os

    if not os.path.exists(path):
        return
    with open(path) as f:
        for ln in f:
            if not ln.strip():
                continue
            try:
                yield json.loads(ln)
            except ValueError:
                continue  # the writer is mid-append


def _drill_full_rounds(path, workers: int) -> int:
    """Completed line-rounds with FULL membership recorded in a master's
    metrics JSONL — recovery progress only counts when every node is back
    in the line."""
    return sum(
        1
        for rec in _drill_jsonl_records(path)
        if rec.get("kind") == "round" and rec.get("workers") == workers
    )


def _drill_phase_waiter(timeout_s: float, failures: list):
    """``await_phase(pred, what)`` with one shared timeout/report shape."""

    def await_phase(pred, what: str) -> bool:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            if pred():
                return True
            time.sleep(0.2)
        failures.append(f"timed out waiting for {what}")
        return False

    return await_phase


def _cmd_bench_wire(argv: list[str]) -> int:
    """Deterministic host data-plane microbench (``make bench-wire``):
    per-core codec throughput (encode+checksum / decode+verify) and the
    syscall-batching comparison (one ``sendmsg`` per frame vs one
    ``sendmmsg`` per burst, plus the recv side) over loopback TCP. The
    pair-cluster A/B (BENCHMARKS.md round 8) measures the system; this
    measures the LEVERS, on a box whose run-to-run drift would otherwise
    drown them — legs are interleaved and the medians reported."""
    p = argparse.ArgumentParser(
        "bench-wire",
        description="wire codec + batch-syscall microbench (JSON output)",
    )
    p.add_argument(
        "--size", type=int, default=4096,
        help="floats per payload frame (default 16KB frames — small "
        "enough that per-syscall overhead is visible)",
    )
    p.add_argument("--frames", type=int, default=64, help="frames per burst")
    p.add_argument("--reps", type=int, default=9, help="interleaved reps/leg")
    p.add_argument("--json", action="store_true", help="print the JSON record")
    p.add_argument("--out", default=None, help="append the JSON record here")
    # data plane v3 per-lever A/Bs (BENCHMARKS.md round 9): each flag runs
    # its lever's leg and emits ONE extra JSON record, so `make bench-wire`
    # reproduces every A/B in one command
    p.add_argument(
        "--uring", action="store_true",
        help="A/B io_uring burst submission vs sendmmsg (or record the "
        "runtime probe's fallback reason on a kernel without io_uring)",
    )
    p.add_argument(
        "--intra-chunk", action="store_true", dest="intra_chunk",
        help="A/B a ONE-chunk round (one giant frame) on one stream vs "
        "split across payload streams, over per-stream-paced loopback "
        "drains (the per-connection bandwidth-ceiling model)",
    )
    p.add_argument(
        "--congestion", action="store_true",
        help="run the stripe scheduler's shed/restore simulation under a "
        "fake clock (deterministic: the record includes the replay check)",
    )
    args = p.parse_args(argv)

    import json
    import socket
    import statistics
    import threading

    import numpy as np

    from akka_allreduce_tpu import native
    from akka_allreduce_tpu.control import wire
    from akka_allreduce_tpu.protocol import ScatterBlock

    rng = np.random.default_rng(7)
    payloads = [
        rng.standard_normal(args.size).astype(np.float32)
        for _ in range(args.frames)
    ]
    msgs = [
        ScatterBlock(v, 0, 1, i, 1) for i, v in enumerate(payloads)
    ]
    dest = "worker:1"
    payload_bytes = args.size * 4 * args.frames

    def timed(fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    # -- codec legs (pure compute, no sockets) --------------------------------
    def leg_encode() -> None:
        for m in msgs:
            wire.encode_frame_parts(dest, m)

    frames_bytes = [b"".join(wire.encode_frame_parts(dest, m)) for m in msgs]

    def leg_decode() -> None:
        for f in frames_bytes:
            wire.decode_frame_body_ex(memoryview(f)[4:])

    def leg_checksum() -> None:
        for v in payloads:
            native.wire_checksum(memoryview(v).cast("B"))

    codec: dict[str, list[float]] = {"encode": [], "decode": [], "checksum": []}
    for _ in range(args.reps):
        codec["encode"].append(timed(leg_encode))
        codec["decode"].append(timed(leg_decode))
        codec["checksum"].append(timed(leg_checksum))

    # -- syscall legs: loopback TCP, a drain thread on the far end ------------
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    tx = socket.create_connection(srv.getsockname())
    rx, _ = srv.accept()
    srv.close()
    tx.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    for sk in (tx, rx):
        for opt in (socket.SO_SNDBUF, socket.SO_RCVBUF):
            try:
                sk.setsockopt(socket.SOL_SOCKET, opt, 8 << 20)
            except OSError:
                pass
    stop = threading.Event()

    def drain() -> None:
        sink = bytearray(1 << 20)
        while not stop.is_set():
            try:
                if not rx.recv_into(sink):
                    return
            except OSError:
                return

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()

    frame_views = [
        [memoryview(f)] for f in frames_bytes
    ]  # one message per frame, rebuilt per send below

    def send_all(batched: bool, force_fallback: bool = False) -> None:
        frames = [list(f) for f in frame_views]
        if batched:
            while frames:
                n = native.batch_send(
                    tx.fileno(), frames, force_fallback=force_fallback
                )
                # advance past sent bytes
                while n and frames:
                    head = frames[0]
                    while n and head:
                        seg = head[0]
                        if n >= len(seg):
                            n -= len(seg)
                            head.pop(0)
                        else:
                            head[0] = seg[n:]
                            n = 0
                    if not head:
                        frames.pop(0)
            return
        for f in frames:  # one syscall per frame: the un-batched baseline
            views = list(f)
            while views:
                n = tx.sendmsg(views)
                while n and views:
                    seg = views[0]
                    if n >= len(seg):
                        n -= len(seg)
                        views.pop(0)
                    else:
                        views[0] = seg[n:]
                        n = 0

    have_native = native.batch_send_available()
    have_mmsg = native.sendmmsg_available()
    sysc: dict[str, list[float]] = {
        "sendmsg_loop": [], "sendmmsg": [], "sendmmsg_fallback": [],
    }
    for _ in range(args.reps):  # interleaved: noise hits every leg alike
        sysc["sendmsg_loop"].append(timed(lambda: send_all(False)))
        if have_native:
            sysc["sendmmsg"].append(timed(lambda: send_all(True)))
            sysc["sendmmsg_fallback"].append(
                timed(lambda: send_all(True, force_fallback=True))
            )
    stop.set()
    tx.close()
    rx.close()
    drainer.join(timeout=2.0)

    # -- recv legs: recvmmsg batch vs recv loop over a pre-pumped stream ------
    recv: dict[str, list[float]] = {"recv_loop": [], "recvmmsg": []}
    if have_native:
        chunk = 64 << 10
        nbufs = 16
        bufs = [bytearray(chunk) for _ in range(nbufs)]
        total = payload_bytes

        def recv_bench(batched: bool) -> float:
            a = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            a.bind(("127.0.0.1", 0))
            a.listen(1)
            c = socket.create_connection(a.getsockname())
            b, _ = a.accept()
            a.close()
            blob = b"\x00" * total

            def pump() -> None:
                try:
                    c.sendall(blob)
                finally:
                    c.close()

            th = threading.Thread(target=pump, daemon=True)
            th.start()
            got = 0
            t0 = time.perf_counter()
            while got < total:
                if batched:
                    n = native.batch_recv(b.fileno(), bufs)
                else:
                    n = b.recv_into(bufs[0])
                if n <= 0:
                    break
                got += n
            dt = time.perf_counter() - t0
            b.close()
            th.join(timeout=2.0)
            return dt

        for _ in range(args.reps):
            recv["recv_loop"].append(recv_bench(False))
            recv["recvmmsg"].append(recv_bench(True))

    def mbps(times: list[float]) -> float | None:
        if not times:
            return None
        return round(payload_bytes / statistics.median(times) / 1e6, 1)

    record = {
        "bench": "wire",
        "size_floats": args.size,
        "frames": args.frames,
        "reps": args.reps,
        "native_loaded": native.loaded(),
        "sendmmsg_available": have_mmsg,
        "encode_mbps": mbps(codec["encode"]),
        "decode_mbps": mbps(codec["decode"]),
        "checksum_mbps": mbps(codec["checksum"]),
        "sendmsg_loop_mbps": mbps(sysc["sendmsg_loop"]),
        "sendmmsg_mbps": mbps(sysc["sendmmsg"]),
        "sendmmsg_fallback_mbps": mbps(sysc["sendmmsg_fallback"]),
        "recv_loop_mbps": mbps(recv["recv_loop"]),
        "recvmmsg_mbps": mbps(recv["recvmmsg"]),
    }
    records = [record]
    if args.uring:
        records.append(_bench_wire_uring(args, frames_bytes, payload_bytes))
    if args.intra_chunk:
        records.append(_bench_wire_intra_chunk(args))
    if args.congestion:
        records.append(_bench_wire_congestion())
    out_lines = [json.dumps(r, sort_keys=True) for r in records]
    if args.out:
        with open(args.out, "a") as f:
            for line in out_lines:
                f.write(line + "\n")
    if args.json or not args.out:
        for line in out_lines:
            print(line)
    return 0


def _bench_wire_uring(args, frames_bytes, payload_bytes) -> dict:
    """Lever (a): io_uring burst submission vs the sendmmsg batch — same
    frame mix, same loopback drain, interleaved legs. On a kernel without
    io_uring the record carries the probe's fallback reason instead of a
    number: the lever's honest state on this box."""
    import socket
    import statistics
    import threading

    from akka_allreduce_tpu import native

    rec: dict = {
        "bench": "wire",
        "lever": "uring",
        "uring_available": native.uring_available(),
        "uring_probe_reason": native.uring_probe_reason(),
        "uring_mbps": None,
        "sendmmsg_mbps": None,
    }
    if not native.uring_available() or not native.batch_send_available():
        return rec
    srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
    srv.bind(("127.0.0.1", 0))
    srv.listen(1)
    tx = socket.create_connection(srv.getsockname())
    rx, _ = srv.accept()
    srv.close()
    tx.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
    stop = threading.Event()

    def drain() -> None:
        sink = bytearray(1 << 20)
        while not stop.is_set():
            try:
                if not rx.recv_into(sink):
                    return
            except OSError:
                return

    drainer = threading.Thread(target=drain, daemon=True)
    drainer.start()
    ring = native.UringRing()

    def advance(frames: list, n: int) -> None:
        while n and frames:
            head = frames[0]
            while n and head:
                seg = head[0]
                if n >= len(seg):
                    n -= len(seg)
                    head.pop(0)
                else:
                    head[0] = seg[n:]
                    n = 0
            if not head:
                frames.pop(0)

    def send_all(use_uring: bool) -> None:
        frames = [[memoryview(f)] for f in frames_bytes]
        while frames:
            if use_uring:
                flat = [v for fr in frames for v in fr]
                try:
                    n = ring.send(tx.fileno(), flat)
                except BlockingIOError:
                    continue
            else:
                n = native.batch_send(tx.fileno(), frames)
            advance(frames, n)

    times: dict[str, list[float]] = {"sendmmsg": [], "uring": []}
    try:
        for _ in range(args.reps):
            for key, flag in (("sendmmsg", False), ("uring", True)):
                t0 = time.perf_counter()
                send_all(flag)
                times[key].append(time.perf_counter() - t0)
    finally:
        ring.close()
        stop.set()
        tx.close()
        rx.close()
        drainer.join(timeout=2.0)
    for key in times:
        rec[f"{key}_mbps"] = round(
            payload_bytes / statistics.median(times[key]) / 1e6, 1
        )
    rec["uring_ge_sendmmsg"] = rec["uring_mbps"] >= rec["sendmmsg_mbps"]
    return rec


def _bench_wire_intra_chunk(args) -> dict:
    """Lever (b): a ONE-chunk round's bytes over one stream (what chunk-id
    striping does to a single-tensor allreduce or a state-transfer frame)
    vs split across 3 payload streams — over loopback connections whose
    drains are PACED to a fixed per-stream rate, the model of the real
    phenomenon (each TCP stream has a bandwidth ceiling; on loopback the
    kernel would otherwise hide it). The bytes are a real encoded frame,
    split at the same offsets the transport's splitter uses."""
    import socket
    import statistics
    import threading

    import numpy as np

    from akka_allreduce_tpu.control import wire
    from akka_allreduce_tpu.protocol import ScatterBlock

    n_payload = 3  # streams=4
    pace_mbps = 200.0  # per-stream drain ceiling
    read_chunk = 256 << 10
    value = np.random.default_rng(7).standard_normal(6_000_000).astype(
        np.float32
    )  # ~24 MB one-chunk frame
    body = b"".join(
        bytes(p) for p in wire.encode_frame_parts("worker:1", ScatterBlock(value, 0, 1, 0, 1))
    )

    def leg(n_streams: int) -> float:
        frag = -(-len(body) // n_streams)
        slices = [
            body[i * frag : (i + 1) * frag] for i in range(n_streams)
        ]
        pairs = []
        for _ in range(n_streams):
            srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
            srv.bind(("127.0.0.1", 0))
            srv.listen(1)
            c = socket.create_connection(srv.getsockname())
            a, _ = srv.accept()
            srv.close()
            pairs.append((c, a))
        done = threading.Barrier(2 * n_streams + 1)

        def write(sock, blob) -> None:
            try:
                sock.sendall(blob)
            finally:
                done.wait()

        def drain(sock, want: int) -> None:
            sink = bytearray(read_chunk)
            got = 0
            budget = time.perf_counter()
            try:
                while got < want:
                    n = sock.recv_into(sink)
                    if not n:
                        break
                    got += n
                    # pace: this stream may not drain faster than the
                    # per-stream ceiling — sleep off any surplus
                    budget += n / (pace_mbps * 1e6)
                    now = time.perf_counter()
                    if budget > now:
                        time.sleep(budget - now)
            finally:
                done.wait()

        threads = []
        t0 = time.perf_counter()
        for (c, a), blob in zip(pairs, slices):
            threads.append(
                threading.Thread(target=write, args=(c, blob), daemon=True)
            )
            threads.append(
                threading.Thread(
                    target=drain, args=(a, len(blob)), daemon=True
                )
            )
        for t in threads:
            t.start()
        done.wait()
        dt = time.perf_counter() - t0
        for c, a in pairs:
            c.close()
            a.close()
        return dt

    single: list[float] = []
    striped: list[float] = []
    for _ in range(max(3, args.reps // 3)):
        single.append(leg(1))
        striped.append(leg(n_payload))
    s, m = statistics.median(single), statistics.median(striped)
    return {
        "bench": "wire",
        "lever": "intra_chunk",
        "model": f"per-stream drains paced at {pace_mbps:g} MB/s",
        "frame_mb": round(len(body) / 1e6, 1),
        "payload_streams": n_payload,
        "single_stream_s": round(s, 4),
        "striped_s": round(m, 4),
        "speedup": round(s / m, 2),
    }


def _bench_wire_congestion() -> dict:
    """Lever (c): the stripe scheduler's closed loop under a FAKE clock —
    a 3-stream endpoint where stream 2 drains at 15% (the chaos ``delay``
    shape), then heals. Deterministic by construction (no wall clock, no
    RNG): the record carries a replay check and the windows-to-shed the
    acceptance bar asks for."""
    from akka_allreduce_tpu.control.stripes import StripeScheduler

    degraded = 2
    frame = 1 << 20

    def run() -> tuple[list[float], dict]:
        sched = StripeScheduler(3)
        fair = 1.0 / 3.0
        shares: list[float] = []
        backlog = [0, 0, 0]  # the simulated sockets' unsent bytes
        windows_to_half = None
        restored_at = None
        for w in range(40):
            now = w * sched.window_s
            for _ in range(12):
                idx = sched.pick(frame, now)
                backlog[idx] += frame
            healed = w >= 20
            for i in range(3):
                # per-window drain capacity: healthy streams clear their
                # queue (backlog included — a healed stream catches up),
                # the degraded one moves 15% of a fair window
                cap = (16 << 20) if (i != degraded or healed) else int(
                    0.15 * (4 << 20)
                )
                sent = min(backlog[i], cap)
                backlog[i] -= sent
                sched.note_sent(i, sent, now)
            share = sched.share(degraded)
            shares.append(round(share, 4))
            if windows_to_half is None and share <= fair / 2.0:
                windows_to_half = w + 1
            if (
                windows_to_half is not None
                and restored_at is None
                and healed
                and share >= fair * 0.9
            ):
                restored_at = w + 1
        return shares, {
            "windows_to_half_share": windows_to_half,
            "restored_by_window": restored_at,
            "final_weights": sched.snapshot()["weights"],
            "sheds": sched.sheds,
            "restores": sched.restores,
        }

    shares_a, rec = run()
    shares_b, _ = run()
    return {
        "bench": "wire",
        "lever": "congestion",
        "degraded_stream": degraded,
        "share_trajectory": shares_a[:12],
        "deterministic": shares_a == shares_b,
        **rec,
    }


def _cmd_chaos(argv: list[str]) -> int:
    """Chaos harness: a real master + N node OS processes over loopback,
    every transport armed with the SAME seeded fault schedule (the master
    distributes the spec via Welcome), invariants summarized at the end.
    ``make chaos`` runs the fixed-seed 30-second variant (RESILIENCE.md)."""
    p = argparse.ArgumentParser(
        "chaos",
        description="run a tiny cluster under seeded fault injection and "
        "report what happened (chaos events vs rounds completed)",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument(
        "--spec",
        default="drop:p=0.05;delay:ms=10;corrupt:p=0.02",
        help="fault spec (see RESILIENCE.md for the grammar)",
    )
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument(
        "--rounds", type=int, default=50,
        help="line-round budget; ignored when --duration is set",
    )
    p.add_argument(
        "--duration", type=float, default=None,
        help="run open-ended for this many seconds instead of a round "
        "budget (the 30s soak `make chaos` uses)",
    )
    p.add_argument("--size", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome); "
        "2 makes the drill exercise the multi-stream reassembly path "
        "under every injected fault",
    )
    p.add_argument("--out-dir", default="chaos_run")
    _add_drill_gossip_flags(p)
    _add_drill_lever_flags(p)
    args = p.parse_args(argv)
    # fail fast on a malformed spec BEFORE spawning anything — a parse
    # error inside the master subprocess would surface as an opaque
    # "never reported its endpoint" failure here
    from akka_allreduce_tpu.control.chaos import parse_spec

    try:
        parse_spec(args.spec)
    except ValueError as e:
        p.error(str(e))

    import json
    import os
    import signal as _signal
    import subprocess

    os.makedirs(args.out_dir, exist_ok=True)
    metrics_path = os.path.join(args.out_dir, "rounds.jsonl")
    master_log = os.path.join(args.out_dir, "chaos-master.jsonl")
    stale = [f for f in os.listdir(args.out_dir) if f.endswith(".jsonl")]
    for f in stale:  # MetricsLogger appends; never mix two runs' records
        os.remove(os.path.join(args.out_dir, f))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)
    rounds = -1 if args.duration else args.rounds
    wedged = False
    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", str(rounds), "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        "--chaos-seed", str(args.seed), "--chaos-spec", args.spec,
        "--chaos-log", master_log, "--metrics-out", metrics_path,
        *_drill_gossip_args(args),
        *_drill_lever_args(args),
    )
    nodes = []
    t0 = time.perf_counter()
    master_done = False
    try:
        seed_ep = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("master never reported its endpoint")
        for k in range(args.nodes):
            nodes.append(
                spawn(
                    "cluster-node", "--seed", seed_ep, "--node-id", str(k),
                    "--chaos-log",
                    os.path.join(args.out_dir, f"chaos-node{k}.jsonl"),
                )
            )
        try:
            if args.duration:
                time.sleep(args.duration)
                master.send_signal(_signal.SIGTERM)
                master.wait(timeout=30)
                # the Shutdown broadcast is racing any mid-rejoin node:
                # give every node a grace window to exit (and flush its
                # chaos log) before the finally-kill
                for n in nodes:
                    try:
                        n.wait(timeout=10)
                    except subprocess.TimeoutExpired:
                        pass
            else:
                out, _ = master.communicate(timeout=600)
                master_done = "master done" in out
                for n in nodes:
                    try:
                        n.wait(timeout=30)
                    except subprocess.TimeoutExpired:
                        n.kill()
        except subprocess.TimeoutExpired:
            # a wedged cluster is a RESULT for this harness, not a crash:
            # fall through to the summary (which will report the wedge and
            # exit non-zero), never a bare traceback
            wedged = True
    finally:
        for proc in [master, *nodes]:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    elapsed = time.perf_counter() - t0
    rounds_completed = 0
    if os.path.exists(metrics_path):
        with open(metrics_path) as f:
            rounds_completed = sum(
                1
                for ln in f
                if ln.strip() and json.loads(ln).get("kind") == "round"
            )
    events: dict[str, int] = {}
    logs = sorted(
        f for f in os.listdir(args.out_dir) if f.startswith("chaos-")
    )
    for f in logs:
        with open(os.path.join(args.out_dir, f)) as fh:
            for ln in fh:
                if ln.strip():
                    fault = json.loads(ln)["fault"]
                    events[fault] = events.get(fault, 0) + 1
    from akka_allreduce_tpu.control.chaos import CRASH_EXIT_CODE

    summary = {
        "seed": args.seed,
        "spec": args.spec,
        "elapsed_s": round(elapsed, 1),
        "rounds_completed": rounds_completed,
        "master_done": master_done or None,
        "wedged": wedged or None,
        "chaos_events": events,
        "chaos_logs": logs,
        "node_exits": [n.returncode for n in nodes],
        "injected_crashes": sum(
            1 for n in nodes if n.returncode == CRASH_EXIT_CODE
        ),
    }
    print(json.dumps(summary))
    # pass = the cluster made progress UNDER chaos without wedging; with a
    # round budget the budget must also have finished
    ok = (
        not wedged
        and rounds_completed > 0
        and (args.duration is not None or master_done)
    )
    return 0 if ok else 1


def _blobs_match_replicas(
    state_dirs, victim: int, restore: dict, n_nodes: int, failures: list
) -> bool | None:
    """Byte-identity for the chaos-recover drill, against the RESTORE
    record's own leaf->sha evidence (printed by the node at restore time —
    immune to the node's later saves/prunes racing this check): every
    restored blob must exist on some replica with bytes that hash back to
    its content-addressed name (the same verify gate the restore itself
    passed — hash equality IS byte equality here), and when the victim's
    copy is still on disk it is compared raw as well."""
    from akka_allreduce_tpu.control.statetransfer import ChunkStore, npy_sha

    shas = set(restore.get("leaves", {}).values())
    if not shas:
        failures.append("restore record carries no leaf evidence")
        return None
    own = ChunkStore(state_dirs[victim])
    ok = True
    for sha in sorted(shas):
        replica_bytes = None
        for k in range(n_nodes):
            if k == victim:
                continue
            peer = ChunkStore(state_dirs[k])
            try:
                # the replicas are LIVE and pruning; a blob vanishing
                # between has() and read() is the next peer's problem,
                # not a harness crash
                if peer.has(sha):
                    replica_bytes = peer.read(sha)
                    break
            except FileNotFoundError:
                continue
        if replica_bytes is None:
            ok = False
            failures.append(f"blob {sha[:12]} held by no replica")
            continue
        if npy_sha(replica_bytes) != sha:
            ok = False
            failures.append(f"replica blob {sha[:12]} fails content hash")
        try:
            mine = own.read(sha) if own.has(sha) else None
        except FileNotFoundError:  # pruned between has() and read()
            mine = None
        if mine is not None and mine != replica_bytes:
            ok = False
            failures.append(f"blob {sha[:12]} differs from replica")
    return ok


def _cmd_chaos_recover(argv: list[str]) -> int:
    """Crash + disk-loss recovery drill (RESILIENCE.md "Recovery", ISSUE 6
    acceptance): a real master + N state-armed node processes run a round
    budget under a SEEDED chaos crash of one node; the harness then deletes
    the crashed node's checkpoint directory (disk loss) and respawns it.
    The node must rejoin, pull its state back from live peer replicas
    (``RESTORE {"source": "peer", ...}``), keep contributing, and the
    budget must finish — with the restored blobs byte-identical to the
    replica copies. ``make chaos-recover`` runs the fixed-seed variant;
    tests/test_peer_restore.py wires it into tier-1."""
    p = argparse.ArgumentParser(
        "chaos-recover",
        description="seeded crash + checkpoint-dir loss; assert the node "
        "recovers via peer restore and the round budget completes",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument(
        "--crash-round", type=int, default=25,
        help="round at which the victim's seeded crash fires (several "
        "save/replicate cycles must fit before it — see --state-every)",
    )
    p.add_argument(
        "--min-post-rounds", type=int, default=40,
        help="full-membership rounds that must complete AFTER the peer "
        "restore before the run is allowed to finish (the post-recovery "
        "half of the training budget)",
    )
    p.add_argument(
        "--phase-timeout", type=float, default=240.0,
        help="wall-clock bound on each recovery phase",
    )
    p.add_argument("--size", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    p.add_argument("--state-every", type=int, default=5)
    p.add_argument("--out-dir", default="chaos_recover_run")
    _add_drill_gossip_flags(p)
    _add_drill_lever_flags(p)
    args = p.parse_args(argv)
    if args.nodes < 3:
        p.error("need >= 3 nodes: the victim plus at least 2 replica holders")

    import json
    import os
    import shutil
    import signal as _signal
    import subprocess

    from akka_allreduce_tpu.control.chaos import CRASH_EXIT_CODE

    victim = args.nodes - 1
    spec = f"crash:node={victim},at=round{args.crash_round}"
    os.makedirs(args.out_dir, exist_ok=True)
    metrics_path = os.path.join(args.out_dir, "rounds.jsonl")
    if os.path.exists(metrics_path):
        os.remove(metrics_path)  # MetricsLogger appends; one run per file
    state_dirs = [
        os.path.join(args.out_dir, f"state{k}") for k in range(args.nodes)
    ]
    for d in state_dirs:
        if os.path.isdir(d):
            shutil.rmtree(d)  # a fresh drill must not inherit old state
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)

    def spawn_node(seed_ep, k):
        return spawn(
            "cluster-node", "--seed", seed_ep, "--node-id", str(k),
            "--state-dir", state_dirs[k],
            "--state-every", str(args.state_every),
        )

    failures: list[str] = []
    restore = None
    crash_exit = None
    master_done = False
    byte_identical = None
    reborn = None
    reborn_lines: list[str] = []
    rounds_at_crash = rounds_at_done = 0

    def full_rounds() -> int:
        return _drill_full_rounds(metrics_path, args.nodes)

    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", "-1", "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--metrics-out", metrics_path,
        *_drill_gossip_args(args),
        *_drill_lever_args(args),
    )
    nodes = []
    try:
        seed_ep = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("master never reported its endpoint")
        nodes = [spawn_node(seed_ep, k) for k in range(args.nodes)]
        # phase 1: the seeded crash fires (deterministic round trigger; the
        # run is open-ended, so no machine is "too fast" for the drill)
        try:
            crash_exit = nodes[victim].wait(timeout=args.phase_timeout)
        except subprocess.TimeoutExpired:
            failures.append("victim never crashed (chaos round not reached)")
        if crash_exit is not None and crash_exit != CRASH_EXIT_CODE:
            failures.append(
                f"victim exited {crash_exit}, not the chaos crash "
                f"{CRASH_EXIT_CODE}"
            )
        rounds_at_crash = full_rounds()
        # phase 2: the disk dies with the process
        shutil.rmtree(state_dirs[victim], ignore_errors=True)
        # phase 2.5 (the deflake gate): wait for the MASTER to have
        # OBSERVED the death — a reduced-membership round record in its
        # metrics JSONL proves the victim was expelled and the grid
        # re-organized. Respawning before that races the detector: the
        # victim's id still reads as a LIVE member, so the reborn
        # process's preferred id is "taken" and it gets minted a fresh
        # id whose checkpoint history is empty — the restore then misses
        # through no fault of the recovery path (the historical flake).
        if not failures:
            await_phase(
                lambda: _drill_full_rounds(metrics_path, args.nodes - 1) >= 1,
                "the master's expulsion of the victim "
                "(reduced-membership rounds in the metrics log)",
            )
        # phase 3: same identity, empty disk — recovery must come from
        # peers; its stdout is pumped on a thread so RESTORE is observable
        # while the cluster keeps running
        if not failures:
            reborn = spawn_node(seed_ep, victim)
            pump = _drill_pump(reborn, reborn_lines)
            await_phase(
                lambda: any(
                    ln.startswith("RESTORE ") for ln in list(reborn_lines)
                ),
                "the respawned node's restore report",
            )
            for line in list(reborn_lines):
                if line.startswith("RESTORE "):
                    restore = json.loads(line[len("RESTORE "):])
            # byte-identity is checked NOW, against the RESTORED step's
            # manifest, while its blobs and the replicas' copies are all
            # still on disk — the node keeps saving (and pruning) after
            # this, and the FINAL save's replication is asynchronous, so a
            # shutdown-time check against `latest()` would race both
            if restore is not None and restore.get("complete"):
                byte_identical = _blobs_match_replicas(
                    state_dirs, victim, restore, args.nodes, failures
                )
            # phase 4: the post-recovery training budget — min_post_rounds
            # MORE full-membership rounds with the restored node in the line
            target = full_rounds() + args.min_post_rounds
            await_phase(
                lambda: full_rounds() >= target,
                f"{args.min_post_rounds} full-membership rounds post-restore",
            )
        rounds_at_done = full_rounds()
        # phase 5: end the open-ended run gracefully (Shutdown broadcast)
        master.send_signal(_signal.SIGTERM)
        try:
            out_master, _ = master.communicate(timeout=60)
            master_done = "master done" in out_master
        except subprocess.TimeoutExpired:
            failures.append("master did not shut down on SIGTERM")
        for n in (n for i, n in enumerate(nodes) if i != victim):
            try:
                n.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                n.kill()
        if reborn is not None:
            # its stdout is owned by the pump thread — wait, don't
            # communicate (two readers on one pipe)
            try:
                reborn.wait(timeout=30)
            except subprocess.TimeoutExpired:
                reborn.kill()
            pump.join(timeout=10)
    finally:
        for proc in [master, *nodes, *([reborn] if reborn else [])]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()

    post_rounds = 0
    for line in reborn_lines:
        if line.startswith("RESTORE ") and restore is None:
            restore = json.loads(line[len("RESTORE "):])
        if "shut down" in line and " rounds" in line:
            try:
                post_rounds = int(line.split(":")[-1].split()[0])
            except ValueError:
                pass
    if restore is None:
        failures.append("respawned node never reported a restore")
    else:
        if restore.get("source") != "peer":
            failures.append(f"restore source {restore.get('source')!r} != 'peer'")
        if not restore.get("complete"):
            failures.append("peer restore incomplete")
        elif byte_identical is None:
            failures.append("byte-identity was never checked")
    if not master_done:
        failures.append("run did not finish cleanly")
    if reborn is not None and reborn.returncode not in (0, None):
        failures.append(f"respawned node exited {reborn.returncode}")
    if not post_rounds:
        failures.append("no post-restore round progress at the reborn node")

    # torn-tolerant via the shared reader: when the master had to be
    # killed (a failure path), its metrics writer may have died mid-append
    # — the summary must still come out instead of a JSON traceback
    rounds_completed = sum(
        1
        for rec in _drill_jsonl_records(metrics_path)
        if rec.get("kind") == "round"
    )
    summary = {
        "seed": args.seed,
        "spec": spec,
        "rounds_completed": rounds_completed,
        "full_rounds_at_crash": rounds_at_crash,
        "full_rounds_post_restore": rounds_at_done - rounds_at_crash,
        "master_done": master_done,
        "crash_exit": crash_exit,
        "restore": restore,
        "post_restore_rounds": post_rounds,
        "byte_identical": byte_identical,
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def _cmd_chaos_gossip(argv: list[str]) -> int:
    """Decentralized-membership drill (RESILIENCE.md "Tier 6",
    ``make chaos-gossip``): a real master + N node processes run under
    SWIM gossip membership while a SEEDED ONE-DIRECTIONAL partition cuts
    one node's sends TO the master (``partition:from=K,to=m``) — the
    exact asymmetric loss that makes a hub detector read a healthy node
    as dead. Pass requires:

    - ZERO expulsions while the bad link is down (indirect probes through
      the other nodes keep vouching for the victim — full-membership
      rounds keep completing throughout);
    - after the window heals, a node SIGKILLed for real IS expelled by
      the gossip verdict and the grid reorganizes (the detector still
      detects — it just needs more than one vantage point to convict).
    """
    p = argparse.ArgumentParser(
        "chaos-gossip",
        description="seeded asymmetric partition of the master's inbound "
        "link under gossip membership; assert zero false expulsions, "
        "then a real kill is still detected",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument("--nodes", type=int, default=5)
    p.add_argument(
        "--partition-at", type=float, default=6.0,
        help="seconds (per-process clock) until the one-way partition",
    )
    p.add_argument(
        "--partition-for", type=float, default=6.0,
        help="how long the bad link stays down",
    )
    p.add_argument(
        "--min-post-rounds", type=int, default=10,
        help="reduced-membership rounds required after the real kill",
    )
    p.add_argument("--phase-timeout", type=float, default=240.0)
    p.add_argument("--size", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument("--gossip-interval", type=float, default=0.25)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    _add_drill_lever_flags(p)
    p.add_argument("--out-dir", default="chaos_gossip_run")
    args = p.parse_args(argv)
    if args.nodes < 4:
        # th=0.66 must stay satisfiable by the reporters the master can
        # hear while ONE node's completions are cut: need
        # ceil(0.66*N) <= N-1, and >= 2 relays for indirect probes
        p.error("need >= 4 nodes (threshold headroom + indirect relays)")

    import json
    import os
    import signal as _signal
    import subprocess

    victim = args.nodes - 1  # the bad-link node (stays healthy)
    killed = args.nodes - 2  # the really-dead node of phase 2
    spec = (
        f"partition:from={victim},to=m,"
        f"at={args.partition_at:g}s,heal={args.partition_for:g}s"
    )
    os.makedirs(args.out_dir, exist_ok=True)
    metrics_path = os.path.join(args.out_dir, "rounds.jsonl")
    stale = [f for f in os.listdir(args.out_dir) if f.endswith(".jsonl")]
    for f in stale:
        os.remove(os.path.join(args.out_dir, f))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)
    failures: list[str] = []
    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    def full_rounds() -> int:
        return _drill_full_rounds(metrics_path, args.nodes)

    def reduced_rounds() -> int:
        return _drill_full_rounds(metrics_path, args.nodes - 1)

    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", "-1", "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        *_drill_lever_args(args),
        "--gossip", "--gossip-interval", str(args.gossip_interval),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--chaos-log", os.path.join(args.out_dir, "chaos-master.jsonl"),
        "--metrics-out", metrics_path,
    )
    nodes = []
    master_done = False
    master_lines: list[str] = []
    rounds_before_partition = rounds_after_heal = 0
    false_expulsions = kill_detected = None
    detect_s = None
    try:
        seed_ep = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("master never reported its endpoint")
        t_spawn = time.monotonic()
        for k in range(args.nodes):
            nodes.append(
                spawn(
                    "cluster-node", "--seed", seed_ep, "--node-id", str(k),
                    "--chaos-log",
                    os.path.join(args.out_dir, f"chaos-node{k}.jsonl"),
                )
            )
        # phase 1: a healthy baseline before the bad link goes down
        await_phase(
            lambda: full_rounds() >= 5, "pre-partition full-membership rounds"
        )
        rounds_before_partition = full_rounds()
        # phase 2: full-membership rounds must KEEP accumulating through
        # the one-way partition — gated on observed round records, not a
        # wall anchor: the partition triggers are per-process clocks
        # (each injector's t0 is its process start), and on a loaded box
        # the jax imports alone can eat most of a wall-anchored window,
        # turning a progress comparison into a vacuous 6 -> 6
        await_phase(
            lambda: full_rounds() >= rounds_before_partition + 8,
            "full-membership rounds continuing through the one-way "
            "partition (a stall here means the bad link wedged the line)",
        )
        # ...and the kill phase must not overlap the partition window:
        # ride out whatever remains of it (per-process t0 >= t_spawn, so
        # this bounds every process's window from above) plus several
        # suspicion windows of post-heal slack
        window_end = (
            t_spawn + args.partition_at + args.partition_for
            + 8 * args.gossip_interval
        )
        while time.monotonic() < window_end:
            time.sleep(0.2)
        rounds_after_heal = full_rounds()
        false_expulsions = reduced_rounds()
        if false_expulsions:
            failures.append(
                f"{false_expulsions} reduced-membership round(s) during the "
                "one-way partition: a healthy node was expelled"
            )
        # phase 3: a REAL death must still be detected by the ring
        t_kill = time.monotonic()
        nodes[killed].kill()
        target = args.min_post_rounds
        kill_detected = await_phase(
            lambda: reduced_rounds() >= target,
            f"{target} reduced-membership rounds after the real kill",
        )
        detect_s = round(time.monotonic() - t_kill, 2)
        # phase 4: graceful end (Shutdown broadcast flushes every log)
        master.send_signal(_signal.SIGTERM)
        try:
            out_master, _ = master.communicate(timeout=60)
            master_lines = out_master.splitlines()
            master_done = any("master done" in ln for ln in master_lines)
        except subprocess.TimeoutExpired:
            failures.append("master did not shut down on SIGTERM")
        for i, n in enumerate(nodes):
            if i == killed:
                continue
            try:
                n.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                n.kill()
    finally:
        for proc in [master, *nodes]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()

    # the master's exit snapshot carries the gossip counters (expulsions
    # must be exactly 1: the killed node — never the bad-link victim)
    gossip_metrics = {}
    for rec in _drill_jsonl_records(metrics_path):
        if (
            rec.get("kind") == "metrics_snapshot"
            and rec.get("role") == "master"
        ):
            gossip_metrics = {
                k: v
                for k, v in rec.get("metrics", {}).items()
                if k.startswith("gossip.")
            }
    if gossip_metrics.get("gossip.expulsions") != 1:
        failures.append(
            "expected exactly 1 gossip expulsion (the killed node), got "
            f"{gossip_metrics.get('gossip.expulsions')!r}"
        )
    if not master_done:
        failures.append("run did not finish cleanly")
    summary = {
        "seed": args.seed,
        "spec": spec,
        "full_rounds_pre_partition": rounds_before_partition,
        "full_rounds_post_heal": rounds_after_heal,
        "false_expulsions": false_expulsions,
        "kill_detected": bool(kill_detected),
        "reduced_rounds_post_kill": reduced_rounds(),
        "detect_plus_rounds_s": detect_s,
        "gossip": gossip_metrics,
        "master_done": master_done,
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def _cmd_chaos_scale(argv: list[str]) -> int:
    """Pod-scale control-plane drill (RESILIENCE.md "Scale",
    ``make chaos-scale``): the largest real-process grid the box allows —
    a leader + warm standby + an RxC pod of nodes bootstrapped from GRID
    COORDINATES (``--grid``/``--process-index``, node id = coordinate)
    and sharded into ``--line-shards`` free-running LineMasters — runs a
    partition + leader kill + node kill sequence:

    - phase 1: EVERY shard completes rounds at its full membership
      (per-shard round records under distinct line ids);
    - phase 2: a seeded ONE-WAY partition cuts one node's master-bound
      sends; gossip's indirect path must keep it in — zero re-shards;
    - phase 3: the leader is SIGKILLed; the warm standby takes over
      (epoch >= 2) and — because shard assignment is a pure function of
      the view — rebuilds the SAME shard layout, every shard resuming
      its own sequence;
    - phase 4: a node is SIGKILLed; its coordinate-anchored shard
      shrinks by exactly one while every other shard keeps its size and
      rounds keep completing;
    - phase 5: graceful SIGTERM end; node exits clean.

    The summary JSON also records the deterministic Fabric's measured
    sim rate on this box (nodes/sec — the 256..1024-node sim arms'
    cost evidence, tests/test_gossip_scale.py).
    """
    p = argparse.ArgumentParser(
        "chaos-scale",
        description="grid-coordinate pod bootstrap + hierarchical shard "
        "drill: partition, leader kill, node kill — per-shard rounds "
        "must survive all three",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument(
        "--grid", default="2x8", metavar="RxC",
        help="pod layout; every coordinate is spawned as a real process",
    )
    p.add_argument("--line-shards", type=int, default=4)
    p.add_argument(
        "--partition-at", type=float, default=6.0,
        help="seconds (per-process clock) until the one-way partition",
    )
    p.add_argument(
        "--partition-for", type=float, default=6.0,
        help="how long the bad link stays down",
    )
    p.add_argument(
        "--min-shard-rounds", type=int, default=5,
        help="full-membership rounds required per shard per phase",
    )
    p.add_argument(
        "--min-post-rounds", type=int, default=8,
        help="post-node-kill rounds required in the shrunken shard",
    )
    p.add_argument("--phase-timeout", type=float, default=240.0)
    p.add_argument("--size", type=int, default=32768)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument("--gossip-interval", type=float, default=0.25)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    _add_drill_lever_flags(p)
    p.add_argument("--out-dir", default="chaos_scale_run")
    args = p.parse_args(argv)

    import json
    import os
    import signal as _signal
    import subprocess

    from akka_allreduce_tpu.control import pod as _pod
    from akka_allreduce_tpu.control.simfabric import sim_rate

    try:
        rows, cols = _pod.parse_grid(args.grid)
    except ValueError as e:
        p.error(str(e))
    n_nodes = rows * cols
    blocks = _pod.coordinate_shard_assignment(
        range(n_nodes), rows, cols, args.line_shards
    )
    sizes = {lid: len(b) for lid, b in enumerate(blocks)}
    if min(sizes.values()) < 3:
        # th=0.66 must stay satisfiable inside the partitioned node's
        # shard: ceil(0.66*size) <= size-1 needs size >= 3
        p.error(
            f"shard sizes {sorted(sizes.values())} too small for the "
            "partition phase: need >= 3 nodes per shard (use a larger "
            "--grid or fewer --line-shards)"
        )
    victim_link = blocks[0][-1]  # the bad-link node (stays healthy)
    killed = n_nodes - 1  # the really-dead node (last shard shrinks)
    killed_line = len(blocks) - 1
    sizes_post_kill = dict(sizes)
    sizes_post_kill[killed_line] -= 1
    spec = (
        f"partition:from={victim_link},to=m,"
        f"at={args.partition_at:g}s,heal={args.partition_for:g}s"
    )
    os.makedirs(args.out_dir, exist_ok=True)
    leader_metrics = os.path.join(args.out_dir, "rounds-leader.jsonl")
    standby_metrics = os.path.join(args.out_dir, "rounds-standby.jsonl")
    stale = [f for f in os.listdir(args.out_dir) if f.endswith(".jsonl")]
    for f in stale:
        os.remove(os.path.join(args.out_dir, f))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)
    failures: list[str] = []
    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    def shard_rounds(path, expected: dict[int, int]) -> dict[int, int]:
        """Per-line count of round records at the line's EXPECTED full
        size (shard assignment is pure in the view, so line id -> size
        is stable across reorganizations of the same membership)."""
        per = {lid: 0 for lid in expected}
        for rec in _drill_jsonl_records(path):
            if rec.get("kind") != "round":
                continue
            lid = rec.get("line")
            if lid in per and rec.get("workers") == expected[lid]:
                per[lid] += 1
        return per

    def reshard_anomalies(path) -> int:
        """Round records whose (line, size) does not match the full
        layout — a healthy-node expulsion would show here first."""
        return sum(
            1
            for rec in _drill_jsonl_records(path)
            if rec.get("kind") == "round"
            and rec.get("workers") != sizes.get(rec.get("line"))
        )

    leader = spawn(
        "cluster-master", "--port", "0", "--nodes", str(n_nodes),
        "--grid", args.grid, "--line-shards", str(args.line_shards),
        "--rounds", "-1", "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        *_drill_lever_args(args),
        "--gossip", "--gossip-interval", str(args.gossip_interval),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--chaos-log", os.path.join(args.out_dir, "chaos-leader.jsonl"),
        "--metrics-out", leader_metrics,
    )
    standby = None
    nodes: list = []
    standby_lines: list[str] = []
    takeover = None
    standby_done = False
    rounds_before_partition: dict[int, int] = {}
    rounds_after_heal: dict[int, int] = {}
    anomalies_pre_kill = None
    node_exits: dict = {}
    try:
        seed_ep = None
        for line in leader.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("leader never reported its endpoint")
        standby = spawn(
            "cluster-standby", "--seed", seed_ep,
            "--heartbeat", str(args.heartbeat),
            "--metrics-out", standby_metrics,
        )
        standby_ep = None
        for line in standby.stdout:
            if line.startswith("standby listening on "):
                standby_ep = line.split()[3]
                break
        if standby_ep is None:
            raise RuntimeError("standby never reported its endpoint")
        standby_pump = _drill_pump(standby, standby_lines)
        t_spawn = time.monotonic()
        for k in range(n_nodes):
            nodes.append(
                spawn(
                    "cluster-node", "--seed", seed_ep,
                    "--grid", args.grid, "--process-index", str(k),
                    "--chaos-log",
                    os.path.join(args.out_dir, f"chaos-node{k}.jsonl"),
                )
            )
        # phase 1: EVERY shard completes rounds at full membership
        await_phase(
            lambda: min(
                shard_rounds(leader_metrics, sizes).values()
            )
            >= args.min_shard_rounds,
            "pre-partition full-membership rounds on every shard",
        )
        rounds_before_partition = shard_rounds(leader_metrics, sizes)
        # phase 2: rounds keep accumulating per shard THROUGH the one-way
        # partition (round-record gated, like chaos-gossip), and no
        # re-shard happens (the indirect path keeps the victim in)
        def _partition_progress() -> int:
            per = shard_rounds(leader_metrics, sizes)  # ONE parse per poll
            return min(
                per[lid] - rounds_before_partition.get(lid, 0)
                for lid in sizes
            )

        await_phase(
            lambda: _partition_progress() >= args.min_shard_rounds,
            "per-shard rounds continuing through the one-way partition",
        )
        window_end = (
            t_spawn + args.partition_at + args.partition_for
            + 8 * args.gossip_interval
        )
        while time.monotonic() < window_end:
            time.sleep(0.2)
        rounds_after_heal = shard_rounds(leader_metrics, sizes)
        anomalies_pre_kill = reshard_anomalies(leader_metrics)
        if anomalies_pre_kill:
            failures.append(
                f"{anomalies_pre_kill} off-layout round record(s) during "
                "the partition window: a healthy node was expelled or a "
                "shard re-split"
            )
        # phase 3: SIGKILL the LEADER; the warm standby must take over
        # and rebuild the SAME shard layout from the replicated view
        leader.send_signal(_signal.SIGKILL)
        leader.wait()
        await_phase(
            lambda: any(
                ln.startswith("TAKEOVER ") for ln in list(standby_lines)
            ),
            "the standby's TAKEOVER line",
        )
        for ln in list(standby_lines):
            if ln.startswith("TAKEOVER "):
                takeover = json.loads(ln[len("TAKEOVER "):])
        await_phase(
            lambda: min(
                shard_rounds(standby_metrics, sizes).values()
            )
            >= args.min_shard_rounds,
            "post-takeover rounds on every shard (same layout)",
        )
        # phase 4: SIGKILL a node — its coordinate-anchored shard shrinks
        # by one, the other shards keep their sizes, rounds continue
        nodes[killed].send_signal(_signal.SIGKILL)
        nodes[killed].wait()

        def _post_kill_progress() -> int:
            per = shard_rounds(standby_metrics, sizes_post_kill)
            return min(per[lid] for lid in sizes_post_kill)

        await_phase(
            lambda: _post_kill_progress() >= args.min_post_rounds,
            "post-node-kill rounds (shrunken shard included)",
        )
        # phase 5: graceful end at the promoted master
        standby.send_signal(_signal.SIGTERM)
        try:
            standby.wait(timeout=60)
        except subprocess.TimeoutExpired:
            failures.append("promoted standby did not shut down on SIGTERM")
        standby_pump.join(timeout=10)
        standby_done = any("master done" in ln for ln in standby_lines)
        for k, n in enumerate(nodes):
            if k == killed:
                node_exits[k] = n.returncode
                continue
            try:
                n.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                # a survivor that wedges in its shutdown path is exactly
                # the defect class this drill exists to catch — record
                # it, don't let the cleanup kill() read as a clean exit
                n.kill()
                n.wait()
                failures.append(
                    f"node {k} did not exit within 30s of the Shutdown "
                    "broadcast (killed)"
                )
            node_exits[k] = n.returncode
            if n.returncode not in (0, None):
                failures.append(f"node {k} exited {n.returncode}")
    finally:
        for proc in [leader, standby, *nodes]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()

    if takeover is None:
        failures.append("standby never took over")
    elif takeover.get("epoch", 0) < 2:
        failures.append(f"takeover did not bump the epoch: {takeover}")
    if not standby_done:
        failures.append("run did not finish cleanly")
    summary = {
        "seed": args.seed,
        "grid": args.grid,
        "line_shards": args.line_shards,
        "shard_sizes": {str(k): v for k, v in sorted(sizes.items())},
        "spec": spec,
        "shard_rounds_pre_partition": {
            str(k): v for k, v in sorted(rounds_before_partition.items())
        },
        "shard_rounds_post_heal": {
            str(k): v for k, v in sorted(rounds_after_heal.items())
        },
        "reshard_anomalies_pre_kill": anomalies_pre_kill,
        "takeover": takeover,
        "shard_rounds_under_standby": {
            str(k): v
            for k, v in sorted(shard_rounds(standby_metrics, sizes).items())
        },
        "shard_rounds_post_kill": {
            str(k): v
            for k, v in sorted(
                shard_rounds(standby_metrics, sizes_post_kill).items()
            )
        },
        "node_exits": {str(k): v for k, v in sorted(node_exits.items())},
        "standby_done": standby_done,
        "sim": sim_rate(256, 5.0),
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def _cmd_chaos_failover(argv: list[str]) -> int:
    """Master-kill failover drill (RESILIENCE.md "Tier 4", ISSUE 7
    acceptance): a real leader + warm standby + N state-armed nodes run an
    open-ended round budget; a SEEDED chaos crash (``crash:node=m``) kills
    the leader mid-round. The standby must take over within one lease
    window (TAKEOVER line), rounds must resume under the bumped epoch with
    no round applied twice (every node's ``dup_flushes`` stays 0 — the
    cross-epoch dedup), and a node killed+disk-wiped AFTER the failover
    must still restore from peers via the REPLICATED holder registry. The
    run then ends gracefully via SIGTERM at the promoted master. ``make
    chaos-failover`` runs the fixed-seed variant; exit 0 iff every
    assertion holds."""
    p = argparse.ArgumentParser(
        "chaos-failover",
        description="seeded leader kill mid-round; assert warm-standby "
        "takeover, epoch fencing, cross-epoch round dedup, and a "
        "post-failover peer restore",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument(
        "--crash-round", type=int, default=25,
        help="round at which the leader's seeded crash fires",
    )
    p.add_argument(
        "--min-post-rounds", type=int, default=40,
        help="full-membership rounds that must complete under the standby "
        "AFTER the post-failover peer restore (the drill's round budget)",
    )
    p.add_argument(
        "--extra-spec", default="",
        help="additional chaos faults layered onto the leader kill "
        "(e.g. 'drop:p=0.02')",
    )
    p.add_argument(
        "--phase-timeout", type=float, default=240.0,
        help="wall-clock bound on each drill phase",
    )
    p.add_argument("--size", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    p.add_argument("--state-every", type=int, default=5)
    p.add_argument("--out-dir", default="chaos_failover_run")
    _add_drill_gossip_flags(p)
    _add_drill_lever_flags(p)
    args = p.parse_args(argv)
    if args.nodes < 3:
        p.error("need >= 3 nodes: a restore victim plus 2 replica holders")

    import json
    import os
    import re
    import shutil
    import signal as _signal
    import subprocess

    from akka_allreduce_tpu.control.chaos import CRASH_EXIT_CODE, parse_spec

    spec = f"crash:node=m,at=round{args.crash_round}"
    if args.extra_spec:
        spec = f"{spec};{args.extra_spec}"
    try:
        parse_spec(spec)
    except ValueError as e:
        p.error(str(e))
    os.makedirs(args.out_dir, exist_ok=True)
    leader_metrics = os.path.join(args.out_dir, "rounds-leader.jsonl")
    standby_metrics = os.path.join(args.out_dir, "rounds-standby.jsonl")
    for f in (leader_metrics, standby_metrics):
        if os.path.exists(f):
            os.remove(f)  # MetricsLogger appends; one run per file
    state_dirs = [
        os.path.join(args.out_dir, f"state{k}") for k in range(args.nodes)
    ]
    for d in state_dirs:
        if os.path.isdir(d):
            shutil.rmtree(d)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)

    def spawn_node(seed_ep, k):
        return spawn(
            "cluster-node", "--seed", seed_ep, "--node-id", str(k),
            "--state-dir", state_dirs[k],
            "--state-every", str(args.state_every),
        )

    pump = _drill_pump

    def full_rounds(path) -> int:
        return _drill_full_rounds(path, args.nodes)

    failures: list[str] = []
    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    victim = args.nodes - 1
    crash_exit = None
    rounds_at_crash = 0
    takeover = None
    restore = None
    standby_done = False
    dup_flushes: dict[int, int] = {}
    node_exits: dict[int, int | None] = {}
    standby_lines: list[str] = []
    reborn_lines: list[str] = []
    reborn = None

    leader = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", "-1", "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--chaos-log", os.path.join(args.out_dir, "chaos-leader.jsonl"),
        "--metrics-out", leader_metrics,
        *_drill_gossip_args(args),
        *_drill_lever_args(args),
    )
    standby = None
    nodes = []
    try:
        seed_ep = None
        for line in leader.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("leader never reported its endpoint")
        standby = spawn(
            "cluster-standby", "--seed", seed_ep,
            "--heartbeat", str(args.heartbeat),
            "--metrics-out", standby_metrics,
        )
        standby_ep = None
        for line in standby.stdout:
            if line.startswith("standby listening on "):
                standby_ep = line.split()[3]
                break
        if standby_ep is None:
            raise RuntimeError("standby never reported its endpoint")
        standby_pump = pump(standby, standby_lines)
        nodes = [spawn_node(seed_ep, k) for k in range(args.nodes)]
        # phase 1: the seeded master kill fires (round trigger mid-run)
        try:
            crash_exit = leader.wait(timeout=args.phase_timeout)
        except subprocess.TimeoutExpired:
            failures.append("leader never crashed (chaos round not reached)")
        if crash_exit is not None and crash_exit != CRASH_EXIT_CODE:
            failures.append(
                f"leader exited {crash_exit}, not the chaos crash "
                f"{CRASH_EXIT_CODE}"
            )
        rounds_at_crash = full_rounds(leader_metrics)
        # phase 2: the standby's lease expires and it takes over
        if not failures:
            await_phase(
                lambda: any(
                    ln.startswith("TAKEOVER ") for ln in list(standby_lines)
                ),
                "the standby's TAKEOVER line",
            )
            for ln in list(standby_lines):
                if ln.startswith("TAKEOVER "):
                    takeover = json.loads(ln[len("TAKEOVER "):])
        # phase 3: rounds resume under the new epoch with full membership
        if not failures:
            await_phase(
                lambda: full_rounds(standby_metrics) >= 5,
                "post-takeover full-membership rounds",
            )
        # phase 4: kill a NODE after the failover, wipe its disk, respawn
        # it at the promoted master — the restore must find peer holders
        # via the registry the digest replicated (plus re-adverts)
        if not failures:
            nodes[victim].send_signal(_signal.SIGKILL)
            nodes[victim].wait()
            node_exits[victim] = nodes[victim].returncode
            shutil.rmtree(state_dirs[victim], ignore_errors=True)
            # phase 4.5 — the chaos-recover deflake applied here too:
            # respawn only after the PROMOTED master demonstrably expelled
            # the victim (a reduced-membership round in its metrics). A
            # join that races the detector reads the victim's id as a
            # LIVE member and mints the reborn node a FRESH id with no
            # checkpoint history — its restore then honestly reports
            # 'none' while the replicas sit on live peers under the old
            # id.
            await_phase(
                lambda: _drill_full_rounds(standby_metrics, args.nodes - 1)
                >= 1,
                "the promoted master's observed expulsion of the victim",
            )
        if not failures:
            reborn = spawn_node(standby_ep, victim)
            reborn_pump = pump(reborn, reborn_lines)
            await_phase(
                lambda: any(
                    ln.startswith("RESTORE ") for ln in list(reborn_lines)
                ),
                "the respawned node's restore report",
            )
            for ln in list(reborn_lines):
                if ln.startswith("RESTORE "):
                    restore = json.loads(ln[len("RESTORE "):])
        # phase 5: the drill's round budget completes under the standby
        if not failures:
            target = full_rounds(standby_metrics) + args.min_post_rounds
            await_phase(
                lambda: full_rounds(standby_metrics) >= target,
                f"{args.min_post_rounds} full-membership rounds "
                "post-restore",
            )
        # phase 6: graceful end at the PROMOTED master
        standby.send_signal(_signal.SIGTERM)
        try:
            standby.wait(timeout=60)
        except subprocess.TimeoutExpired:
            failures.append("promoted standby did not shut down on SIGTERM")
        standby_pump.join(timeout=10)
        standby_done = any("master done" in ln for ln in standby_lines)
        for k, n in enumerate(nodes):
            if k == victim:
                continue
            try:
                out, _ = n.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                n.kill()
                out = ""
            node_exits[k] = n.returncode
            m = re.search(r"dup_flushes=(\d+)", out or "")
            if m:
                dup_flushes[k] = int(m.group(1))
        if reborn is not None:
            try:
                reborn.wait(timeout=30)
            except subprocess.TimeoutExpired:
                reborn.kill()
            reborn_pump.join(timeout=10)
            node_exits[f"{victim}-reborn"] = reborn.returncode
            for ln in reborn_lines:
                m = re.search(r"dup_flushes=(\d+)", ln)
                if m:
                    dup_flushes[victim] = int(m.group(1))
    finally:
        for proc in [leader, standby, *nodes, *([reborn] if reborn else [])]:
            if proc is not None and proc.poll() is None:
                proc.kill()
                proc.wait()

    # assertions over the collected evidence
    if takeover is None:
        failures.append("standby never took over")
    elif takeover.get("epoch", 0) < 2:
        failures.append(f"takeover did not bump the epoch: {takeover}")
    if restore is None:
        failures.append("respawned node never reported a restore")
    else:
        if restore.get("source") != "peer":
            failures.append(
                f"post-failover restore source {restore.get('source')!r} "
                "!= 'peer' (replicated registry not consulted?)"
            )
        if not restore.get("complete"):
            failures.append("post-failover peer restore incomplete")
    if not standby_done:
        failures.append("promoted standby did not finish cleanly")
    for k, dups in sorted(dup_flushes.items()):
        if dups:
            failures.append(
                f"node {k} applied {dups} round(s) twice across the "
                "failover (cross-epoch dedup broken)"
            )
    if len(dup_flushes) < args.nodes:
        failures.append(
            f"dup-flush evidence from only {sorted(dup_flushes)} of "
            f"{args.nodes} node(s)"
        )
    for k, rc in sorted(node_exits.items(), key=str):
        if k == victim:  # SIGKILLed by the drill itself
            continue
        if rc not in (0, None):
            failures.append(f"node {k} exited {rc}")

    summary = {
        "seed": args.seed,
        "spec": spec,
        "crash_exit": crash_exit,
        "full_rounds_at_crash": rounds_at_crash,
        "takeover": takeover,
        "rounds_under_standby": full_rounds(standby_metrics),
        "restore": restore,
        "dup_flushes": dup_flushes,
        "node_exits": {str(k): v for k, v in sorted(node_exits.items(), key=lambda kv: str(kv[0]))},
        "standby_done": standby_done,
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def _cmd_chaos_adapt(argv: list[str]) -> int:
    """Adaptive-degradation drill (RESILIENCE.md "Tier 5", ISSUE 8
    acceptance): a real master running the AdaptiveController + N nodes
    with IDENTICAL payloads run an open-ended budget; a SEEDED staged
    straggler (a windowed targeted ``delay`` + a ``stall`` burst inside
    it) slows one node's sends. The controller must DEGRADE (lower
    th_reduce, f16 -> int8 wire) within K rounds of the straggler's
    onset, HOLD without oscillation (total mode transitions bounded),
    RESTORE to full fidelity after the heal, and every node's reduced
    values must stay within the EF error budget (identical payloads =>
    the true average is the payload itself; ``--uniform-check`` measures
    the deviation). The verdict spans the master's records up to the
    ``--post-rounds``-th full round at level 0 since the LAST restore;
    transitions the open-ended master made after that, before the
    SIGTERM reached it, are reported and not judged. ``make chaos-adapt``
    runs the fixed-seed variant; exit 0 iff every assertion holds."""
    p = argparse.ArgumentParser(
        "chaos-adapt",
        description="seeded staged straggler; assert the adaptive "
        "controller degrades, holds, restores, and stays inside the EF "
        "error budget",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument("--nodes", type=int, default=3)
    p.add_argument(
        "--straggle-at", type=int, default=30,
        help="round at which the straggler's delay window opens",
    )
    p.add_argument(
        "--heal-at", type=int, default=150,
        help="round at which the straggler's delay window closes",
    )
    p.add_argument(
        "--delay-ms", type=float, default=400.0,
        help="the straggler's per-send hold inside the window",
    )
    p.add_argument(
        "--stall-for", type=float, default=0.25,
        help="layer a stall burst of this many seconds 20 rounds into the "
        "straggle window (0 = delay only). The default stays under the "
        "phi detector's expulsion point (~0.35s at heartbeat 0.1 with "
        "min_std 0.05) — a slow-but-alive burst, which is the "
        "controller's case; longer values exercise expulsion/rejoin "
        "churn instead",
    )
    p.add_argument(
        "--k-rounds", type=int, default=60,
        help="the controller must first degrade within this many rounds "
        "of the straggle round",
    )
    p.add_argument(
        "--max-transitions", type=int, default=6,
        help="total mode transitions allowed (no-oscillation bound: "
        "2 degrades + 2 restores + slack)",
    )
    p.add_argument(
        "--err-budget", type=float, default=0.15,
        help="max |reduced average - payload| any node may observe "
        "(int8 quantization step ~max|x|/127 with EF; see RESILIENCE.md)",
    )
    p.add_argument(
        "--post-rounds", type=int, default=40,
        help="full-membership rounds that must complete AFTER the restore",
    )
    p.add_argument("--phase-timeout", type=float, default=240.0)
    p.add_argument("--size", type=int, default=65536)
    p.add_argument("--chunk", type=int, default=8192)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.1)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    p.add_argument("--adapt-window", type=int, default=6)
    p.add_argument("--adapt-dwell", type=int, default=12)
    p.add_argument("--adapt-lag", type=int, default=8)
    p.add_argument("--out-dir", default="chaos_adapt_run")
    _add_drill_gossip_flags(p)
    _add_drill_lever_flags(p)
    args = p.parse_args(argv)

    import json
    import os
    import signal as _signal
    import re
    import subprocess

    from akka_allreduce_tpu.control.chaos import parse_spec

    straggler = args.nodes - 1
    spec = (
        f"delay:node={straggler},ms={args.delay_ms:g},"
        f"at=round{args.straggle_at},for=round{args.heal_at}"
    )
    if args.stall_for > 0:
        spec += (
            f";stall:node={straggler},at=round{args.straggle_at + 20},"
            f"for={args.stall_for:g}s"
        )
    try:
        parse_spec(spec)
    except ValueError as e:
        p.error(str(e))
    os.makedirs(args.out_dir, exist_ok=True)
    metrics_path = os.path.join(args.out_dir, "rounds.jsonl")
    adapt_log = os.path.join(args.out_dir, "adapt-decisions.jsonl")
    for f in (metrics_path, adapt_log):
        if os.path.exists(f):
            os.remove(f)  # MetricsLogger appends; one run per file
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)

    failures: list[str] = []
    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    def adapt_events() -> list[dict]:
        return [
            rec
            for rec in _drill_jsonl_records(metrics_path)
            if rec.get("kind") == "adapt"
        ]

    def script_end() -> int | None:
        """How many of the controller's transitions the drill's script
        spans: those up to the ``post_rounds``-th full-membership round
        since the LAST restore to level 0, with no transition in between —
        or None while that has not happened. The master runs open-ended at
        ~3 ms a round until the SIGTERM reaches it, a 0.2 s poll later at
        the earliest; on a loaded machine the controller rightly degrades
        again for real slowness, so the END is read from the record
        stream, not from when this process happened to look."""
        transitions = 0
        quiet = None  # full rounds since the last restore, None if degraded
        for rec in _drill_jsonl_records(metrics_path):
            if rec.get("kind") == "adapt":
                transitions += 1
                quiet = 0 if rec["to"] == 0 else None
            elif (
                quiet is not None
                and rec.get("kind") == "round"
                and rec.get("workers") == args.nodes
            ):
                quiet += 1
                if quiet >= args.post_rounds:
                    return transitions
        return None

    def full_rounds() -> int:
        return _drill_full_rounds(metrics_path, args.nodes)

    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", "-1", "--size", str(args.size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--chaos-log", os.path.join(args.out_dir, "chaos-master.jsonl"),
        "--metrics-out", metrics_path,
        "--adapt", "--adapt-window", str(args.adapt_window),
        "--adapt-dwell", str(args.adapt_dwell),
        "--adapt-lag", str(args.adapt_lag),
        "--adapt-log", adapt_log,
        *_drill_gossip_args(args),
        *_drill_lever_args(args),
    )
    nodes = []
    node_out: dict[int, str] = {}
    master_done = False
    try:
        seed_ep = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("master never reported its endpoint")
        nodes = [
            spawn(
                "cluster-node", "--seed", seed_ep, "--node-id", str(k),
                # IDENTICAL payloads on every node: the reduced average
                # must equal the payload, so deviation == wire error
                "--data-seed", "7", "--uniform-check",
                "--chaos-log",
                os.path.join(args.out_dir, f"chaos-node{k}.jsonl"),
            )
            for k in range(args.nodes)
        ]
        # phase 1: the straggler window opens and the controller degrades
        await_phase(
            lambda: any(e["to"] > e["from"] for e in adapt_events()),
            "the controller's first degrade decision",
        )
        first_degrade = next(
            (e for e in adapt_events() if e["to"] > e["from"]), None
        )
        if first_degrade is not None:
            lateness = first_degrade["round"] - args.straggle_at
            if lateness > args.k_rounds:
                failures.append(
                    f"controller degraded {lateness} rounds after the "
                    f"straggle round (budget {args.k_rounds})"
                )
        # phase 2: after the heal the controller walks back to level 0
        if not failures:
            await_phase(
                lambda: any(
                    e["to"] == 0 and e["from"] == 1 for e in adapt_events()
                ),
                "the controller's restore to full fidelity",
            )
        # phase 3: the post-restore round budget completes at level 0 —
        # counted from the LAST restore, so a load-made second dip costs
        # time and transitions (both bounded), not the ending
        if not failures:
            await_phase(
                lambda: script_end() is not None,
                f"{args.post_rounds} full-membership rounds at level 0 "
                "after the last restore",
            )
        master.send_signal(_signal.SIGTERM)
        try:
            out, _ = master.communicate(timeout=60)
            master_done = "master done" in out
        except subprocess.TimeoutExpired:
            failures.append("master did not shut down on SIGTERM")
        for k, n in enumerate(nodes):
            try:
                node_out[k], _ = n.communicate(timeout=30)
            except subprocess.TimeoutExpired:
                n.kill()
                node_out[k] = ""
    finally:
        for proc in [master, *nodes]:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # the verdict covers the script's span; what the open-ended master did
    # between the budget's last round and the SIGTERM is reported, not judged
    events = adapt_events()
    end = script_end()
    if end is None:  # a phase timed out: judge them all
        end = len(events)
    events, late_events = events[:end], events[end:]
    degrades = sum(1 for e in events if e["to"] > e["from"])
    restores = sum(1 for e in events if e["to"] < e["from"])
    max_errs: dict[int, float] = {}
    for k, out in node_out.items():
        m = re.search(r"max_err=([0-9.eE+-]+)", out or "")
        if m:
            max_errs[k] = float(m.group(1))
    # assertions over the collected evidence
    if not events:
        failures.append("controller never made a transition")
    if degrades + restores > args.max_transitions:
        failures.append(
            f"{degrades + restores} mode transitions > bound "
            f"{args.max_transitions} (oscillation)"
        )
    if events and events[-1]["to"] != 0:
        failures.append(
            f"controller ended at level {events[-1]['to']}, not restored"
        )
    if not any(e.get("policy", "").startswith("int8") for e in events):
        failures.append("controller never reached the int8 wire mode")
    if len(max_errs) < args.nodes:
        failures.append(
            f"max_err evidence from only {sorted(max_errs)} of "
            f"{args.nodes} node(s)"
        )
    for k, err in sorted(max_errs.items()):
        if err > args.err_budget:
            failures.append(
                f"node {k} reduced-value error {err:.4f} exceeds the EF "
                f"budget {args.err_budget}"
            )
    if not master_done:
        failures.append("master did not finish cleanly")
    decision_log = None
    if os.path.exists(adapt_log):
        with open(adapt_log) as f:
            decision_log = [json.loads(ln) for ln in f if ln.strip()]

    summary = {
        "seed": args.seed,
        "spec": spec,
        "rounds_completed": full_rounds(),
        "adapt_events": events,
        "adapt_events_after_script": late_events,
        "decision_log": decision_log,
        "degrades": degrades,
        "restores": restores,
        "max_err": max_errs,
        "err_budget": args.err_budget,
        "master_done": master_done,
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def _cmd_chaos_train_node(argv: list[str]) -> int:
    """Tier-7 node role (RESILIENCE.md "Tier 7 — workload resilience"):
    one REAL trainer family, ElasticTrainer-wrapped, riding the TCP
    cluster. The cluster's membership view drives the wrapper's
    snapshot -> rebuild -> restore re-mesh between steps, and the
    leader's RoundPolicy wire stamp drives the trainer's ICI compress
    mode through the same factory rebuild path — the ``chaos-train``
    drill spawns one of these per cluster node."""
    p = argparse.ArgumentParser(
        "chaos-train-node",
        description="training node driving an ElasticTrainer-wrapped real "
        "trainer; membership re-meshes and RoundPolicy compress changes "
        "follow the cluster",
    )
    p.add_argument("--seed", required=True, help="master host:port")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("--port", type=int, default=0)
    p.add_argument(
        "--node-id", type=int, required=True,
        help="this node's id AND its device-group index (the drill "
        "assigns 0..nodes-1 so every process re-meshes identically)",
    )
    p.add_argument(
        "--nodes", type=int, required=True,
        help="planned cluster size: the local virtual-device mesh is "
        "partitioned into this many node device groups",
    )
    p.add_argument(
        "--family", choices=("dp", "zero1", "fsdp", "pipeline"),
        default="dp",
        help="which real trainer family rides the elastic cycle "
        "(train/zoo.py)",
    )
    p.add_argument("--model-seed", type=int, default=0)
    p.add_argument("--elastic-rate", type=float, default=0.5)
    p.add_argument(
        "--min-nodes", type=int, default=1,
        help="below this many live nodes the learner PAUSES (holds "
        "position) instead of stepping — recovery resumes it",
    )
    p.add_argument(
        "--max-steps", type=int, default=0,
        help="0 = train until the master broadcasts Shutdown",
    )
    p.add_argument(
        "--warmup-steps", type=int, default=8,
        help="local steps taken BEFORE joining the cluster (compile + a "
        "real loss trajectory first; rounds only start once every node "
        "joined, so a round-triggered kill lands mid-training)",
    )
    p.add_argument("--metrics-out", default=None, help="per-step JSONL path")
    p.add_argument("--chaos-log", default=None, metavar="FILE")
    args = p.parse_args(argv)
    logging.basicConfig(level=logging.INFO, format="%(message)s")

    import asyncio

    import jax
    import numpy as np

    from akka_allreduce_tpu.control.cluster import Endpoint
    from akka_allreduce_tpu.train import ElasticClusterNode
    from akka_allreduce_tpu.train import zoo
    from akka_allreduce_tpu.utils.metrics import MetricsLogger

    per = zoo.devices_per_node(args.family)
    devices = jax.devices()
    if len(devices) < args.nodes * per:
        raise SystemExit(
            f"{args.family} needs {args.nodes * per} devices "
            f"({per}/node), have {len(devices)}: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={args.nodes * per}"
        )
    assignment = {
        n: devices[n * per : (n + 1) * per] for n in range(args.nodes)
    }
    elastic = zoo.make_elastic(
        args.family, assignment,
        seed=args.model_seed, min_nodes=args.min_nodes,
    )
    ds = zoo.dataset_for(args.family)
    step_seq = {"i": 0}

    def batches(trainer):
        # this node's OWN data shard: the seed offset folds the node id,
        # the batch geometry follows the LIVE trainer (re-mesh aware)
        step_seq["i"] += 1
        return zoo.batch_for(
            args.family, ds, elastic,
            seed_offset=args.node_id * 100_003 + step_seq["i"],
        )

    logger = MetricsLogger(args.metrics_out) if args.metrics_out else None

    def on_step(m) -> None:
        if logger is None:
            return
        logger.log_event(
            kind="train_step",
            step=m.step,
            loss=round(float(m.loss), 6),
            contributors=float(m.contributors),
            generation=elastic.generation,
            members=list(elastic.member_nodes),
            n_devices=elastic.n_devices,
            compress=elastic.compress_mode or "full",
            # pipeline restage evidence (the drill pins the gcd rule)
            stages=getattr(elastic.trainer, "stages", None),
        )

    async def run() -> int:
        cnode = ElasticClusterNode(
            Endpoint.parse(args.seed),
            elastic,
            batches,
            elastic_rate=args.elastic_rate,
            host=args.host,
            port=args.port,
            preferred_node_id=args.node_id,
            on_step=on_step,
            # real OS process: the chaos `crash` fault may os._exit here
            # (the drill's seeded mid-step node kill)
            allow_crash=True,
            chaos_log=args.chaos_log,
        )
        t0 = time.perf_counter()
        steps = await cnode.run(
            args.max_steps or None, warmup_steps=args.warmup_steps
        )
        dt = time.perf_counter() - t0
        losses = cnode.losses
        trend = (
            f"loss {losses[0]:.4f} -> {losses[-1]:.4f}"
            if losses
            else "no steps taken"
        )
        print(
            f"trained {steps} steps in {dt:.1f}s "
            f"({cnode.rounds_applied} sync rounds applied) "
            f"remeshes={cnode.remeshes} "
            f"compress_changes={cnode.compress_changes} "
            f"generation={elastic.generation} "
            f"final_compress={elastic.compress_mode or 'full'}; {trend}",
            flush=True,
        )
        return 0

    rc = asyncio.run(run())
    if logger is not None:
        logger.close()
    return rc


def _cmd_chaos_train(argv: list[str]) -> int:
    """Workload-resilience drill (RESILIENCE.md "Tier 7", ISSUE 14
    acceptance): a real master + N ``chaos-train-node`` processes — each
    driving an ElasticTrainer-wrapped REAL trainer of one family — run an
    open-ended round budget; a SEEDED ``crash:node=K,at=roundN`` kills
    one node mid-train-step. The drill asserts, from the processes' own
    evidence: the crash was the injected one (exit 23); every survivor
    re-meshed (snapshot -> rebuild over the survivors' devices ->
    restore) and its loss trajectory RESUMED within the pinned band (the
    restore lost no optimizer state); rounds kept completing at the
    reduced membership (zero wedged rounds); and the run finished
    gracefully. ``make chaos-train`` runs the fixed-seed pipeline arm —
    the restage case."""
    p = argparse.ArgumentParser(
        "chaos-train",
        description="seeded mid-step node kill under a real trainer "
        "family; assert loss-curve continuity across the re-mesh, zero "
        "wedged rounds, graceful completion",
    )
    p.add_argument("--seed", type=int, default=1234, help="chaos seed")
    p.add_argument(
        "--family", choices=("dp", "zero1", "fsdp", "pipeline"),
        default="pipeline",
    )
    p.add_argument(
        "--nodes", type=int, default=0,
        help="cluster size (0 = family default: 4 for pipeline — enough "
        "devices that a node loss RESTAGES the trunk — else 3)",
    )
    p.add_argument(
        "--kill-at-round", type=int, default=30,
        help="allreduce round at which the victim's seeded crash fires",
    )
    p.add_argument(
        "--post-rounds", type=int, default=25,
        help="survivor-membership rounds that must complete AFTER the "
        "kill (the zero-wedged-rounds evidence)",
    )
    p.add_argument(
        "--post-steps", type=int, default=6,
        help="post-re-mesh train steps each survivor must log (the "
        "loss-continuity sample)",
    )
    p.add_argument(
        "--warmup-steps", type=int, default=8,
        help="per-node local steps BEFORE joining (rounds, and so the "
        "round-triggered kill, start only once every node joined — the "
        "victim dies mid-training, not mid-compile)",
    )
    p.add_argument(
        "--loss-band", type=float, default=0.35,
        help="pinned continuity band: each survivor's median loss over "
        "its first post-re-mesh steps must stay within (1 + band) x its "
        "median over the last pre-kill steps (+0.05 absolute slack for "
        "near-converged curves) — a restore that lost optimizer state "
        "resets the curve and blows this bar",
    )
    p.add_argument("--phase-timeout", type=float, default=300.0)
    p.add_argument("--th", type=float, default=0.66)
    p.add_argument("--heartbeat", type=float, default=0.25)
    p.add_argument("--chunk", type=int, default=16384)
    p.add_argument(
        "--streams", type=int, default=1,
        help="data-plane sockets per endpoint (distributed via Welcome)",
    )
    p.add_argument(
        "--adapt", action="store_true",
        help="also run the leader's AdaptiveController (the ICI "
        "compress-follows-policy plumbing is live either way; the "
        "dedicated pin lives in tests/test_chaos_train.py)",
    )
    p.add_argument("--out-dir", default="chaos_train_run")
    _add_drill_gossip_flags(p)
    _add_drill_lever_flags(p)
    args = p.parse_args(argv)

    import json
    import os
    import re
    import signal as _signal
    import statistics
    import subprocess

    from akka_allreduce_tpu.control.chaos import parse_spec

    nodes = args.nodes or (4 if args.family == "pipeline" else 3)
    victim = nodes - 1
    spec = f"crash:node={victim},at=round{args.kill_at_round}"
    try:
        parse_spec(spec)
    except ValueError as e:
        p.error(str(e))
    os.makedirs(args.out_dir, exist_ok=True)
    metrics_path = os.path.join(args.out_dir, "rounds.jsonl")
    node_jsonl = {
        k: os.path.join(args.out_dir, f"train-node{k}.jsonl")
        for k in range(nodes)
    }
    for f in (metrics_path, *node_jsonl.values()):
        if os.path.exists(f):
            os.remove(f)  # MetricsLogger appends; one run per file

    # size the cluster's data plane to the family model (the elastic-
    # averaging payload IS the flat params) — built on one device, cheap
    from akka_allreduce_tpu.train import zoo

    size = zoo.family_param_count(args.family)
    print(f"{args.family}: {size} params -> data_size {size}", flush=True)
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        # every node process simulates the SAME global device set locally
        # (node k owns device group k), so their re-meshes agree
        "XLA_FLAGS": "--xla_force_host_platform_device_count="
        f"{nodes * zoo.devices_per_node(args.family)}",
    }
    spawn = _drill_spawn(env)

    failures: list[str] = []
    await_phase = _drill_phase_waiter(args.phase_timeout, failures)

    def node_steps(k: int) -> list[dict]:
        return [
            r
            for r in _drill_jsonl_records(node_jsonl[k])
            if r.get("kind") == "train_step"
        ]

    def survivor_rounds() -> int:
        return _drill_full_rounds(metrics_path, nodes - 1)

    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(nodes),
        "--rounds", "-1", "--size", str(size),
        "--chunk", str(args.chunk), "--th", str(args.th),
        "--heartbeat", str(args.heartbeat),
        "--streams", str(args.streams),
        "--chaos-seed", str(args.seed), "--chaos-spec", spec,
        "--chaos-log", os.path.join(args.out_dir, "chaos-master.jsonl"),
        "--metrics-out", metrics_path,
        *(["--adapt"] if args.adapt else []),
        *_drill_gossip_args(args),
        *_drill_lever_args(args),
    )
    procs: list = []
    node_out: dict[int, str] = {}
    master_done = False
    victim_rc: int | None = None
    try:
        seed_ep = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed_ep = line.split()[-1]
                break
        if seed_ep is None:
            raise RuntimeError("master never reported its endpoint")
        procs = [
            spawn(
                "chaos-train-node", "--seed", seed_ep,
                "--node-id", str(k), "--nodes", str(nodes),
                "--family", args.family,
                "--warmup-steps", str(args.warmup_steps),
                "--metrics-out", node_jsonl[k],
                "--chaos-log",
                os.path.join(args.out_dir, f"chaos-node{k}.jsonl"),
            )
            for k in range(nodes)
        ]
        # phase 1: every node trained its warm-up trajectory (these steps
        # run BEFORE the join, so the round-triggered kill cannot fire
        # until every node is genuinely training)
        warm = max(1, args.warmup_steps)
        await_phase(
            lambda: all(len(node_steps(k)) >= warm for k in range(nodes)),
            "every node's warm-up trajectory",
        )
        # phase 2: the seeded crash takes the victim down (exit 23)
        if not failures:
            await_phase(
                lambda: procs[victim].poll() is not None,
                f"the seeded crash of node {victim}",
            )
            victim_rc = procs[victim].poll()
        # phase 3: every survivor re-meshed to the surviving membership
        survivors = [k for k in range(nodes) if k != victim]
        want = sorted(survivors)

        def remeshed(k: int) -> bool:
            return any(
                r["generation"] >= 1 and r.get("members") == want
                for r in node_steps(k)
            )

        if not failures:
            await_phase(
                lambda: all(remeshed(k) for k in survivors),
                "every survivor's re-mesh to the surviving membership",
            )
        # phase 4: loss continuity sample + zero wedged rounds — the
        # reduced membership keeps completing rounds AND steps
        if not failures:

            def post_steps(k: int) -> int:
                return sum(
                    1 for r in node_steps(k) if r["generation"] >= 1
                )

            target = survivor_rounds() + args.post_rounds
            await_phase(
                lambda: survivor_rounds() >= target
                and all(
                    post_steps(k) >= args.post_steps for k in survivors
                ),
                f"{args.post_rounds} survivor-membership rounds and "
                f"{args.post_steps} post-re-mesh steps per survivor",
            )
        master.send_signal(_signal.SIGTERM)
        try:
            out, _ = master.communicate(timeout=60)
            master_done = "master done" in out
        except subprocess.TimeoutExpired:
            failures.append("master did not shut down on SIGTERM")
        for k, n in enumerate(procs):
            try:
                node_out[k], _ = n.communicate(timeout=90)
            except subprocess.TimeoutExpired:
                n.kill()
                node_out[k] = ""
    finally:
        for proc in [master, *procs]:
            if proc.poll() is None:
                proc.kill()
                proc.wait()

    # -- assertions over the collected evidence ------------------------------
    if victim_rc is None:
        victim_rc = procs[victim].poll() if procs else None
    if victim_rc != 23:
        failures.append(
            f"victim exited {victim_rc}, not the chaos crash exit 23"
        )
    survivors = [k for k in range(nodes) if k != victim]
    continuity: dict[int, dict] = {}
    for k in survivors:
        steps = node_steps(k)
        pre = [r["loss"] for r in steps if r["generation"] == 0]
        post = [r["loss"] for r in steps if r["generation"] >= 1]
        if not pre or len(post) < args.post_steps:
            failures.append(
                f"node {k}: not enough steps for the continuity check "
                f"(pre={len(pre)}, post={len(post)})"
            )
            continue
        pre_med = statistics.median(pre[-args.post_steps:])
        post_med = statistics.median(post[: args.post_steps])
        bar = pre_med * (1.0 + args.loss_band) + 0.05
        continuity[k] = {
            "pre_median": round(pre_med, 4),
            "post_median": round(post_med, 4),
            "bar": round(bar, 4),
        }
        if not (post_med <= bar):
            failures.append(
                f"node {k}: post-re-mesh median loss {post_med:.4f} "
                f"exceeds the continuity bar {bar:.4f} "
                f"(pre-kill median {pre_med:.4f}, band {args.loss_band})"
            )
        if any(not np_isfinite(loss) for loss in pre + post):
            failures.append(f"node {k}: non-finite loss in the trajectory")
        if args.family == "pipeline":
            # the restage rule, end to end: at the surviving membership
            # the trunk must run at S' = gcd(live devices, n_layers)
            # stages (train/zoo.py pins n_layers=4; a DP-only fallback
            # would show stages == 1 here and is equally legal only when
            # the gcd says so)
            import math as _math

            n_live = len(survivors) * 2  # zoo: 2 devices per node
            want_pp = _math.gcd(n_live, 4)
            at_survivors = [
                r for r in steps if r.get("members") == sorted(survivors)
            ]
            bad = [
                r["stages"] for r in at_survivors if r["stages"] != want_pp
            ]
            if not at_survivors:
                failures.append(
                    f"node {k}: no steps at the surviving membership"
                )
            elif bad:
                failures.append(
                    f"node {k}: restaged to {bad[0]} stages, expected "
                    f"{want_pp} (gcd of {n_live} devices and 4 layers)"
                )
    summaries: dict[int, dict] = {}
    for k in survivors:
        out = node_out.get(k, "")
        m = re.search(
            r"trained (\d+) steps .*remeshes=(\d+) compress_changes=(\d+) "
            r"generation=(\d+) final_compress=(\S+);",
            out or "",
        )
        if not m:
            failures.append(f"node {k} never reported its summary line")
            continue
        summaries[k] = {
            "steps": int(m.group(1)),
            "remeshes": int(m.group(2)),
            "compress_changes": int(m.group(3)),
            "generation": int(m.group(4)),
            "final_compress": m.group(5),
        }
        if int(m.group(2)) < 1:
            failures.append(f"node {k} reported zero re-meshes")
    if not master_done:
        failures.append("master did not finish cleanly")

    summary = {
        "seed": args.seed,
        "family": args.family,
        "spec": spec,
        "nodes": nodes,
        "victim": victim,
        "victim_exit": victim_rc,
        "survivor_rounds": survivor_rounds(),
        "continuity": continuity,
        "loss_band": args.loss_band,
        "node_summaries": summaries,
        "master_done": master_done,
        "failures": failures,
    }
    print(json.dumps(summary))
    return 0 if not failures else 1


def np_isfinite(x) -> bool:
    """math.isfinite over drill-JSON floats (no numpy import needed in
    the drill parent's assertion path)."""
    import math

    try:
        return math.isfinite(float(x))
    except (TypeError, ValueError):
        return False


def _cmd_obs(argv: list[str]) -> int:
    """Observability toolbox: run the 2-process trace demo, inspect flight
    dumps, merge per-process Perfetto traces (OBSERVABILITY.md)."""
    p = argparse.ArgumentParser(
        "obs",
        description="observability tools: trace demo, flight-dump inspect, "
        "trace merge",
    )
    sub = p.add_subparsers(dest="action", required=True)

    d = sub.add_parser(
        "demo",
        help="run a tiny local cluster (master + N node processes), emit a "
        "merged Perfetto trace + per-role metrics snapshots",
    )
    d.add_argument("--out-dir", default="trace_demo")
    d.add_argument("--nodes", type=int, default=2)
    d.add_argument("--rounds", type=int, default=3)
    d.add_argument("--size", type=int, default=65536)
    d.add_argument("--chunk", type=int, default=8192)

    i = sub.add_parser(
        "inspect", help="summarize a flight-recorder JSONL dump"
    )
    i.add_argument("file")

    m = sub.add_parser(
        "merge-trace",
        help="merge per-process Chrome/Perfetto trace files into one",
    )
    m.add_argument("--out", required=True)
    m.add_argument("inputs", nargs="+")

    args = p.parse_args(argv)
    import json

    if args.action == "merge-trace":
        from akka_allreduce_tpu.obs import trace as obs_trace

        out = obs_trace.merge_chrome_traces(args.inputs, args.out)
        print(f"merged {len(args.inputs)} trace file(s) into {out}")
        return 0

    if args.action == "inspect":
        lines = []
        with open(args.file) as f:
            for ln in f:
                if ln.strip():
                    lines.append(json.loads(ln))
        header = next(
            (l for l in lines if l.get("kind") == "flight_header"), {}
        )
        state = next((l for l in lines if l.get("kind") == "state"), {})
        metrics = next((l for l in lines if l.get("kind") == "metrics"), {})
        spans = [l for l in lines if l.get("kind") == "span"]
        events = [l for l in lines if l.get("kind") == "event"]
        print(
            json.dumps(
                {
                    "reason": header.get("reason"),
                    "pid": header.get("pid"),
                    "round_in_flight": state.get("worker.round_in_flight"),
                    "last_transport_stage": state.get("transport.last_stage"),
                    "stalled_round": state.get("watchdog.stalled_round"),
                    "spans": len(spans),
                    "events": len(events),
                    "rounds_completed": metrics.get("worker.rounds_completed"),
                    "dropped": {
                        k.removeprefix("transport.dropped."): v
                        for k, v in metrics.items()
                        if k.startswith("transport.dropped.") and v
                    },
                },
                indent=2,
            )
        )
        return 0

    # demo: one master + N nodes as real OS processes over loopback, each
    # writing its own Perfetto trace; merged at the end so one allreduce
    # round reads as a single timeline across every process
    return _run_obs_demo(args)


def _run_obs_demo(args) -> int:
    import json
    import os

    os.makedirs(args.out_dir, exist_ok=True)
    traces = [os.path.join(args.out_dir, "trace-master.json")]
    metrics_path = os.path.join(args.out_dir, "metrics-master.jsonl")
    for f in (metrics_path, *traces):
        if os.path.exists(f):
            os.remove(f)
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    spawn = _drill_spawn(env)
    master = spawn(
        "cluster-master", "--port", "0", "--nodes", str(args.nodes),
        "--rounds", str(args.rounds), "--size", str(args.size),
        "--chunk", str(args.chunk), "--heartbeat", "0.1",
        "--trace-out", traces[0], "--metrics-out", metrics_path,
    )
    nodes = []
    try:
        seed = None
        for line in master.stdout:
            if line.startswith("master listening on "):
                seed = line.split()[-1]
                break
        if seed is None:
            raise RuntimeError("master never reported its endpoint")
        for k in range(args.nodes):
            t = os.path.join(args.out_dir, f"trace-node{k}.json")
            node_metrics = os.path.join(
                args.out_dir, f"metrics-node{k}.jsonl"
            )
            # MetricsLogger appends: stale files from a previous demo run
            # would mix two runs' records in one artifact
            for f in (t, node_metrics):
                if os.path.exists(f):
                    os.remove(f)
            traces.append(t)
            nodes.append(
                spawn(
                    "cluster-node", "--seed", seed, "--trace-out", t,
                    "--metrics-out", node_metrics,
                )
            )
        master.communicate(timeout=120)
        for n in nodes:
            n.communicate(timeout=60)
    finally:
        for proc in [master, *nodes]:
            if proc.poll() is None:
                proc.kill()

    from akka_allreduce_tpu.obs import trace as obs_trace

    merged = obs_trace.merge_chrome_traces(
        traces, os.path.join(args.out_dir, "trace.json")
    )
    with open(merged) as f:
        events = json.load(f)["traceEvents"]
    by_trace: dict[str, set] = {}
    for e in events:
        tid = e.get("args", {}).get("trace_id")
        if tid:
            by_trace.setdefault(tid, set()).add(e["cat"])
    full = [
        t for t, cats in by_trace.items()
        if {"line_master", "worker", "transport"} <= cats
    ]
    print(
        f"demo: {len(events)} spans, {len(by_trace)} traces, "
        f"{len(full)} round trace(s) spanning line_master+worker+transport"
    )
    print(f"merged Perfetto trace: {merged} (open at https://ui.perfetto.dev)")
    print(f"metrics snapshots: {args.out_dir}/metrics-*.jsonl")
    return 0 if full else 1


COMMANDS = {
    "local-demo": _cmd_local_demo,
    "cluster-master": _cmd_cluster_master,
    "cluster-node": _cmd_cluster_node,
    "cluster-standby": _cmd_cluster_standby,
    "train-cluster-master": _cmd_train_cluster_master,
    "train-cluster-node": _cmd_train_cluster_node,
    "soak": _cmd_soak,
    "train-mlp": _cmd_train_mlp,
    "train-resnet": _cmd_train_resnet,
    "train-zero1": _cmd_train_zero1,
    "train-fsdp": _cmd_train_fsdp,
    "train-lm": _cmd_train_lm,
    "train-moe": _cmd_train_moe,
    "train-pp": _cmd_train_pp,
    "lm-generate": _cmd_lm_generate,
    "elastic-demo": _cmd_elastic_demo,
    "obs": _cmd_obs,
    "bench-wire": _cmd_bench_wire,
    "chaos": _cmd_chaos,
    "chaos-recover": _cmd_chaos_recover,
    "chaos-failover": _cmd_chaos_failover,
    "chaos-adapt": _cmd_chaos_adapt,
    "chaos-gossip": _cmd_chaos_gossip,
    "chaos-scale": _cmd_chaos_scale,
    "chaos-train": _cmd_chaos_train,
    "chaos-train-node": _cmd_chaos_train_node,
}


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if not argv or argv[0] in ("-h", "--help"):
        print(__doc__)
        print("commands:", ", ".join(COMMANDS))
        return 0
    cmd = argv[0]
    if cmd not in COMMANDS:
        print(f"unknown command {cmd!r}; expected one of {sorted(COMMANDS)}")
        return 2
    return COMMANDS[cmd](argv[1:])


# Commands whose PROCESS keeps compiled programs between runs (and across
# an elastic re-mesh back to a mesh size already compiled): the training
# CLIs. The cluster roles and the drills' children get no
# persistent cache — ROADMAP Design 1 ties a hang/abort to it.
_COMPILE_CACHED = frozenset(
    {
        "train-mlp", "train-resnet", "train-zero1", "train-fsdp",
        "train-lm", "train-moe", "train-pp", "elastic-demo", "lm-generate",
    }
)


def _process_main() -> int:
    """``python -m akka_allreduce_tpu``: place the compile cache (process
    entry only — in-process ``main([...])`` callers such as the tests never
    turn it on), then dispatch."""
    if any(arg in _COMPILE_CACHED for arg in sys.argv[1:2]):
        from akka_allreduce_tpu.utils import enable_compile_cache

        enable_compile_cache()
    return main()


if __name__ == "__main__":
    sys.exit(_process_main())

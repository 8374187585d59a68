"""One run of one cell: set-up, the measured window, the traced part, the
check, the result line. Knows no cell, configuration or metric by name: the
runner, the readers and the reference are found through the cell's files."""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import os
import shutil
import sys
import threading
import time

from . import spec
from .trace_reduce import WINDOW_SPAN, find_xplane, reduce_trace

COMPILE_EVENTS = "/jax/core/compile/"
#: a real compile or a load from the persistent cache (not mere tracing)
BACKEND_EVENTS = ("backend_compile_duration", "cache_retrieval_time")


_T0 = time.perf_counter()


def emit(kind: str, **fields) -> None:
    """An earlier line of the output: one JSON object; ``at`` is seconds
    since the harness was imported."""
    at = round(time.perf_counter() - _T0, 3)
    print(json.dumps({"line": kind, "at": at, **fields}, default=str), flush=True)


class CompileLog:
    """Every compile-related duration event JAX reports, with its time."""

    def __init__(self) -> None:
        import jax.monitoring

        self.events: list[tuple[float, str, float]] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name: str, seconds: float, **_) -> None:
        self.events.append((time.perf_counter(), name, seconds))

    def between(self, lo: float, hi: float, only=None) -> list:
        return [
            e for e in self.events
            if lo <= e[0] <= hi and (COMPILE_EVENTS in e[1] or "compilation_cache" in e[1])
            and (only is None or any(k in e[1] for k in only))
        ]


@dataclasses.dataclass
class Context:
    """What a runner is given."""

    cell: spec.Cell
    seed: int
    seconds: float
    trace: bool
    devices: list
    peak: dict  # this device_kind's entry of peaks.json

    def span(self, name: str):
        """A host span on the profiler's clock (free when nothing traces)."""
        import jax

        return jax.profiler.TraceAnnotation(name)


def _memory_peak(devices) -> tuple[int, dict]:
    stats = [d.memory_stats() or {} for d in devices]
    fullest = max(stats, key=lambda s: s.get("peak_bytes_in_use", 0))
    return int(fullest.get("peak_bytes_in_use", 0)), {
        k: v for k, v in fullest.items() if "bytes" in k
    }


def _cpu_stat() -> dict:
    """This process's CPU seconds: little CPU beside a slow unit says the
    stall was the host's, not the harness's."""
    usage = os.times()
    return {"user_s": usage.user, "system_s": usage.system}


class GcLog:
    """Pauses of Python's collector, by generation, while it is watched."""

    def __init__(self) -> None:
        self.pauses: list[tuple[int, float]] = []
        self._t = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], time.perf_counter() - self._t))

    def __enter__(self):
        # Set-up leaves millions of long-lived objects (imports, traced
        # programs); a full collection over them, due at some random step,
        # stops the host loop for over half a second. Collect now and set
        # what is alive aside, so that a collection in the window is cheap.
        gc.collect()
        gc.freeze()
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc) -> None:
        gc.callbacks.remove(self)
        gc.unfreeze()


def _measure(runner, ctx: Context, trace_dir: str | None) -> dict:
    """The window: units one after another until ``seconds`` have passed.
    A unit's clock covers ``runner.unit`` only, which returns when its
    result is ready; ``prepare`` and ``after_unit`` are the untimed rest."""
    import jax

    trace_for = min(ctx.seconds, float(ctx.cell.traffic.get("trace_seconds", 8)))
    stack, traced, paused = contextlib.ExitStack(), False, 0.0
    units, i = [], 0
    if trace_dir is not None:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
    cpu0 = _cpu_stat()
    with GcLog() as collector, stack:
        if trace_dir is not None:  # after the collection, which is no part of the window
            stack.enter_context(jax.profiler.TraceAnnotation(WINDOW_SPAN))
        start = time.perf_counter()
        while True:
            runner.prepare(i)
            t0 = time.perf_counter()
            out = runner.unit(i)
            t1 = time.perf_counter()
            runner.after_unit(i)
            units.append({"t0": t0, "t1": t1, **out})
            i += 1
            now = time.perf_counter()
            if trace_dir is not None and not traced and now - start >= trace_for:
                stack.close()
                jax.profiler.stop_trace()  # writes the trace: seconds, not work
                traced = True
                paused = time.perf_counter() - now
            if now - start >= ctx.seconds:
                break
    return {"start": start, "end": time.perf_counter(), "units": units,
            "paused": paused, "gc_pauses": collector.pauses,
            "host_cpu": {k: v - cpu0[k] for k, v in _cpu_stat().items()}}


def run_cell(workload: str, seed: int, seconds: float, trace: bool, *,
             devices: list, peak: dict, t_process: float,
             overrides: dict | None = None, scratch: str | None = None) -> dict:
    """Returns the result object (the last line). ``devices`` and ``peak`` come
    from ``run.py``'s look for a chip; tests pass CPU devices and a made-up peak."""
    cell = spec.load_cell(workload, overrides=overrides)
    ctx = Context(cell, seed, seconds, trace, list(devices[: cell.chips]), peak)
    compiles = CompileLog()
    runner = spec.load_module("runners", cell.config["runner"]).Runner(ctx)
    emit("start", workload=workload, seed=seed, seconds=seconds, trace=trace,
         config=cell.config_name, traffic=cell.traffic_name, chips=cell.chips,
         runner=cell.config["runner"])

    phases = runner.setup()  # build, load, warm every shape the window uses
    t_setup_end = time.perf_counter()
    setup_s = t_setup_end - t_process
    set_up = compiles.between(0.0, t_setup_end)
    emit("setup", setup_s=setup_s, phases=phases,
         compile_events=len(set_up),
         backend_events=len(compiles.between(0.0, t_setup_end, BACKEND_EVENTS)))

    trace_dir = None
    if trace:
        trace_dir = os.path.join(scratch or os.path.join(spec.ROOT, ".bench_scratch"),
                                 f"trace_{workload}")
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    window = _measure(runner, ctx, trace_dir)
    in_window = compiles.between(window["start"], window["end"], BACKEND_EVENTS)
    facts = runner.close_window()
    memory_peak, memory = _memory_peak(ctx.devices)
    unit_s = sorted(u["t1"] - u["t0"] for u in window["units"])
    slow = [(i, round(u["t1"] - u["t0"], 4), round(u["t0"] - window["start"], 3))
            for i, u in enumerate(window["units"])
            if u["t1"] - u["t0"] > 1.15 * unit_s[len(unit_s) // 2]]
    emit("window", units=len(window["units"]),
         seconds=window["end"] - window["start"], paused_s=window["paused"],
         gc_pauses=[(g, round(t, 4)) for g, t in window["gc_pauses"] if t > 0.002],
         unit_seconds={"min": unit_s[0], "median": unit_s[len(unit_s) // 2],
                       "max": unit_s[-1],
                       "first": [u["t1"] - u["t0"] for u in window["units"][:4]],
                       "slow": slow[:10]},
         host_cpu=window["host_cpu"],
         threads=sorted({t.name.rstrip("0123456789-_ ") for t in threading.enumerate()}),
         compiles_in_window=[e[1] for e in in_window], memory=memory, **facts)
    if in_window:
        raise SystemExit(
            f"{len(in_window)} program(s) compiled or loaded inside the "
            f"measured window: {sorted({e[1] for e in in_window})}"
        )

    t0 = time.perf_counter()
    compared = runner.check()  # frees the program's state, runs the reference
    emit("check", seconds=time.perf_counter() - t0, compared=compared)

    record = {
        "cell": cell, "peak": peak, "chips": cell.chips, "setup_s": setup_s,
        "compile_s": sum(e[2] for e in set_up if COMPILE_EVENTS in e[1]),
        "window": window,
    }
    reduced = None
    if trace:
        reduced = reduce_trace(find_xplane(trace_dir), spans=tuple(runner.spans))
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics, folder = {}, "layer_metrics" if trace else "end_to_end"
    for name in cell.per_layer if trace else cell.end_to_end:
        reader = spec.load_module(folder, name)
        value = reader.compute(record, reduced)
        if value is not None:  # a reader that finds nothing reports nothing
            metrics[name] = {"value": float(value), "unit": reader.UNIT}

    failed = sum(1 for u in window["units"] if not u["ok"]) + int(facts.get("failed_late", 0))
    first = ctx.devices[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(ctx.devices), "memory_peak_bytes": memory_peak}
    result = {
        "correct": bool(compared) and all(c["ok"] for c in compared) and failed == 0,
        "attempted": len(window["units"]), "failed": failed,
        "metrics": metrics, "device": device,
    }
    if reduced is not None:
        device["busy_s"], device["window_s"] = reduced.busy_s, reduced.window_s
        result["breakdown"] = reduced.breakdown()
    return result


def main(argv, *, t_process: float) -> int:
    import argparse

    p = argparse.ArgumentParser(prog="run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = spec.load_cell(args.workload)

    import jax

    with open(os.path.join(spec.BENCH_DIR, "peaks.json"), encoding="utf-8") as f:
        peaks = json.load(f)
    devices = jax.devices()
    kind = devices[0].device_kind
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0]!r}", file=sys.stderr)
        return 2
    if len(devices) < cell.chips:
        print(f"{args.workload} needs {cell.chips} chips, JAX sees "
              f"{len(devices)}", file=sys.stderr)
        return 2
    if kind not in peaks:
        print(f"device_kind {kind!r} is not in peaks.json", file=sys.stderr)
        return 2

    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    emit("environment", jax=jax.__version__, device_kind=kind,
         devices=len(devices), cache_dir=cache_dir,
         cache_entries=len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0)
    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                      devices=devices, peak=peaks[kind], t_process=t_process)
    print(json.dumps(result), flush=True)
    return 0

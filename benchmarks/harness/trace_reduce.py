"""From the profiler's ``.xplane.pb`` to what the per-layer readers use.

Read with ``jax.profiler.ProfileData`` and nothing else. What a v5e trace
holds (looked at by hand, PR 23): one plane ``/device:TPU:<n>`` per chip with
the lines ``XLA Modules`` (one event per run of a compiled program, named
``jit_<fn>(<fingerprint>)``) and ``XLA Ops`` (one event per operation, never
overlapping, named by its HLO text ``%<op> = ...``); asynchronous copies sit on
a line of their own and are not counted as busy. The host plane ``/host:CPU``
carries the ``jax.profiler.TraceAnnotation`` spans of the runner on the same
clock, in nanoseconds from the start of the trace.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
WINDOW_SPAN = "bench_window"


def op_name(event_name: str) -> str:
    """``%fusion.13 = (f32[...]) fusion(...)`` -> ``fusion.13``."""
    return event_name.split(" = ", 1)[0].lstrip("%").strip()


_OPCODE = re.compile(r"\s([a-z][a-z0-9_\-]*)\(")


def op_kind(event_name: str) -> str:
    """The HLO opcode: ``%x.1 = f32[8]{0} all-reduce(f32[8]{0} %y), ...`` ->
    ``all-reduce``; empty where the name is not an HLO text."""
    hit = _OPCODE.search(event_name.split(" = ", 1)[-1]) if " = " in event_name else None
    return hit.group(1) if hit else ""


def _merge(intervals: list[tuple[float, float]]) -> list[list[float]]:
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def _clip(events, lo: float, hi: float):
    """Events cut to [lo, hi]: (start, end, name), in nanoseconds."""
    out = []
    for start, dur, name in events:
        a, b = max(start, lo), min(start + dur, hi)
        if b > a:
            out.append((a, b, name))
    return out


@dataclasses.dataclass
class Reduced:
    """Times in seconds; ``ops``, ``modules`` and ``gaps`` are the first
    device's (every chip of a cell runs the same program)."""

    window_s: float
    busy_s: float  # union of op intervals in the window, mean over devices
    devices: int
    ops: dict[str, list]  # op name -> [count, seconds, HLO opcode]
    modules: list[tuple[str, float, float]]  # (name, start_s, seconds)
    gaps: list[tuple[float, float, str]]  # (start_s, seconds, host span)

    def matching(self, name: str | None = None, kind: str | None = None) -> tuple[int, float]:
        """``(events, seconds)`` of the ops whose name matches the pattern
        ``name`` or whose HLO opcode matches the pattern ``kind``."""
        hits = [
            v for k, v in self.ops.items()
            if (name is not None and re.search(name, k))
            or (kind is not None and re.search(kind, v[2]))
        ]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)

    def idle_pct(self) -> float:
        """Share of the window in which no operation ran on the device."""
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def main_module(self) -> list[tuple[float, float]]:
        """Runs (start_s, seconds) of the program that took most device time
        - the train step or the collective, whatever its name."""
        total: dict[str, float] = {}
        for name, _, dur in self.modules:
            total[name] = total.get(name, 0.0) + dur
        if not total:
            return []
        top = max(total, key=total.get)
        return [(s, d) for name, s, d in self.modules if name == top]

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:top]
        by_span: dict[str, float] = {}
        for _, dur, span in self.gaps:
            by_span[span] = by_span.get(span, 0.0) + dur
        gaps = sorted(by_span.items(), key=lambda kv: -kv[1])[:top]
        return {
            "device_ops": [[k, v[1]] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps],
        }


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(path: str, spans: tuple[str, ...] = ()) -> Reduced:
    """``spans`` are the runner's annotation names (they do not nest); an idle
    gap is given to the span the host was in at its middle, or to
    ``outside_spans``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host_spans, window = {}, [], None
    for plane in data.planes:
        hit = DEVICE_PLANE.match(plane.name)
        if hit:
            lines = {line.name: line for line in plane.lines}
            devices[int(hit.group(1))] = {
                key: [
                    (e.start_ns, e.duration_ns, e.name)
                    for e in lines[key].events
                ] if key in lines else []
                for key in (OPS_LINE, MODULES_LINE)
            }
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    elif e.name in spans:
                        host_spans.append(
                            (e.start_ns, e.start_ns + e.duration_ns, e.name)
                        )
    if not devices:
        raise ValueError(f"{path} holds no /device:TPU:<n> plane")
    if window is None:  # no span from the runner: the devices' own extent
        every = [e for d in devices.values() for e in d[OPS_LINE]]
        window = (
            min(e[0] for e in every), max(e[0] + e[1] for e in every)
        )
    lo, hi = window
    busy = []
    for d in devices.values():
        merged = _merge([(a, b) for a, b, _ in _clip(d[OPS_LINE], lo, hi)])
        busy.append(sum(b - a for a, b in merged))
    first = devices[min(devices)]
    ops: dict[str, list] = {}
    for a, b, name in _clip(first[OPS_LINE], lo, hi):
        slot = ops.setdefault(op_name(name), [0, 0.0, op_kind(name)])
        slot[0] += 1
        slot[1] += (b - a) / 1e9
    merged = _merge([(a, b) for a, b, _ in _clip(first[OPS_LINE], lo, hi)])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    host_spans.sort()
    starts = [h[0] for h in host_spans]
    gaps = []
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:  # the span the host was in at the middle of the gap
            at = bisect.bisect_right(starts, (a + b) / 2) - 1
            inside = at >= 0 and (a + b) / 2 < host_spans[at][1]
            gaps.append((
                (a - lo) / 1e9, (b - a) / 1e9,
                host_spans[at][2] if inside else "outside_spans",
            ))
    modules = [
        (name.split("(", 1)[0], (a - lo) / 1e9, (b - a) / 1e9)
        for a, b, name in _clip(first[MODULES_LINE], lo, hi)
    ]
    return Reduced(
        window_s=(hi - lo) / 1e9, busy_s=sum(busy) / len(busy) / 1e9,
        devices=len(devices), ops=ops, modules=modules, gaps=gaps,
    )

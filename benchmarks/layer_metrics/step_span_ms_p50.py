"""The program's own clock around each step of the window: the 0.5 quantile,
in ms, of the root span ``trainer.step`` that ``ShardedLMTrainer.train_step``
records (``akka_allreduce_tpu.obs.trace``): ``step_ms_p50`` from inside.

This file also holds what the readers of those spans share. A span's record
carries ``t0``, its start on ``time.perf_counter``: the clock of the window
(``record["window"]["start"]``, each unit's ``t0`` / ``t1``). The harness
enters the ``bench_window`` annotation, the zero of every time in a reduced
trace, one statement before it reads ``start``, so ``t0 - window.start`` is a
span's place on the trace's axis. A program that records no such span (or
none with ``t0``) gives every reader here nothing to read.
"""

import numpy as np

UNIT = "ms"
ROOT = "trainer.step"
PHASES = ("place", "dispatch", "fetch")
#: the harness's own rule for a window's ``slow`` units (``harness/cell_run.py``)
SLOW = 1.15
#: the share of the traced steps that may fail ``gap_parts``' check
MISPAIRED = 0.01


def step_spans(record) -> list[dict]:
    """The window's steps in order, each ``{"step": (start, end), "place":
    ..., "dispatch": ..., "fetch": ...}`` in seconds from the window's start:
    the ``trainer.step*`` records of ``obs.trace.snapshot()`` whose ``t0``
    lies in the window, a root kept only with all three of its children."""
    try:
        from akka_allreduce_tpu.obs.trace import snapshot
    except ImportError:
        return []
    window = record["window"]
    zero = window["start"]
    inside = [
        r for r in snapshot()
        if r["name"].startswith(ROOT) and zero <= r.get("t0", -1.0) <= window["end"]
    ]
    children: dict[int, dict] = {}
    for r in inside:
        if r["name"] != ROOT:
            children.setdefault(r["parent_id"], {})[r["name"][len(ROOT) + 1:]] = r
    steps = []
    for r in inside:
        phases = children.get(r["span_id"], {})
        if r["name"] == ROOT and set(phases) == set(PHASES):
            steps.append({
                name: (s["t0"] - zero, s["t0"] + s["dur"] - zero)
                for name, s in (("step", r), *phases.items())
            })
    return sorted(steps, key=lambda s: s["step"])


def seconds(span: tuple[float, float]) -> float:
    return span[1] - span[0]


def step_ms_quantile(record, q: float):
    steps = step_spans(record)
    if not steps:
        return None
    return 1e3 * float(np.quantile([seconds(s["step"]) for s in steps], q))


def slow_steps(record):
    """``(the steps whose root span exceeds SLOW x the window's median, all
    the window's steps)``, or None without spans. The median is the harness's:
    the upper middle of the sorted durations."""
    steps = step_spans(record)
    if not steps:
        return None
    ordered = sorted(seconds(s["step"]) for s in steps)
    limit = SLOW * ordered[len(ordered) // 2]
    return [s for s in steps if seconds(s["step"]) > limit], steps


def slow_excess_ms(record, part) -> float | None:
    """Over the slow steps, the sum of ``part(step)`` less its median over the
    window's steps, in ms; 0 where no step is slow."""
    found = slow_steps(record)
    if found is None:
        return None
    slow, steps = found
    usual = float(np.median([part(s) for s in steps]))
    return 1e3 * sum(part(s) - usual for s in slow)


def gap_parts(record, trace):
    """Each gap between one run of the step program on the device and the next
    (what ``host_gap_ms.train`` averages), as far as the host's own clock can
    say what it went to:

    - ``caller``: from the end of step k's root span to the start of step
      k + 1's: not inside ``train_step`` at all;
    - ``place``: from step k + 1's start to the start of its ``dispatch``;
    - ``around_run``: the rest: from the end of step k's program to its
      ``train_step``'s return (the wait in ``fetch`` that is left, the results'
      way back, the root's own time after it) and from step k + 1's
      ``dispatch`` to its program's start on the device.

    The first two lie between two host spans and inside the gap whatever the
    trace's clock says (step k's program has ended when its ``fetch`` returns;
    step k + 1's starts after its ``dispatch``); the third is the gap less
    them. How ``around_run`` divides at the device's two edges is NOT
    reported: the profiler places the device's events on its own host clock
    to within about a millisecond, the same for a whole session (PERF.md
    section 6, PR 37: in some sessions the step program reads as starting
    0.3 ms BEFORE the call that enqueues it), and the two sides are 1-2 ms
    each.

    Returns the three means over the traced gaps in ms (they sum to
    ``host_gap_ms.train``), or None. Run k and step k are paired in order,
    both from the window's first step; the pairing is checked by what a
    millisecond between the clocks does not change: run k's middle must lie in
    step k's root span, step k's ``dispatch``-to-``fetch`` must last as long
    as run k does, and a gap as long as the ``caller`` and ``place`` in it.
    Where more than ``MISPAIRED`` of the traced steps break that, nothing is
    reported. Worked out once a record; a line ``step_spans`` of the output
    gives the check's result and where the trace put each run's edges
    against ``dispatch``'s start and ``fetch``'s end."""
    if "step_gap_parts" not in record:
        record["step_gap_parts"] = _gap_parts(record, trace)
    return record["step_gap_parts"]


def _gap_parts(record, trace):
    runs, steps = trace.main_module(), step_spans(record)
    if len(runs) < 2 or len(steps) < len(runs):
        return None
    pairs = list(zip(runs, steps))
    gap, caller, place, sound = [], [], [], 0
    for ((a, d), this), ((b, _), nxt) in zip(pairs, pairs[1:]):
        gap.append(b - (a + d))
        caller.append(nxt["step"][0] - this["step"][1])
        place.append(nxt["dispatch"][0] - nxt["step"][0])
        sound += (
            this["step"][0] <= a + d / 2 <= this["step"][1]
            and this["fetch"][1] - this["dispatch"][0] >= d
            and gap[-1] >= caller[-1] + place[-1]
        )
    lead = [1e3 * (a - s["dispatch"][0]) for (a, _), s in pairs]
    tail = [1e3 * (s["fetch"][1] - (a + d)) for (a, d), s in pairs]
    from harness.cell_run import emit

    emit("step_spans", steps=len(steps), traced_runs=len(runs),
         paired_soundly=sound / len(gap),
         run_start_after_dispatch_ms=[min(lead), float(np.median(lead)), max(lead)],
         fetch_end_after_run_end_ms=[min(tail), float(np.median(tail)), max(tail)])
    if sound < (1.0 - MISPAIRED) * len(gap):
        return None
    mean_ms = lambda xs: 1e3 * sum(xs) / len(gap)  # noqa: E731
    parts = {"caller": mean_ms(caller), "place": mean_ms(place)}
    parts["around_run"] = mean_ms(gap) - parts["caller"] - parts["place"]
    return parts


def compute(record, trace):
    return step_ms_quantile(record, 0.5)

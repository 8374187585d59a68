"""The arithmetic the readers share."""

from __future__ import annotations

import numpy as np


def unit_ms_quantile(record: dict, q: float) -> float:
    """The ``q`` quantile of the host-clock time of the window's units, in ms
    (linear interpolation between order statistics)."""
    seconds = [u["t1"] - u["t0"] for u in record["window"]["units"]]
    return 1e3 * float(np.quantile(seconds, q))


def worst_leaf_gap(program: dict, reference: dict) -> float:
    """The widest gap between the program's norm and the reference's over the
    leaves, measured against the reference's norm of that leaf or of the
    median leaf, whichever is larger (some gradients are all but zero)."""
    ref = sorted(reference.values())
    median = ref[len(ref) // 2]
    return max(
        abs(program[n] - reference[n]) / max(reference[n], median)
        for n in reference
    )


def work_rate_window(record: dict) -> float:
    """Work of the units that succeeded over ALL the time of the window, from
    its start to the end of its last unit (what lies between units counts;
    the pause in which a traced run writes its trace out does not)."""
    w = record["window"]
    done = sum(u["work"] for u in w["units"] if u["ok"])
    return done / (w["units"][-1]["t1"] - w["start"] - w["paused"])


def work_rate_timed(record: dict) -> float:
    """Work of the units that succeeded over the time on the units' clocks."""
    units = record["window"]["units"]
    return sum(u["work"] for u in units if u["ok"]) / sum(
        u["t1"] - u["t0"] for u in units
    )

"""What the masked kernels' tiles run of pairs the learned mask keeps: the
program's gauges ``attention.sparse.mask_pairs`` over
``attention.sparse.visited_pairs``, written when the selection first makes a
mask of a shape and the masked kernels first take it (``ops/``; the runner
hands the gauges with the first unit, ``counters``). 100 % would be a grid that visits no
masked-out pair. A program without the gauges reports nothing."""

from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    gauges = load_module("layer_metrics", "moe_past_first_rung_pct").window_counters(record)
    visited = gauges.get("attention.sparse.visited_pairs")
    if not visited or "attention.sparse.mask_pairs" not in gauges:
        return None
    return 100.0 * gauges["attention.sparse.mask_pairs"] / visited

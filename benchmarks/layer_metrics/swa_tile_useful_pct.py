"""What the band's tiles run of pairs the mask leaves: the program's gauges
``attention.band.mask_pairs`` over ``attention.band.visited_pairs``, written
from the forward kernel's own block tables when the windowed kernel is built
(``ops/local_attention.py``; the runner hands the gauges with the first unit,
``counters``). 100 % would be a grid that visits no masked-out pair. A program
without the gauges reports nothing."""

from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    gauges = load_module("layer_metrics", "moe_past_first_rung_pct").window_counters(record)
    visited = gauges.get("attention.band.visited_pairs")
    if not visited or "attention.band.mask_pairs" not in gauges:
        return None
    return 100.0 * gauges["attention.band.mask_pairs"] / visited

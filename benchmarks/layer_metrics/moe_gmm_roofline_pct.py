"""The grouped-product kernels' share of their roofline. The least time of
a step's products is the larger of ``harness/moe_flops.grouped_products``'s
FLOPs over the bf16 peak and its bytes over the HBM rate, each expert layer
at the rows the program's counter says were routed to it (empty rows of the
buffer count for nothing), over the kernels' device time."""

from harness.moe_flops import grouped_products
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    seconds = load_module("layer_metrics", "moe_gmm_ms").per_step_seconds(trace)
    rows = load_module("layer_metrics", "moe_load_max_over_mean").routed_rows(record)
    if seconds is None or rows is None:
        return None
    peak, least = record["peak"], 0.0
    for layer_rows in rows:
        need = grouped_products(record["cell"].config, layer_rows)
        least += max(need["flops"] / peak["bf16_flops_per_s"],
                     need["bytes"] / peak["hbm_bytes_per_s"])
    return 100.0 * least / seconds

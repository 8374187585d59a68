"""The windowed attention kernels' share of their roofline, as
``swa_kernel_roofline_pct`` reads it, from ``mellum``'s keys: the band's
count alone (``harness/mellum_flops.py``: a score and a value product forward
and two of each backward over ``sum_i min(i + 1, window)`` pairs a head, in
the windowed layers) over the bf16 peak, over ``swa_kernel_ms``'s seconds. It
reads the same work whatever tiles or mask implement the kernel, so what the
tiles still run of masked-out pairs shows as a low share. Compute-bound."""

from harness.mellum_flops import attention_train_flops
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    seconds = load_module("layer_metrics", "swa_kernel_ms").per_step_seconds(
        record, trace
    )
    if seconds is None:
        return None
    cell = record["cell"]
    flops = attention_train_flops(
        cell.config, cell.traffic["batch"], cell.traffic["seq_len"], windowed=True
    )
    return 100.0 * flops / record["peak"]["bf16_flops_per_s"] / seconds

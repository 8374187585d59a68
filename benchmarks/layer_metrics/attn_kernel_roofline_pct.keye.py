"""The attention kernels' share of their roofline in a cell whose attention
runs under a learned mask: ``harness/keye_flops.py``'s attention count (a
score and a value product forward and two of each backward over the pairs the
masks KEEP, ``sum_t min(t + 1, topk)`` a layer) over the bf16 peak, over
``attn_kernel_ms``'s seconds - the same work whatever implements it, so a
kernel that runs every causal pair under the mask cannot read above the kept
share (43.7 % at 8,192 positions and 2,048 keys) of its own efficiency.
Compute-bound."""

from harness.keye_flops import attention_train_flops
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    ms = load_module("layer_metrics", "attn_kernel_ms").compute(record, trace)
    if ms is None:
        return None
    cell = record["cell"]
    flops = attention_train_flops(
        cell.config, cell.traffic["batch"], cell.traffic["seq_len"]
    )
    return 100.0 * flops / record["peak"]["bf16_flops_per_s"] / (1e-3 * ms)

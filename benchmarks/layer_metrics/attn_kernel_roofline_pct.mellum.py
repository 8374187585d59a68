"""The attention kernels' share of their roofline in a cell of ``mellum``'s
keys: ``harness/mellum_flops.py``'s attention count (a score and a value
product forward and two of each backward over the pairs each layer's mask
leaves, windowed and full layers alike) over the bf16 peak, over
``attn_kernel_ms``'s seconds - whatever tiles implement the kernels.
Compute-bound."""

from harness.mellum_flops import attention_train_flops
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

"""The attention kernels' share of their roofline in a cell of ``qwen3_next``'s
keys: ``harness/qwen3_next_flops.py``'s attention count (a score and a value
product forward and two of each backward over the causal pairs of every full
layer, 16 heads of 256 on 2) over the bf16 peak, over ``attn_kernel_ms``'s
seconds - whatever tiles implement the kernels. Compute-bound."""

from harness.qwen3_next_flops import attention_train_flops
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    ms = load_module("layer_metrics", "attn_kernel_ms").compute(record, trace)
    cell = record["cell"]
    if ms is None or "linear_num_value_heads" not in cell.config:
        return None
    flops = attention_train_flops(
        cell.config, cell.traffic["batch"], cell.traffic["seq_len"]
    )
    return 100.0 * flops / record["peak"]["bf16_flops_per_s"] / (1e-3 * ms)

"""The flash kernels' share of their roofline in a cell of a
configuration-built decoder: ``harness/moe_flops.py``'s attention count (its
attention layers only; two score/value matmuls forward and four backward
over a causal-halved T x T) over the bf16 peak, over ``flash_attn_ms``'s
seconds. Compute-bound."""

from harness.moe_flops import attention_train_flops
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    seconds = load_module("layer_metrics", "flash_attn_ms").per_step_seconds(trace)
    if seconds is None:
        return None
    cell = record["cell"]
    flops = attention_train_flops(
        cell.config, cell.traffic["batch"], cell.traffic["seq_len"]
    )
    return 100.0 * flops / record["peak"]["bf16_flops_per_s"] / seconds

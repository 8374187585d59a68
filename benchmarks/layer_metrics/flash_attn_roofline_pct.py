"""The flash kernels' share of their roofline. Compute-bound: the least
time is the attention count of ``harness/flops.py`` (two score/value
matmuls forward and four backward over a causal-halved T x T; what the
backward kernels recompute is not counted) over the bf16 peak."""

from harness.flops import attention_train_flops
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

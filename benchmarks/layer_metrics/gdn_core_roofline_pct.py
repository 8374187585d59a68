"""The gated delta rule's share of its roofline: ``harness/qwen3_next_flops.
py``'s count of the OPERATION in one linear layer (fixed at chunks of 64
whatever the program does; the solve not counted) over the bf16 peak, or the
least bytes (q, k, v, o and their gradients once each in bf16) over the HBM
bandwidth, whichever takes longer, times the linear layers, over
``gdn_core_ms``'s seconds. No reading over 100 % can come from it unless the
program leaves work out."""

from harness.qwen3_next_flops import delta_rule_layer, layer_kinds
from harness.spec import load_module

UNIT = "%"


def compute(record, trace):
    ms = load_module("layer_metrics", "gdn_core_ms").compute(record, trace)
    cell = record["cell"]
    if ms is None or "linear_num_value_heads" not in cell.config:
        return None
    one = delta_rule_layer(cell.config, cell.traffic["batch"], cell.traffic["seq_len"])
    least = max(one["flops"] / record["peak"]["bf16_flops_per_s"],
                one["bytes"] / record["peak"]["hbm_bytes_per_s"])
    layers = layer_kinds(cell.config).count("linear_attention")
    return 100.0 * layers * least / (1e-3 * ms)

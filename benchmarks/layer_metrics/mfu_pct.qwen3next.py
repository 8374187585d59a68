"""The whole step's share of the bf16 peak in a cell of Gated DeltaNet layers
among gated full-attention layers: ``harness/qwen3_next_flops.py``'s count (the
mixers' projections, the gated delta rule counted as the operation, full
attention on the causal pairs, shared experts, routers, the head, the experts'
share from the rows the program's counter says were routed here) times steps
per second, over chips times the bf16 peak."""

from harness.qwen3_next_flops import train_flops_per_step
from harness.spec import load_module
from harness.stats import work_rate_window

UNIT = "%"


def compute(record, trace):
    rows = load_module("layer_metrics", "moe_load_max_over_mean").routed_rows(record)
    if rows is None or "linear_num_value_heads" not in record["cell"].config:
        return None
    cell = record["cell"]
    batch, seq_len = cell.traffic["batch"], cell.traffic["seq_len"]
    per_step = train_flops_per_step(cell.config, batch, seq_len, sum(rows))["total"]
    steps_per_s = work_rate_window(record) / (batch * seq_len)
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * per_step * steps_per_s / peak

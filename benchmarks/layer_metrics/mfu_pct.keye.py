"""The whole step's share of the bf16 peak in a cell whose attention runs
under a learned mask with held experts in every layer:
``harness/keye_flops.py``'s count (attention's and the indexer's projections,
routers, the head, the experts' share from the rows the program's counter
says were routed here, attention on the kept pairs, index scores on the
causal pairs, the loss's target) times steps per second, over chips times
the bf16 peak."""

from harness.keye_flops import train_flops_per_step
from harness.spec import load_module
from harness.stats import work_rate_window

UNIT = "%"


def compute(record, trace):
    rows = load_module("layer_metrics", "moe_load_max_over_mean").routed_rows(record)
    if rows is None or "sa_config" not in record["cell"].config:
        return None
    cell = record["cell"]
    batch, seq_len = cell.traffic["batch"], cell.traffic["seq_len"]
    per_step = train_flops_per_step(cell.config, batch, seq_len, sum(rows))["total"]
    steps_per_s = work_rate_window(record) / (batch * seq_len)
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * per_step * steps_per_s / peak

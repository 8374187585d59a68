"""The whole step's share of the bf16 peak in a cell of a latent-attention
decoder with held experts and a prediction module:
``harness/mla_moe_flops.py``'s count (the head twice, the experts' share from
the rows the program's counter says were routed here, the module's layer
among them) times tokens per second, over chips times the bf16 peak."""

from harness.mla_moe_flops import train_flops_per_token
from harness.spec import load_module
from harness.stats import work_rate_window

UNIT = "%"


def compute(record, trace):
    rows = load_module("layer_metrics", "moe_load_max_over_mean").routed_rows(record)
    if rows is None:
        return None
    cell = record["cell"]
    tokens = cell.traffic["batch"] * cell.traffic["seq_len"]
    per_token = train_flops_per_token(
        cell.config, cell.traffic["seq_len"], sum(rows) / tokens
    )["total"]
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * per_token * work_rate_window(record) / peak

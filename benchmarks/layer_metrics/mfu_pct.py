"""Model-FLOP utilisation: the operations the forward and backward passes
need per token (``harness/flops.py``: embedding lookup left out, nothing
recomputed counted) times tokens per second, over chips times the bf16 peak."""

from harness.flops import lm_train_flops_per_token
from harness.stats import work_rate_window

UNIT = "%"


def compute(record, trace):
    cell = record["cell"]
    per_token = lm_train_flops_per_token(cell.config, cell.traffic["seq_len"])["total"]
    peak = record["chips"] * record["peak"]["bf16_flops_per_s"]
    return 100.0 * per_token * work_rate_window(record) / peak

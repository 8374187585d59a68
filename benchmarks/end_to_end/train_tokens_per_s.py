"""Tokens of the steps completed in the window over all the time of the
window: input placement, dispatch and the per-step loss sync included."""

from harness.stats import work_rate_window

UNIT = "tokens/s"


def compute(record, trace):
    return work_rate_window(record)

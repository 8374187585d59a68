"""Host clock around each unit of the window: the 0.9 quantile, in ms."""

from harness.stats import unit_ms_quantile

UNIT = "ms"


def compute(record, trace):
    return unit_ms_quantile(record, 0.9)

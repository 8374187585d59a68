"""Share of the traced window in which no operation ran on the device
(1 - union of op intervals / window, mean over the chips used)."""

UNIT = "%"


def compute(record, trace):
    return trace.idle_pct()

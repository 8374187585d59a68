"""Bus bandwidth of the rounds of the window: 2 (n-1)/n x payload bytes a
round, over the time on the rounds' clocks (call to ``block_until_ready``),
all rounds together. Making a round's payload is not on the clock."""

from harness.stats import work_rate_timed

UNIT = "GB/s"


def compute(record, trace):
    return work_rate_timed(record) / 1e9

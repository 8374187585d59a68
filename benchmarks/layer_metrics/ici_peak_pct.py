"""Bus bandwidth over the published per-chip interconnect peak (all ports
together; a chip of a 2x2 has fewer neighbours than ports, so the reachable
share is well under 100)."""

from harness.stats import work_rate_timed

UNIT = "%"


def compute(record, trace):
    return 100.0 * work_rate_timed(record) / (record["peak"]["ici_bits_per_s"] / 8)

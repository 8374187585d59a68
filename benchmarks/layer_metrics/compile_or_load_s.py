"""Seconds JAX spent tracing, lowering and compiling - or loading from the
persistent cache - during set-up (its own duration events, summed)."""

UNIT = "s"


def compute(record, trace):
    return record["compile_s"]

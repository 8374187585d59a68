"""Process start to the first timed unit: imports, device start-up, making
the weights or the payload, compiling or loading every program, warm-up."""

UNIT = "s"


def compute(record, trace):
    return record["setup_s"]

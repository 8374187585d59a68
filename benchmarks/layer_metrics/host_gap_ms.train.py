"""Device time with no program running between one run of the step program
and the next (the ``XLA Modules`` line): what the host loop's batch
placement, dispatch and loss sync leave idle, per step."""

UNIT = "ms"


def compute(record, trace):
    runs = trace.main_module()
    if len(runs) < 2:
        return None
    gaps = [b[0] - (a[0] + a[1]) for a, b in zip(runs, runs[1:])]
    return 1e3 * sum(gaps) / len(gaps)

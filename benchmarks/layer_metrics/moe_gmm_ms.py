"""Device time of the grouped-product kernels per step: the forward and
rows'-gradient products (ops named ``gmm*`` in a v5e trace) and the weights'
gradient (``tgmm*``) of every expert layer."""

UNIT = "ms"
KERNELS = r"^t?gmm(\.\d+)?$"


def per_step_seconds(trace):
    steps = len(trace.main_module())
    events, seconds = trace.matching(KERNELS)
    if not steps or not events:
        return None
    return seconds / steps


def compute(record, trace):
    s = per_step_seconds(trace)
    return None if s is None else 1e3 * s

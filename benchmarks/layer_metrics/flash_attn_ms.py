"""Device time of the library flash-attention kernels per step. In a v5e
trace they are the ops named ``flash_attention*`` (forward) and
``flash_mha_bwd_dkv*`` / ``flash_mha_bwd_dq*`` (backward)."""

UNIT = "ms"
KERNELS = r"^flash_(attention|mha)"


def per_step_seconds(trace):
    steps = len(trace.main_module())
    events, seconds = trace.matching(KERNELS)
    if not steps or not events:
        return None
    return seconds / steps


def compute(record, trace):
    s = per_step_seconds(trace)
    return None if s is None else 1e3 * s

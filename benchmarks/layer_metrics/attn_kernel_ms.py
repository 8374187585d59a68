"""Device time of the attention kernels per step, whatever implements them:
the library's splash kernels (ops named ``splash_mha_fwd*`` and
``splash_mha_dkv*`` / ``splash_mha_dq*`` in a v5e trace; ``splash_mqa*`` with
one K/V head) and the flash kernels before them (``flash_attention*``,
``flash_mha_bwd_*``). The sum XLA makes of the fused backward's ``dq``
partials and the layout changes around the kernels are not in it."""

UNIT = "ms"
KERNELS = r"^(flash_(attention|mha)|splash_m[hq]a)"


def compute(record, trace):
    steps = len(trace.main_module())
    events, seconds = trace.matching(KERNELS)
    if not steps or not events:
        return None
    return 1e3 * seconds / steps

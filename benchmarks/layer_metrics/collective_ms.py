"""Device time of the collective ops per round, first device: the ops whose
HLO opcode is ``all-reduce`` (in a v5e trace jax's ``psum`` names them
``psum_invariant.<n>``, so the name alone would miss them)."""

UNIT = "ms"
NAME, KIND = r"^(all-reduce|psum)", r"^all-reduce"


def compute(record, trace):
    rounds = len(trace.main_module())
    events, seconds = trace.matching(NAME, KIND)
    if not rounds or not events:
        return None
    return 1e3 * seconds / rounds

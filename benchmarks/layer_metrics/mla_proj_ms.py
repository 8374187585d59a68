"""Device time per step of latent attention's projections: the ops of a
trace whose HLO instruction carries one of the program's named scopes
``mla_down`` (``W_qa``, ``W_kva``), ``mla_up`` (the two latent norms,
``W_qb``, ``W_kvb``, RoPE, the broadcast of ``k_r``) or ``mla_out``
(``W_o``), forward and backward, in every
layer and the prediction module's. The runner hands the instruction ->
``op_name`` map of the compiled step with the first unit of a traced run; a
program without the scopes, or a runner without the map, reports nothing."""

import re

UNIT = "ms"
SCOPES = r"(?:^|/)mla_(?:down|up|out)(?:/|$)"
#: a trace holds an event for such an op AND one for each op of its bodies
WRAPPERS = ("conditional", "while", "call")


def scoped_seconds(record, trace, pattern: str):
    """``(seconds of the ops under a scope matching ``pattern``, seconds of
    all ops, steps)`` or None where the map or the scope is absent; an op
    that wraps other ops' events (:data:`WRAPPERS`) counts on neither side."""
    scopes = next(
        (u["op_scopes"] for u in record["window"]["units"] if u.get("op_scopes")),
        None,
    )
    steps = len(trace.main_module())
    if not scopes or not steps:
        return None
    ops = {k: v for k, v in trace.ops.items() if v[2] not in WRAPPERS}
    hit = [v[1] for k, v in ops.items() if re.search(pattern, scopes.get(k, ""))]
    if not hit:
        return None
    return sum(hit), sum(v[1] for v in ops.values()), steps


def compute(record, trace):
    found = scoped_seconds(record, trace, SCOPES)
    return None if found is None else 1e3 * found[0] / found[2]

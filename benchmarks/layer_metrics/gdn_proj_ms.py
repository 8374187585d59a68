"""Device time per step of the linear-attention mixers' projections: the ops of a
trace whose HLO instruction carries one of the program's named scopes ``gdn_in``
(``W_qkvz``, ``W_ba``) or ``gdn_out`` (the gated norm and ``W_o``), forward and
backward, in every linear layer. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a
runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)gdn_(?:in|out)(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

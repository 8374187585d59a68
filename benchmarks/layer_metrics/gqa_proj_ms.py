"""Device time per step of grouped-query attention's projections: the ops of
a trace whose HLO instruction carries one of the program's named scopes
``attn_qkv`` (``W_q``, ``W_k``, ``W_v``, the gate's ``W_g``) or ``attn_out``
(``W_o``), forward and backward, in every attention layer. Through the
instruction -> ``op_name`` map that ``mla_proj_ms.scoped_seconds`` reads: a
program without the scopes, or a runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)attn_(?:qkv|out)(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

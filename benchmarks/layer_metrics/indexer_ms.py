"""Device time per step of the learned indexer of sparse attention: the ops
of a trace whose HLO instruction carries the program's named scope
``attn_indexer`` (its three projections, the index scores, the selection that
makes the mask, the target of its loss and the loss with its gradient),
forward and backward, in every layer. Through the instruction -> ``op_name``
map that ``mla_proj_ms.scoped_seconds`` reads: a program without the scope,
or a runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)attn_indexer(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

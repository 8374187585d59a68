"""Device time per step of the index scores alone: the ops of a trace whose HLO
instruction carries the program's named scope ``indexer_scores`` (``sum_j w
relu(q_I . k_I)``, made for the mask and made again with its gradient for the
indexer's loss, whatever implements them: XLA's products or the program's own
``index_scores_fwd`` / ``index_scores_bwd`` kernels), in every layer. Through
the instruction -> ``op_name`` map that ``mla_proj_ms.scoped_seconds`` reads:
a program without the scope, or a runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)indexer_scores(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

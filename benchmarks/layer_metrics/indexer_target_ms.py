"""Device time per step of the target of the indexer's loss: the ops of a
trace whose HLO instruction carries the program's named scope
``indexer_target`` (the head mean of the main attention's probabilities on
the kept keys: a second pass over the scores), in every layer. Through the
instruction -> ``op_name`` map that ``mla_proj_ms.scoped_seconds`` reads: a
program without the scope, or a runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)indexer_target(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

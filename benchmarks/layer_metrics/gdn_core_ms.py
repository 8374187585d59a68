"""Device time per step of the gated delta rule itself: the ops of a trace whose
HLO instruction carries the program's named scope ``gdn_core`` (the two
l2-norms, ``beta``, the log decay, the chunked rule with its solve and its loop
over the chunks, whatever implements them), forward and backward, in every
linear layer. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a
runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)gdn_core(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

"""Device time per step of the linear-attention mixers, all of them: the ops of
a trace whose HLO instruction carries the program's named scope
``linear_attention`` (the projections in and out, the convolution, the gated
delta rule with its norms and decays, the gated norm), forward and backward, in
every linear layer. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a
runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = r"(?:^|/)linear_attention(?:/|$)"


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

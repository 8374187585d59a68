"""Device time per step of the held experts' path: the ops of a trace whose
HLO instruction carries one of the program's named scopes ``moe_route`` (the
f32 router, top-k, the sort, the rung), ``moe_experts`` (the gathers into the
row buffer, the grouped products, the gate) or ``moe_combine`` (the rows back
to their tokens, weighed), forward and backward, kernels included, in every
expert layer. Also the products of a layer past the ladder's first rung:
XLA names what it makes of ``lax.ragged_dot`` ``.../jit(_rung_forward)/
ragged-dot-none``, without the ``moe_experts`` scope the program put around
it (28.9 ms a step of the ten recorded ones, ``tests/data``). Through the
instruction -> ``op_name`` map that ``mla_proj_ms.scoped_seconds`` reads: a
program without the scopes, or a runner without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPES = (r"(?:^|/)moe_(?:route|experts|combine)(?:/|$)"
          r"|/jit\(_rung_(?:forward|backward)\)/ragged-dot")


def compute(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, trace, SCOPES
    )
    return None if found is None else 1e3 * found[0] / found[2]

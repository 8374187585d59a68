"""Device time per step of what grouped-query attention does around its
kernels: the ops of a trace whose HLO instruction carries the program's named
scope ``attn_core`` and whose name is NOT an attention kernel's
(``attn_kernel_ms.KERNELS``) - the two RoPE rules, the output gate, the
transposes into and out of the kernel's layout, the sum XLA makes of the fused
backward's ``dq`` partials, forward and backward, in every attention layer.
With ``gqa_proj_ms`` (``attn_qkv``, ``attn_out``) and ``attn_kernel_ms`` it is
the whole of attention. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a runner
without the map, reports nothing."""

from harness.spec import load_module

UNIT = "ms"
SCOPE = r"(?:^|/)attn_core(?:/|$)"


def compute(record, trace):
    around = load_module("layer_metrics", "swa_kernel_ms").split_trace(trace, False)
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, around, SCOPE
    )
    return None if found is None else 1e3 * found[0] / found[2]

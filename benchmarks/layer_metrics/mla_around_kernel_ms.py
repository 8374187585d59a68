"""Device time per step of what latent attention does around its attention
kernels: the ops of a trace whose HLO instruction carries the program's named
scope ``mla_attention`` and whose name is NOT an attention kernel's
(``attn_kernel_ms.KERNELS``) - the sum XLA makes of the fused backward's
``dq`` partials and whatever slices, pads, scales, concatenates or copies
the kernels' operands and results between the layout the projections write
and the one the kernels read, forward and backward, in every layer and the
prediction module's. With ``mla_proj_ms`` (the scopes ``mla_down``,
``mla_up``, ``mla_out``) and ``attn_kernel_ms`` it is the whole of latent
attention. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a
runner without the map, reports nothing."""

import re
import types

from harness.spec import load_module

UNIT = "ms"
SCOPE = r"(?:^|/)mla_attention(?:/|$)"


def compute(record, trace):
    kernels = load_module("layer_metrics", "attn_kernel_ms").KERNELS

    around = types.SimpleNamespace(  # the trace without its attention kernels
        ops={k: v for k, v in trace.ops.items() if not re.search(kernels, k)},
        main_module=trace.main_module,
    )
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, around, SCOPE
    )
    return None if found is None else 1e3 * found[0] / found[2]

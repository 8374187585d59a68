"""Device time per step of the windowed layers' attention kernels: the ops of
a trace whose name is an attention kernel's (``attn_kernel_ms.KERNELS``) and
whose HLO instruction carries the program's named scope ``sliding_attention``,
forward and backward. Through the instruction -> ``op_name`` map that
``mla_proj_ms.scoped_seconds`` reads: a program without the scope, or a runner
without the map, reports nothing."""

import re
import types

from harness.spec import load_module

UNIT = "ms"
SCOPE = r"(?:^|/)sliding_attention(?:/|$)"


def split_trace(trace, kernels: bool):
    """``trace`` with its attention kernels alone (``attn_kernel_ms.KERNELS``),
    or without them, for ``mla_proj_ms.scoped_seconds``."""
    names = load_module("layer_metrics", "attn_kernel_ms").KERNELS
    return types.SimpleNamespace(
        ops={k: v for k, v in trace.ops.items() if bool(re.search(names, k)) == kernels},
        main_module=trace.main_module,
    )


def per_step_seconds(record, trace):
    found = load_module("layer_metrics", "mla_proj_ms").scoped_seconds(
        record, split_trace(trace, True), SCOPE
    )
    return None if found is None else found[0] / found[2]


def compute(record, trace):
    s = per_step_seconds(record, trace)
    return None if s is None else 1e3 * s

"""``attn_kernel_ms`` reads the attention kernels under either name: on the
recorded v5e trace of the old flash kernels it equals ``flash_attn_ms`` to the
digit, on a made-up trace it finds the splash kernels, and where a trace has
neither it reports nothing and does not raise.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_attn_kernel_ms.py -q
"""

import os
import re

import common
import pytest
from harness import spec
from harness.trace_reduce import reduce_trace

TRACE = os.path.join(common.TESTS, "data", "sc2_3b_d4_b2_t4096_4steps.xplane.pb")


def _read(name, trace):
    return spec.load_module("layer_metrics", name).compute({}, trace)


class _Trace:
    """Ten steps with the given ops: name -> [events, seconds]."""

    def __init__(self, ops):
        self.ops = ops

    def main_module(self):
        return [(i * 0.1, 0.09) for i in range(10)]

    def matching(self, name=None, kind=None):
        hits = [v for k, v in self.ops.items() if re.search(name, k)]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)


def test_equals_flash_attn_ms_on_the_recorded_trace():
    reduced = reduce_trace(TRACE, spans=("make_batch", "train_step"))
    assert _read("attn_kernel_ms", reduced) == _read("flash_attn_ms", reduced)
    assert _read("attn_kernel_ms", reduced) == pytest.approx(40.19, abs=0.01)


def test_reads_the_splash_kernels_and_nothing_else():
    trace = _Trace({
        "splash_mha_fwd_residuals.1": [40, 0.08],
        "splash_mha_dkv_no_residuals.3": [40, 0.17],
        "splash_mqa_fwd_residuals": [10, 0.01],
        "fusion.7": [10, 0.5], "reduce_splash": [10, 0.2], "gmm.3": [240, 0.08],
    })
    assert _read("attn_kernel_ms", trace) == pytest.approx(26.0)
    assert _read("flash_attn_ms", trace) is None  # the old reader falls silent
    assert _read("attn_kernel_ms", _Trace({"fusion.7": [10, 0.5]})) is None

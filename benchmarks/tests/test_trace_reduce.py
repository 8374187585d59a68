"""The reduction from a trace to numbers, pinned on a recorded v5e trace.

``data/sc2_3b_d4_b2_t4096_4steps.xplane.pb`` is four host-loop steps of the
first cell's trainer (my chip run, PR 23), trimmed to what the reduction
reads: the first device's ``XLA Ops`` and ``XLA Modules`` lines and the
runner's three host spans, HLO texts cut short, stats dropped.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_trace_reduce.py -q
"""

import os

import common
import pytest
from harness import spec
from harness.trace_reduce import op_kind, op_name, reduce_trace

TRACE = os.path.join(common.TESTS, "data", "sc2_3b_d4_b2_t4096_4steps.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE, spans=("make_batch", "train_step"))


def test_op_name():
    assert op_name("%fusion.13 = (f32[3072,49152]{1,0}) fusion(") == "fusion.13"
    assert op_name("jit_step(181)") == "jit_step(181)"
    assert op_kind("%fusion.13 = (f32[3072,49152]{1,0:T(8,128)S(1)}, f32[2]{0}) fusion(f32[2]") == "fusion"
    assert op_kind("%psum_invariant.14 = f32[268435456]{0:T(1024)} all-reduce(f32[26") == "all-reduce"
    assert op_kind("jit_step(181)") == ""


def test_busy_and_idle_share(reduced):
    assert reduced.devices == 1
    assert reduced.window_s == pytest.approx(0.98299981, rel=1e-9)
    assert reduced.busy_s == pytest.approx(0.964854162, rel=1e-9)
    idle = sum(g[1] for g in reduced.gaps)
    assert idle == pytest.approx(reduced.window_s - reduced.busy_s, rel=1e-6)


def test_per_op_sums(reduced):
    assert reduced.matching(r"^flash_(attention|mha)") == (
        48, pytest.approx(0.160753, rel=1e-4))
    assert reduced.matching(r"^flash_mha_bwd_dkv")[0] == 16
    assert reduced.ops["fusion.13"] == [4, pytest.approx(0.099711043, rel=1e-9), "fusion"]
    assert reduced.matching(kind=r"^custom-call$")[0] >= 48  # the kernels are custom calls
    top = reduced.breakdown()["device_ops"]
    assert len(top) == 10 and top[0][0] == "fusion.13"
    assert [t[1] for t in top] == sorted((t[1] for t in top), reverse=True)


def test_modules_and_gap_attribution(reduced):
    runs = reduced.main_module()
    assert len(runs) == 4
    assert all(d == pytest.approx(0.2412, abs=1e-4) for _, d in runs)
    # the device waits ~4-5 ms between steps while the host is in train_step
    gaps = reduced.breakdown()["idle_gaps"]
    assert gaps[0][0] == "train_step"
    assert gaps[0][1] == pytest.approx(0.0181456, rel=1e-4)
    longest = sorted(reduced.gaps, key=lambda g: -g[1])[:3]
    assert all(0.004 < g[1] < 0.0055 and g[2] == "train_step" for g in longest)


def test_readers_on_the_recorded_trace(reduced):
    cell = spec.load_cell("sc2_3b_train_b2_t4096")
    record = {"cell": cell, "chips": 1, "peak": {"bf16_flops_per_s": 197e12}}
    read = lambda name: spec.load_module("layer_metrics", name).compute(record, reduced)  # noqa: E731
    assert read("host_gap_ms.train") == pytest.approx(4.628, abs=0.01)
    assert read("flash_attn_ms") == pytest.approx(40.19, abs=0.01)
    assert read("flash_attn_roofline_pct") == pytest.approx(31.25, abs=0.05)
    assert read("device_idle_pct.train") == pytest.approx(1.846, abs=0.001)
    # a reader that finds nothing to read reports nothing
    assert spec.load_module("layer_metrics", "collective_ms").compute(record, reduced) is None

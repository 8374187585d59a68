"""The readers of the program's step spans (``layer_metrics/step_span_ms_p50.py``
and the seven files on it) and of its ``optimizer`` scope
(``optimizer_own_pass_ms.py``), on the recorded v5e trace and on hand-made
records.

``data/sc2_3b_d4_b2_t4096_4steps.xplane.pb`` predates the spans inside
``train_step``: each of its four ``train_step`` events (the runner's, around
the whole call) stands in for a root span here, with children laid inside it.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_step_spans.py -q
"""

import os

import common
import pytest
from harness import spec
from harness.trace_reduce import WINDOW_SPAN, reduce_trace

TRACE = os.path.join(common.TESTS, "data", "sc2_3b_d4_b2_t4096_4steps.xplane.pb")
WINDOW_START = 5000.0  # any reading of perf_counter
PLACE, DISPATCH, SELF = 200e-6, 300e-6, 50e-6  # made up: the trace has no children
FIVE = ["sc2_3b_train_b2_t4096", "sc2_3b_train_b1_t4096", "lfm2_ep8_train_b1_t8192",
        "joyai_ep32_train_b1_t8192", "laguna_xs2_train_b1_t8192"]


def _reader(name):
    return spec.load_module("layer_metrics", name)


def _span(name, t0, dur, span_id, parent_id=0):
    return {"name": name, "ts": 1.7e9 + t0, "t0": t0, "dur": dur, "trace_id": 1,
            "span_id": span_id, "parent_id": parent_id}


def _step_records(t0, dur, number):
    """A root and its three children as ``train_step`` leaves them: children
    finish (and are recorded) before their root."""
    root = 10 * number + 1
    return [
        _span("trainer.step.place", t0 + 1e-6, PLACE - 1e-6, root + 1, root),
        _span("trainer.step.dispatch", t0 + PLACE, DISPATCH, root + 2, root),
        _span("trainer.step.fetch", t0 + PLACE + DISPATCH,
              dur - PLACE - DISPATCH - SELF, root + 3, root),
        {**_span("trainer.step", t0, dur, root), "attrs": {"step": number}},
    ]


@pytest.fixture
def spans(monkeypatch):
    """What ``obs.trace.snapshot()`` returns to the readers, set by the test."""
    from akka_allreduce_tpu.obs import trace

    held = []
    monkeypatch.setattr(trace, "snapshot", lambda: list(held))
    return held


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace(TRACE, spans=("make_batch", "train_step"))


@pytest.fixture(scope="module")
def train_step_events():
    """(start, seconds) of the runner's ``train_step`` spans, from the window's zero."""
    from jax.profiler import ProfileData

    events = [e for plane in ProfileData.from_file(TRACE).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events]
    zero = next(e.start_ns for e in events if e.name == WINDOW_SPAN)
    return [((e.start_ns - zero) / 1e9, e.duration_ns / 1e9)
            for e in events if e.name == "train_step"]


def _record(reduced):
    return {"window": {"start": WINDOW_START, "end": WINDOW_START + reduced.window_s}}


def test_gap_parts_on_the_recorded_trace(reduced, train_step_events, spans, capsys):
    for k, (start, dur) in enumerate(train_step_events):
        spans += _step_records(WINDOW_START + start, dur, k + 1)
    spans.append(_span("worker.reduce", WINDOW_START + 0.1, 0.01, 99))  # another layer's
    record = _record(reduced)
    parts = _reader("step_span_ms_p50").gap_parts(record, reduced)
    runs = reduced.main_module()
    assert len(runs) == 4 and set(parts) == {"around_run", "caller", "place"}
    # the parts are the gap, and what the spans' edges say of each
    gap = _reader("host_gap_ms.train").compute(record, reduced)
    assert sum(parts.values()) == pytest.approx(gap, rel=1e-9)
    ends = [s + d for s, d in train_step_events]
    three = range(3)
    assert parts["caller"] == pytest.approx(
        1e3 * sum(train_step_events[k + 1][0] - ends[k] for k in three) / 3, rel=1e-6)
    assert parts["place"] == pytest.approx(1e3 * PLACE, rel=1e-6)
    assert parts["around_run"] == pytest.approx(1e3 * sum(
        ends[k] - sum(runs[k]) + runs[k + 1][0] - train_step_events[k + 1][0] - PLACE
        for k in three) / 3, rel=1e-6)
    # what ISSUE 37 read off this trace by hand: 3.3-4.0 after the program's
    # end, 0.7-0.9 from train_step's start to the program's, 0.13-0.19 between
    assert 0.1 < parts["caller"] < 0.2 and 3.8 < parts["around_run"] + parts["place"] < 4.9
    for name, value in parts.items():
        assert _reader(f"host_gap_ms.{name}").compute(record, reduced) == value
    # one line of the output says how the check went, once a record
    lines = [l for l in capsys.readouterr().out.splitlines() if '"step_spans"' in l]
    assert len(lines) == 1 and '"paired_soundly": 1.0' in lines[0]


@pytest.mark.parametrize("shift_ms", [1.0, -1.0])
def test_gap_parts_do_not_turn_on_where_the_trace_puts_the_device(
        reduced, train_step_events, spans, shift_ms):
    """The profiler lines the device's events up with the host to about a
    millisecond (PERF.md, PR 37): the parts reported are the same."""
    for k, (start, dur) in enumerate(train_step_events):
        spans += _step_records(WINDOW_START + start, dur, k + 1)
    parts = _reader("step_span_ms_p50").gap_parts(_record(reduced), reduced)

    class Shifted:
        def main_module(self):
            return [(a + shift_ms / 1e3, d) for a, d in reduced.main_module()]

    assert _reader("step_span_ms_p50").gap_parts(_record(reduced), Shifted()) == (
        pytest.approx(parts, rel=1e-9))


def test_gap_parts_report_nothing_where_runs_and_steps_do_not_pair(
        reduced, train_step_events, spans):
    """The first step's spans are lost: every run is paired with the next
    step's, whose durations fit it just as well."""
    later = train_step_events[1:] + [(train_step_events[-1][0] + 0.2459, 0.2455)]
    for k, (start, dur) in enumerate(later):
        spans += _step_records(WINDOW_START + start, dur, k + 2)
    record = {"window": {"start": WINDOW_START, "end": WINDOW_START + 2.0}}
    assert _reader("step_span_ms_p50").gap_parts(record, reduced) is None
    for name in ("around_run", "caller", "place"):
        assert _reader(f"host_gap_ms.{name}").compute(record, reduced) is None


def test_gap_parts_allow_one_step_in_a_hundred_out_of_line(spans):
    class Trace:  # 201 steps of 100 ms, 96 ms of it on the device
        def main_module(self):
            return [(0.1 * k + 0.001, 0.096) for k in range(201)]

    for k in range(201):
        spans += _step_records(WINDOW_START + 0.1 * k, 0.0995, k + 1)
    for at in (7, 90):  # two steps whose dispatch-to-fetch is shorter than their run: 1 %
        spans[4 * at + 2]["dur"] -= 0.005
    record = {"window": {"start": WINDOW_START, "end": WINDOW_START + 20.2}}
    parts = _reader("step_span_ms_p50").gap_parts(record, Trace())
    assert sum(parts.values()) == pytest.approx(4.0, rel=1e-9)
    spans[4 * 150 + 2]["dur"] -= 0.005  # a third: 1.5 %
    again = {"window": record["window"]}  # a record keeps what it worked out
    assert _reader("step_span_ms_p50").gap_parts(again, Trace()) is None


def test_step_spans_keeps_the_windows_whole_steps_in_order(spans):
    spans += _step_records(99.0, 0.1, 1)  # set-up's: before the window
    spans += _step_records(100.3, 0.1, 3) + _step_records(100.1, 0.2, 2)
    spans += _step_records(100.5, 0.1, 4)[1:]  # a child missing
    spans.append({"name": "trainer.step", "ts": 0.0, "dur": 0.1, "trace_id": 1,
                  "span_id": 7, "parent_id": 0})  # a record of before ``t0``
    steps = _reader("step_span_ms_p50").step_spans(
        {"window": {"start": 100.0, "end": 101.0}})
    assert [s["step"] for s in steps] == [
        pytest.approx((0.1, 0.3)), pytest.approx((0.3, 0.4))]
    assert steps[0]["dispatch"] == pytest.approx((0.1 + PLACE, 0.1 + PLACE + DISPATCH))
    assert steps[0]["fetch"][1] == pytest.approx(0.3 - SELF)


def _window_of(spans, durations, fetch_extra=()):
    """Steps back to back with these root durations; ``fetch_extra[k]`` seconds
    of step k's lie inside its fetch (the rest of a longer step outside it)."""
    t = WINDOW_START
    for k, dur in enumerate(durations):
        records = _step_records(t, dur, k + 1)
        extra = dict(fetch_extra).get(k)
        if extra is not None:  # move the fetch's start: the host took the rest
            shift = dur - durations[0] - extra
            records[2]["t0"] += shift
            records[2]["dur"] -= shift
            records[1]["dur"] += shift
        spans += records
        t += dur + 0.001
    return {"window": {"start": WINDOW_START, "end": t}}


def test_quantiles_of_the_root_span(spans):
    record = _window_of(spans, [0.100, 0.102, 0.101, 0.140, 0.103])
    assert _reader("step_span_ms_p50").compute(record, None) == pytest.approx(102.0)
    assert _reader("step_span_ms_p90").compute(record, None) == pytest.approx(125.2)


def test_slow_steps_and_their_excess_by_phase(spans):
    # step 3 waited 40 ms longer for the device, step 6 30 ms longer on the host
    record = _window_of(
        spans, [0.100, 0.100, 0.100, 0.140, 0.100, 0.100, 0.130, 0.100],
        fetch_extra={3: 0.040, 6: 0.0})
    assert _reader("slow_steps").compute(record, None) == 2
    assert _reader("slow_step_excess_ms.fetch").compute(record, None) == pytest.approx(40.0)
    assert _reader("slow_step_excess_ms.host").compute(record, None) == pytest.approx(30.0)


def test_no_slow_step_reads_zero_not_nothing(spans):
    record = _window_of(spans, [0.100, 0.101, 0.102, 0.110])
    assert _reader("slow_steps").compute(record, None) == 0
    assert _reader("slow_step_excess_ms.fetch").compute(record, None) == 0.0
    assert _reader("slow_step_excess_ms.host").compute(record, None) == 0.0


@pytest.mark.parametrize("name", [
    "step_span_ms_p50", "step_span_ms_p90", "host_gap_ms.around_run",
    "host_gap_ms.caller", "host_gap_ms.place", "slow_steps",
    "slow_step_excess_ms.fetch", "slow_step_excess_ms.host", "optimizer_own_pass_ms",
])
def test_a_program_without_the_spans_or_the_scope_reports_nothing(name, reduced, spans):
    """The parent's side of this PR: its records carry no ``t0``, it records no
    ``trainer.step``, its step has no ``optimizer`` scope."""
    spans.append({"name": "line_master.round", "ts": 1.7e9, "dur": 0.02,
                  "trace_id": 1, "span_id": 2, "parent_id": 0})
    record = {**_record(reduced), "window": {
        **_record(reduced)["window"],
        "units": [{"op_scopes": {"fusion.13": "jit(step)/shard_map/Block_0/mlp_up"}}]}}
    assert _reader(name).compute(record, reduced) is None


def test_optimizer_own_pass_ms_reads_the_scope_as_a_whole_path_segment(reduced):
    ops = sorted(reduced.ops, key=lambda k: -reduced.ops[k][1])[:4]
    scopes = {
        ops[0]: "jit(step)/shard_map/optimizer/mul",
        ops[1]: "jit(step)/shard_map/transpose(jvp(Block_0))/mlp_up/dot_general",
        ops[2]: "jit(step)/shard_map/my_optimizer_state/add",
        ops[3]: "optimizer",
    }
    record = {"window": {"units": [{"ok": True}, {"op_scopes": scopes}]}}
    want = 1e3 * (reduced.ops[ops[0]][1] + reduced.ops[ops[3]][1]) / 4
    assert _reader("optimizer_own_pass_ms").compute(record, reduced) == pytest.approx(want)


def test_every_new_metric_is_declared_with_its_cells_and_has_its_file():
    import json

    with open(os.path.join(common.ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        declared = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in ("step_span_ms_p50", "step_span_ms_p90", "host_gap_ms.around_run",
                 "host_gap_ms.caller", "host_gap_ms.place",
                 "slow_steps", "slow_step_excess_ms.fetch", "slow_step_excess_ms.host"):
        assert declared[name]["workloads"] == FIVE and declared[name]["source"] == "program_span"
        assert declared[name]["moves"] == "train_tokens_per_s"
        assert _reader(name).UNIT == declared[name]["unit"]
    own = declared["optimizer_own_pass_ms"]
    assert own["workloads"] == FIVE[3:] and own["source"] == "device_trace"
    assert "step_span_ms_p50" in spec.load_cell("sc2_3b_train_b1_t4096").per_layer
    assert "step_span_ms_p50" not in spec.load_cell("allreduce_256m_mask1").per_layer

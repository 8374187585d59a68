"""Observability layer unit tests: metrics registry, tracing, flight
recorder, stall watchdog, and the MetricsLogger satellites (PR 4).

The end-to-end multi-process assertions (merged Perfetto trace across a
real 2-node cluster, SIGUSR1 kill-with-post-mortem) live in
tests/test_obs_cluster.py; these cover the pillars in isolation.
"""

from __future__ import annotations

import asyncio
import io
import json
import os
import signal

import pytest

from akka_allreduce_tpu.obs import flight, trace
from akka_allreduce_tpu.obs.metrics import REGISTRY, Registry
from akka_allreduce_tpu.obs.watchdog import RoundWatchdog

# --- metrics registry ---------------------------------------------------------


class TestRegistry:
    def test_counter_gauge_roundtrip(self):
        reg = Registry()
        c = reg.counter("x.count")
        c.inc()
        c.inc(3)
        reg.gauge("x.level").set(7.5)
        snap = reg.snapshot()
        assert snap["x.count"] == 4
        assert snap["x.level"] == 7.5
        # get-or-create returns the same object
        assert reg.counter("x.count") is c

    def test_type_collision_rejected(self):
        reg = Registry()
        reg.counter("dual")
        with pytest.raises(TypeError):
            reg.gauge("dual")

    def test_histogram_buckets(self):
        reg = Registry()
        h = reg.histogram("lat", bounds=(0.01, 0.1, 1.0))
        for v in (0.005, 0.05, 0.5, 5.0, 0.5):
            h.observe(v)
        d = reg.snapshot()["lat"]
        assert d["count"] == 5
        assert d["buckets"] == {"le_0.01": 1, "le_0.1": 1, "le_1": 2, "inf": 1}
        assert d["sum"] == pytest.approx(6.055)

    def test_histogram_bad_bounds(self):
        with pytest.raises(ValueError):
            Registry().histogram("bad", bounds=(1.0, 0.5))

    def test_series_is_bounded(self):
        reg = Registry()
        s = reg.series("ev", maxlen=3)
        for i in range(5):
            s.append({"i": i})
        assert [e["i"] for e in s.values] == [0, 1, 2]
        assert s.dropped == 2
        assert reg.snapshot()["ev"] == [{"i": 0}, {"i": 1}, {"i": 2}]

    def test_collectors_merge_into_snapshot(self):
        reg = Registry()
        reg.register_collector(lambda: {"pulled.value": 42})
        assert reg.snapshot()["pulled.value"] == 42

    def test_broken_collector_does_not_kill_snapshot(self):
        reg = Registry()
        reg.counter("ok").inc()

        def boom():
            raise RuntimeError("collector bug")

        reg.register_collector(boom)
        snap = reg.snapshot()
        assert snap["ok"] == 1 and snap["collector_errors"] == 1

    def test_snapshot_is_json_ready(self):
        reg = Registry()
        reg.counter("a").inc()
        reg.histogram("b").observe(0.2)
        reg.series("c").append({"k": 1})
        json.dumps(reg.snapshot())  # must not raise

    def test_global_registry_has_transport_collector(self):
        """remote.py registers a pull-time collector on import: transport
        stage seconds appear in the global snapshot without any transport
        hot-path registry writes."""
        import akka_allreduce_tpu.control.remote  # noqa: F401  (collector side effect)

        snap = REGISTRY.snapshot()
        assert "transport.instances" in snap


# --- tracing ------------------------------------------------------------------


class TestTrace:
    def setup_method(self):
        trace.drain()

    def test_span_records_and_nests(self):
        with trace.span("layer.outer", tag=1) as outer:
            with trace.span("layer.inner"):
                pass
        recs = trace.drain()
        names = {r["name"]: r for r in recs}
        assert set(names) == {"layer.outer", "layer.inner"}
        inner, out = names["layer.inner"], names["layer.outer"]
        assert inner["trace_id"] == out["trace_id"]
        assert inner["parent_id"] == out["span_id"]
        assert out["attrs"] == {"tag": 1}
        assert out["dur"] >= 0

    def test_context_propagates_and_resets(self):
        assert trace.current() is None
        ctx = trace.new_context()
        with trace.use(ctx):
            assert trace.current() == ctx
            s = trace.start_span("x.child")
            assert s.trace_id == ctx.trace_id and s.parent_id == ctx.span_id
            s.end()
        assert trace.current() is None

    def test_root_span_ignores_ambient_context(self):
        with trace.span("a.ambient"):
            s = trace.start_span("b.root", root=True)
            assert s.trace_id != trace.current().trace_id
            s.end()
        trace.drain()

    def test_unsampled_spans_are_not_recorded(self):
        ctx = trace.TraceContext(1, 2, sampled=False)
        with trace.use(ctx):
            with trace.span("x.skipped"):
                pass
        assert trace.drain() == []

    def test_disable_enable(self):
        trace.set_enabled(False)
        try:
            with trace.span("x.off"):
                pass
            assert trace.drain() == []
        finally:
            trace.set_enabled(True)

    def test_chrome_export_shape(self, tmp_path):
        with trace.span("worker.step", round=3):
            pass
        path = trace.write_chrome_trace(str(tmp_path / "t.json"))
        doc = json.loads(open(path).read())
        (ev,) = [e for e in doc["traceEvents"] if e["name"] == "worker.step"]
        assert ev["ph"] == "X" and ev["cat"] == "worker"
        assert ev["pid"] == os.getpid()
        assert ev["args"]["round"] == 3
        assert len(ev["args"]["trace_id"]) == 16  # hex u64
        # the buffer was drained by the export
        assert trace.snapshot() == []

    def test_merge_chrome_traces(self, tmp_path):
        with trace.span("a.one"):
            pass
        p1 = trace.write_chrome_trace(str(tmp_path / "1.json"))
        with trace.span("b.two"):
            pass
        p2 = trace.write_chrome_trace(str(tmp_path / "2.json"))
        merged = trace.merge_chrome_traces([p1, p2], str(tmp_path / "m.json"))
        doc = json.loads(open(merged).read())
        assert {e["name"] for e in doc["traceEvents"]} == {"a.one", "b.two"}

    def test_record_holds_its_start_on_perf_counter(self):
        """``t0`` is the span's start on the clock a caller's own timings (and
        the benchmark's window) run on; ``ts`` stays the wall clock."""
        import time

        before = time.perf_counter()
        with trace.span("layer.timed"):
            inside = time.perf_counter()
        after = time.perf_counter()
        (rec,) = trace.drain()
        assert before <= rec["t0"] <= inside
        assert rec["t0"] + rec["dur"] <= after
        assert abs(rec["ts"] - time.time()) < 60  # epoch seconds, not perf_counter

    def test_span_keeps_its_duration_sampled_or_not(self):
        with trace.span("layer.kept") as s:
            assert s.dur is None
        assert s.dur == trace.drain()[0]["dur"]
        with trace.use(trace.TraceContext(1, 2, sampled=False)):
            with trace.span("x.skipped") as s:
                pass
        assert s.dur >= 0 and trace.drain() == []


class _Notes:
    """An annotator that keeps what was entered and left."""

    def __init__(self):
        self.log = []

    def __call__(self, name):
        notes = self

        class _Note:
            def __enter__(self):
                notes.log.append(("enter", name))

            def __exit__(self, *exc):
                notes.log.append(("exit", name))

        return _Note()


class TestAnnotator:
    """``set_annotator``: a recorded ``span()`` also runs inside
    ``factory(name)`` (the trainers hand in the profiler's annotation)."""

    def setup_method(self):
        trace.drain()
        self.before = trace._annotator

    def teardown_method(self):
        trace.set_annotator(self.before)

    def test_span_enters_and_leaves_it_once(self):
        notes = _Notes()
        trace.set_annotator(notes)
        with trace.span("layer.outer"):
            assert notes.log == [("enter", "layer.outer")]
            with trace.span("layer.inner"):
                pass
        assert notes.log == [
            ("enter", "layer.outer"), ("enter", "layer.inner"),
            ("exit", "layer.inner"), ("exit", "layer.outer"),
        ]
        assert [r["name"] for r in trace.drain()] == ["layer.inner", "layer.outer"]

    def test_it_is_left_when_the_body_raises(self):
        notes = _Notes()
        trace.set_annotator(notes)
        with pytest.raises(KeyError):
            with trace.span("layer.fails"):
                raise KeyError("x")
        assert notes.log == [("enter", "layer.fails"), ("exit", "layer.fails")]
        assert trace.current() is None

    def test_unrecorded_spans_are_not_annotated(self):
        notes = _Notes()
        trace.set_annotator(notes)
        trace.set_enabled(False)
        try:
            with trace.span("x.off"):
                pass
        finally:
            trace.set_enabled(True)
        with trace.use(trace.TraceContext(1, 2, sampled=False)):
            with trace.span("x.unsampled"):
                pass
        s = trace.start_span("x.manual")  # start_span has no with-body to wrap
        s.end()
        assert notes.log == []

    def test_with_none_set_nothing_changes(self):
        trace.set_annotator(None)
        with trace.span("layer.plain", tag=1):
            pass
        (rec,) = trace.drain()
        assert set(rec) == {
            "name", "ts", "t0", "dur", "trace_id", "span_id", "parent_id", "attrs",
        }

    def test_a_step_worth_of_spans_costs_microseconds(self):
        """A loose guard (the measurement is the chip's A/B, CHANGES.md PR 37):
        one root and three children, as ``train_step`` opens them, under the
        profiler's annotation where jax is there, 1,000 times."""
        import statistics
        import time

        try:
            import jax

            trace.set_annotator(jax.profiler.TraceAnnotation)
        except ImportError:
            trace.set_annotator(None)
        costs = []
        for i in range(1000):
            t = time.perf_counter()
            with trace.span("trainer.step", root=True, step=i):
                with trace.span("trainer.step.place"):
                    pass
                with trace.span("trainer.step.dispatch"):
                    pass
                with trace.span("trainer.step.fetch"):
                    pass
            costs.append(time.perf_counter() - t)
        assert len(trace.drain()) == 4000
        assert statistics.median(costs) < 200e-6, statistics.median(costs)


# --- flight recorder ----------------------------------------------------------


def _read_dump(path):
    return [json.loads(l) for l in open(path).read().splitlines() if l.strip()]


class TestFlightRecorder:
    def setup_method(self):
        flight.clear()

    def test_dump_format(self, tmp_path):
        flight.note("something", round=9)
        flight.set_state("worker.round_in_flight", 9)
        flight.set_state("transport.last_stage", "decode")
        REGISTRY.counter("worker.rounds_completed")  # ensure key exists
        path = flight.dump(str(tmp_path / "f.jsonl"), reason="unit")
        recs = _read_dump(path)
        assert recs[0]["kind"] == "flight_header"
        assert recs[0]["reason"] == "unit" and recs[0]["pid"] == os.getpid()
        state = recs[1]
        assert state["kind"] == "state"
        assert state["worker.round_in_flight"] == 9
        assert state["transport.last_stage"] == "decode"
        metrics = recs[2]
        assert metrics["kind"] == "metrics"
        assert "worker.rounds_completed" in metrics
        assert any(
            r["kind"] == "event" and r["event"] == "something" for r in recs[3:]
        )

    def test_ring_is_bounded(self):
        for i in range(flight._RING_MAX + 100):
            flight.note("e", i=i)
        evs = flight.events()
        assert len(evs) == flight._RING_MAX
        assert evs[0]["i"] == 100  # oldest were evicted

    def test_spans_land_in_ring(self):
        with trace.span("x.spanned"):
            pass
        assert any(
            e["kind"] == "span" and e["name"] == "x.spanned"
            for e in flight.events()
        )
        trace.drain()

    def test_sigusr1_dump_without_exit(self, tmp_path):
        """The dump trigger (non-fatal mode): SIGUSR1 writes a parseable
        dump and the process keeps running."""
        flight.note("pre_signal")
        flight.install(str(tmp_path), signal_exit=False)
        try:
            os.kill(os.getpid(), signal.SIGUSR1)
            dumps = [f for f in os.listdir(tmp_path) if "sigusr1" in f]
            assert len(dumps) == 1
            recs = _read_dump(tmp_path / dumps[0])
            assert recs[0]["reason"] == "sigusr1"
            assert any(
                r.get("event") == "pre_signal" for r in recs
            )
        finally:
            flight.uninstall()

    def test_excepthook_dumps_on_crash(self, tmp_path):
        import sys

        flight.install(str(tmp_path))
        try:
            try:
                raise RuntimeError("boom")
            except RuntimeError:
                sys.excepthook(*sys.exc_info())
            dumps = [f for f in os.listdir(tmp_path) if "crash" in f]
            assert len(dumps) == 1
            recs = _read_dump(tmp_path / dumps[0])
            assert any(
                r.get("event") == "unhandled_exception"
                and r.get("type") == "RuntimeError"
                for r in recs
            )
        finally:
            flight.uninstall()


# --- stall watchdog -----------------------------------------------------------


class TestRoundWatchdog:
    def setup_method(self):
        flight.clear()

    def test_deadline_and_latch(self, tmp_path):
        now = {"t": 0.0}
        stalls = []
        flight.install(str(tmp_path))
        try:
            wd = RoundWatchdog(
                5.0,
                clock=lambda: now["t"],
                on_stall=lambda l, r, age: stalls.append((l, r)),
            )
            wd.round_started(0, 41)
            assert wd.check() == []
            now["t"] = 5.1
            assert [(l, r) for l, r, _ in wd.check()] == [(0, 41)]
            assert stalls == [(0, 41)]
            # latched: the same stalled round is reported once, not per poll
            now["t"] = 50.0
            assert wd.check() == []
            # ...and the dump it wrote names the round
            recs = _read_dump(wd.last_dump_path)
            assert recs[1]["watchdog.stalled_round"] == 41
            assert "stall-round41" in wd.last_dump_path
        finally:
            flight.uninstall()

    def test_completion_retires_older_rounds(self):
        now = {"t": 0.0}
        wd = RoundWatchdog(1.0, clock=lambda: now["t"], dump=False)
        wd.round_started(0, 1)
        wd.round_started(0, 2)
        wd.round_started(1, 1)
        wd.round_completed(0, 2)  # retires line 0 rounds 1 AND 2
        now["t"] = 10.0
        assert [(l, r) for l, r, _ in wd.check()] == [(1, 1)]

    def test_async_poll_task_trips_watchdog(self, tmp_path):
        """The self-driven mode: the watchdog's own observed_task poll loop
        notices an injected round delay and dumps."""
        flight.install(str(tmp_path))

        async def run():
            wd = RoundWatchdog(0.05, poll_interval_s=0.02)
            wd.start()
            try:
                flight.set_state("transport.last_stage", "handler")
                wd.round_started(0, 7)  # ...and never completed: the delay
                await asyncio.sleep(0.3)
            finally:
                wd.stop()
            assert wd.stalls.value >= 1
            assert wd.last_dump_path is not None
            recs = _read_dump(wd.last_dump_path)
            assert recs[1]["watchdog.stalled_round"] == 7
            assert recs[1]["transport.last_stage"] == "handler"

        try:
            asyncio.run(run())
        finally:
            flight.uninstall()

    def test_rejects_nonpositive_deadline(self):
        with pytest.raises(ValueError):
            RoundWatchdog(0.0)

    def test_reorganization_retires_deadlines_and_abandons_spans(self):
        """A grid re-mesh abandons the replaced lines' in-flight rounds by
        design: the watchdog must NOT read them as stalls, and their open
        root spans must land in the trace buffer marked abandoned instead
        of vanishing with the GC'd line masters."""
        from akka_allreduce_tpu.config import (
            LineMasterConfig,
            MasterConfig,
            ThresholdConfig,
        )
        from akka_allreduce_tpu.control.grid_master import GridMaster
        from akka_allreduce_tpu.protocol import ConfirmPreparation

        trace.drain()
        now = {"t": 0.0}
        wd = RoundWatchdog(5.0, clock=lambda: now["t"], dump=False)
        gm = GridMaster(
            ThresholdConfig(),
            MasterConfig(node_num=2),
            LineMasterConfig(round_window=1, max_rounds=-1),
            on_round_start=wd.round_started,
            on_reorganize=wd.reset,
        )
        gm.member_up(0)
        gm.member_up(1)
        # confirm both workers: round 0 starts, deadline armed
        gm.handle(ConfirmPreparation(gm.config_id, 0))
        out = gm.handle(ConfirmPreparation(gm.config_id, 1))
        assert any(
            type(e.msg).__name__ == "StartAllreduce" for e in out
        )
        assert wd._inflight, "round 0's deadline should be armed"
        # re-mesh while round 0 is in flight
        gm.member_unreachable(1)
        now["t"] = 100.0
        stale = [s for s in wd.check() if s[1] == 0 and s[0] == 0]
        # the abandoned round must not fire as a stall...
        assert not stale, stale
        # ...and its root span was recorded, flagged abandoned
        recs = [
            r for r in trace.drain() if r["name"] == "line_master.round"
        ]
        assert any(
            r.get("attrs", {}).get("abandoned")
            and r["attrs"].get("reorganized")
            for r in recs
        ), recs


# --- MetricsLogger satellites (utils/metrics.py) ------------------------------


class TestMetricsLogger:
    def test_close_flushes_non_owned_stream(self, tmp_path):
        """A caller-owned buffered stream must be FLUSHED by close() (its
        writes would otherwise sit in the buffer), but not closed — its
        lifetime belongs to the caller."""
        from akka_allreduce_tpu.utils.metrics import MetricsLogger

        path = tmp_path / "m.jsonl"
        stream = open(path, "w", buffering=1 << 20)  # big buffer: no autoflush
        logger = MetricsLogger(stream)
        logger.log_event(kind="probe", v=1)
        assert path.read_text() == ""  # still buffered
        logger.close()
        assert not stream.closed, "close() must not close a caller's stream"
        assert json.loads(path.read_text().splitlines()[0])["v"] == 1
        stream.close()

    def test_dump_works_after_close_for_stringio(self):
        from akka_allreduce_tpu.utils.metrics import MetricsLogger

        logger = MetricsLogger()  # in-memory StringIO sink
        logger.log_event(kind="probe", v=2)
        logger.close()
        # even if the underlying StringIO is closed afterwards, the
        # contents stay readable
        logger._stream.close()
        recs = [json.loads(l) for l in logger.dump().splitlines()]
        assert recs[0]["v"] == 2

    def test_close_tolerates_already_closed_stream(self):
        from akka_allreduce_tpu.utils.metrics import MetricsLogger

        sio = io.StringIO()
        logger = MetricsLogger(sio)
        logger.log_event(kind="probe")
        sio.close()
        logger.close()  # must not raise

    def test_log_snapshot(self):
        from akka_allreduce_tpu.utils.metrics import MetricsLogger

        reg = Registry()
        reg.counter("c").inc(5)
        logger = MetricsLogger()
        logger.log_snapshot(reg, role="test")
        rec = json.loads(logger.dump().splitlines()[0])
        assert rec["kind"] == "metrics_snapshot"
        assert rec["role"] == "test"
        assert rec["metrics"]["c"] == 5

"""Closed-loop adaptive degradation (RESILIENCE.md "Tier 5", ISSUE 8):

- the AdaptiveController's ladder, hysteresis (distinct degrade/restore
  thresholds + dwell — a noisy tail cannot flap the mode), latency-
  baseline evidence, churn-blocks-restore rule, and DETERMINISM: the same
  evidence sequence replays a byte-identical decision log;
- the RoundPolicy plumbing: LineMaster freezes the policy per round at
  start, ``restart_stalled`` re-sends the round's ORIGINAL policy (never
  the controller's current one — regression pin alongside the PR-5
  idempotent re-Start pins), re-sent Prepares carry the prepare-time
  stamp, and the grid propagates the level into re-organized lines;
- the worker side: a policy-stamped Start lowers the round's reduce
  trigger (including retroactively, when peers ran ahead — the once-only
  edge), payload envelopes ride the round's wire mode, and the int8 EF
  loop carries exactly the residual the wire injected (the
  ``ring_ef_residual`` identity with v=1);
- the int8 wire mode's error accounting mirrors f16's, both exported to
  the obs registry (``wire.f16_clipped`` / ``wire.int8_*``);
- a real-subprocess ``chaos-adapt`` drill at reduced budgets: the
  controller degrades within K rounds of a seeded staged straggler,
  holds without oscillation, restores after heal, and reduced values
  stay within the EF error budget.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from akka_allreduce_tpu.config import (
    AdaptConfig,
    MetaDataConfig,
    ThresholdConfig,
    WorkerConfig,
)
from akka_allreduce_tpu.control import wire
from akka_allreduce_tpu.control.adapt import AdaptiveController
from akka_allreduce_tpu.control.line_master import LineMaster
from akka_allreduce_tpu.control.worker import AllreduceWorker
from akka_allreduce_tpu.obs import metrics as obs_metrics
from akka_allreduce_tpu.protocol import (
    DEFAULT_POLICY,
    AllReduceInput,
    CompleteAllreduce,
    ConfirmPreparation,
    PrepareAllreduce,
    ReduceBlock,
    RoundPolicy,
    ScatterBlock,
    StartAllreduce,
)

# --- the controller -----------------------------------------------------------


def make_ctl(**over):
    cfg = dict(
        enabled=True, levels=2, floor_th_reduce=0.5, window=4,
        lag_degrade=6, lag_restore=2, min_dwell=8, slow_factor=5.0,
    )
    cfg.update(over)
    return AdaptiveController(AdaptConfig(**cfg), ThresholdConfig(1.0, 1.0, 1.0))


def drive(ctl, rounds, lags, counters=None, latency=None, start=0):
    """Feed ``rounds`` identical evidence ticks; return transitions seen."""
    out = []
    for r in range(start, start + rounds):
        pol = ctl.observe_round(r, dict(lags), dict(counters or {}), latency)
        if pol is not None:
            out.append(pol)
    return out


def test_ladder_policies():
    ctl = make_ctl()
    assert ctl.policy_for_level(0) is DEFAULT_POLICY
    assert ctl.policy_for_level(1) == RoundPolicy(0.75, "f16")
    assert ctl.policy_for_level(2) == RoundPolicy(0.5, "int8")
    # floor respected when configured th is already low
    low = AdaptiveController(
        AdaptConfig(enabled=True, floor_th_reduce=0.6),
        ThresholdConfig(th_reduce=0.66),
    )
    assert low.policy_for_level(2).th_reduce == pytest.approx(0.6)


def test_degrade_needs_sustained_lag_and_dwell_gates_the_next_step():
    ctl = make_ctl()
    # healthy evidence: no transition ever
    assert drive(ctl, 12, {1: 0, 2: 1}) == []
    # lag above the degrade bar: transition at the next window boundary...
    pols = drive(ctl, 4, {1: 7}, start=12)
    assert pols == [RoundPolicy(0.75, "f16")] and ctl.level == 1
    # ...but the SECOND step waits for the dwell (8 rounds), not just the
    # next window (4): one more window of pressure does nothing
    assert drive(ctl, 4, {1: 7}, start=16) == []
    pols = drive(ctl, 4, {1: 7}, start=20)
    assert pols == [RoundPolicy(0.5, "int8")] and ctl.level == 2


def test_restore_hysteresis_is_distinct_and_dwelled():
    ctl = make_ctl()
    # sustained lag walks the ladder down, one dwell apart (rounds 7, 15)
    drive(ctl, 20, {1: 7})
    assert ctl.level == 2
    # lag back under degrade but ABOVE the restore bar: hold forever
    assert drive(ctl, 16, {1: 4}, start=20) == []
    assert ctl.level == 2
    # fully recovered: walks back one level per dwell, down to 0
    pols = drive(ctl, 24, {1: 0}, start=36)
    assert [p.wire for p in pols] == ["f16", ""]
    assert ctl.level == 0 and pols[-1] is DEFAULT_POLICY
    assert ctl.transitions == 4


def test_reorganization_in_window_blocks_restore():
    ctl = make_ctl()
    drive(ctl, 8, {1: 7})  # first dwell-satisfying window degrades
    assert ctl.level == 1
    # quiet lag but membership churn (reorgs counter moved): never restore
    # on churn evidence — an expelled straggler re-joining reads as healed
    # for a moment
    for w in range(6):
        assert drive(ctl, 4, {1: 0}, {"reorgs": w + 1}, start=8 + 4 * w) == []
    assert ctl.level == 1
    # churn stops: the restore goes through
    assert drive(ctl, 4, {1: 0}, {"reorgs": 6}, start=32) != []
    assert ctl.level == 0


def test_latency_evidence_degrades_the_full_tail():
    """th=1.0's straggler produces NO lag (no round completes without it):
    the window-mean-vs-baseline signal is what catches it."""
    ctl = make_ctl(min_dwell=4)
    # first quiet window learns the baseline
    drive(ctl, 4, {1: 0}, latency=0.02)
    assert ctl.baseline_latency_s == pytest.approx(0.02)
    # 5x-the-baseline windows degrade (twice, through the dwell)
    pols = drive(ctl, 8, {1: 0}, latency=0.5, start=4)
    assert [p.wire for p in pols] == ["f16", "int8"]
    # baseline is FROZEN: degraded-era latencies do not drag it down
    assert ctl.baseline_latency_s == pytest.approx(0.02)


def test_restart_counter_delta_is_degrade_pressure():
    ctl = make_ctl()
    assert drive(ctl, 8, {1: 0}, {"restarts": 0}) == []  # quiet baseline
    # the cumulative counter MOVES inside a dwelt window: degrade
    assert drive(ctl, 4, {1: 0}, {"restarts": 2}, start=8) != []
    assert ctl.level == 1
    assert ctl.decisions[-1]["why"] == ["restarts"]
    # an UNCHANGED cumulative counter is not pressure (deltas, not levels)
    assert drive(ctl, 16, {1: 0}, {"restarts": 2}, start=12) != []  # restores
    assert ctl.level == 0


def test_noise_counter_deltas_are_degrade_pressure_with_hysteresis():
    """Reconnects+drops window deltas are pressure at ``noise_degrade``
    and block restores until they fall below HALF of it — retried loss
    that never forces a re-Start still drives the loop."""
    ctl = make_ctl(noise_degrade=8)
    assert drive(ctl, 8, {1: 0}, {"drops": 0}) == []  # quiet baseline
    # 5 drops + 3 reconnects land in one dwelt window: degrade
    assert drive(
        ctl, 4, {1: 0}, {"drops": 5, "reconnects": 3}, start=8
    ) != []
    assert ctl.level == 1
    assert ctl.decisions[-1]["why"] == ["noise"]
    # loss eases but stays AT the restore bar (4*2 == 8): no restore
    for w in range(6):
        assert (
            drive(
                ctl, 4, {1: 0},
                {"drops": 9 + 4 * w, "reconnects": 3},
                start=12 + 4 * w,
            )
            == []
        )
    assert ctl.level == 1
    # below half the degrade bar (delta 3): the restore goes through
    assert (
        drive(ctl, 4, {1: 0}, {"drops": 32, "reconnects": 3}, start=36)
        != []
    )
    assert ctl.level == 0
    # noise_degrade=0 disables the arm entirely
    ctl2 = make_ctl(noise_degrade=0)
    assert drive(ctl2, 16, {1: 0}, {"drops": 10 ** 6}) == []
    assert ctl2.level == 0


def test_bandwidth_imbalance_is_degrade_pressure_with_its_own_bar():
    """PR-9's per-endpoint bandwidth gauges as a straggler-evidence arm
    (ROADMAP item 4's follow-on): an endpoint whose per-window byte delta
    falls below ``bw_degrade_ratio`` of the MEDIAN endpoint's reads as
    pressure; restores need the ratio back above DOUBLE the bar."""

    def bw_drive(ctl, rounds, bw, start=0):
        out = []
        for r in range(start, start + rounds):
            pol = ctl.observe_round(r, {1: 0}, {}, None, bandwidth=dict(bw))
            if pol is not None:
                out.append(pol)
        return out

    ctl = make_ctl(bw_degrade_ratio=0.25, min_dwell=4)
    # balanced window: everyone moved ~1MB since the zero watermark
    base = {"a:1": 1e6, "b:1": 1.1e6, "c:1": 0.9e6}
    assert bw_drive(ctl, 4, base) == []
    assert ctl.level == 0
    # endpoint a crawls: +10KB vs the median's +1MB (ratio 0.01 < 0.25)
    skewed = {"a:1": 1.01e6, "b:1": 2.1e6, "c:1": 1.9e6}
    assert bw_drive(ctl, 4, skewed, start=4) != []
    assert ctl.level == 1
    assert ctl.decisions[-1]["why"] == ["bandwidth"]
    # recovery to 0.3x the median: above the degrade bar but below the
    # restore bar (2 x 0.25 = 0.5) — the hysteresis gap holds the level
    partial = {"a:1": 1.31e6, "b:1": 3.1e6, "c:1": 2.9e6}
    assert bw_drive(ctl, 4, partial, start=8) == []
    assert ctl.level == 1
    # fully balanced again (ratio 1.0 >= 0.5): restore goes through
    healed = {"a:1": 2.31e6, "b:1": 4.1e6, "c:1": 3.9e6}
    assert bw_drive(ctl, 4, healed, start=12) != []
    assert ctl.level == 0
    # thin evidence is inert: two endpoints have no median to stand
    # against, and a quiet (zero-delta) window indicts nobody
    ctl2 = make_ctl(bw_degrade_ratio=0.25)
    assert bw_drive(ctl2, 8, {"a:1": 1e6, "b:1": 100.0}) == []
    assert ctl2.level == 0
    ctl2b = make_ctl(bw_degrade_ratio=0.25)
    assert bw_drive(ctl2b, 4, base) == []
    # identical snapshot again: every delta 0, median 0 -> arm inert
    assert bw_drive(ctl2b, 4, base, start=4) == []
    assert ctl2b.level == 0
    # the default (0) disables the arm entirely
    ctl3 = make_ctl()
    assert bw_drive(ctl3, 8, skewed) == []
    assert ctl3.level == 0
    # the watermark rides the failover digest like the counter watermarks
    d = ctl.digest()
    assert d["bw"] == {k: float(v) for k, v in healed.items()}
    ctl4 = make_ctl(bw_degrade_ratio=0.25)
    ctl4.restore(d)
    assert ctl4._last_bw == d["bw"]


def test_decision_log_is_deterministic():
    """Same evidence sequence => byte-identical decision log (the chaos
    event log's determinism contract applied to decisions)."""

    def run():
        ctl = make_ctl()
        script = (
            [({1: 7}, {})] * 12 + [({1: 0}, {})] * 24 + [({2: 9}, {})] * 8
        )
        for r, (lags, counters) in enumerate(script):
            ctl.observe_round(r, lags, counters, latency_s=None)
        return ctl.decision_log_jsonl()

    a, b = run(), run()
    assert a == b and a  # non-empty and byte-identical
    for line in a.splitlines():
        rec = json.loads(line)
        assert "t" not in rec  # logical fields only, no timestamps


def test_digest_restore_inherits_level_dwell_and_baseline():
    ctl = make_ctl()
    drive(ctl, 4, {1: 0}, latency=0.02)  # learn baseline
    drive(ctl, 4, {1: 7}, {"reconnects": 3}, start=4)
    assert ctl.level == 1
    heir = make_ctl()
    heir.restore(ctl.digest())
    assert heir.level == 1 and heir.policy() == RoundPolicy(0.75, "f16")
    assert heir.baseline_latency_s == pytest.approx(ctl.baseline_latency_s)
    assert heir._rounds_at_level == ctl._rounds_at_level
    # counter watermarks carried: the first post-takeover window does not
    # read the whole run's cumulative counters as one spike
    assert heir._last_counters == ctl._last_counters
    assert drive(heir, 4, {1: 0}, {"reconnects": 3}, start=8) == []  # dwell


# --- LineMaster / policy stamping ---------------------------------------------


def make_line(th=1.0, window=2, n=4):
    clock = {"t": 0.0}
    lm = LineMaster(
        ThresholdConfig(th, th, th),
        __import__("akka_allreduce_tpu.config", fromlist=["LineMasterConfig"])
        .LineMasterConfig(round_window=window),
        clock=lambda: clock["t"],
    )
    lm.prepare((0, 1, 2, 3)[:n], config_id=1, from_round=0)
    for w in range(n):
        lm.handle(ConfirmPreparation(1, w))
    return lm, clock


def test_fill_window_stamps_current_policy_and_span():
    lm, _ = make_line()
    pol = RoundPolicy(0.75, "f16")
    lm.policy = pol
    out = lm.handle(CompleteAllreduce(0, 0))  # no-op round: just poke
    starts = [
        e.msg for e in lm._fill_window() if isinstance(e.msg, StartAllreduce)
    ]
    # window already full from prepare; complete round 0 to refill
    for w in range(4):
        out = lm.handle(CompleteAllreduce(w, 0))
    starts = [e.msg for e in out if isinstance(e.msg, StartAllreduce)]
    assert starts and all(s.policy == pol for s in starts)


def test_restart_stalled_carries_the_rounds_original_policy():
    """Regression pin (ISSUE 8 satellite, alongside the PR-5 idempotent
    re-Start pins): a re-issued Start must agree with the buffers workers
    already reduced under the round's first Start — the ORIGINAL stamp,
    not the controller's current level."""
    lm, clock = make_line()
    original = RoundPolicy(0.75, "f16")
    lm.policy = original
    for w in range(4):
        out = lm.handle(CompleteAllreduce(w, 0))  # rounds 0,1 open; starts 2
    started = [e.msg for e in out if isinstance(e.msg, StartAllreduce)]
    assert started and all(s.policy == original for s in started)
    # the controller degrades further AFTER round 2 started
    lm.policy = RoundPolicy(0.5, "int8")
    clock["t"] += 10.0
    restarts = [
        e.msg for e in lm.restart_stalled(0.5)
        if isinstance(e.msg, StartAllreduce)
    ]
    assert restarts, "stalled rounds must re-Start"
    by_round = {s.round_num: s.policy for s in restarts}
    # round 2 started under `original` — its re-Start must carry exactly
    # that, and a round started under the DEFAULT (round 1, from the
    # prepare-time fill) must NOT inherit the current level either
    assert by_round[started[0].round_num] == original
    assert all(
        pol in (original, DEFAULT_POLICY) for pol in by_round.values()
    )
    # a round started AFTER the change carries the new stamp
    for w in range(4):
        out = lm.handle(CompleteAllreduce(w, started[0].round_num))
    newer = [e.msg for e in out if isinstance(e.msg, StartAllreduce)]
    assert newer and all(s.policy == RoundPolicy(0.5, "int8") for s in newer)


def test_reprepare_carries_the_prepare_time_stamp():
    lm, clock = make_line()
    pol = RoundPolicy(0.75, "f16")
    lm.policy = pol
    lm.prepare((0, 1), config_id=2, from_round=5)
    lm.policy = RoundPolicy(0.5, "int8")  # degraded AFTER the handshake began
    clock["t"] += 10.0
    reprep = [e.msg for e in lm.reprepare_pending(0.5)]
    assert reprep and all(p.policy == pol for p in reprep)


def test_worker_lags_track_late_assertions():
    lm, _ = make_line()
    # rounds 0 and 1 complete via workers 0..2 only; 3 is silent
    for r in (0, 1):
        for w in (0, 1, 2):
            lm.handle(CompleteAllreduce(w, r))
    assert lm.completed_up_to == -1  # th=1.0: nothing completes without 3
    lm.handle(CompleteAllreduce(3, 0))
    lm.handle(CompleteAllreduce(3, 1))
    assert lm.completed_up_to == 1
    lags = lm.worker_lags()
    assert lags[3] == 0 and lags[0] == 0
    # a chronically-late worker: the others finish rounds 2,3 at th<1 —
    # use a 0.75-threshold line so rounds retire without worker 3
    lm2, _ = make_line(th=0.75)
    for r in range(2):
        for w in (0, 1, 2):
            lm2.handle(CompleteAllreduce(w, r))
    assert lm2.completed_up_to == 1
    assert lm2.worker_lags()[3] == 2
    # its STALE assertion for round 0 still moves the watermark
    lm2.handle(CompleteAllreduce(3, 0))
    assert lm2.worker_lags()[3] == 1


def test_mode_rounds_counter_accounts_completed_rounds():
    ctr = obs_metrics.counter("adapt.mode_rounds.f16")
    before = ctr.value
    lm, _ = make_line(th=0.75)
    lm.policy = RoundPolicy(0.75, "f16")
    for w in range(4):
        lm.handle(CompleteAllreduce(w, 0))  # round 0 under the default
    for w in range(4):
        lm.handle(CompleteAllreduce(w, 2))  # round 2 started under f16
    assert ctr.value == before + 1


# --- worker-side policy application -------------------------------------------


def make_worker(data, sink, th=ThresholdConfig(), chunk=8):
    w = AllreduceWorker(
        data_source=lambda req: AllReduceInput(data),
        data_sink=sink.append,
        config=WorkerConfig(),
    )
    w.configure(MetaDataConfig(data_size=len(data), max_chunk_size=chunk), th)
    return w


def test_policy_lowers_reduce_trigger_for_the_round():
    """th_reduce=1.0 configured; the round's policy says 0.5 — the chunk
    reduces after 2 of 4 contributions (our own + one peer)."""
    data = np.ones(32, np.float32)
    w = make_worker(data, [])
    w.handle(PrepareAllreduce(1, (0, 1, 2, 3), worker_id=1, round_num=0))
    w.handle(StartAllreduce(0, policy=RoundPolicy(th_reduce=0.5)))
    out = w.handle(ScatterBlock(np.full(8, 3.0, np.float32), 0, 1, 0, 0))
    reduces = [e for e in out if isinstance(e.msg, ReduceBlock)]
    assert len(reduces) == 3  # 2 contributions (self + peer 0) sufficed
    assert all(e.msg.count == 2 for e in reduces)


def test_policy_applies_retroactively_to_run_ahead_peers():
    """Peers ran ahead: 2 contributions landed BEFORE our Start carried
    the lowered threshold — the Start fires the pending reduce exactly
    once (the set_reduce_trigger edge)."""
    data = np.ones(32, np.float32)
    w = make_worker(data, [])
    w.handle(PrepareAllreduce(1, (0, 1, 2, 3), worker_id=1, round_num=0))
    for src in (0, 2):
        out = w.handle(ScatterBlock(np.full(8, 2.0, np.float32), src, 1, 0, 0))
        assert not [e for e in out if isinstance(e.msg, ReduceBlock)]
    out = w.handle(StartAllreduce(0, policy=RoundPolicy(th_reduce=0.5)))
    reduces = [e for e in out if isinstance(e.msg, ReduceBlock)]
    assert len(reduces) == 3 and all(e.msg.count == 2 for e in reduces)
    # the threshold crossing cannot fire a second time
    out = w.handle(ScatterBlock(np.full(8, 9.0, np.float32), 3, 1, 0, 0))
    assert not [e for e in out if isinstance(e.msg, ReduceBlock)]


def test_round_envelopes_ride_the_policy_wire_mode():
    data = np.arange(32, dtype=np.float32)
    w = make_worker(data, [])
    w.handle(PrepareAllreduce(1, (0, 1, 2, 3), worker_id=1, round_num=0))
    out = w.handle(StartAllreduce(0, policy=RoundPolicy(0.5, "int8")))
    scatters = [e for e in out if isinstance(e.msg, ScatterBlock)]
    assert scatters and all(e.wire == "int8" for e in scatters)
    reduces = [e for e in out if isinstance(e.msg, ReduceBlock)]
    assert all(e.wire == "int8" for e in reduces)
    # a default round leaves the transport default in force
    out = w.handle(StartAllreduce(1))
    assert all(
        e.wire is None for e in out if isinstance(e.msg, ScatterBlock)
    )


def test_default_start_clears_a_prepare_seeded_policy():
    """The Start's stamp is authoritative: a Prepare seeded int8 for the
    round (controller degraded at reorganize time), but the controller
    restored before the line's first Start — the round must run at the
    Start's (default) mode, not the stale seed."""
    data = np.arange(32, dtype=np.float32)
    w = make_worker(data, [])
    w.handle(
        PrepareAllreduce(
            1, (0, 1, 2, 3), worker_id=1, round_num=0,
            policy=RoundPolicy(0.5, "int8"),
        )
    )
    assert w._wire_for(0) == "int8"  # seeded for a not-yet-Started round
    out = w.handle(StartAllreduce(0))  # default stamp supersedes the seed
    assert w._round_policy(0).is_default
    assert all(
        e.wire is None for e in out if isinstance(e.msg, ScatterBlock)
    )


def test_int8_ef_residual_carries_forward_and_matches_identity():
    """Round r+1's wire-bound chunk is chunk + residual(r); the residual
    is exactly ``c - int8_roundtrip(c)`` — the ring_ef_residual identity
    with v=1 (c·(1−v) + hop_err == hop_err)."""
    rng = np.random.default_rng(3)
    data = rng.standard_normal(32).astype(np.float32)
    w = make_worker(data, [])
    w.handle(PrepareAllreduce(1, (0, 1, 2, 3), worker_id=1, round_num=0))
    pol = RoundPolicy(0.5, "int8")
    out0 = w.handle(StartAllreduce(0, policy=pol))
    sent0 = {
        e.dest: e.msg.value
        for e in out0
        if isinstance(e.msg, ScatterBlock)
    }
    # round 0 sends the raw chunks; the residual of each send is stored
    resid = {k: np.array(v) for k, v in w._ef_residual.items()}
    assert resid
    for (dest_id, c), r0 in resid.items():
        chunk = sent0[f"worker:{dest_id}"]
        expect = chunk - wire.int8_roundtrip(chunk)
        np.testing.assert_allclose(r0, expect, atol=0)
    # the comm-layer identity (one shared definition): residual == c*(1-v)
    # + hop_err with v=1 — numerically identical by construction
    try:
        from akka_allreduce_tpu.comm.allreduce import ring_ef_residual
    except Exception:
        pytest.skip("comm layer (jax) unavailable")
    c = next(iter(sent0.values()))
    hop_err = c - wire.int8_roundtrip(c)
    np.testing.assert_allclose(
        np.asarray(ring_ef_residual(c, np.float32(1.0), hop_err)),
        hop_err, atol=0,
    )
    # round 1: the wire-bound chunk is chunk + residual (EF feed-forward)
    w.rounds.complete(0)
    out1 = w.handle(StartAllreduce(1, policy=pol))
    for e in out1:
        if isinstance(e.msg, ScatterBlock):
            dest_id = int(e.dest.split(":")[1])
            lo = e.msg.dest_id * 8
            base = data[lo : lo + 8]
            np.testing.assert_allclose(
                e.msg.value, base + resid[(dest_id, 0)], atol=1e-6
            )
    # a restore out of int8 drops the pending corrections
    w.handle(StartAllreduce(2, policy=RoundPolicy(0.75, "f16")))
    assert not w._ef_residual


# --- wire error accounting ----------------------------------------------------


def test_f16_clip_counter_reaches_the_obs_registry():
    ctr = obs_metrics.counter("wire.f16_clipped")
    before_reg, before_mod = ctr.value, wire.f16_clip_count()
    big = np.array([1e6, -2e6, 1.0], dtype=np.float32)
    wire.encode(ScatterBlock(big, 0, 1, 0, 0), f16=True)
    assert wire.f16_clip_count() == before_mod + 2
    assert ctr.value == before_reg + 2  # metrics_snapshot sees it too


def test_int8_residual_counter_mirrors_f16():
    ctr = obs_metrics.counter("wire.int8_residual_l1")
    pays = obs_metrics.counter("wire.int8_payloads")
    b_ctr, b_mod, b_pay = ctr.value, wire.int8_residual_l1(), pays.value
    x = np.random.default_rng(5).standard_normal(256).astype(np.float32)
    wire.encode(ScatterBlock(x, 0, 1, 0, 0), wire="int8")
    expect = float(np.abs(x - wire.int8_roundtrip(x)).sum())
    assert wire.int8_residual_l1() == pytest.approx(b_mod + expect)
    assert ctr.value == pytest.approx(b_ctr + expect)
    assert pays.value == b_pay + 1


def test_int8_nonfinite_inputs_saturate_and_count():
    ctr = obs_metrics.counter("wire.int8_saturated")
    before = ctr.value
    x = np.array([np.inf, -np.inf, np.nan, 1.0], dtype=np.float32)
    back = wire.decode(wire.encode(ScatterBlock(x, 0, 1, 0, 0), wire="int8"))
    assert np.all(np.isfinite(back.value))
    assert ctr.value == before + 3


# --- the real-subprocess drill (tier-1 twin of `make chaos-adapt`) ------------


def test_chaos_adapt_drill_subprocess(tmp_path):
    """The fixed-seed drill at reduced budgets: degrade within K rounds of
    the staged straggler, bounded transitions, restore after heal, EF
    error budget — the same binary `make chaos-adapt` gates on.

    The payload is 4x the drill's default: at 65536 floats a round takes
    ~3 ms, so the controller's degrade bar (8 rounds of lag) is a 30 ms
    stall of any node and the whole script spans under a second — on a
    busy machine real slowness made real extra transitions (11 of 20 runs
    failed beside a loop of pytest start-ups; ISSUE 25). At 262144 floats
    a round takes ~25 ms, the script ~3 s, and 20 of 20 passed there with
    exactly 2 degrades and 2 restores each."""
    proc = subprocess.run(
        [
            sys.executable, "-m", "akka_allreduce_tpu", "chaos-adapt",
            "--seed", "1234", "--out-dir", str(tmp_path / "run"),
            "--straggle-at", "15", "--heal-at", "80",
            "--post-rounds", "15", "--phase-timeout", "120",
            "--size", "262144", "--chunk", "32768",
        ],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=280,
    )
    last = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else "{}"
    summary = json.loads(last)
    assert proc.returncode == 0, summary.get("failures", proc.stderr[-2000:])
    assert summary["degrades"] >= 2 and summary["restores"] >= 2
    assert any(
        e["policy"].startswith("int8") for e in summary["adapt_events"]
    )
    assert all(v <= summary["err_budget"] for v in summary["max_err"].values())


def test_bandwidth_first_seen_endpoint_is_not_a_straggler():
    """An endpoint with no prior watermark (a peer that joined mid-
    window) carries only partial-window bytes — it must be watermark-
    seeded and judged from the NEXT window, never read as pressure."""
    ctl = make_ctl(bw_degrade_ratio=0.25, min_dwell=4)

    def bw_drive(rounds, bw, start):
        out = []
        for r in range(start, start + rounds):
            pol = ctl.observe_round(r, {1: 0}, {}, None, bandwidth=dict(bw))
            if pol is not None:
                out.append(pol)
        return out

    base = {"a:1": 1e6, "b:1": 1.1e6, "c:1": 0.9e6}
    assert bw_drive(4, base, 0) == []  # window 1 seeds the watermarks
    # node d joins 90% through window 2: tiny partial-window bytes
    joined = {k: v * 2 for k, v in base.items()} | {"d:1": 0.1e6}
    assert bw_drive(4, joined, 4) == []
    assert ctl.level == 0, "fresh endpoint read as a straggler"
    # from window 3 on, d is judged like everyone: balanced -> quiet
    settled = {k: v + 1e6 for k, v in joined.items()}
    assert bw_drive(4, settled, 8) == []
    assert ctl.level == 0

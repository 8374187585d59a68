"""The everything-on endurance run (VERDICT r4 #3).

Every feature is proven pairwise elsewhere; this module composes the
WHOLE framework in one unattended run — the flagship FSDP LM (remat /
prefetch / compressed collectives) under the elastic membership harness,
with async checkpointing, a mid-run restore, per-step metrics JSONL, and
at least one induced dropout + late-joiner re-mesh — and reports the
budgets that make up the recovery story: steady-state step time and MFU,
re-mesh latencies, checkpoint capture stalls, and the loss curve across
every disruption.

``python -m akka_allreduce_tpu soak`` runs it (flagship-sized by
default, on whatever devices are visible); tests/test_soak.py drives the
same loop at tiny shapes on the 8-device CPU mesh.
"""

from __future__ import annotations

import dataclasses
import statistics
import time
from typing import Any

import numpy as np


@dataclasses.dataclass
class SoakReport:
    """Summary of one soak run (also serialized as the last JSONL line)."""

    steps: int
    wall_s: float
    steady_ms_per_step: float
    mfu: float | None
    first_loss: float
    final_loss: float
    remesh_events: list  # [{step, kind, seconds, n_devices}]
    # the re-mesh accounting SPLIT by provenance: `forced` re-meshes were
    # scripted by the harness itself (the leader-failover schedule entry —
    # membership unchanged, the cluster re-runs Prepare under a new
    # epoch), `detected` ones came out of the failure detector (drop /
    # rejoin edges). The old single trail conflated them, so a soak JSON
    # could not say whether churn was injected or observed.
    remeshes_forced: int
    remeshes_detected: int
    # {at_step, restored_step, seconds, source: disk|peer, [pull]} — the
    # disk-vs-peer A/B is readable from this one record: `seconds` always
    # measures the SAME span (wipe-if-any + state fetch + trainer restore),
    # and `source` names which path supplied the bytes
    restore: dict | None
    # peer replication bookkeeping when the replica sidecar is on
    # (chunks/bytes copied into the replica store across the run)
    replication: dict | None
    # the per-round policy trail of the AdaptiveController driven by the
    # chaos schedule's straggler evidence (``--chaos`` runs only):
    # {degrades, restores, final_level, mode_rounds: {mode: steps},
    # transitions: [...]} — so an A/B pair of soak JSONs can attribute a
    # throughput shift to mode changes instead of guessing
    adapt: dict | None
    checkpoint_saves: int
    # a skip because a background save is still in flight (real contention —
    # the stall signal) vs a skip because the step is already durable (the
    # post-restore rewind makes save() a dedup no-op; ADVICE r5 said the old
    # single counter conflated the two and inflated the stall metric)
    checkpoint_skipped_busy: int
    checkpoint_skipped_dedup: int
    max_capture_stall_s: float
    generation: int

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


def run_soak(
    *,
    steps: int = 1000,
    nodes: int = 4,
    vocab: int = 256,
    d_model: int = 2048,
    n_heads: int | None = None,
    n_layers: int = 8,
    seq_len: int = 2048,
    batch_per_replica: int = 2,
    bf16: bool = True,
    remat: str | bool = "params",
    prefetch: bool = True,
    compress: str | None = "int8",
    learning_rate: float = 1e-3,
    drop_at: int | None = None,
    rejoin_at: int | None = None,
    restore_at: int | None = None,
    chaos_seed: int | None = None,
    checkpoint_every: int = 100,
    checkpoint_dir: str | None = None,
    delta: bool = False,
    peer_restore: bool = False,
    metrics_out: str | None = None,
    log=print,
) -> SoakReport:
    """Run the composed soak loop; every disruption is induced from
    inside (no manual intervention). Defaults follow the round-4 flagship
    recipe (``--remat params --prefetch --compress int8``); the drop /
    rejoin / restore steps default to 1/4, 1/2 and 3/4 of the run.

    ``chaos_seed`` (``soak --chaos SEED``) swaps the single scripted
    drop/rejoin for a deterministic seeded schedule of per-node silence
    windows (``control.chaos.membership_schedule``): each node other than
    0 independently flaps in and out, so one run exercises MANY detector
    trips and re-meshes — and the same seed replays the same churn.

    ``peer_restore`` (requires ``delta``) drives the mid-run restore
    through the peer state-transfer path instead of the local disk
    (RESILIENCE.md "Recovery"): every completed delta save is replicated
    into a replica ``ChunkStore`` sidecar, and at ``restore_at`` the local
    delta store is WIPED (the disk-loss scenario) and rebuilt chunk by
    chunk from the replica through the same verify-before-publish gate the
    TCP pull uses — the report's ``restore.source`` flips to ``"peer"``
    and ``restore.seconds`` measures the full wipe+pull+restore span, so
    the disk-vs-peer A/B is one flag and one JSON field apart."""
    import tempfile

    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.models import data
    from akka_allreduce_tpu.train import (
        AsyncDeltaCheckpointer,
        AsyncTrainerCheckpointer,
        ElasticTrainer,
        FSDPLMTrainer,
    )
    from akka_allreduce_tpu.utils import metrics as metrics_mod
    from akka_allreduce_tpu.utils.benchmarking import (
        mfu as mfu_of,
        transformer_train_flops,
    )

    drop_at = steps // 4 if drop_at is None else drop_at
    rejoin_at = steps // 2 if rejoin_at is None else rejoin_at
    restore_at = (3 * steps) // 4 if restore_at is None else restore_at
    n_heads = n_heads or max(1, d_model // 128)

    devices = jax.devices()
    nodes = min(nodes, max(2, len(devices)))
    per = max(1, len(devices) // nodes)
    if len(devices) >= nodes:
        assignment = {
            k: devices[k * per : (k + 1) * per] for k in range(nodes)
        }
    else:
        # one real chip: a zero-device control node still exercises the
        # full membership/re-mesh machinery
        assignment = {0: list(devices), 1: []}
        nodes = 2
    lost = nodes - 1
    now = {"t": 0.0}

    def factory(mesh):
        return FSDPLMTrainer(
            mesh,
            vocab=vocab,
            d_model=d_model,
            n_heads=n_heads,
            n_layers=n_layers,
            seq_len=seq_len,
            learning_rate=learning_rate,
            compute_dtype=jnp.bfloat16 if bf16 else jnp.float32,
            remat=remat,
            prefetch=prefetch,
            compress=compress,
        )

    silent_plan = None
    leader_kill = None
    adapt_ctl = None
    adapt_lags: dict[int, int] = {}
    # steps the simulated control plane is LEADERLESS after the kill (the
    # lease window): the detector dies with the leader — no polls, no
    # expulsions — then the standby's takeover re-meshes everyone
    failover_steps = 3
    if chaos_seed is not None:
        from akka_allreduce_tpu.config import AdaptConfig
        from akka_allreduce_tpu.control.adapt import AdaptiveController
        from akka_allreduce_tpu.config import ThresholdConfig
        from akka_allreduce_tpu.control.chaos import (
            leader_kill_step,
            membership_schedule,
        )

        silent_plan = membership_schedule(chaos_seed, nodes, steps)
        leader_kill = leader_kill_step(chaos_seed, steps)
        # the adaptive controller rides the SAME seeded schedule: a node's
        # consecutive silent steps feed it as contribution lag, so the
        # policy trail is a pure function of the chaos seed (deterministic
        # A/B). The trail is REPORTED, not applied — re-compiling the
        # trainer per mode flip would swamp the soak's timing story; the
        # TCP cluster (cluster-master --adapt) is where the policy drives
        # the actual wire.
        adapt_ctl = AdaptiveController(
            AdaptConfig(
                enabled=True, window=4, min_dwell=8,
                lag_degrade=3, lag_restore=1,
            ),
            ThresholdConfig(),
        )
    elastic = ElasticTrainer(factory, assignment, clock=lambda: now["t"])
    churn = (
        f"chaos seed {chaos_seed} "
        f"({sum(len(v) for v in silent_plan.values())} node-step silences, "
        f"leader kill@{leader_kill})"
        if silent_plan is not None
        else f"drop@{drop_at} rejoin@{rejoin_at}"
    )
    log(
        f"soak: {elastic.trainer.param_count / 1e6:.1f}M params over "
        f"{elastic.trainer.n_devices} devices / {nodes} nodes; "
        f"{churn} restore@{restore_at}"
    )

    ckpt_dir = checkpoint_dir or tempfile.mkdtemp(prefix="soak_ckpt_")
    if peer_restore and not delta:
        raise ValueError(
            "peer_restore replicates delta-checkpoint chunks; pass delta=True"
        )
    ckpt_cls = AsyncDeltaCheckpointer if delta else AsyncTrainerCheckpointer
    ckpt = ckpt_cls(ckpt_dir)
    replica = None
    replication: dict | None = None
    if peer_restore:
        from akka_allreduce_tpu.control.statetransfer import ChunkStore

        # the replica sidecar: the in-process stand-in for the K=2 peer
        # stores the TCP cluster pushes to — same layout, same
        # verify-before-publish copy path (copy_delta)
        replica = ChunkStore(ckpt_dir + "_replica")
        replication = {"rounds": 0, "chunks_copied": 0, "bytes_copied": 0}
    ds = data.lm_copy_task(seq_len, vocab=vocab)
    logger = (
        metrics_mod.MetricsLogger(metrics_out) if metrics_out else None
    )

    step_ms: list[float] = []
    losses: list[float] = []
    restore_rec: dict | None = None
    # run-scoped metrics registry (obs.metrics): the loop records its
    # checkpoint / re-mesh bookkeeping HERE and the final SoakReport reads
    # it BACK, so the report and any live metrics consumer (log_snapshot
    # below) can never disagree — there is one set of numbers.
    from akka_allreduce_tpu.obs.metrics import Registry

    reg = Registry()
    remesh_events = reg.series("soak.remesh_events")
    c_steps = reg.counter("soak.steps")
    c_saves = reg.counter("soak.checkpoint.saves")
    c_skip_busy = reg.counter("soak.checkpoint.skipped_busy")
    c_skip_dedup = reg.counter("soak.checkpoint.skipped_dedup")
    g_capture = reg.gauge("soak.checkpoint.max_capture_stall_s")
    g_loss = reg.gauge("soak.loss")
    # restore accounting (RESILIENCE.md "Recovery"): the source split and
    # the seconds live in the SAME registry the report reads, so the soak
    # JSON and any live metrics consumer agree by construction
    c_restore_disk = reg.counter("soak.restore.from_disk")
    c_restore_peer = reg.counter("soak.restore.from_peer")
    g_restore_s = reg.gauge("soak.restore.seconds")
    replicated = {"step": -1}

    def replicate_completed() -> None:
        """Mirror the newest COMPLETED delta save into the replica store
        (content-addressed: an unchanged leaf copies zero bytes)."""
        if replica is None or ckpt.busy():
            return
        latest = ckpt.latest_step()
        if latest is None or latest <= replicated["step"]:
            return
        from akka_allreduce_tpu.control.statetransfer import ChunkStore, copy_delta

        s = copy_delta(ChunkStore(ckpt_dir), replica, step=latest)
        replicated["step"] = latest
        replication["rounds"] += 1
        replication["chunks_copied"] += s["chunks_copied"]
        replication["bytes_copied"] += s["bytes_copied"]
    compile_steps: set[int] = {0}  # steps whose time includes an XLA compile
    t_start = time.perf_counter()

    def batch(seed):
        rows = elastic.trainer.dp * batch_per_replica
        return next(ds.batches(rows, 1, seed_offset=seed))

    adapt_trail = reg.series("soak.adapt.transitions")
    adapt_mode_steps: dict[str, int] = {}
    for step in range(steps):
        if silent_plan is not None:
            silent = silent_plan.get(step, frozenset())
            alive = [k for k in range(nodes) if k not in silent]
        else:
            alive = [
                k for k in range(nodes)
                if not (drop_at <= step < rejoin_at and k == lost)
            ]
        for k in alive:
            elastic.heartbeat(k)
        # steady 1 s heartbeat cadence: the detector's interval model
        # settles in the first few steps, and a node that then goes
        # silent accrues phi within a handful of ticks
        now["t"] += 1.0
        t0 = time.perf_counter()
        members_before = len(elastic.member_nodes)
        forced_kind = None
        if (
            leader_kill is not None
            and leader_kill <= step < leader_kill + failover_steps
        ):
            # leaderless window: the failure detector died WITH the leader,
            # so nobody polls and nobody is expelled (the warm standby
            # carries the membership state — nothing is forgotten)
            remeshed = False
        elif leader_kill is not None and step == leader_kill + failover_steps:
            # the standby's lease expired and it took over: every node
            # re-joins the new leader -> one full re-mesh with unchanged
            # membership (the in-process analog of the TCP failover walk)
            remeshed = elastic.remesh("leader_failover")
            forced_kind = "leader_failover"
        else:
            remeshed = elastic.poll()
        x, y = batch(step)
        m = elastic.train_step(x, y)
        dt = time.perf_counter() - t0
        if remeshed:
            # kind from the authoritative membership delta, not the step
            # index (phi detection lags the induced silence by a few
            # heartbeats)
            kind = forced_kind or (
                "drop"
                if len(elastic.member_nodes) < members_before
                else "rejoin"
            )
            remesh_events.append(
                {
                    "step": step,
                    "kind": kind,
                    "seconds": round(dt, 3),
                    "n_devices": elastic.trainer.n_devices,
                }
            )
            reg.counter(f"soak.remesh.{kind}").inc()
            # provenance split (pinned in test_soak): forced = the
            # harness scripted it; detected = the phi detector found it
            reg.counter(
                "soak.remesh.forced"
                if forced_kind
                else "soak.remesh.detected"
            ).inc()
            compile_steps.add(step)
            log(
                f"step {step}: re-mesh ({kind}) -> "
                f"{elastic.trainer.n_devices} devices in {dt:.2f}s"
            )
        if adapt_ctl is not None:
            # one "round" of straggler evidence per step: a silent node's
            # lag is its consecutive silent steps (round units — the same
            # shape the TCP master feeds from LineMaster.worker_lags)
            for k in range(nodes):
                adapt_lags[k] = 0 if k in alive else adapt_lags.get(k, 0) + 1
            pol = adapt_ctl.observe_round(step, dict(adapt_lags), {})
            if pol is not None:
                rec = dict(adapt_ctl.decisions[-1], step=step)
                adapt_trail.append(rec)
                reg.counter(
                    "soak.adapt.degrades"
                    if rec["to"] > rec["from"]
                    else "soak.adapt.restores"
                ).inc()
                log(
                    f"step {step}: adapt level {rec['from']} -> "
                    f"{rec['to']} ({'+'.join(rec['why'])}) policy "
                    f"{rec['policy']}"
                )
            mode = adapt_ctl.policy().wire or "full"
            adapt_mode_steps[mode] = adapt_mode_steps.get(mode, 0) + 1
        step_ms.append(dt * 1e3)
        losses.append(m.loss)
        c_steps.inc()
        g_loss.set(m.loss)
        if logger:
            logger.log_event(
                step=step, loss=m.loss, ms=round(dt * 1e3, 2)
            )

        if step == restore_at and ckpt.latest_step() is not None:
            t0 = time.perf_counter()
            ckpt.wait_until_finished()
            source, pull = "disk", None
            if replica is not None:
                # the disk-loss drill: catch the replica up, WIPE the local
                # delta store, rebuild it chunk-verified from the replica —
                # then restore through the ordinary checkpointer path so
                # the restored state is byte-identical to the disk path
                import shutil

                from akka_allreduce_tpu.control.statetransfer import (
                    ChunkStore,
                    copy_delta,
                )

                replicate_completed()
                own = ChunkStore(ckpt_dir)
                shutil.rmtree(own.blobs)
                for m in own.manifests().values():
                    m.unlink()
                own.blobs.mkdir()
                pull = copy_delta(replica, own, verify=True)
                source = "peer"
            restored = ckpt.restore(elastic.trainer)
            rs = time.perf_counter() - t0
            restore_rec = {
                "at_step": step,
                "restored_step": int(restored),
                "seconds": round(rs, 3),
                "source": source,
            }
            if pull is not None:
                restore_rec["pull"] = pull
            (c_restore_peer if source == "peer" else c_restore_disk).inc()
            g_restore_s.set(restore_rec["seconds"])
            compile_steps.add(step + 1)  # rewound shapes may recompile
            log(
                f"step {step}: restored checkpoint of step {restored} "
                f"from {source} in {rs:.2f}s; training continues from there"
            )

        replicate_completed()
        if checkpoint_every and step and step % checkpoint_every == 0:
            if ckpt.busy():
                # a background save is still in flight: THIS is the
                # contention the stall metric exists to count
                c_skip_busy.inc()
            else:
                t0 = time.perf_counter()
                launched = ckpt.save(elastic.trainer)
                cap = time.perf_counter() - t0
                if launched:
                    c_saves.inc()
                    g_capture.set(max(g_capture.value, cap))
                else:
                    # not busy and not launched: the step is already durable
                    # (e.g. the restore rewound step_num onto a saved step)
                    c_skip_dedup.inc()

    ckpt.wait_until_finished()
    wall = time.perf_counter() - t_start
    steady = [
        ms for i, ms in enumerate(step_ms) if i not in compile_steps
    ]
    steady_ms = statistics.median(steady) if steady else float("nan")
    flops = transformer_train_flops(
        n_params=elastic.trainer.param_count,
        batch=elastic.trainer.dp * batch_per_replica,
        seq=seq_len,
        d_model=d_model,
        n_layers=n_layers,
    )
    # the report is a READ of the registry — same numbers any live
    # metrics_snapshot consumer saw, by construction
    report = SoakReport(
        steps=steps,
        wall_s=round(wall, 1),
        steady_ms_per_step=round(steady_ms, 1),
        # flops is the GLOBAL whole-batch work -> whole-mesh peak
        mfu=mfu_of(
            flops, steady_ms / 1e3, n_devices=elastic.trainer.n_devices
        ),
        first_loss=round(losses[0], 4),
        final_loss=round(losses[-1], 4),
        remesh_events=list(remesh_events.values),
        remeshes_forced=reg.counter("soak.remesh.forced").value,
        remeshes_detected=reg.counter("soak.remesh.detected").value,
        restore=restore_rec,
        replication=replication,
        adapt=(
            {
                "degrades": reg.counter("soak.adapt.degrades").value,
                "restores": reg.counter("soak.adapt.restores").value,
                "final_level": adapt_ctl.level,
                "mode_rounds": dict(adapt_mode_steps),
                "transitions": list(adapt_trail.values),
            }
            if adapt_ctl is not None
            else None
        ),
        checkpoint_saves=c_saves.value,
        checkpoint_skipped_busy=c_skip_busy.value,
        checkpoint_skipped_dedup=c_skip_dedup.value,
        max_capture_stall_s=round(g_capture.value, 3),
        generation=elastic.generation,
    )
    if logger:
        logger.log_snapshot(reg)
        logger.log_event(summary=report.as_dict())
        logger.close()
    return report

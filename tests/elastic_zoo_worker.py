"""Workload-resilience scenario worker (RESILIENCE.md "Tier 7").

Runs the ElasticTrainer edge scenarios that need a REAL jax mesh in an
interpreter of their own (an 8-device virtual CPU mesh, forced below).

Invoked by tests/test_chaos_train.py (and test_soak.py) as::

    python tests/elastic_zoo_worker.py <scenario> [<scenario> ...]

Prints ``OK <scenario>`` per passing scenario; any assertion failure
exits nonzero with a traceback.
"""

from __future__ import annotations

import os
import sys

SCENARIOS = sys.argv[1:]

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402
import numpy as np  # noqa: E402


def _assignment(n_nodes: int, per: int = 1) -> dict:
    devs = jax.devices()
    assert len(devs) >= n_nodes * per, (len(devs), n_nodes, per)
    return {i: devs[i * per : (i + 1) * per] for i in range(n_nodes)}


def _dp_elastic(n_nodes=4, min_nodes=1):
    from akka_allreduce_tpu.train import zoo

    return zoo.make_elastic("dp", _assignment(n_nodes), min_nodes=min_nodes)


def _step(elastic, ds, seed):
    from akka_allreduce_tpu.train import zoo

    x, y = zoo.batch_for("dp", ds, elastic, seed_offset=seed)
    return elastic.train_step(x, y)


def compress_follows_policy():
    """The ICI half of the adaptive loop, end to end in one process: a
    REAL AdaptiveController walks its ladder on straggler evidence, every
    emitted RoundPolicy is applied to a live dp elastic trainer mid-run
    via apply_policy_wire, and the trainer's compress mode follows
    f16 -> int8 -> restore through the trainer-factory rebuild path with
    the EF residual preserved and the int8 step error inside the 0.15
    budget."""
    from akka_allreduce_tpu.config import AdaptConfig, ThresholdConfig
    from akka_allreduce_tpu.control.adapt import AdaptiveController
    from akka_allreduce_tpu.train import zoo

    ctl = AdaptiveController(
        AdaptConfig(
            enabled=True, window=2, min_dwell=2, lag_degrade=4,
            lag_restore=1, floor_th_reduce=0.5,
        ),
        ThresholdConfig(1.0, 1.0, 1.0),
    )
    elastic = _dp_elastic()
    ds = zoo.dataset_for("dp")
    seen_modes = [elastic.compress_mode]
    generations = [elastic.generation]
    # the objects, not their id()s: a freed trainer's address can be reused
    # by a later one (seen: 1 of 2 whole tier-1 runs, PR 25)
    trainers = [elastic.trainer]
    lag = {1: 0}
    for rnd in range(40):
        # straggler window: rounds 4..24 show heavy lag, then heal
        lag[1] = lag[1] + 1 if 4 <= rnd < 24 else 0
        pol = ctl.observe_round(rnd, dict(lag), {})
        _step(elastic, ds, rnd)
        if pol is None:
            continue
        before_ef = (
            np.asarray(elastic.trainer._ef).sum()
            if getattr(elastic.trainer, "_ef", None) is not None
            else None
        )
        changed = elastic.apply_policy_wire(pol.wire)
        assert changed, (rnd, pol.wire, elastic.compress_mode)
        seen_modes.append(elastic.compress_mode)
        generations.append(elastic.generation)
        trainers.append(elastic.trainer)
        if before_ef is not None and elastic.compress_mode is not None:
            # residual identity across the rebuild: what the collective is
            # owed survives the snapshot -> factory -> restore cycle
            after_ef = np.asarray(elastic.trainer._ef).sum()
            np.testing.assert_allclose(after_ef, before_ef, rtol=1e-5)
    # the ladder walked: full -> bf16 -> int8 -> bf16 -> full (the
    # controller's own hysteresis pacing; modes must follow WIRE_TO_COMPRESS)
    assert seen_modes == [None, "bf16", "int8", "bf16", None], seen_modes
    # every change was a REBUILD (new trainer object, generation bump) —
    # never a per-step retrace of the same trainer
    assert len({id(t) for t in trainers}) == len(trainers), trainers
    assert generations == sorted(generations) and generations[-1] == 4
    assert ctl.level == 0

    # EF error budget: one int8+EF step vs an f32 oracle from the SAME
    # state — the quantization error net of the residual carry stays
    # inside the host drill's 0.15 budget
    from akka_allreduce_tpu.train.checkpoint import Snapshot

    elastic.set_compress("int8")
    oracle = _dp_elastic()
    Snapshot.capture(elastic.trainer).restore_into(oracle.trainer)
    x, y = zoo.batch_for("dp", ds, elastic, seed_offset=999)
    elastic.train_step(x, y)
    oracle.train_step(x, y)
    err = float(
        np.max(np.abs(elastic.get_flat_params() - oracle.get_flat_params()))
    )
    assert err <= 0.15, err
    print(f"int8-vs-f32 step error {err:.5f} <= 0.15")

    # zero1's clamp: int8 degrades to the family floor (bf16), and a
    # stamp the clamp maps onto the CURRENT mode is a no-op, not a
    # rebuild of an identical trainer
    z = zoo.make_elastic("zero1", _assignment(2))
    assert z.apply_policy_wire("f16") is True and z.compress_mode == "bf16"
    g = z.generation
    assert z.apply_policy_wire("int8") is False  # clamped onto bf16
    assert z.compress_mode == "bf16" and z.generation == g
    assert z.apply_policy_wire("") is True and z.compress_mode is None


def min_nodes_refusal_recovery():
    """min_nodes floor under the cluster-driven membership path: shrink
    below the floor -> train_step refuses (RuntimeError, state intact);
    rejoin -> recovery, weights identical."""
    from akka_allreduce_tpu.train import zoo

    elastic = _dp_elastic(n_nodes=3, min_nodes=2)
    ds = zoo.dataset_for("dp")
    _step(elastic, ds, 0)
    ref = elastic.get_flat_params().copy()
    assert elastic.apply_membership([0]) is True
    assert elastic.n_nodes == 1
    try:
        _step(elastic, ds, 1)
        raise AssertionError("train_step below min_nodes must refuse")
    except RuntimeError as e:
        assert "min_nodes" in str(e)
    np.testing.assert_array_equal(elastic.get_flat_params(), ref)
    # rejoin -> recovery on the same path
    assert elastic.apply_membership([0, 1, 2]) is True
    np.testing.assert_array_equal(elastic.get_flat_params(), ref)
    m = _step(elastic, ds, 2)
    assert np.isfinite(m.loss) and m.contributors == 3.0


def back_to_back_remesh():
    """A second membership change landing immediately after (the drill's
    churny 2-core reality): consecutive re-meshes with no step between
    them, logical state exact throughout."""
    from akka_allreduce_tpu.train import zoo

    elastic = _dp_elastic(n_nodes=4)
    ds = zoo.dataset_for("dp")
    _step(elastic, ds, 0)
    ref = elastic.get_flat_params().copy()
    assert elastic.apply_membership([0, 1, 2]) is True
    assert elastic.apply_membership([0, 2]) is True  # no step between
    np.testing.assert_array_equal(elastic.get_flat_params(), ref)
    assert elastic.apply_membership([0, 1, 2, 3]) is True
    np.testing.assert_array_equal(elastic.get_flat_params(), ref)
    assert elastic.generation == 3
    m = _step(elastic, ds, 1)
    assert np.isfinite(m.loss) and m.contributors == 4.0


def sharded_snapshot_determinism():
    """The sharded (zero1 / fsdp) checkpoint protocol under a
    device-count change: snapshot -> restore onto a DIFFERENT device
    count -> snapshot again must be leaf-for-leaf byte-identical (the
    serialized form is mesh-size-independent, so the round trip is
    deterministic — what the drill's loss-continuity bar rests on)."""
    from akka_allreduce_tpu.train import zoo
    from akka_allreduce_tpu.train.checkpoint import Snapshot

    for family in ("zero1", "fsdp"):
        elastic = zoo.make_elastic(family, _assignment(4))
        ds = zoo.dataset_for(family)
        for s in range(2):
            x, y = zoo.batch_for(family, ds, elastic, seed_offset=s)
            elastic.train_step(x, y)
        snap = Snapshot.capture(elastic.trainer)
        assert elastic.apply_membership([0, 1, 2]) is True  # 4 -> 3 devices
        again = Snapshot.capture(elastic.trainer)
        a, b = snap.custom, again.custom
        assert a is not None and b is not None, family
        leaves_a = jax.tree.leaves(a)
        leaves_b = jax.tree.leaves(b)
        assert len(leaves_a) == len(leaves_b), family
        for la, lb in zip(leaves_a, leaves_b):
            np.testing.assert_array_equal(np.asarray(la), np.asarray(lb))
        print(f"{family}: {len(leaves_a)} leaves byte-identical across 4->3")


def pipeline_restage_fallback():
    """The restage rule and its DP-only floor: 4 stages x 1 layer over 4
    devices re-stages to gcd(3, 4) = 1 stage (the whole trunk on every
    device) when a node dies — logical params exact; and a factory that
    REFUSES the restaged mesh degrades through fallback_mesh_factory
    instead of wedging, with the old trainer intact when everything
    fails."""
    from akka_allreduce_tpu.train import zoo
    from akka_allreduce_tpu.train.elastic import ElasticTrainer
    from akka_allreduce_tpu.train.pipeline import PipelineLMTrainer

    elastic = zoo.make_elastic("pipeline", _assignment(4, per=1))
    assert elastic.trainer.stages == 4
    ds = zoo.dataset_for("pipeline")
    x, y = zoo.batch_for("pipeline", ds, elastic, seed_offset=0)
    elastic.train_step(x, y)
    ref = elastic.get_flat_params().copy()
    assert elastic.apply_membership([0, 1, 2]) is True
    # gcd(3 devices, 4 layers) = 1: the DP-only fallback by construction
    assert elastic.trainer.stages == 1 and elastic.trainer.dp == 3
    np.testing.assert_array_equal(elastic.get_flat_params(), ref)
    x, y = zoo.batch_for("pipeline", ds, elastic, seed_offset=1)
    m = elastic.train_step(x, y)
    assert np.isfinite(m.loss)

    # a REFUSING factory (pinned to 4 stages) + the DP-only fallback
    def rigid_factory(mesh):
        pp = int(mesh.shape["pipe"])
        if pp not in (1, 4):
            raise ValueError(f"this factory only builds pp in (1, 4), got {pp}")
        return PipelineLMTrainer(
            mesh, vocab=16, d_model=32, n_heads=2, seq_len=32, seed=0,
            layers_per_stage=4 // pp, microbatches=2,
        )

    def rigid_mesh(*, devices):
        if len(devices) % 4:
            # hand the factory a mesh it will refuse (stages != 1 or 4)
            return jax.make_mesh(
                (1, len(devices)), ("data", "pipe"), devices=devices
            )
        return jax.make_mesh(
            (len(devices) // 4, 4), ("data", "pipe"), devices=devices
        )

    def dp_only(*, devices):
        return jax.make_mesh(
            (len(devices), 1), ("data", "pipe"), devices=devices
        )

    e2 = ElasticTrainer(
        rigid_factory,
        _assignment(4, per=1),
        mesh_factory=rigid_mesh,
        fallback_mesh_factory=dp_only,
    )
    assert e2.trainer.stages == 4
    ref2 = e2.get_flat_params().copy()
    assert e2.apply_membership([0, 1, 2]) is True
    # the primary mesh (pp=3) was refused; the fallback restaged DP-only
    assert e2.trainer.stages == 1 and e2.trainer.dp == 3
    np.testing.assert_array_equal(e2.get_flat_params(), ref2)

    # and with NO fallback, the refusal leaves the OLD trainer usable
    e3 = ElasticTrainer(
        rigid_factory, _assignment(4, per=1), mesh_factory=rigid_mesh
    )
    before = e3.trainer
    try:
        e3.apply_membership([0, 1, 2])
        raise AssertionError("refusing factory without fallback must raise")
    except ValueError:
        pass
    assert e3.trainer is before and e3.member_nodes == (0, 1, 2, 3)


def soak_forced_split():
    """soak --chaos's scripted leader_failover re-mesh counts as FORCED;
    detector-driven churn counts as DETECTED — the split the SoakReport
    now carries (ISSUE 14 satellite)."""
    import tempfile

    from akka_allreduce_tpu.soak import run_soak

    with tempfile.TemporaryDirectory(prefix="soak_split_") as d:
        report = run_soak(
            steps=24,
            nodes=3,
            vocab=16,
            d_model=32,
            n_heads=4,
            n_layers=2,
            seq_len=32,
            batch_per_replica=2,
            bf16=False,
            remat="params",
            prefetch=False,
            compress=None,
            learning_rate=1e-2,
            chaos_seed=7,
            checkpoint_every=10,
            checkpoint_dir=os.path.join(d, "ckpt"),
            log=lambda *_: None,
        )
    kinds = [e["kind"] for e in report.remesh_events]
    assert "leader_failover" in kinds, kinds
    forced = sum(1 for k in kinds if k == "leader_failover")
    assert report.remeshes_forced == forced, report
    assert report.remeshes_detected == len(kinds) - forced, report
    print(
        f"remeshes: forced={report.remeshes_forced} "
        f"detected={report.remeshes_detected} kinds={kinds}"
    )


if __name__ == "__main__":
    scenarios = {
        "compress_follows_policy": compress_follows_policy,
        "min_nodes_refusal_recovery": min_nodes_refusal_recovery,
        "back_to_back_remesh": back_to_back_remesh,
        "sharded_snapshot_determinism": sharded_snapshot_determinism,
        "pipeline_restage_fallback": pipeline_restage_fallback,
        "soak_forced_split": soak_forced_split,
    }
    for name in SCENARIOS:
        scenarios[name]()
        print(f"OK {name}", flush=True)

"""Checkpoint/resume tests (SURVEY.md §6 "Checkpoint / resume"; the durable
half of BASELINE config 5's recovery story)."""

import numpy as np
import optax
import pytest

from akka_allreduce_tpu.models import MLP, data
from akka_allreduce_tpu.parallel import line_mesh
from akka_allreduce_tpu.train import DPTrainer, Snapshot, TrainerCheckpointer


def make_trainer(mesh, seed=0):
    return DPTrainer(
        MLP(hidden=(16,), classes=10),
        mesh,
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        optimizer=optax.adam(1e-3),  # nontrivial opt state (mu/nu/count)
        seed=seed,
    )


#: a small trainer after one step, for the fresh-interpreter tests
#: (``run_fresh``, conftest.py): what this file's ``make_trainer`` builds
_FRESH_TRAINER = """
    import numpy as np, optax
    from akka_allreduce_tpu.models import MLP, data
    from akka_allreduce_tpu.parallel import line_mesh

    def make_trainer(seed=0):
        from akka_allreduce_tpu.train import DPTrainer
        return DPTrainer(
            MLP(hidden=(16,), classes=10), line_mesh(8),
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.adam(1e-3), seed=seed,
        )

    t = make_trainer()
    t.train(data.mnist_like().batches(32, 1))
    ref = t.get_flat_params().copy()
"""


_ASYNC_FIRST_SAVE = _FRESH_TRAINER + """
    import threading
    from akka_allreduce_tpu.obs import metrics
    from akka_allreduce_tpu.train import AsyncTrainerCheckpointer
    from akka_allreduce_tpu.train import checkpoint as ckpt_mod
    assert "orbax.checkpoint" not in sys.modules
    ckpt = AsyncTrainerCheckpointer(sys.argv[1])
    built = metrics.gauge("checkpoint.orbax_import_s").value
    callers = []
    real = ckpt_mod._orbax
    def spy():
        callers.append(threading.current_thread().name)
        return real()
    ckpt_mod._orbax = spy
    assert ckpt.save(t)
    ckpt.wait_until_finished()
    fresh = make_trainer(seed=3)
    step = ckpt.restore(fresh)
    ckpt.close()
    extra = {
        "built_import_s": built,
        "callers": callers,
        "step": step,
        "equal": bool((fresh.get_flat_params() == ref).all()),
    }
"""

_DELTA_WITHOUT_ORBAX = _FRESH_TRAINER + """
    from akka_allreduce_tpu.train import DeltaCheckpointer, Snapshot
    store = DeltaCheckpointer(sys.argv[1])
    stats = store.save(t)
    snap = Snapshot.capture(t)
    fresh = make_trainer(seed=3)
    step = store.restore(fresh)
    from_snap = make_trainer(seed=4)
    snap.restore_into(from_snap)
    extra = {
        "written": stats["written_leaves"],
        "step": step,
        "equal": bool((fresh.get_flat_params() == ref).all()),
        "snap_equal": bool((from_snap.get_flat_params() == ref).all()),
    }
"""


def _flat_tree(tree) -> np.ndarray:
    import jax

    return np.concatenate(
        [np.ravel(np.asarray(l)) for l in jax.tree.leaves(tree)]
    )


class TestSnapshot:
    def test_capture_restore_roundtrip(self):
        mesh = line_mesh(8)
        t = make_trainer(mesh)
        ds = data.mnist_like()
        t.train(ds.batches(32, 3))
        snap = Snapshot.capture(t)
        ref = t.get_flat_params().copy()

        t.train(ds.batches(32, 2, seed_offset=7))  # diverge
        assert not np.allclose(t.get_flat_params(), ref)

        snap.restore_into(t)
        assert t.step_num == 3
        np.testing.assert_array_equal(t.get_flat_params(), ref)

    def test_snapshot_survives_mesh_change(self):
        # the elastic re-mesh path: capture on 8 devices, restore into a
        # 4-device trainer, and training continues identically to a trainer
        # that had those weights natively
        t8 = make_trainer(line_mesh(8), seed=1)
        ds = data.mnist_like()
        t8.train(ds.batches(32, 2))
        snap = Snapshot.capture(t8)

        t4 = make_trainer(line_mesh(4), seed=99)
        snap.restore_into(t4)
        assert t4.step_num == 2
        np.testing.assert_array_equal(t4.get_flat_params(), t8.get_flat_params())
        m = t4.train_step(*next(iter(ds.batches(16, 1, seed_offset=3))))
        assert m.contributors == 4.0 and np.isfinite(m.loss)


class TestTrainerCheckpointer:
    def test_save_restore_roundtrip(self, tmp_path):
        mesh = line_mesh(8)
        t = make_trainer(mesh, seed=2)
        ds = data.mnist_like()
        t.train(ds.batches(32, 3))
        with TrainerCheckpointer(tmp_path / "ckpt") as ckpt:
            assert ckpt.save(t)
            assert ckpt.latest_step() == 3
            ref = t.get_flat_params().copy()

            t.train(ds.batches(32, 2, seed_offset=5))
            step = ckpt.restore(t)
        assert step == 3 and t.step_num == 3
        np.testing.assert_array_equal(t.get_flat_params(), ref)

    def test_restore_into_fresh_process_equivalent(self, tmp_path):
        # a brand-new trainer (fresh params) restores the full state
        ds = data.mnist_like()
        t = make_trainer(line_mesh(8), seed=3)
        t.train(ds.batches(32, 2))
        with TrainerCheckpointer(tmp_path / "c2") as ckpt:
            ckpt.save(t)
            fresh = make_trainer(line_mesh(8), seed=77)
            ckpt.restore(fresh)
        np.testing.assert_array_equal(
            fresh.get_flat_params(), t.get_flat_params()
        )
        # post-restore training matches the original exactly (opt state too)
        batch = next(iter(ds.batches(32, 1, seed_offset=9)))
        t.train_step(*batch)
        fresh.train_step(*batch)
        np.testing.assert_allclose(
            fresh.get_flat_params(), t.get_flat_params(), rtol=1e-6, atol=1e-7
        )

    def test_restore_without_checkpoint_raises(self, tmp_path):
        t = make_trainer(line_mesh(1))
        with TrainerCheckpointer(tmp_path / "empty") as ckpt:
            with pytest.raises(FileNotFoundError):
                ckpt.restore(t)

    def test_max_to_keep_prunes(self, tmp_path):
        t = make_trainer(line_mesh(1), seed=4)
        ds = data.mnist_like()
        with TrainerCheckpointer(tmp_path / "c3", max_to_keep=2) as ckpt:
            for _ in range(4):
                t.train(ds.batches(8, 1))
                ckpt.save(t)
            steps = ckpt._mgr.all_steps()
        assert list(steps) == [3, 4]


class TestShardedTrainerCheckpoint:
    """Checkpoint/resume for sharded trainers (TP / EP / PP): state must
    round-trip onto each leaf's OWN sharding, not be flattened to replicated."""

    def _tp_trainer(self, seed=0):
        from akka_allreduce_tpu.parallel import data_seq_model_mesh
        from akka_allreduce_tpu.train import LongContextTrainer

        return LongContextTrainer(
            data_seq_model_mesh(2, 2, 2),
            vocab=16, d_model=32, n_heads=4, n_layers=1, seq_len=32,
            learning_rate=1e-2, seed=seed,
        )

    def test_tp_roundtrip_preserves_values_and_sharding(self, tmp_path):
        from akka_allreduce_tpu.models import data
        from akka_allreduce_tpu.train import TrainerCheckpointer

        t = self._tp_trainer()
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(4, 1))
        t.train_step(x, y)
        before = t.get_flat_params()
        with TrainerCheckpointer(tmp_path / "tp") as ckpt:
            assert ckpt.save(t)
            fresh = self._tp_trainer(seed=9)  # different init
            assert ckpt.restore(fresh) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), before)
        # sharded leaf came back SHARDED over the model axis
        q = fresh.params["params"]["Block_0"]["Attention_0"]["q"]["kernel"]
        assert q.addressable_shards[0].data.shape == (32, 2, 8)
        # and training continues from the restored state
        m = fresh.train_step(x, y)
        assert m.step == 2 and np.isfinite(m.loss)

    def test_snapshot_restores_sharded_layout(self):
        from akka_allreduce_tpu.models import data
        from akka_allreduce_tpu.train import Snapshot

        t = self._tp_trainer()
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(4, 1))
        t.train_step(x, y)
        snap = Snapshot.capture(t)
        other = self._tp_trainer(seed=5)
        snap.restore_into(other)
        np.testing.assert_array_equal(
            other.get_flat_params(), t.get_flat_params()
        )
        q = other.params["params"]["Block_0"]["Attention_0"]["q"]["kernel"]
        assert q.addressable_shards[0].data.shape == (32, 2, 8)
        m = other.train_step(x, y)
        assert np.isfinite(m.loss)

    def test_tp_restore_into_differently_factored_mesh(self, tmp_path):
        """A checkpoint saved on a (2,2,2) mesh restores onto a (1,2,4)
        mesh — the re-mesh path PARITY.md advertises: leaves land on the NEW
        mesh's shardings (tp=4 -> 1 head per device) with identical values."""
        from akka_allreduce_tpu.models import data
        from akka_allreduce_tpu.parallel import data_seq_model_mesh
        from akka_allreduce_tpu.train import (
            LongContextTrainer,
            TrainerCheckpointer,
        )

        kw = dict(
            vocab=16, d_model=32, n_heads=4, n_layers=1, seq_len=32,
            learning_rate=1e-2,
        )
        t = LongContextTrainer(data_seq_model_mesh(2, 2, 2), seed=0, **kw)
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(4, 1))
        t.train_step(x, y)
        with TrainerCheckpointer(tmp_path / "remesh") as ckpt:
            assert ckpt.save(t)
            other = LongContextTrainer(
                data_seq_model_mesh(1, 2, 4), seed=7, **kw
            )
            assert ckpt.restore(other) == 1
        np.testing.assert_array_equal(
            other.get_flat_params(), t.get_flat_params()
        )
        q = other.params["params"]["Block_0"]["Attention_0"]["q"]["kernel"]
        assert q.addressable_shards[0].data.shape == (32, 1, 8)  # tp=4
        m = other.train_step(*next(ds.batches(4, 1, seed_offset=3)))
        assert np.isfinite(m.loss)


class TestErrorFeedbackCheckpoint:
    """The EF residual is training state: save/restore must carry it, and a
    re-mesh must preserve its SUM (the mass the collective is still owed)."""

    def _trainer(self, n, seed=0):
        import optax

        from akka_allreduce_tpu.models import MLP
        from akka_allreduce_tpu.parallel import line_mesh
        from akka_allreduce_tpu.train import DPTrainer

        return DPTrainer(
            MLP(hidden=(8,), classes=10),
            line_mesh(n),
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.sgd(0.1),
            seed=seed,
            compress="bf16",
            error_feedback=True,
        )

    def test_checkpoint_roundtrips_residual(self, tmp_path):
        from akka_allreduce_tpu.models import data
        from akka_allreduce_tpu.train import TrainerCheckpointer

        t = self._trainer(8)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        valid = np.ones(8, np.float32)
        valid[3] = 0.0  # device 3's whole gradient lives only in _ef
        t.train_step(x, y, valid)
        ef_before = np.asarray(t._ef)
        assert np.linalg.norm(ef_before[3]) > 0
        with TrainerCheckpointer(tmp_path / "ef") as ckpt:
            assert ckpt.save(t)
            fresh = self._trainer(8, seed=9)
            ckpt.restore(fresh)
        np.testing.assert_array_equal(np.asarray(fresh._ef), ef_before)

    def test_snapshot_remesh_preserves_residual_sum(self):
        from akka_allreduce_tpu.models import data
        from akka_allreduce_tpu.train import Snapshot

        t8 = self._trainer(8)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        t8.train_step(x, y, valid=[1, 1, 1, 0, 1, 1, 1, 1])
        snap = Snapshot.capture(t8)
        t4 = self._trainer(4, seed=9)  # re-mesh: 8 -> 4 devices
        snap.restore_into(t4)
        np.testing.assert_allclose(
            np.asarray(t4._ef).sum(axis=0),
            np.asarray(t8._ef).sum(axis=0),
            rtol=1e-5,
            atol=1e-7,
        )


class TestAsyncCheckpointer:
    """Async, non-stalling saves (VERDICT r3 next-round #2): capture is an
    on-device copy + async device-to-host launch; serialization runs
    off-thread; training keeps stepping (and donating its buffers) while
    the save is in flight. Crash mid-save must leave the previous
    checkpoint intact."""

    def test_state_is_capture_time_not_write_time(self, tmp_path):
        from akka_allreduce_tpu.train import AsyncTrainerCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 2))
        ref = t.get_flat_params().copy()
        with AsyncTrainerCheckpointer(tmp_path / "a") as ckpt:
            assert ckpt.save(t)
            # training continues immediately; step buffers are donated,
            # which must not corrupt the in-flight copy
            t.train(ds.batches(32, 3, seed_offset=5))
            assert not np.allclose(t.get_flat_params(), ref)
            ckpt.wait_until_finished()
            fresh = make_trainer(line_mesh(8), seed=3)
            step = ckpt.restore(fresh)
        assert step == 2
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)

    def test_first_save_from_the_writer_thread_imports_nothing(
        self, tmp_path, run_fresh
    ):
        """Orbax is loaded when the checkpointer is BUILT (train/checkpoint.py
        ``_orbax``): the first save, on the writer thread, finds it there."""
        got = run_fresh(_ASYNC_FIRST_SAVE, str(tmp_path / "a"))
        extra = got["extra"]
        assert extra["step"] == 1 and extra["equal"]
        # the save's use of the library ran on the writer thread ...
        assert extra["callers"][0] == "ckpt-save-1"
        # ... and found it loaded: one import, timed at construction
        assert got["spans"] == 1
        assert got["import_s"] == extra["built_import_s"] > 0

    def test_second_save_skipped_while_busy(self, tmp_path, monkeypatch):
        import threading

        from akka_allreduce_tpu.train import AsyncTrainerCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        with AsyncTrainerCheckpointer(tmp_path / "b") as ckpt:
            # hold the background write at a gate so busy() is deterministic
            gate = threading.Event()
            real_save = ckpt._mgr.save

            def slow_save(*a, **k):
                assert gate.wait(30)
                return real_save(*a, **k)

            monkeypatch.setattr(ckpt._mgr, "save", slow_save)
            assert ckpt.save(t)
            t.train(ds.batches(32, 1, seed_offset=1))
            assert not ckpt.save(t)  # busy -> skipped, not queued
            gate.set()
            ckpt.wait_until_finished()
            assert ckpt.latest_step() == 1
            # not busy anymore: the next interval's save goes through
            assert ckpt.save(t, block=True)
            assert ckpt.latest_step() == 2

    def test_custom_protocol_trainer_async(self, tmp_path):
        from akka_allreduce_tpu.models import MLP
        from akka_allreduce_tpu.train import (
            AsyncTrainerCheckpointer,
            Zero1DPTrainer,
        )

        t = Zero1DPTrainer(
            MLP(hidden=(16,), classes=10),
            line_mesh(8),
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.adam(1e-3),
            seed=0,
        )
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(32, 1)))
        t.train_step(x, y)
        ref = t.get_flat_params().copy()
        with AsyncTrainerCheckpointer(tmp_path / "z") as ckpt:
            assert ckpt.save(t)
            t.train_step(x, y)  # keep going while the write runs
            ckpt.wait_until_finished()
            fresh = Zero1DPTrainer(
                MLP(hidden=(16,), classes=10),
                line_mesh(8),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.adam(1e-3),
                seed=7,
            )
            assert ckpt.restore(fresh) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)

    def test_background_failure_surfaces(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.train import AsyncTrainerCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        ckpt = AsyncTrainerCheckpointer(tmp_path / "f")
        monkeypatch.setattr(
            ckpt._mgr, "save",
            lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")),
        )
        assert ckpt.save(t)
        with pytest.raises(RuntimeError, match="disk full"):
            ckpt.wait_until_finished()

    def test_crash_mid_save_preserves_old_checkpoint(self, tmp_path):
        """SIGKILL a writer process mid-save: the previous step must stay
        the latest durable checkpoint and restore cleanly (Orbax finalizes
        step directories atomically)."""
        import os
        import signal
        import subprocess
        import sys
        import textwrap
        import time as _time

        d = tmp_path / "crash"
        script = textwrap.dedent(f"""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np, optax, jax
            jax.config.update("jax_platforms", "cpu")
            from akka_allreduce_tpu.models import MLP, data
            from akka_allreduce_tpu.parallel import line_mesh
            from akka_allreduce_tpu.train import (
                AsyncTrainerCheckpointer, DPTrainer,
            )
            t = DPTrainer(
                MLP(hidden=(256, 256), classes=10), line_mesh(1),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.adam(1e-3), seed=0,
            )
            ds = data.mnist_like()
            t.train(ds.batches(8, 1))
            ckpt = AsyncTrainerCheckpointer({str(d)!r})
            ckpt.save(t, block=True)   # step 1: durable baseline
            t.train(ds.batches(8, 1, seed_offset=1))
            ckpt.save(t)               # step 2: async, about to be killed
            print("SAVING", flush=True)
            import time; time.sleep(30)
        """)
        proc = subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        try:
            line = proc.stdout.readline().decode()
            assert "SAVING" in line, line
            # kill while the step-2 write is (likely) in flight
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        _time.sleep(0.2)
        ckpt = TrainerCheckpointer(d)
        latest = ckpt.latest_step()
        assert latest is not None, "baseline checkpoint lost"
        fresh = DPTrainer_for_crash_test()
        step = ckpt.restore(fresh, latest)
        assert step == latest >= 1
        assert np.isfinite(fresh.get_flat_params()).all()


def DPTrainer_for_crash_test():
    from akka_allreduce_tpu.models import MLP
    from akka_allreduce_tpu.train import DPTrainer

    return DPTrainer(
        MLP(hidden=(256, 256), classes=10),
        line_mesh(1),
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        optimizer=optax.adam(1e-3),
        seed=5,
    )


class TestAsyncShardLocalCapture:
    """VERDICT r4 #1: sharded-state trainers (ZeRO-1 / FSDP / Pipeline)
    checkpoint asynchronously WITHOUT a capture-phase gather — capture is
    an on-device copy of each trainer's own shards; the unshard/serialize
    (``checkpoint_assemble``) runs on the writer thread."""

    def _fsdp(self, seed=0):
        from akka_allreduce_tpu.train import FSDPLMTrainer

        return FSDPLMTrainer(
            line_mesh(8), vocab=16, d_model=32, n_heads=4, n_layers=2,
            seq_len=32, optimizer=optax.adam(1e-3), seed=seed,
        )

    def _pp(self, seed=0):
        import jax

        from akka_allreduce_tpu.train import PipelineLMTrainer

        mesh = jax.make_mesh((2, 4), ("data", "pipe"))
        return PipelineLMTrainer(
            mesh, layers_per_stage=1, vocab=16, d_model=32, n_heads=4,
            microbatches=2, seq_len=32, learning_rate=1e-2, seed=seed,
        )

    def _no_sync_gather(self, monkeypatch, t):
        """Fail the test if the synchronous gathering path runs on the
        caller thread during an async save."""

        def boom(*a, **k):
            raise AssertionError(
                "checkpoint_state (sync gather) called during async save"
            )

        monkeypatch.setattr(t, "checkpoint_state", boom)

    def test_fsdp_async_no_gather_in_capture(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.models import data as mdata
        from akka_allreduce_tpu.train import AsyncTrainerCheckpointer

        t = self._fsdp()
        ds = mdata.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(8, 1))
        t.train_step(x, y)
        ref = _flat_tree(t.gathered_params())
        self._no_sync_gather(monkeypatch, t)
        with AsyncTrainerCheckpointer(tmp_path / "f") as ckpt:
            assert ckpt.save(t)
            t.train_step(x, y)  # donation while the transfer is in flight
            ckpt.wait_until_finished()
            fresh = self._fsdp(seed=9)
            assert ckpt.restore(fresh) == 1
        np.testing.assert_array_equal(_flat_tree(fresh.gathered_params()), ref)
        # capture really was shard-local: every captured trunk leaf is a
        # device array sharded over the mesh, not a host gather
        import jax

        cap = t.checkpoint_capture()
        trunk = jax.tree.leaves(cap["params"]["trunk"])
        assert all(isinstance(l, jax.Array) for l in trunk)
        # each device holds strictly less than the full leaf (no gather)
        assert all(
            l.addressable_shards[0].data.shape[1] < l.shape[1] for l in trunk
        )

    def test_pipeline_async_roundtrip(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.models import data as mdata
        from akka_allreduce_tpu.train import AsyncTrainerCheckpointer

        t = self._pp()
        ds = mdata.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(4, 1))
        t.train_step(x, y)
        ref = t.get_flat_params().copy()
        self._no_sync_gather(monkeypatch, t)
        with AsyncTrainerCheckpointer(tmp_path / "p") as ckpt:
            assert ckpt.save(t)
            t.train_step(x, y)
            ckpt.wait_until_finished()
            fresh = self._pp(seed=9)
            assert ckpt.restore(fresh) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)

    def test_zero1_async_no_gather_with_ef(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.models import MLP
        from akka_allreduce_tpu.train import (
            AsyncTrainerCheckpointer,
            Zero1DPTrainer,
        )

        def mk(seed):
            return Zero1DPTrainer(
                MLP(hidden=(16,), classes=10), line_mesh(8),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.adam(1e-3), seed=seed,
                compress="bf16", error_feedback=True,
            )

        t = mk(0)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        t.train_step(x, y, valid=[1, 1, 1, 0, 1, 1, 1, 1])
        ref = t.get_flat_params().copy()
        ef_sum = np.asarray(t._ef).sum(axis=0)[: t.param_count].copy()
        self._no_sync_gather(monkeypatch, t)
        with AsyncTrainerCheckpointer(tmp_path / "z") as ckpt:
            assert ckpt.save(t)
            ckpt.wait_until_finished()
            fresh = mk(9)
            assert ckpt.restore(fresh) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)
        np.testing.assert_allclose(
            np.asarray(fresh._ef).sum(axis=0)[: fresh.param_count],
            ef_sum, rtol=1e-6, atol=1e-7,
        )


class TestAsyncDeltaCheckpointer:
    """VERDICT r4 #1 second half: link-sized (delta) saves that also do
    not stall — hashing and blob writes run on the writer thread over the
    same non-gathering capture."""

    def test_roundtrip_stats_and_dedup(self, tmp_path):
        from akka_allreduce_tpu.train import AsyncDeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        ref = t.get_flat_params().copy()
        store = AsyncDeltaCheckpointer(tmp_path / "ad")
        assert store.save(t)
        store.wait_until_finished()
        s1 = store.last_stats
        assert s1["written_leaves"] > 0 and s1["reused_leaves"] == 0

        # identical immediate re-save: every blob reused, zero bytes
        assert store.save(t, block=True)
        s2 = store.last_stats
        assert s2["written_bytes"] == 0
        assert s2["reused_leaves"] == s1["written_leaves"]

        t.train(ds.batches(32, 2, seed_offset=5))  # diverge
        fresh = make_trainer(line_mesh(8), seed=3)
        assert store.restore(fresh, 1) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)

    def test_busy_skip_then_next_save(self, tmp_path, monkeypatch):
        import threading

        from akka_allreduce_tpu.train import AsyncDeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        store = AsyncDeltaCheckpointer(tmp_path / "busy")
        gate = threading.Event()
        real = store._write_delta

        def slow(*a, **k):
            assert gate.wait(30)
            return real(*a, **k)

        monkeypatch.setattr(store, "_write_delta", slow)
        assert store.save(t)
        assert not store.save(t)  # busy -> skipped, not queued
        gate.set()
        store.wait_until_finished()
        assert store.latest_step() == 1

    def test_fsdp_shard_local_delta(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.models import data as mdata
        from akka_allreduce_tpu.train import (
            AsyncDeltaCheckpointer,
            FSDPLMTrainer,
        )

        def mk(seed):
            return FSDPLMTrainer(
                line_mesh(8), vocab=16, d_model=32, n_heads=4, n_layers=2,
                seq_len=32, optimizer=optax.adam(1e-3), seed=seed,
            )

        t = mk(0)
        ds = mdata.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(8, 1))
        t.train_step(x, y)
        ref = _flat_tree(t.gathered_params())

        def boom(*a, **k):
            raise AssertionError("sync gather during async delta save")

        monkeypatch.setattr(t, "checkpoint_state", boom)
        store = AsyncDeltaCheckpointer(tmp_path / "fd")
        assert store.save(t, block=True)
        assert store.last_stats["written_leaves"] > 0
        fresh = mk(9)
        assert store.restore(fresh) == 1
        np.testing.assert_array_equal(_flat_tree(fresh.gathered_params()), ref)

    def test_background_failure_surfaces(self, tmp_path, monkeypatch):
        from akka_allreduce_tpu.train import AsyncDeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        store = AsyncDeltaCheckpointer(tmp_path / "err")
        monkeypatch.setattr(
            store, "_write_delta",
            lambda *a, **k: (_ for _ in ()).throw(OSError("disk full")),
        )
        assert store.save(t)
        with pytest.raises(RuntimeError, match="disk full"):
            store.wait_until_finished()

    def test_crash_mid_save_preserves_old_delta(self, tmp_path):
        """SIGKILL a writer mid-delta-save: the previous manifest must stay
        the latest durable step and restore cleanly (manifests publish via
        atomic rename; a crash leaves orphan blobs/.tmp files the next
        save's prune sweeps, never a torn manifest)."""
        import os
        import signal
        import subprocess
        import sys
        import textwrap
        import time as _time

        d = tmp_path / "dcrash"
        script = textwrap.dedent(f"""
            import os
            os.environ["JAX_PLATFORMS"] = "cpu"
            import numpy as np, optax, jax
            jax.config.update("jax_platforms", "cpu")
            from akka_allreduce_tpu.models import MLP, data
            from akka_allreduce_tpu.parallel import line_mesh
            from akka_allreduce_tpu.train import (
                AsyncDeltaCheckpointer, DPTrainer,
            )
            t = DPTrainer(
                MLP(hidden=(256, 256), classes=10), line_mesh(1),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.adam(1e-3), seed=0,
            )
            ds = data.mnist_like()
            t.train(ds.batches(8, 1))
            store = AsyncDeltaCheckpointer({str(d)!r})
            store.save(t, block=True)   # step 1: durable baseline
            t.train(ds.batches(8, 1, seed_offset=1))
            store.save(t)               # step 2: async, about to be killed
            print("SAVING", flush=True)
            import time; time.sleep(30)
        """)
        proc = subprocess.Popen(
            [sys.executable, "-c", script],
            stdout=subprocess.PIPE,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        )
        try:
            line = proc.stdout.readline().decode()
            assert "SAVING" in line, line
            os.kill(proc.pid, signal.SIGKILL)
            proc.wait(timeout=30)
        finally:
            if proc.poll() is None:
                proc.kill()
        _time.sleep(0.2)
        from akka_allreduce_tpu.train import DeltaCheckpointer

        store = DeltaCheckpointer(d)
        latest = store.latest_step()
        assert latest is not None, "baseline delta checkpoint lost"
        fresh = DPTrainer_for_crash_test()
        step = store.restore(fresh, latest)
        assert step == latest >= 1
        assert np.isfinite(fresh.get_flat_params()).all()
        # a fresh save sweeps any crash orphans (.tmp blobs/manifests)
        fresh.step_num += 1
        store.save(fresh)
        assert not list(store.blobs.glob("*.tmp"))
        assert not list(store.directory.glob(".manifest_*.tmp"))


class TestDeltaCheckpointer:
    """Per-leaf content-addressed delta saves: unchanged leaves cost zero
    bytes, blobs dedupe across steps, pruning drops unreferenced blobs."""

    def test_roundtrip_and_dedup(self, tmp_path):
        from akka_allreduce_tpu.train import DeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        store = DeltaCheckpointer(tmp_path / "d")
        s1 = store.save(t)
        assert s1["written_leaves"] > 0 and s1["reused_leaves"] == 0
        ref = t.get_flat_params().copy()

        # an IDENTICAL immediate re-save reuses every blob
        s2 = store.save(t)
        assert s2["written_bytes"] == 0
        assert s2["reused_leaves"] == s1["written_leaves"]

        # another step changes params + both adam moments, but count-like
        # scalars and unchanged leaves still dedupe partially or fully;
        # at minimum the manifest-level roundtrip must hold
        t.train(ds.batches(32, 1, seed_offset=1))
        store.save(t)
        fresh = make_trainer(line_mesh(8), seed=3)
        assert store.restore(fresh, 1) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)

    def test_roundtrip_in_a_process_that_never_loads_orbax(
        self, tmp_path, run_fresh
    ):
        got = run_fresh(_DELTA_WITHOUT_ORBAX, str(tmp_path / "d"))
        assert got["deferred"] == [] and got["import_s"] is None
        extra = got["extra"]
        assert extra["written"] > 0 and extra["step"] == 1
        assert extra["equal"] and extra["snap_equal"]

    def test_partial_change_writes_only_delta(self, tmp_path):
        from akka_allreduce_tpu.train import DeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        t.train(ds.batches(32, 1))
        store = DeltaCheckpointer(tmp_path / "p")
        store.save(t)
        # mutate ONE leaf only (a frozen-most-of-the-model scenario)
        import jax

        leaves, treedef = jax.tree.flatten(t.params)
        leaves[0] = leaves[0] + 1.0
        t.params = jax.tree.unflatten(treedef, leaves)
        t.step_num += 1
        s = store.save(t)
        assert s["written_leaves"] == 1, s
        assert s["reused_leaves"] > 0

    def test_prune_drops_unreferenced_blobs(self, tmp_path):
        from akka_allreduce_tpu.train import DeltaCheckpointer

        t = make_trainer(line_mesh(8))
        ds = data.mnist_like()
        store = DeltaCheckpointer(tmp_path / "k", max_to_keep=2)
        for i in range(4):
            t.train(ds.batches(32, 1, seed_offset=i))
            store.save(t)
        steps = sorted(store._manifests())
        assert steps == [3, 4]
        # every kept blob is referenced by a kept manifest
        import json

        live = set()
        for f in store._manifests().values():
            live.update(json.loads(f.read_text())["leaves"].values())
        on_disk = {b.stem for b in store.blobs.glob("*.npy")}
        assert on_disk == live

    def test_max_to_keep_must_be_positive(self, tmp_path):
        from akka_allreduce_tpu.train import DeltaCheckpointer

        with pytest.raises(ValueError, match="max_to_keep"):
            DeltaCheckpointer(tmp_path / "bad", max_to_keep=0)

    def test_restore_zeroes_stale_ef_when_checkpoint_has_none(self, tmp_path):
        """ADVICE r4: restoring a no-EF checkpoint into a trainer with a
        live nonzero residual must zero it — post-restore state is purely
        the saved state."""
        from akka_allreduce_tpu.train import DeltaCheckpointer

        def mk_ef(seed):
            return DPTrainer(
                MLP(hidden=(8,), classes=10), line_mesh(8),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.sgd(0.1), seed=seed,
                compress="bf16", error_feedback=True,
            )

        import jax

        ds = data.mnist_like()
        t = mk_ef(3)
        x, y = next(iter(ds.batches(64, 1)))
        t.train_step(x, y, valid=[1, 1, 1, 0, 1, 1, 1, 1])
        assert np.linalg.norm(np.asarray(t._ef)) > 0  # live stale residual
        # a checkpoint of the same structure but WITHOUT ef leaves
        # (simulates an older no-EF save)
        t2 = mk_ef(5)
        t2.train_step(x, y)
        store = DeltaCheckpointer(tmp_path / "ef1")
        host = jax.tree.map(
            np.asarray, {"params": t2.params, "opt_state": t2.opt_state}
        )
        store._write_delta(host, False, int(t2.step_num))

        t.step_num = t2.step_num
        store.restore(t)
        assert np.linalg.norm(np.asarray(t._ef)) == 0.0

    def test_custom_protocol_trainer(self, tmp_path):
        from akka_allreduce_tpu.models import MLP
        from akka_allreduce_tpu.train import DeltaCheckpointer, Zero1DPTrainer

        def mk(seed):
            return Zero1DPTrainer(
                MLP(hidden=(16,), classes=10),
                line_mesh(8),
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.adam(1e-3),
                seed=seed,
            )

        t = mk(0)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(32, 1)))
        t.train_step(x, y)
        ref = t.get_flat_params().copy()
        store = DeltaCheckpointer(tmp_path / "z")
        store.save(t)
        fresh = mk(7)
        assert store.restore(fresh) == 1
        np.testing.assert_array_equal(fresh.get_flat_params(), ref)


# --- corruption-on-crash regression (ISSUE 6 satellite; no trainer needed) ----


class TestDeltaDurability:
    """A crash mid-save must never publish a manifest that names torn or
    unsynced chunk files. These drive ``_write_delta`` on plain host dicts
    (the writer-thread half), so they run even where the XLA trainer
    suites cannot."""

    def test_crash_between_blobs_publishes_no_manifest(self, tmp_path, monkeypatch):
        """Simulated crash after the first blob, before the second: no
        manifest becomes visible (old latest_step is preserved), and no
        half-written temp file is left masquerading as a manifest."""
        from akka_allreduce_tpu.train.checkpoint import DeltaCheckpointer

        d = DeltaCheckpointer(tmp_path / "ckpt")
        d._write_delta({"a": np.zeros(4, np.float32)}, False, 1)
        calls = {"n": 0}
        real_save = np.save

        def dying_save(f, arr, **kw):
            calls["n"] += 1
            if calls["n"] == 2:
                raise OSError("simulated crash mid-save")
            return real_save(f, arr, **kw)

        monkeypatch.setattr(np, "save", dying_save)
        with pytest.raises(OSError):
            d._write_delta(
                {
                    "a": np.ones(4, np.float32),
                    "b": np.full(4, 2.0, np.float32),
                },
                False,
                2,
            )
        monkeypatch.undo()
        # the torn save is invisible: step 1 is still the newest manifest
        assert d.latest_step() == 1
        assert not (d.directory / "manifest_2.json").exists()
        # and the next prune sweeps the orphan temp files (crash recovery)
        d._write_delta({"a": np.zeros(4, np.float32)}, False, 3)
        assert not list(d.blobs.glob("*.tmp"))
        assert not list(d.directory.glob(".manifest_*.tmp"))

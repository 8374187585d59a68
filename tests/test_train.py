"""Trainer + model tests (BASELINE configs 3-4 scaled to the CPU test mesh).

Convergence-to-parity oracle (BASELINE.md row 3): an n-device DP run on a
global batch must match a single-device run on the same batch step for step,
because the masked average of per-shard mean gradients equals the full-batch
mean gradient.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import MLP, ResNet50, data
from akka_allreduce_tpu.parallel import grid_mesh, line_mesh
from akka_allreduce_tpu.train import DPTrainer


@pytest.fixture(scope="module")
def line8():
    return line_mesh(8)


def mlp_trainer(mesh, lr=0.1, bucket=None, seed=0):
    model = MLP(hidden=(32,), classes=10)
    return DPTrainer(
        model,
        mesh,
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        learning_rate=lr,
        bucket_size=bucket,
        seed=seed,
    )


class TestMLPTraining:
    def test_loss_decreases(self, line8):
        t = mlp_trainer(line8)
        ds = data.mnist_like()
        hist = t.train(ds.batches(64, 30))
        assert hist[0].contributors == 8.0
        first5 = np.mean([h.loss for h in hist[:5]])
        last5 = np.mean([h.loss for h in hist[-5:]])
        assert last5 < first5 * 0.7, (first5, last5)
        acc_batch = next(iter(ds.batches(256, 1, seed_offset=99)))
        assert t.accuracy(*acc_batch) > 0.5

    def test_multi_device_matches_single_device(self, line8):
        t8 = mlp_trainer(line8, seed=3)
        t1 = mlp_trainer(line_mesh(1), seed=3)
        ds = data.mnist_like()
        batches = list(ds.batches(64, 3))
        t8.train(iter(batches))
        t1.train(iter(batches))
        np.testing.assert_allclose(
            t8.get_flat_params(), t1.get_flat_params(), rtol=2e-4, atol=2e-5
        )

    def test_bucketed_matches_unbucketed(self, line8):
        tb = mlp_trainer(line8, bucket=1000, seed=1)
        tu = mlp_trainer(line8, seed=1)
        ds = data.mnist_like()
        batches = list(ds.batches(32, 3))
        tb.train(iter(batches))
        tu.train(iter(batches))
        np.testing.assert_allclose(
            tb.get_flat_params(), tu.get_flat_params(), rtol=2e-4, atol=2e-5
        )

    def test_masked_devices_do_not_contribute(self, line8):
        # devices 6,7 masked out -> equals a 6-shard run on the same shards
        t = mlp_trainer(line8, seed=5)
        ref_params = t.get_flat_params()
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        valid = np.array([1, 1, 1, 1, 1, 1, 0, 0], np.float32)
        m = t.train_step(x, y, valid)
        assert m.contributors == 6.0

        # oracle: single-device trainer on only the first 6 shards
        t_o = mlp_trainer(line_mesh(1), seed=5)
        np.testing.assert_allclose(ref_params, t_o.get_flat_params(), atol=1e-6)
        shard = 64 // 8
        t_o.train_step(x[: 6 * shard], y[: 6 * shard])
        np.testing.assert_allclose(
            t.get_flat_params(), t_o.get_flat_params(), rtol=2e-4, atol=2e-5
        )

    def test_butterfly_grid_mesh_trains(self):
        t = mlp_trainer(grid_mesh(2, 4))
        ds = data.mnist_like()
        hist = t.train(ds.batches(64, 5))
        assert len(hist) == 5
        assert hist[-1].contributors == 8.0

    def test_rejects_bad_batch_and_mask(self, line8):
        t = mlp_trainer(line8)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(60, 1)))  # 60 % 8 != 0
        with pytest.raises(ValueError, match="divisible"):
            t.train_step(x, y)
        x, y = next(iter(ds.batches(64, 1)))
        with pytest.raises(ValueError, match="valid"):
            t.train_step(x, y, valid=[1.0, 0.0])


class TestGradAccumulation:
    """Microbatched steps: one collective per effective batch, numerically
    identical to a single step on the concatenated batch."""

    def test_accum_matches_full_batch_step(self, line8):
        a, b = mlp_trainer(line8, seed=0), mlp_trainer(line8, seed=0)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        m_full = a.train_step(x, y)
        m_acc = b.train_step_accum(x, y, accum_steps=4)
        assert abs(m_full.loss - m_acc.loss) < 1e-5
        fa = np.concatenate([np.ravel(p) for p in jax.tree.leaves(a.params)])
        fb = np.concatenate([np.ravel(p) for p in jax.tree.leaves(b.params)])
        np.testing.assert_allclose(fa, fb, atol=2e-5)

    def test_accum_bucketed_matches_full_batch_step(self, line8):
        """Accumulation composes with the bucketed (chunked) collective."""
        a = mlp_trainer(line8, seed=0, bucket=4096)
        b = mlp_trainer(line8, seed=0, bucket=4096)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        m_full = a.train_step(x, y)
        m_acc = b.train_step_accum(x, y, accum_steps=2)
        assert abs(m_full.loss - m_acc.loss) < 1e-5
        fa = np.concatenate([np.ravel(p) for p in jax.tree.leaves(a.params)])
        fb = np.concatenate([np.ravel(p) for p in jax.tree.leaves(b.params)])
        np.testing.assert_allclose(fa, fb, atol=2e-5)

    def test_accum_masked_devices(self, line8):
        trainer = mlp_trainer(line8)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(32, 1)))
        valid = np.ones(8, np.float32)
        valid[0] = 0.0
        m = trainer.train_step_accum(x, y, accum_steps=2, valid=valid)
        assert m.contributors == 7.0 and np.isfinite(m.loss)

    def test_accum_rejects_indivisible(self, line8):
        trainer = mlp_trainer(line8)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(40, 1)))
        with pytest.raises(ValueError):
            trainer.train_step_accum(x, y, accum_steps=3)


class TestTrainChain:
    """On-device training chain: data sampled per device inside the jitted
    scan, zero host I/O per step (the data-loader path)."""

    def test_chain_loss_decreases(self, line8):
        trainer = mlp_trainer(line8)
        sampler = data.mnist_like().device_sampler()
        history = trainer.train_chain(sampler, steps=25, batch_per_device=8)
        assert len(history) == 25
        assert trainer.step_num == 25
        assert history[-1].step == 25
        assert np.mean([m.loss for m in history[-5:]]) < history[0].loss / 2

    def test_chain_masked_contributors(self, line8):
        trainer = mlp_trainer(line8)
        sampler = data.mnist_like().device_sampler()
        valid = np.ones(8, np.float32)
        valid[2] = valid[5] = 0.0
        history = trainer.train_chain(
            sampler, steps=4, batch_per_device=4, valid=valid
        )
        assert all(m.contributors == 6.0 for m in history)
        assert all(np.isfinite(m.loss) for m in history)

    def test_consecutive_chains_advance_the_data_stream(self, line8):
        """Back-to-back chain calls must continue the stream, not replay the
        same batches (step_num is folded into the chain key)."""
        trainer = mlp_trainer(line8, lr=1e-4)  # tiny lr: params ~ constant
        sampler = data.mnist_like().device_sampler()
        first = [m.loss for m in trainer.train_chain(sampler, 3, 4)]
        second = [m.loss for m in trainer.train_chain(sampler, 3, 4)]
        # same batches on near-identical params would give near-identical
        # losses; fresh batches give distinctly different ones
        assert not np.allclose(first, second, rtol=1e-3), (first, second)

    def test_chain_then_host_steps_compose(self, line8):
        trainer = mlp_trainer(line8)
        sampler = data.mnist_like().device_sampler()
        trainer.train_chain(sampler, steps=5, batch_per_device=4)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(32, 1)))
        m = trainer.train_step(x, y)
        assert m.step == 6 and np.isfinite(m.loss)


class TestResNet:
    def test_resnet50_param_count_matches_reference_buffer(self):
        # BASELINE.json:10: 25M-param chunked buffer
        model = ResNet50(classes=1000)
        t = DPTrainer(
            model,
            line_mesh(1),
            example_input=np.zeros((1, 32, 32, 3), np.float32),
            learning_rate=0.1,
        )
        assert 24_000_000 < t.param_count < 27_000_000, t.param_count

    def test_resnet_small_trains_on_mesh(self, line8):
        # scaled-down ResNet (same block structure) so the CPU mesh stays fast
        model = ResNet50(classes=10)
        t = DPTrainer(
            model,
            line8,
            example_input=np.zeros((1, 32, 32, 3), np.float32),
            learning_rate=0.05,
            bucket_size=262_144,  # the reference's chunked-buffer geometry
        )
        ds = data.SyntheticClassification((32, 32, 3), 10, seed=2)
        hist = t.train(ds.batches(16, 2))
        assert len(hist) == 2 and np.isfinite(hist[-1].loss)


class TestCompressedGradSync:
    """bf16 gradient sync: collective payload halves on the wire, params stay
    close to the f32 run, training still converges."""

    def _trainer(self, mesh, seed=0, bucket=None, compress=None):
        return DPTrainer(
            MLP(hidden=(32,), classes=10),
            mesh,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            learning_rate=0.1,
            bucket_size=bucket,
            seed=seed,
            compress=compress,
        )

    def test_bf16_close_to_f32_and_converges(self, line8):
        tc = self._trainer(line8, seed=2, compress="bf16")
        tf = self._trainer(line8, seed=2)
        ds = data.mnist_like()
        batches = list(ds.batches(64, 10))
        hc = tc.train(iter(batches))
        tf.train(iter(batches))
        # per-step grads agree to bf16 precision; after 10 steps params stay close
        a, b = tc.get_flat_params(), tf.get_flat_params()
        scale = np.abs(b).max()
        assert np.abs(a - b).max() / scale < 5e-2
        assert hc[-1].loss < hc[0].loss
        assert hc[0].contributors == 8.0

    def test_bf16_with_buckets_and_mask(self, line8):
        t = self._trainer(line8, seed=4, bucket=1000, compress="bf16")
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(16, 1)))
        valid = np.ones(8, np.float32)
        valid[3] = 0.0
        m = t.train_step(x, y, valid)
        assert m.contributors == 7.0 and np.isfinite(m.loss)

    def test_bf16_accum_path(self, line8):
        t = self._trainer(line8, seed=6, compress="bf16")
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(32, 1)))
        m = t.train_step_accum(x, y, accum_steps=2)
        assert m.contributors == 8.0 and np.isfinite(m.loss)

    def test_rejects_unknown_mode(self, line8):
        with pytest.raises(ValueError, match="compress"):
            self._trainer(line8, compress="fp4")


def test_compress_bucketed_accum_masked_combo(line8):
    """bf16 wire x bucketed grads x gradient accumulation x dropped replica —
    the full stack of DPTrainer options in one step."""
    t = DPTrainer(
        MLP(hidden=(32,), classes=10),
        line8,
        example_input=np.zeros((1, 28, 28, 1), np.float32),
        learning_rate=0.1,
        bucket_size=1000,
        compress="bf16",
    )
    ds = data.mnist_like()
    x, y = next(iter(ds.batches(32, 1)))
    valid = np.ones(8, np.float32)
    valid[5] = 0.0
    m = t.train_step_accum(x, y, accum_steps=2, valid=valid)
    assert m.contributors == 7.0 and np.isfinite(m.loss)


class TestErrorFeedback:
    """EF compression: c = g + e, send cast(c*v), e' = c - sent — lossy sync
    becomes unbiased over time, and a masked device's whole contribution
    carries forward instead of being lost."""

    def _make(self, line8, compress=None, ef=False, seed=0):
        import optax

        return DPTrainer(
            MLP(hidden=(32,), classes=10),
            line8,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.sgd(0.1),
            seed=seed,
            compress=compress,
            error_feedback=ef,
        )

    def test_trains_and_stays_close_to_f32(self, line8):
        t_f32 = self._make(line8)
        t_ef = self._make(line8, "bf16", True)
        ds = data.mnist_like()
        batches = list(ds.batches(64, 15))
        h = []
        for x, y in batches:
            t_f32.train_step(x, y)
            h.append(t_ef.train_step(x, y))
        assert h[-1].loss < h[0].loss
        drift = np.abs(t_ef.get_flat_params() - t_f32.get_flat_params()).max()
        scale = np.abs(t_f32.get_flat_params()).max()
        assert drift / scale < 1e-2
        # the residual is live (bf16 truncation error being carried)
        assert float(np.abs(np.asarray(t_ef._ef)).max()) > 0

    def test_masked_device_carries_full_contribution(self, line8):
        t = self._make(line8, "bf16", True)
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        valid = np.ones(8, np.float32)
        valid[3] = 0.0
        m = t.train_step(x, y, valid)
        assert m.contributors == 7.0
        ef = np.asarray(t._ef)
        masked_norm = np.linalg.norm(ef[3])
        other = max(
            np.linalg.norm(ef[i]) for i in range(8) if i != 3
        )
        # the dropped device withheld its WHOLE gradient; contributors only
        # carry bf16 truncation crumbs
        assert masked_norm > 50 * other, (masked_norm, other)

    def test_requires_compress(self, line8):
        with pytest.raises(ValueError, match="error_feedback"):
            self._make(line8, None, True)

    def test_accum_matches_plain_ef_step(self, line8):
        """EF over the accumulated mean gradient == EF over the full-batch
        gradient (same oracle discipline as test_accum_matches_full_batch_step:
        the mean of equal-size microbatch means IS the full-batch mean)."""
        t_step = self._make(line8, "bf16", True)
        t_accum = self._make(line8, "bf16", True)
        ds = data.mnist_like()
        valid = np.ones(8, np.float32)
        valid[5] = 0.0
        for i, (x, y) in enumerate(ds.batches(64, 4)):
            v = valid if i == 2 else None
            m1 = t_step.train_step(x, y, v)
            m2 = t_accum.train_step_accum(x, y, accum_steps=2, valid=v)
            assert m1.contributors == m2.contributors
        np.testing.assert_allclose(
            t_accum.get_flat_params(), t_step.get_flat_params(),
            rtol=1e-4, atol=1e-5,
        )
        # residuals are bf16-truncation dust: each element sits on a cast
        # rounding boundary (ulp scales with element magnitude, up to ~1e-4
        # here), so accum-vs-full reassociation flips individual elements and
        # only the magnitude CLASS is comparable — a banked masked-step
        # gradient surviving in one trainer but not the other would be ~1e-2
        diff = np.abs(np.asarray(t_accum._ef) - np.asarray(t_step._ef)).max()
        assert diff < 1e-3, diff

    def test_chain_matches_stepwise_ef(self, line8):
        """The EF chain must equal step-by-step EF on the SAME data. The
        chain's per-device batches are reconstructed on the host with the
        chain's exact key schedule (fold step_num, then the device's mesh
        coordinate, then the scan index) and fed to EF train_step, which runs
        the same explicit_step kernel — the step-by-step EF oracle."""
        import jax

        t_chain = self._make(line8, "bf16", True)
        t_steps = self._make(line8, "bf16", True)
        sampler = data.mnist_like().device_sampler()
        steps, bpd = 6, 4
        hist = t_chain.train_chain(sampler, steps, bpd)

        base = jax.random.fold_in(jax.random.PRNGKey(0), 0)  # seed=0, step 0
        hist2 = []
        for i in range(steps):
            xs, ys = [], []
            for d in range(8):
                k = jax.random.fold_in(jax.random.fold_in(base, d), i)
                x, y = sampler(k, bpd)
                xs.append(np.asarray(x))
                ys.append(np.asarray(y))
            hist2.append(
                t_steps.train_step(np.concatenate(xs), np.concatenate(ys))
            )
        for a, b in zip(hist, hist2):
            # per-step losses pin data equality + step equivalence tightly
            np.testing.assert_allclose(a.loss, b.loss, rtol=1e-5)
        # params drift only by compounded bf16 rounding chaos (a 1-ulp cast
        # difference in step k perturbs every later residual) — the same
        # <1e-2 relative bar as the EF-vs-f32 oracle above
        np.testing.assert_allclose(
            t_chain.get_flat_params(), t_steps.get_flat_params(),
            rtol=5e-3, atol=1e-5,
        )
        ef_diff = np.abs(
            np.asarray(t_chain._ef) - np.asarray(t_steps._ef)
        ).max()
        assert ef_diff < 1e-3, ef_diff  # dust, not a lost banked gradient
        assert hist[-1].loss < hist[0].loss
        # the residual is live after the chain
        assert float(np.abs(np.asarray(t_chain._ef)).max()) > 0

    def test_chain_masked_device_accumulates_residual(self, line8):
        t = self._make(line8, "bf16", True)
        valid = np.ones(8, np.float32)
        valid[3] = 0.0
        hist = t.train_chain(
            data.mnist_like().device_sampler(), 4, 4, valid=valid
        )
        assert all(m.contributors == 7.0 for m in hist)
        ef = np.asarray(t._ef)
        masked_norm = np.linalg.norm(ef[3])
        other = max(np.linalg.norm(ef[i]) for i in range(8) if i != 3)
        # the masked device banked four whole gradients; contributors only
        # carry bf16 truncation crumbs
        assert masked_norm > 50 * other, (masked_norm, other)


class TestInt8GradSync:
    """int8 grad sync on the explicit ring: quarter-width wire, per-segment
    max-abs scales; close to f32, exact counts, guarded combinations."""

    def _make(self, mesh, compress=None, seed=0):
        import optax

        return DPTrainer(
            MLP(hidden=(32,), classes=10),
            mesh,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.sgd(0.1),
            seed=seed,
            compress=compress,
        )

    def test_int8_close_to_f32_and_converges(self, line8):
        t8 = self._make(line8, "int8")
        tf = self._make(line8)
        ds = data.mnist_like()
        batches = list(ds.batches(64, 10))
        hist = []
        for x, y in batches:
            hist.append(t8.train_step(x, y))
            tf.train_step(x, y)
        assert hist[-1].loss < hist[0].loss
        a, b = t8.get_flat_params(), tf.get_flat_params()
        scale = np.abs(b).max()
        assert np.abs(a - b).max() / scale < 0.1

    def test_int8_masked_device(self, line8):
        t = self._make(line8, "int8")
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        valid = np.ones(8, np.float32)
        valid[2] = 0.0
        m = t.train_step(x, y, valid)
        assert m.contributors == 7.0 and np.isfinite(m.loss)

    def test_int8_chain_works(self, line8):
        t = self._make(line8, "int8")
        ds = data.mnist_like()
        hist = t.train_chain(ds.device_sampler(), 3, 4)
        assert len(hist) == 3 and np.isfinite(hist[-1].loss)

    def test_int8_accum_close_to_f32_accum(self, line8):
        """The accumulation path syncs the accumulated mean gradient through
        ONE int8 ring pass at scan end (VERDICT r3 #5a) — same quantization
        tolerance as the plain int8 step, exact contributor counts."""
        t8 = self._make(line8, "int8", seed=1)
        tf = self._make(line8, seed=1)
        ds = data.mnist_like()
        mask = np.ones(8, np.float32)
        mask[3] = 0.0
        for i, (x, y) in enumerate(ds.batches(64, 6)):
            v = mask if i == 2 else None
            m8 = t8.train_step_accum(x, y, 2, v)
            mf = tf.train_step_accum(x, y, 2, v)
            assert m8.contributors == mf.contributors
            assert np.isfinite(m8.loss)
        a, b = t8.get_flat_params(), tf.get_flat_params()
        assert np.abs(a - b).max() / np.abs(b).max() < 0.1

    def test_int8_rejects_grid_mesh(self, line8):
        from akka_allreduce_tpu.parallel import grid_mesh

        with pytest.raises(ValueError, match="ONE mesh axis"):
            self._make(grid_mesh(2, 4), "int8")

    def test_int8_ef_trains_and_tightens_drift(self, line8):
        """EF for the int8 ring (VERDICT r3 #7a): the residual compensates
        each device's FIRST-HOP quantization (the locally computable
        part); per-hop requantization of partial sums remains. Training
        must stay inside the int8 band of the f32 run and the residual
        must be live."""
        import optax

        def mk(compress=None, ef=False):
            return DPTrainer(
                MLP(hidden=(32,), classes=10),
                line8,
                example_input=np.zeros((1, 28, 28, 1), np.float32),
                optimizer=optax.sgd(0.1),
                seed=0,
                compress=compress,
                error_feedback=ef,
            )

        t_f32, t_ef = mk(), mk("int8", True)
        ds = data.mnist_like()
        h = []
        for x, y in ds.batches(64, 15):
            t_f32.train_step(x, y)
            h.append(t_ef.train_step(x, y))
        assert h[-1].loss < h[0].loss
        drift = np.abs(t_ef.get_flat_params() - t_f32.get_flat_params()).max()
        scale = np.abs(t_f32.get_flat_params()).max()
        assert drift / scale < 5e-2, drift / scale
        assert float(np.abs(np.asarray(t_ef._ef)).max()) > 0

    def test_int8_ef_chain_runs(self, line8):
        """The EF chain's shard_map needs the int8 check_vma relaxation
        (the ring's ppermute loop erases varying-axes typing) — pin that
        train_chain composes with compress='int8' + EF."""
        import optax

        t = DPTrainer(
            MLP(hidden=(16,), classes=10),
            line8,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.sgd(0.1),
            compress="int8",
            error_feedback=True,
        )
        h = t.train_chain(data.mnist_like().device_sampler(), 3, 4)
        assert len(h) == 3 and np.isfinite(h[-1].loss)
        assert float(np.abs(np.asarray(t._ef)).max()) > 0

    def test_int8_ef_masked_device_carries_full_contribution(self, line8):
        """A masked device sends dq(q(0)) = 0, so its residual is its
        ENTIRE folded contribution — threshold dropout delays the
        gradient, never loses it (same invariant as bf16 EF)."""
        import optax

        t = DPTrainer(
            MLP(hidden=(32,), classes=10),
            line8,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.sgd(0.1),
            seed=0,
            compress="int8",
            error_feedback=True,
        )
        ds = data.mnist_like()
        x, y = next(iter(ds.batches(64, 1)))
        valid = np.ones(8, np.float32)
        valid[3] = 0.0
        m = t.train_step(x, y, valid)
        assert m.contributors == 7.0
        ef = np.asarray(t._ef)
        masked_norm = np.linalg.norm(ef[3])
        other = max(np.linalg.norm(ef[i]) for i in range(8) if i != 3)
        # contributors carry only first-hop int8 crumbs (coarser than
        # bf16's, hence the looser ratio)
        assert masked_norm > 10 * other, (masked_norm, other)


class TestFileDataset:
    """The file-backed loader seam (VERDICT r4 #8): real data drops into
    the same batches/device_sampler API the synthetic stand-ins expose."""

    def _write_shards(self, tmp_path, n_shards=2, rows=24):
        rng = np.random.default_rng(0)
        for i in range(n_shards):
            x = rng.standard_normal((rows, 28, 28, 1)).astype(np.float32)
            y = rng.integers(0, 10, size=rows).astype(np.int32)
            # np.savez appends .npz to bare paths — write via handle
            with open(tmp_path / f"shard_{i}.npz", "wb") as f:
                np.savez(f, x=x, y=y)
        return n_shards * rows

    def test_batches_cycle_and_cover(self, tmp_path):
        from akka_allreduce_tpu.models.data import FileDataset

        total = self._write_shards(tmp_path)
        ds = FileDataset(tmp_path)
        assert ds.n == total
        seen = []
        got = list(ds.batches(16, 5))
        assert len(got) == 5
        for x, y in got:
            assert x.shape == (16, 28, 28, 1) and y.shape == (16,)
            assert y.dtype == np.int32
            seen.append(x)
        # deterministic: same seed_offset -> identical stream
        again = list(ds.batches(16, 5))
        for (x1, _), (x2, _) in zip(got, again):
            np.testing.assert_array_equal(x1, x2)

    def test_trains_a_dp_model(self, tmp_path, line8):
        import optax

        from akka_allreduce_tpu.models import MLP
        from akka_allreduce_tpu.models.data import FileDataset
        from akka_allreduce_tpu.train import DPTrainer

        self._write_shards(tmp_path)
        ds = FileDataset(tmp_path)
        t = DPTrainer(
            MLP(hidden=(16,), classes=10), line8,
            example_input=np.zeros((1, 28, 28, 1), np.float32),
            optimizer=optax.adam(1e-2),
        )
        h = t.train(ds.batches(16, 4))
        assert np.isfinite([m.loss for m in h]).all()
        # and the on-device sampler feeds the jitted chain
        h2 = t.train_chain(ds.device_sampler(), 3, 2)
        assert len(h2) == 3 and np.isfinite(h2[-1].loss)

    def test_missing_keys_and_empty_dir_fail_loudly(self, tmp_path):
        from akka_allreduce_tpu.models.data import FileDataset

        with pytest.raises(FileNotFoundError):
            FileDataset(tmp_path / "nothing_here")
        with open(tmp_path / "bad.npz", "wb") as f:
            np.savez(f, a=np.zeros(3))
        with pytest.raises(KeyError, match="lacks"):
            FileDataset(tmp_path / "bad.npz")


# -- spans, counters and the optimizer's scope inside the sharded LM step -----


_TINY_LM = dict(vocab=16, d_model=32, n_heads=4, n_layers=1, seq_len=16)
_STEP_SPANS = (
    "trainer.step.place", "trainer.step.dispatch", "trainer.step.fetch",
    "trainer.step",
)


def _tiny_sharded_trainer(kind):
    from akka_allreduce_tpu.parallel import data_seq_mesh
    from akka_allreduce_tpu.train import LongContextTrainer, MoETrainer

    if kind == "lm":
        return LongContextTrainer(data_seq_mesh(2, 2), **_TINY_LM)
    return MoETrainer(
        jax.make_mesh((2, 2), ("data", "expert")), n_experts=4, **_TINY_LM
    )


def _lowered_step_text(trainer, tokens):
    from akka_allreduce_tpu.train.trainer import normalize_valid, place_mask

    xd, yd = trainer._place(tokens, tokens)
    vd = place_mask(normalize_valid(None, trainer.dp), trainer._valid_sharding)
    return trainer._step.lower(
        trainer.params, trainer.opt_state, xd, yd, vd
    ).as_text(dialect="hlo", debug_info=True)


@pytest.fixture(scope="module", params=["lm", "moe"])
def three_steps(request):
    """Three host-loop steps of a tiny trainer: what they left in the span
    buffer and how far they moved the registry."""
    import types

    from akka_allreduce_tpu.obs import trace
    from akka_allreduce_tpu.obs.metrics import REGISTRY

    trainer = _tiny_sharded_trainer(request.param)
    tokens = np.random.default_rng(0).integers(0, 16, (4, 16)).astype(np.int32)

    def read():
        snap = REGISTRY.snapshot()
        return (snap["trainer.steps"], snap["trainer.tokens"],
                snap["trainer.step_time_s"]["count"],
                snap["trainer.step_time_s"]["sum"])

    trace.drain()
    before = read()
    out = [trainer.train_step(tokens, tokens) for _ in range(3)]
    records = [r for r in trace.drain() if r["name"].startswith("trainer.step")]
    moved = tuple(b - a for a, b in zip(before, read()))
    return types.SimpleNamespace(
        trainer=trainer, tokens=tokens, out=out, records=records, moved=moved,
        last_loss=REGISTRY.snapshot()["trainer.loss"],
    )


class TestStepSpans:
    def test_each_step_is_one_trace_of_a_root_and_three_children(self, three_steps):
        out, records = three_steps.out, three_steps.records
        assert [r["name"] for r in records] == list(_STEP_SPANS) * 3
        for i in range(3):
            place, dispatch, fetch, root = records[4 * i: 4 * i + 4]
            assert root["parent_id"] == 0 and root["attrs"] == {"step": out[i].step}
            for child in (place, dispatch, fetch):
                assert child["trace_id"] == root["trace_id"]
                assert child["parent_id"] == root["span_id"]
        assert len({r["trace_id"] for r in records}) == 3

    def test_children_lie_inside_the_root_in_order(self, three_steps):
        records = three_steps.records
        for i in range(3):
            place, dispatch, fetch, root = records[4 * i: 4 * i + 4]
            end = lambda r: r["t0"] + r["dur"]  # noqa: E731
            assert root["t0"] <= place["t0"] <= end(place) <= dispatch["t0"]
            assert end(dispatch) <= fetch["t0"] <= end(fetch) <= end(root)
        roots = [r for r in records if r["name"] == "trainer.step"]
        assert all(a["t0"] + a["dur"] <= b["t0"] for a, b in zip(roots, roots[1:]))

    def test_counters_are_written_where_the_step_runs(self, three_steps):
        steps, seen, timed, seconds = three_steps.moved
        assert (steps, seen, timed) == (3, 3 * three_steps.tokens.size, 3)
        roots = [r["dur"] for r in three_steps.records if r["name"] == "trainer.step"]
        assert seconds == pytest.approx(sum(roots), rel=1e-9)
        assert three_steps.last_loss == three_steps.out[-1].loss

    def test_recording_off_leaves_no_span_and_keeps_the_counters(self, three_steps):
        from akka_allreduce_tpu.obs import trace
        from akka_allreduce_tpu.obs.metrics import REGISTRY

        trainer, tokens = three_steps.trainer, three_steps.tokens
        before = REGISTRY.snapshot()["trainer.steps"]
        trace.set_enabled(False)
        try:
            trainer.train_step(tokens, tokens)
        finally:
            trace.set_enabled(True)
        assert not [r for r in trace.drain() if r["name"].startswith("trainer.")]
        assert REGISTRY.snapshot()["trainer.steps"] == before + 1

    def test_the_profiler_gets_the_spans_too(self, three_steps):
        from akka_allreduce_tpu.obs import trace

        assert trace._annotator is jax.profiler.TraceAnnotation

    def test_optimizer_scope_is_metadata_and_nothing_else(
        self, three_steps, monkeypatch
    ):
        """The lowered step names Adam's ops ``optimizer/...``; with every
        instruction's ``metadata={...}`` taken out it is the text of the same
        step lowered with no named scope at all."""
        import contextlib
        import re

        trainer, tokens = three_steps.trainer, three_steps.tokens
        scoped = _lowered_step_text(trainer, tokens)
        names = re.findall(r'op_name="([^"]*)"', scoped)
        assert any(re.search(r"(?:^|/)optimizer(?:/|$)", n) for n in names)
        monkeypatch.setattr(
            jax, "named_scope", lambda name: contextlib.nullcontext()
        )
        kind = "lm" if type(trainer).__name__ == "LongContextTrainer" else "moe"
        bare = _lowered_step_text(_tiny_sharded_trainer(kind), tokens)
        assert not re.search(r'op_name="[^"]*optimizer', bare)

        def instructions(text):
            """Without each instruction's metadata and the tables of source
            locations it points into, and with the instructions numbered in
            their order: XLA names one after its ``op_name``'s last part."""
            blocks = re.sub(r",? ?metadata=\{[^}]*\}", "", text).split("\n\n")
            tables = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
            body = "\n\n".join(
                b for b in blocks if not b.lstrip().startswith(tables)
            )
            defined = re.findall(r"^\s*(?:ROOT )?([\w.\-]+) = ", body, flags=re.M)
            number = {n: f"v{i}" for i, n in enumerate(dict.fromkeys(defined))}
            return re.sub(
                r"[\w.\-]+", lambda m: number.get(m.group(0), m.group(0)), body
            ).splitlines()

        assert len(instructions(scoped)) > 1000
        assert instructions(scoped) == instructions(bare)

"""Mixture-of-experts + expert parallelism on the 8-device virtual CPU mesh.

Oracle discipline: the EP run must match the SAME model trained with all
experts local (dense dispatch) — the all_to_all pair is pure data movement,
so losses and params agree to float-reassociation tolerance. Routing-level
units check the Switch capacity/drop semantics directly.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.models import data
from akka_allreduce_tpu.ops.moe import switch_route
from akka_allreduce_tpu.train import MoETrainer

KW = dict(
    vocab=16, d_model=32, n_heads=4, n_layers=2, n_experts=4, seq_len=32,
    learning_rate=1e-2, seed=0,
)


def mesh(shape, axes):
    return jax.make_mesh(shape, axes, devices=jax.devices()[: int(np.prod(shape))])


class TestSwitchRouting:
    def test_every_token_routed_under_capacity(self):
        logits = jnp.array([[2.0, 0.0], [0.0, 2.0], [2.0, 0.0]])
        r = switch_route(logits, capacity=2)
        assert r.dispatch.shape == (3, 2, 2)
        # tokens 0,2 -> expert 0 slots 0,1; token 1 -> expert 1 slot 0
        assert float(r.dispatch[0, 0, 0]) == 1.0
        assert float(r.dispatch[2, 0, 1]) == 1.0
        assert float(r.dispatch[1, 1, 0]) == 1.0
        assert float(r.dropped) == 0.0

    def test_capacity_overflow_drops_later_tokens(self):
        logits = jnp.tile(jnp.array([[5.0, 0.0]]), (4, 1))  # all want expert 0
        r = switch_route(logits, capacity=2)
        kept = r.dispatch.sum()
        assert float(kept) == 2.0  # only the first two fit
        assert float(r.dropped) == pytest.approx(0.5)

    def test_gate_scales_combine(self):
        logits = jnp.array([[3.0, 0.0]])
        r = switch_route(logits, capacity=1)
        gate = jax.nn.softmax(logits)[0, 0]
        assert float(r.combine[0, 0, 0]) == pytest.approx(float(gate))


class TestExpertParallel:
    def test_ep_matches_dense(self):
        t_ep = MoETrainer(mesh((2, 4), ("data", "expert")), **KW)
        t_dn = MoETrainer(mesh((8,), ("data",)), **KW)
        assert t_ep.ep == 4 and t_dn.ep == 1
        ds = data.lm_copy_task(32, vocab=16)
        for i in range(3):
            x, y = next(ds.batches(8, 1, seed_offset=i))
            m1 = t_ep.train_step(x, y)
            m2 = t_dn.train_step(x, y)
            assert abs(m1.loss - m2.loss) < 1e-4
            assert abs(m1.aux_loss - m2.aux_loss) < 1e-4
        d = np.abs(t_ep.get_flat_params() - t_dn.get_flat_params()).max()
        assert d < 1e-3, d

    def test_expert_weights_sharded(self):
        t = MoETrainer(mesh((2, 4), ("data", "expert")), **KW)
        w1 = t.params["params"]["MoEBlock_0"]["moe_w1"]
        assert w1.shape == (4, 32, 128)  # global: all 4 experts
        assert w1.addressable_shards[0].data.shape == (1, 32, 128)

    def test_masked_replica_row(self):
        t = MoETrainer(mesh((2, 4), ("data", "expert")), **KW)
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(8, 1))
        m = t.train_step(x, y, valid=[1.0, 0.0])
        assert m.contributors == 1.0 and np.isfinite(m.loss)

    def test_training_descends_and_balances(self):
        t = MoETrainer(mesh((2, 4), ("data", "expert")), **KW)
        ds = data.lm_copy_task(32, vocab=16)
        hist = [t.train_step(x, y) for x, y in ds.batches(8, 30)]
        assert np.mean([h.loss for h in hist[-5:]]) < hist[0].loss - 0.3
        # Switch aux stays near its balanced value of 1.0 (E * sum(f*P) with
        # uniform f=P=1/E); a collapsed router would drift toward E
        assert np.mean([h.aux_loss for h in hist[-5:]]) < 2.0

    def test_rejects_indivisible_experts(self):
        with pytest.raises(ValueError, match="divisible"):
            MoETrainer(
                mesh((2, 4), ("data", "expert")),
                vocab=16, d_model=32, n_heads=4, n_layers=1, n_experts=6,
                seq_len=16,
            )


class TestMoEDtypes:
    def test_bf16_compute_flows_through_expert_path(self):
        import jax.numpy as jnp

        t = MoETrainer(
            mesh((2, 4), ("data", "expert")), compute_dtype=jnp.bfloat16, **KW
        )
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(8, 1))
        m = t.train_step(x, y)
        assert np.isfinite(m.loss) and m.contributors == 2.0

    def test_train_chain_on_device(self):
        t = MoETrainer(mesh((2, 4), ("data", "expert")), **KW)
        sampler = data.lm_copy_task(32, vocab=16).device_sampler()
        hist = t.train_chain(sampler, steps=4, rows_per_device=2)
        assert len(hist) == 4
        assert all(np.isfinite(h.loss) for h in hist)
        assert hist[-1].step == 4

    def test_train_moe_cli_device_data_runs_the_chain(self, capsys):
        # README's `train-moe --ep 2 --dispatch scatter --device-data`: the
        # one caller of train_chain(rows_per_device=) outside the tests
        from akka_allreduce_tpu.__main__ import main

        rc = main([
            "train-moe", "--dp", "2", "--ep", "2", "--dispatch", "scatter",
            "--device-data", "--steps", "2", "--batch", "8", "--seq-len", "32",
            "--vocab", "16", "--d-model", "32", "--layers", "1",
        ])
        out = capsys.readouterr().out
        assert rc == 0 and "on-device" in out


class TestTop2Routing:
    """GShard-style top-2: tokens mix their two best experts with
    renormalized gates; primary choices take queue slots first."""

    def test_top2_dispatches_two_experts_with_normalized_gates(self):
        import jax
        import jax.numpy as jnp

        from akka_allreduce_tpu.ops.moe import topk_route

        logits = jnp.array([[2.0, 1.0, -5.0, -5.0]])
        r = topk_route(logits, capacity=2, k=2)
        probs = jax.nn.softmax(logits)[0]
        g0 = float(probs[0] / (probs[0] + probs[1]))
        assert float(r.combine[0, 0, 0]) == pytest.approx(g0, rel=1e-5)
        assert float(r.combine[0, 1, 0]) == pytest.approx(1 - g0, rel=1e-5)
        assert float(r.dispatch.sum()) == 2.0
        assert float(r.dropped) == 0.0

    def test_primary_choices_take_slots_first(self):
        import jax.numpy as jnp

        from akka_allreduce_tpu.ops.moe import topk_route

        # both tokens pick expert 0 (primary) then expert 1 (secondary);
        # with capacity 1 per expert, token 0 claims both single slots
        # (rank-major priority) and token 1 loses both assignments
        logits = jnp.array([[3.0, 1.0, -9.0], [3.0, 1.0, -9.0]])
        r = topk_route(logits, capacity=1, k=2)
        # expert 0: token 0's primary kept, token 1's dropped (cap 1)
        assert float(r.dispatch[0, 0, 0]) == 1.0
        assert float(r.dispatch[1, 0, :].sum()) == 0.0
        # expert 1: token 0's secondary kept, token 1's dropped (cap 1)
        assert float(r.dispatch[0, 1, 0]) == 1.0
        assert float(r.dispatch[1, 1, :].sum()) == 0.0
        assert float(r.dropped) == pytest.approx(0.5)

    def test_top2_ep_matches_dense(self):
        kw = dict(KW)
        t_ep = MoETrainer(
            mesh((2, 4), ("data", "expert")), router_topk=2, **kw
        )
        t_dn = MoETrainer(mesh((8,), ("data",)), router_topk=2, **kw)
        ds = data.lm_copy_task(32, vocab=16)
        for i in range(2):
            x, y = next(ds.batches(8, 1, seed_offset=i))
            m1 = t_ep.train_step(x, y)
            m2 = t_dn.train_step(x, y)
            assert abs(m1.loss - m2.loss) < 1e-4
        d = np.abs(t_ep.get_flat_params() - t_dn.get_flat_params()).max()
        assert d < 1e-3, d

    def test_top2_trains(self):
        t = MoETrainer(mesh((2, 4), ("data", "expert")), router_topk=2, **KW)
        ds = data.lm_copy_task(32, vocab=16)
        hist = [t.train_step(x, y) for x, y in ds.batches(8, 15)]
        assert hist[-1].loss < hist[0].loss
        assert all(np.isfinite(h.aux_loss) for h in hist)


class TestSeqParallelMoE:
    """DP x SP x EP: ring attention over `seq` composed with the expert
    all_to_all over `expert`. Oracle: with ample capacity nothing drops, so
    routing is partition-independent and the run must match the dense
    data-parallel run (SGD keeps float reassociation from amplifying)."""

    def _kw(self):
        import optax

        return dict(
            vocab=16, d_model=32, n_heads=4, n_layers=2, n_experts=4,
            seq_len=32, seed=0, capacity_factor=4.0,
            optimizer=optax.sgd(0.05),
        )

    def test_sp_ep_matches_dense(self):
        t_sp = MoETrainer(
            mesh((2, 2, 2), ("data", "seq", "expert")), **self._kw()
        )
        t_dn = MoETrainer(mesh((4,), ("data",)), **self._kw())
        assert t_sp.sp == 2 and t_sp.ep == 2
        ds = data.lm_copy_task(32, vocab=16)
        for i in range(3):
            x, y = next(ds.batches(8, 1, seed_offset=i))
            a = t_sp.train_step(x, y)
            b = t_dn.train_step(x, y)
            assert abs(a.loss - b.loss) < 1e-4
            assert a.dropped == 0.0  # ample capacity: the oracle's premise
        d = np.abs(t_sp.get_flat_params() - t_dn.get_flat_params()).max()
        assert d < 1e-3, d

    def test_sp_ep_masked_row(self):
        t = MoETrainer(
            mesh((2, 2, 2), ("data", "seq", "expert")), **self._kw()
        )
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(8, 1))
        m = t.train_step(x, y, valid=[1.0, 0.0])
        assert m.contributors == 1.0 and np.isfinite(m.loss)

    def test_sp_ep_chain_matches_dp_ep_chain(self):
        """train_chain on the 3-axis mesh (VERDICT r3 #6): the seq shards of
        each (data, expert) coordinate fold the same key and slice their own
        T_local columns, so the data stream is IDENTICAL to the 2-axis
        DP x EP chain — with ample capacity the runs must lockstep."""
        t3 = MoETrainer(
            mesh((2, 2, 2), ("data", "seq", "expert")), **self._kw()
        )
        t2 = MoETrainer(mesh((2, 2), ("data", "expert")), **self._kw())
        sampler = data.lm_copy_task(32, vocab=16).device_sampler()
        h3 = t3.train_chain(sampler, 4, 2)
        h2 = t2.train_chain(sampler, 4, 2)
        for a, b in zip(h3, h2):
            assert abs(a.loss - b.loss) < 1e-4, (a.loss, b.loss)
            assert a.dropped == 0.0  # ample capacity: the oracle's premise
        d = np.abs(t3.get_flat_params() - t2.get_flat_params()).max()
        assert d < 1e-3, d

    def test_sp_ep_ulysses_and_minimal_row_batch(self):
        # Ulysses all-to-all attention composes with EP; a batch of exactly
        # dp*ep rows (rows shard over data x expert only, NOT seq) is legal
        kw = self._kw()
        t = MoETrainer(
            mesh((2, 2, 2), ("data", "seq", "expert")),
            seq_impl="ulysses", **kw,
        )
        ds = data.lm_copy_task(32, vocab=16)
        x, y = next(ds.batches(4, 1))  # 4 rows = dp(2) * ep(2)
        m = t.train_step(x, y)
        assert np.isfinite(m.loss) and m.contributors == 2.0

    def test_sp_ep_trains_under_capacity_pressure(self):
        kw = self._kw()
        kw["capacity_factor"] = 1.0
        t = MoETrainer(mesh((2, 2, 2), ("data", "seq", "expert")), **kw)
        ds = data.lm_copy_task(32, vocab=16)
        hist = [t.train_step(x, y) for x, y in ds.batches(8, 15)]
        assert hist[-1].loss < hist[0].loss
        assert all(np.isfinite(h.dropped) for h in hist)


class TestScatterDispatch:
    """The scatter/gather dispatch (ops.moe.dispatch_scatter/combine_gather)
    against the one-hot einsum oracle: identical routing (shared
    route_indices), so outputs AND gradients must agree to float tolerance
    — including under capacity pressure, top-2, EP, and bf16."""

    def _dispatch(self, impl, *, t=24, d=16, e=4, cf=1.0, k=1, dtype=None):
        import jax
        import jax.numpy as jnp

        from akka_allreduce_tpu.ops.moe import moe_dispatch_compute

        keys = jax.random.split(jax.random.PRNGKey(3), 5)
        dtype = dtype or jnp.float32
        h = 2 * d
        x = jax.random.normal(keys[0], (t, d), dtype)
        router = jax.random.normal(keys[1], (d, e), jnp.float32)
        w1 = jax.random.normal(keys[2], (e, d, h), jnp.float32) * 0.1
        b1 = jax.random.normal(keys[3], (e, h), jnp.float32) * 0.1
        w2 = jax.random.normal(keys[4], (e, h, d), jnp.float32) * 0.1

        def f(x, w1):
            return moe_dispatch_compute(
                x, router, w1, b1, w2, n_experts=e, capacity_factor=cf,
                router_topk=k, dispatch_impl=impl,
            )

        return f, x, w1

    @pytest.mark.parametrize("k", [1, 2])
    @pytest.mark.parametrize("cf", [0.5, 2.0])
    def test_scatter_matches_einsum(self, k, cf):
        f_e, x, w1 = self._dispatch("einsum", k=k, cf=cf)
        f_s, _, _ = self._dispatch("scatter", k=k, cf=cf)
        ye, auxe, de = f_e(x, w1)
        ys, auxs, ds = f_s(x, w1)
        np.testing.assert_allclose(ys, ye, rtol=1e-5, atol=1e-5)
        assert float(auxs) == pytest.approx(float(auxe), rel=1e-6)
        assert float(ds) == pytest.approx(float(de), abs=1e-6)

    def test_scatter_grads_match_einsum(self):
        import jax

        f_e, x, w1 = self._dispatch("einsum", cf=0.75, k=2)
        f_s, _, _ = self._dispatch("scatter", cf=0.75, k=2)
        loss = lambda f: lambda x, w1: (f(x, w1)[0] ** 2).sum()  # noqa: E731
        ge = jax.grad(loss(f_e), argnums=(0, 1))(x, w1)
        gs = jax.grad(loss(f_s), argnums=(0, 1))(x, w1)
        for a, b in zip(gs, ge):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)

    def test_scatter_bf16(self):
        import jax.numpy as jnp

        f_e, x, w1 = self._dispatch("einsum", dtype=jnp.bfloat16)
        f_s, _, _ = self._dispatch("scatter", dtype=jnp.bfloat16)
        ye, _, _ = f_e(x, w1)
        ys, _, _ = f_s(x, w1)
        assert ys.dtype == jnp.bfloat16
        np.testing.assert_allclose(
            ys.astype(np.float32), ye.astype(np.float32), rtol=3e-2, atol=3e-2
        )

    def test_rejects_unknown_impl(self):
        f, x, w1 = self._dispatch("typo")
        with pytest.raises(ValueError, match="dispatch_impl"):
            f(x, w1)

    def test_scatter_ep_trainer_matches_dense_einsum_trainer(self):
        """Trainer-level: EP + scatter vs dense + einsum — the full oracle
        chain (different dispatch impl AND different expert placement)."""
        t_ep = MoETrainer(
            mesh((2, 4), ("data", "expert")), dispatch_impl="scatter", **KW
        )
        t_dn = MoETrainer(
            mesh((8,), ("data",)), dispatch_impl="einsum", **KW
        )
        ds = data.lm_copy_task(32, vocab=16)
        for i in range(3):
            x, y = next(ds.batches(8, 1, seed_offset=i))
            m1 = t_ep.train_step(x, y)
            m2 = t_dn.train_step(x, y)
            assert abs(m1.loss - m2.loss) < 1e-4
        d = np.abs(t_ep.get_flat_params() - t_dn.get_flat_params()).max()
        assert d < 1e-3, d

    def test_scatter_sp_ep_chain(self):
        """Scatter dispatch on the 3-axis mesh chain (the flagship MoE
        surface) stays finite and trains."""
        import optax

        t = MoETrainer(
            mesh((2, 2, 2), ("data", "seq", "expert")),
            vocab=16, d_model=32, n_heads=4, n_layers=2, n_experts=4,
            seq_len=32, seed=0, capacity_factor=4.0,
            optimizer=optax.sgd(0.05), dispatch_impl="scatter",
        )
        sampler = data.lm_copy_task(32, vocab=16).device_sampler()
        hist = t.train_chain(sampler, 4, 2)
        assert all(np.isfinite(h.loss) for h in hist)
        assert hist[-1].loss < hist[0].loss + 1e-6


class TestMuBf16:
    """adam mu_dtype=bfloat16: halves the first-moment traffic of the
    all-expert optimizer update (the largest single cost of a single-chip
    MoE step — BENCHMARKS.md round 4). Numerics must track the f32-moment
    run within bf16 tolerance, and the moment leaves must actually be
    bf16 (so the bandwidth saving is real, not a silent upcast)."""

    def _mk(self, mu):
        import jax.numpy as jnp

        from akka_allreduce_tpu.parallel import line_mesh
        from akka_allreduce_tpu.train import MoETrainer

        return MoETrainer(
            line_mesh(8, axis="data"),
            vocab=16, d_model=32, n_heads=2, n_layers=1, n_experts=4,
            seq_len=32, learning_rate=1e-2, seed=0,
            mu_dtype=jnp.bfloat16 if mu else None,
        )

    def test_tracks_f32_moments(self):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from akka_allreduce_tpu.models import data

        t_b, t_f = self._mk(True), self._mk(False)
        ds = data.lm_copy_task(32, vocab=16)
        for i, (x, y) in enumerate(ds.batches(8, 10)):
            m_b = t_b.train_step(x, y)
            m_f = t_f.train_step(x, y)
            # same routing decisions, bf16-moment drift only
            assert abs(m_b.loss - m_f.loss) < 5e-2, (i, m_b.loss, m_f.loss)
        p_b = t_b.get_flat_params()
        p_f = t_f.get_flat_params()
        drift = np.abs(p_b - p_f).max() / (np.abs(p_f).max() + 1e-9)
        assert drift < 2e-2, drift
        # the mu leaves really are bf16 (and nu stayed f32)
        mu_leaves = jax.tree.leaves(t_b.opt_state[0].mu)
        nu_leaves = jax.tree.leaves(t_b.opt_state[0].nu)
        assert all(l.dtype == jnp.bfloat16 for l in mu_leaves)
        assert all(l.dtype == jnp.float32 for l in nu_leaves)

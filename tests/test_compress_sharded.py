"""Wire compression for the sharded-param trainers (LM / MoE / Pipeline).

These trainers' gradient collective is normally the implicit shard_map
autodiff psum, which has no wire dtype; ``compress="bf16"`` switches to the
explicit path (comm.allreduce.localize_tree + grouped_tree_psum): grads stay
shard-local, then ONE grouped collective per sharding class runs with a bf16
payload. Oracles:

- f32 equivalence: the compressed run must track the uncompressed run within
  bf16 quantization tolerance over several steps (masked step included);
- wire evidence: the JAX-emitted StableHLO must contain all_reduce ops with
  bf16 operands — half the bytes of the f32 collective. (XLA:CPU's float
  normalization then promotes them back to f32 because CPU has no bf16
  collectives; TPU executes them natively, so the STABLEHLO is the honest
  cross-platform artifact.)
"""

from __future__ import annotations

import re

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from akka_allreduce_tpu.binder.api import flatten_pytree
from akka_allreduce_tpu.models import data
from akka_allreduce_tpu.parallel import data_seq_model_mesh
from akka_allreduce_tpu.train import (
    LongContextTrainer,
    MoETrainer,
    PipelineLMTrainer,
)

SEQ = 32


@pytest.fixture(scope="module")
def lm_batches():
    ds = data.lm_copy_task(SEQ, vocab=16)
    return [next(ds.batches(8, 1, seed_offset=i)) for i in range(4)]


def _drift(t_a, t_b) -> float:
    pa = flatten_pytree(t_a.params)[0]
    pb = flatten_pytree(t_b.params)[0]
    return float(np.abs(pa - pb).max() / np.abs(pa).max())


def _run_pair(t_f32, t_comp, batches, dp, *, loss_tol=5e-3, drift_tol=1e-2):
    mask = np.ones((dp,), np.float32)
    mask[-1] = 0.0
    for i, (x, y) in enumerate(batches):
        v = mask if i == 2 else None
        m0 = t_f32.train_step(x, y, v)
        m1 = t_comp.train_step(x, y, v)
        assert m0.contributors == m1.contributors
        assert abs(m0.loss - m1.loss) < loss_tol * max(1.0, abs(m0.loss))
    assert _drift(t_f32, t_comp) < drift_tol


def _stablehlo_bf16_all_reduces(step_jit, *args) -> tuple[int, int]:
    """(#bf16 all_reduces, #total all_reduces) in the emitted StableHLO."""
    txt = step_jit.lower(*args).as_text()
    ops = re.findall(
        r'"stablehlo\.all_reduce".*?\}\) : \(tensor<([^>]*)>', txt, re.S
    )
    return sum("bf16" in t for t in ops), len(ops)


class TestLongContextCompress:
    KW = dict(
        vocab=16, d_model=32, n_heads=4, n_layers=1, seq_len=SEQ,
        optimizer=optax.sgd(1e-2),
    )

    def test_bf16_matches_f32_dp_sp_tp(self, lm_batches):
        mesh = data_seq_model_mesh(2, 2, 2)
        t0 = LongContextTrainer(mesh, **self.KW)
        t1 = LongContextTrainer(mesh, compress="bf16", **self.KW)
        batches = [(x[:4], y[:4]) for x, y in lm_batches]
        _run_pair(t0, t1, batches, t0.dp)

    def test_bf16_wire_visible_in_stablehlo(self, lm_batches):
        mesh = data_seq_model_mesh(2, 2, 2)
        t = LongContextTrainer(mesh, compress="bf16", **self.KW)
        x, y = lm_batches[0]
        xd, yd = t._place(x[:4], y[:4])
        vd = jax.device_put(
            np.ones((t.dp,), np.float32), t._valid_sharding
        )
        n_bf16, n_total = _stablehlo_bf16_all_reduces(
            t._step, t.params, t.opt_state, xd, yd, vd
        )
        # two grad groups (replicated leaves + tp-sharded leaves) ride bf16;
        # loss/denominator/contributor collectives stay f32 by design
        assert n_bf16 >= 2, (n_bf16, n_total)
        assert n_total > n_bf16  # f32 counts/denominators still present

    def test_int8_matches_f32_dp_sp_tp(self, lm_batches):
        """int8 rides the explicit ring over each sharding class's reduce
        axes (grouped_tree_psum, VERDICT r3 #5b): quarter-width wire, f32
        run tracked within quantization tolerance, exact contributor
        counts (masked step included)."""
        mesh = data_seq_model_mesh(2, 2, 2)
        t0 = LongContextTrainer(mesh, **self.KW)
        t1 = LongContextTrainer(mesh, compress="int8", **self.KW)
        batches = [(x[:4], y[:4]) for x, y in lm_batches]
        _run_pair(t0, t1, batches, t0.dp, loss_tol=5e-2, drift_tol=0.1)

    def test_int8_excludes_overlap(self):
        with pytest.raises(ValueError, match="overlap"):
            LongContextTrainer(
                data_seq_model_mesh(2, 2, 2),
                compress="int8",
                overlap=True,
                **self.KW,
            )

    def test_bf16_with_ulysses_attention(self, lm_batches):
        """compress is orthogonal to the attention schedule: same oracle
        with the Ulysses all-to-all core instead of the ring."""
        from akka_allreduce_tpu.parallel import data_seq_mesh

        mesh = data_seq_mesh(2, 4)
        kw = dict(self.KW, seq_impl="ulysses")
        t0 = LongContextTrainer(mesh, **kw)
        t1 = LongContextTrainer(mesh, compress="bf16", **kw)
        batches = [(x[:4], y[:4]) for x, y in lm_batches]
        _run_pair(t0, t1, batches, t0.dp)

    def test_overlap_with_ulysses_attention(self, lm_batches):
        from akka_allreduce_tpu.parallel import data_seq_mesh

        mesh = data_seq_mesh(2, 4)
        kw = dict(self.KW, seq_impl="ulysses")
        t0 = LongContextTrainer(mesh, **kw)
        t1 = LongContextTrainer(mesh, overlap=True, **kw)
        x, y = lm_batches[0]
        for _ in range(3):
            m0 = t0.train_step(x[:4], y[:4])
            m1 = t1.train_step(x[:4], y[:4])
            assert abs(m0.loss - m1.loss) < 1e-5
        np.testing.assert_allclose(
            t1.get_flat_params(), t0.get_flat_params(), rtol=1e-5, atol=1e-6
        )


class TestMoECompress:
    KW = dict(
        vocab=16, d_model=32, n_heads=4, n_layers=1, n_experts=4,
        seq_len=SEQ, optimizer=optax.sgd(1e-2),
    )

    def test_bf16_matches_f32_dp_sp_ep(self, lm_batches):
        mesh = jax.make_mesh((2, 2, 2), ("data", "seq", "expert"))
        t0 = MoETrainer(mesh, **self.KW)
        t1 = MoETrainer(mesh, compress="bf16", **self.KW)
        _run_pair(t0, t1, lm_batches, t0.dp)

    def test_int8_matches_f32_dp_ep(self, lm_batches):
        """Expert-sharded leaves ring over (data,) only; replicated leaves
        over (data, expert) as two sequential rings (VERDICT r3 #5b)."""
        mesh = jax.make_mesh((2, 2), ("data", "expert"))
        t0 = MoETrainer(mesh, **self.KW)
        t1 = MoETrainer(mesh, compress="int8", **self.KW)
        _run_pair(t0, t1, lm_batches, t0.dp, loss_tol=5e-2, drift_tol=0.1)

    def test_bf16_wire_visible_in_stablehlo(self, lm_batches):
        mesh = jax.make_mesh((2, 2), ("data", "expert"))
        t = MoETrainer(mesh, compress="bf16", **self.KW)
        x, y = lm_batches[0]
        xd = jax.device_put(np.asarray(x[:4], np.int32), t._data_sharding)
        yd = jax.device_put(np.asarray(y[:4], np.int32), t._data_sharding)
        vd = jax.device_put(
            np.ones((t.dp,), np.float32), t._valid_sharding
        )
        n_bf16, n_total = _stablehlo_bf16_all_reduces(
            t._step, t.params, t.opt_state, xd, yd, vd
        )
        assert n_bf16 >= 2, (n_bf16, n_total)  # replicated + expert groups


class TestPipelineCompress:
    KW = dict(
        vocab=16, d_model=32, n_heads=4, layers_per_stage=1,
        microbatches=2, seq_len=SEQ, optimizer=optax.sgd(1e-2),
    )

    def test_bf16_matches_f32_dp_pp(self, lm_batches):
        mesh = jax.make_mesh((2, 4), ("data", "pipe"))
        t0 = PipelineLMTrainer(mesh, **self.KW)
        t1 = PipelineLMTrainer(mesh, compress="bf16", **self.KW)
        batches = [(x[:4], y[:4]) for x, y in lm_batches]
        _run_pair(t0, t1, batches, t0.dp)

    def test_int8_matches_f32_dp_pp(self, lm_batches):
        mesh = jax.make_mesh((2, 4), ("data", "pipe"))
        t0 = PipelineLMTrainer(mesh, **self.KW)
        t1 = PipelineLMTrainer(mesh, compress="int8", **self.KW)
        batches = [(x[:4], y[:4]) for x, y in lm_batches]
        _run_pair(t0, t1, batches, t0.dp, loss_tol=5e-2, drift_tol=0.1)

    def test_bf16_wire_visible_in_stablehlo(self, lm_batches):
        mesh = jax.make_mesh((2, 4), ("data", "pipe"))
        t = PipelineLMTrainer(mesh, compress="bf16", **self.KW)
        x, y = lm_batches[0]
        xd = jax.device_put(np.asarray(x[:4], np.int32), t._data_sharding)
        yd = jax.device_put(np.asarray(y[:4], np.int32), t._data_sharding)
        vd = jax.device_put(
            np.ones((t.dp,), np.float32), t._valid_sharding
        )
        n_bf16, n_total = _stablehlo_bf16_all_reduces(
            t._step, t.params, t.opt_state, xd, yd, vd
        )
        assert n_bf16 >= 2, (n_bf16, n_total)  # embed/head + trunk groups


class TestSyncedValueAndGrad:
    """``comm.allreduce.synced_value_and_grad`` owns the choice of sync for
    the three sharded-param trainers: in every mode it must hand back the
    gradient of the masked mean, with a leaf sharded over ``model`` summed
    over ``data`` only — the oracle is plain ``jax.grad`` on whole arrays."""

    @staticmethod
    def _local(w, u_m, x_d):
        return jnp.sum(jnp.tanh(x_d @ w) * (x_d @ u_m))

    @pytest.mark.parametrize(
        "compress,overlap,tol",
        [
            (None, False, 1e-5),
            ("bf16", False, 2e-2),
            ("int8", False, 5e-2),
            (None, True, 1e-5),
            ("bf16", True, 2e-2),
        ],
    )
    def test_gradient_of_the_masked_mean(self, compress, overlap, tol):
        from jax import lax
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import synced_value_and_grad

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        axes = ("data", "model")
        rng = np.random.default_rng(0)
        params = {
            "w": rng.normal(size=(8,)).astype(np.float32),
            "u": rng.normal(size=(2, 8)).astype(np.float32),
        }
        specs = {"w": P(), "u": P("model")}
        x = rng.normal(size=(4, 5, 8)).astype(np.float32)
        valid = np.array([1.0, 1.0, 0.0, 1.0], np.float32)
        local = self._local

        def oracle(p):
            total = sum(
                valid[d] * local(p["w"], p["u"][m], x[d])
                for d in range(4) for m in range(2)
            )
            return total / (valid.sum() * 2 * 5)

        want_loss, want = jax.value_and_grad(oracle)(params)

        def body(p, x_d, v_d):
            v = lax.pcast(v_d.reshape(()), "model", to="varying")
            denom = jnp.maximum(lax.psum(v * 5.0, axes), 1.0)
            val, grads = synced_value_and_grad(
                lambda q: local(q["w"], q["u"][0], x_d[0]) / denom,
                p, specs, axes, v, compress=compress, overlap=overlap,
            )
            return lax.psum(val, axes), grads

        loss, got = jax.jit(
            jax.shard_map(
                body, mesh=mesh, in_specs=(specs, P("data"), P("data")),
                out_specs=(P(), specs),
                check_vma=not overlap and compress != "int8",
            )
        )(
            jax.device_put(
                params,
                jax.tree.map(
                    lambda s: NamedSharding(mesh, s), specs,
                    is_leaf=lambda s: isinstance(s, P),
                ),
            ),
            x, valid,
        )
        np.testing.assert_allclose(loss, want_loss, rtol=1e-5)
        for name in params:
            scale = np.abs(want[name]).max()
            np.testing.assert_allclose(
                got[name], want[name], atol=tol * scale, err_msg=name
            )

    def test_int8_with_overlap_is_refused(self):
        from akka_allreduce_tpu.comm.allreduce import synced_value_and_grad

        with pytest.raises(ValueError, match="overlap excludes compress='int8'"):
            synced_value_and_grad(
                lambda p: p, 1.0, None, ("data",), 1.0,
                compress="int8", overlap=True,
            )


class _DenseAsMoE:
    """A dense ``TransformerLM`` under the signature ``MoETrainer(model=)``
    takes: no auxiliary loss, nothing dropped, no rows routed."""

    def __init__(self, **kw):
        from akka_allreduce_tpu.models.transformer import TransformerLM

        self._apply = TransformerLM(**kw).apply

    def apply(self, variables, tokens):
        zero = jnp.float32(0.0)
        return (
            self._apply(variables, tokens), zero, zero,
            jnp.zeros((1, 2), jnp.float32), jnp.zeros((1,), jnp.float32),
        )


@pytest.mark.parametrize(
    "sync,tol",
    [
        (dict(compress=None), 1e-5),
        (dict(compress="bf16"), 1e-3),
        (dict(overlap=True), 1e-5),
    ],
    ids=["f32", "bf16", "overlap"],
)
def test_long_context_and_moe_trainers_are_one_step(lm_batches, sync, tol):
    """The two trainers the benchmark runs share ``train/sharded_lm.py``:
    the same dense weights through ``LongContextTrainer`` on (data=4, seq=1)
    and through ``MoETrainer(model=)`` on (data=4,) must take the same two
    steps, the second with one replica masked — the guard against the two
    drifting apart again: their ``local_loss`` closures, spec trees and axis
    wiring. It CANNOT see a fault in the shared skeleton (mask, denominator,
    sync, update), which puts both sides wrong alike: those are held by
    ``TestSyncedValueAndGrad`` and by each trainer's own tests against plain
    ``jax.grad`` (test_ring_attention, test_tensor_parallel, test_moe)."""
    from akka_allreduce_tpu.parallel import data_seq_mesh

    size = dict(vocab=16, d_model=32, n_heads=4, n_layers=1)
    kw = dict(seq_len=SEQ, optimizer=optax.sgd(1e-1), **sync)
    dense = LongContextTrainer(data_seq_mesh(4, 1), **size, **kw)
    as_moe = MoETrainer(
        jax.make_mesh((4,), ("data",)), model=_DenseAsMoE(**size),
        params=jax.tree.map(np.array, dense.params), vocab=16, **kw,
    )
    for (x, y), valid in zip(lm_batches, (None, [1.0, 1.0, 1.0, 0.0])):
        m0 = dense.train_step(x, y, valid)
        m1 = as_moe.train_step(x, y, valid)
        assert m0.contributors == m1.contributors == (4.0 if valid is None else 3.0)
        assert abs(m0.loss - m1.loss) < tol * abs(m0.loss)
        assert m1.aux_loss == m1.dropped == 0.0
        assert not m1.expert_rows.any() and not m1.buffer_rows.any()
    np.testing.assert_allclose(
        as_moe.get_flat_params(), dense.get_flat_params(), rtol=tol, atol=tol * 0.1
    )

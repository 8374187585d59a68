"""The configuration-built decoder that mixes windowed and full attention by
the layer's kind (``models/hybrid_decoder.py`` in the ``laguna`` dialect, in
both of its presets: Laguna's - a head count per layer, a per-head output
gate, YaRN on a part of each head, a shared expert, a leading dense layer -
and Mellum2's - one head count, no gate, YaRN over the whole head, experts in
every layer and nothing beside them), ``local_attention`` under a window in
all three cores and ``rope``'s partial rotation and YaRN, against the
benchmark's plain references ``benchmarks/reference/laguna_moe_plain.py`` and
``mellum_moe_plain.py`` and against formulas written out here, at tiny widths
on the CPU, on seeded weights. The tests that take ``cfg`` run on both
presets."""

from __future__ import annotations

import copy
import json
import math
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec, traffic  # noqa: E402

ref = spec.load_module("reference", "laguna_moe_plain")
runner = spec.load_module("runners", "laguna_moe_train")
mellum_ref = spec.load_module("reference", "mellum_moe_plain")
mellum_runner = spec.load_module("runners", "mellum_moe_train")

TRAFFIC = {"batch": 2, "seq_len": 32, "tokens": "copy_half"}
TINY = os.path.join(BENCH, "tests", "tiny_laguna_moe.json")
REAL = os.path.join(BENCH, "configs", "laguna_xs2_d5.json")
CELL = "laguna_xs2_train_b1_t8192"
MELLUM_TINY = os.path.join(BENCH, "tests", "tiny_mellum_moe.json")
MELLUM_REAL = os.path.join(BENCH, "configs", "mellum2_12b_d4.json")
MELLUM_CELL = "mellum2_train_b1_t8192"
#: by ``model_type``: the preset's reference, runner, cell and tiny file
PRESETS = {
    "laguna": (ref, runner, CELL, TINY),
    "mellum": (mellum_ref, mellum_runner, MELLUM_CELL, MELLUM_TINY),
}


def _json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module", params=sorted(PRESETS))
def cfg(request):
    """Each preset's tiny configuration; ``use_expert_bias`` is the runners'
    key for "no bias"."""
    return dict(_json(PRESETS[request.param][3]), use_expert_bias=False)


@pytest.fixture(scope="module")
def laguna():
    return dict(_json(TINY), use_expert_bias=False)


def _ref(cfg):
    return PRESETS[cfg["model_type"]][0]


def _runner(cfg):
    return PRESETS[cfg["model_type"]][1]


def _heads(cfg, i):
    per_layer = cfg.get("num_attention_heads_per_layer")
    return per_layer[i] if per_layer else cfg["num_attention_heads"]


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _batches(cfg, seed, n=3):
    return [traffic.token_batch(TRAFFIC, cfg["vocab_size"], seed, i) for i in range(n)]


def _variables(cfg, seed):
    return _runner(cfg).to_program_tree(_ref(cfg).init_params(cfg, seed), None, cfg)


_TRAINERS: dict = {}


def _trainer(cfg, seed):
    """ONE trainer a preset (one compile of its step) given the seed's
    weights anew."""
    variables, one = _variables(cfg, seed), cfg["model_type"]
    if one not in _TRAINERS:
        _TRAINERS[one] = _runner(cfg).build_trainer(
            cfg, TRAFFIC["seq_len"], variables, jax.devices()
        )
    else:
        t = _TRAINERS[one]
        t.params, t.opt_state = variables, t.tx.init(variables)
    return _TRAINERS[one]


# -- local_attention under a window ---------------------------------------------


def _qkv(b, t, h, h_kv, d, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, t, h, d)),
            jax.random.normal(k[1], (b, t, h_kv, d)),
            jax.random.normal(k[2], (b, t, h_kv, d)))


def _dense_masked_softmax(q, k, v, window):
    """Written out: key j to query i iff ``0 <= i - j < window``."""
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    t = q.shape[1]
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    visible = (i - j >= 0) & (i - j < window)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision="highest") / math.sqrt(q.shape[-1])
    probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v, precision="highest")


def _grads(fn, q, k, v, seed=7):
    probe = jax.random.normal(jax.random.PRNGKey(seed), q.shape)
    return jax.grad(lambda *a: (fn(*a) * probe).sum(), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("core,t,window", [
    ("dense", 60, 16), ("dense", 200, 48), ("blockwise", 1000, 96), ("blockwise", 1024, 512),
])
@pytest.mark.parametrize("heads", [6, 8])
def test_local_attention_under_a_window_matches_a_dense_masked_softmax(core, t, window, heads):
    """The dense core (short T) and the blockwise one, GQA groups of 6 and 8
    on one K/V head, at T that is no multiple of the window: forward and
    gradient; no row is empty (a query always sees itself)."""
    from akka_allreduce_tpu.ops.local_attention import _DENSE_MAX_T, local_attention

    assert (core == "dense") == (t * t <= _DENSE_MAX_T ** 2)
    q, k, v = _qkv(1, t, heads, 1, 16)
    attend = lambda q, k, v: local_attention(q, k, v, causal=True, window=window)  # noqa: E731
    want = lambda q, k, v: _dense_masked_softmax(q, k, v, window)  # noqa: E731
    got = attend(q, k, v)
    _close(got, want(q, k, v), 1e-5)
    assert bool(jnp.isfinite(got).all())
    for a, b in zip(_grads(attend, q, k, v), _grads(want, q, k, v)):
        _close(a, b, 1e-4)
    # and it is not the causal mask: the window matters at this T
    full = local_attention(q, k, v, causal=True)
    assert float(jnp.abs(full - got).max()) > 1e-3


@pytest.mark.parametrize("heads,window", [(6, 512), (8, 200)])
def test_splash_branch_under_a_window_matches_a_dense_masked_softmax(heads, window):
    """The kernel branch, interpreted (tiny: T 1024 on one K/V head): the
    repo's band kernel at 512 (``tests/test_window_band_attention.py`` has its own
    cases), the library's ``LocalMask`` at 200, which no tile divides:
    forward and gradient."""
    from akka_allreduce_tpu.ops.local_attention import _splash_attention

    q, k, v = _qkv(1, 1024, heads, 1, 64, seed=3)
    scale = 64 ** -0.5
    kernel = lambda q, k, v: _splash_attention(  # noqa: E731
        q, k, v, causal=True, scale=scale, interpret=True, window=window)
    want = lambda q, k, v: _dense_masked_softmax(q, k, v, window)  # noqa: E731
    _close(kernel(q, k, v), want(q, k, v), 2e-3)
    for a, b in zip(_grads(kernel, q, k, v, 5), _grads(want, q, k, v, 5)):
        _close(a, b, 5e-3)


def test_a_window_is_causal_only_and_heads_first_takes_it():
    from akka_allreduce_tpu.ops.local_attention import (
        blockwise_attention,
        heads_first_attention,
        local_attention,
    )
    from akka_allreduce_tpu.ops.ring_attention import attention_reference

    q, k, v = _qkv(1, 40, 4, 4, 8)
    for fn in (local_attention, blockwise_attention, attention_reference):
        with pytest.raises(ValueError, match="causal"):
            fn(q, k, v, window=8)
    q, k, v = _qkv(1, 40, 4, 2, 8)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    scaled = q * 8 ** -0.5  # heads-first takes q with the scale in it
    got = swap(heads_first_attention(swap(scaled), swap(k), swap(v), causal=True, window=8))
    _close(got, _dense_masked_softmax(q, k, v, 8), 1e-5)


@pytest.mark.parametrize("t,d,dv", [(4096, 128, None), (8192, 64, None), (8192, 192, 128),
                                    (8192, 128, None)])
def test_splash_blocks_without_a_window_are_todays(t, d, dv):
    """At StarCoder2's, LFM2's and JoyAI's attention shapes, and at this
    dialect's full layers (a new head count only), the tiles PR 31's sweep
    chose; under a window left to the library the rule's own, which divide T
    (the band kernel's rule and gauges: ``tests/test_window_band_attention.py``)."""
    from akka_allreduce_tpu.ops.local_attention import _splash_blocks

    b = _splash_blocks(t, d, dv)
    assert (b.block_q, b.block_kv, b.block_kv_compute) == (1024, 1024, 512)
    assert (b.block_q_dkv, b.block_kv_dkv, b.block_kv_dkv_compute) == (1024, 1024, 1024)
    assert b.use_fused_bwd_kernel and b == _splash_blocks(t, d, dv, 2, None)
    banded = _splash_blocks(t, d, dv, 2, 512)
    for tile in (banded.block_q, banded.block_kv, banded.block_q_dkv, banded.block_kv_dkv):
        assert t % tile == 0 and tile <= 1024
    assert banded.block_kv % banded.block_kv_compute == 0
    assert banded.block_kv_dkv % banded.block_kv_dkv_compute == 0


@pytest.mark.parametrize("rows,held,experts,want", [
    (8192 * 4, 8, 64, (5120, 32768)), (8192 * 8, 8, 256, (2560, 10240, 65536)),
])
def test_row_rungs_at_the_older_cells_shapes(rows, held, experts, want):
    from akka_allreduce_tpu.ops.moe import row_rungs

    assert row_rungs(rows, held, experts) == want


@pytest.mark.parametrize("kind", ["gmm", "tgmm"])
@pytest.mark.parametrize("m,k,n,groups,want", [
    (5120, 2048, 1536, 8, (512, 512, 512)), (5120, 1536, 2048, 8, (512, 512, 512)),
    (2560, 2048, 768, 8, (256, 1024, 768)), (2560, 768, 2048, 8, (256, 768, 1024)),
])
def test_grouped_tiles_at_the_older_cells_shapes(kind, m, k, n, groups, want):
    from akka_allreduce_tpu.ops.moe import grouped_tiles

    assert grouped_tiles(kind, m, k, n, groups) == want


def test_rungs_and_tiles_at_this_cells_shape():
    """A sixteenth of 256 experts held at 8,192 tokens x 8 choices: the first
    rung a quarter over the uniform load, the last over eight times it, so
    one between; under a 512-row tile an expert, so the grouped products
    take the 256-row branch."""
    from akka_allreduce_tpu.ops.moe import grouped_tiles, row_rungs

    rungs = row_rungs(8192 * 8, 16, 256)
    assert rungs == (5120, 20480, 65536)
    assert grouped_tiles("gmm", rungs[0], 2048, 512, 16) == (256, 1024, 512)
    assert grouped_tiles("tgmm", rungs[0], 512, 2048, 16) == (256, 512, 1024)


@pytest.mark.parametrize("kind", ["gmm", "tgmm"])
def test_rungs_and_tiles_at_the_mellum2_cells_shape(kind):
    """A quarter of 64 experts held at 8,192 tokens x 8 choices: the first
    rung a quarter over the uniform load, the last 3.2 times it, under the
    eight times that bring a rung between (on seeded weights this cell's
    layers do flood past the first: the load's to cure, PERF.md, PR 39);
    1,280 rows an expert, so the swept 512-row tile, and an expert of 2304 x
    896 (eighteen and seven 128-lanes, no multiple of 512): the widest tiles
    up to 1024 that divide it (PR 39's sweep), at either rung."""
    from akka_allreduce_tpu.ops.moe import grouped_tiles, row_rungs

    rungs = row_rungs(8192 * 8, 16, 64)
    assert rungs == (20480, 65536)
    for rows in rungs:
        assert grouped_tiles(kind, rows, 2304, 896, 16) == (512, 768, 896)
        assert grouped_tiles(kind, rows, 896, 2304, 16) == (512, 896, 768)
    # an eighth held keeps two rungs, a share that holds everything one
    assert row_rungs(8192 * 8, 8, 64) == (10240, 65536)
    assert row_rungs(8192 * 8, 64, 64) == (65536,)


# -- rope: default arguments, partial rotation, YaRN -------------------------------


@pytest.mark.parametrize("t,h,d,base", [(4096, 24, 128, 10000.0), (8192, 32, 64, 1e6),
                                        (8192, 1, 64, 32e6)])
def test_rope_with_default_arguments_is_todays(t, h, d, base):
    """At the training cells' shapes (shortened in T), bit for bit the
    rotate-half rotation by ``base ** (-2i/d)`` written out here."""
    from akka_allreduce_tpu.models.transformer import rope, rope_angles

    t = t // 64
    x = jax.random.normal(jax.random.PRNGKey(0), (1, t, h, d), jnp.bfloat16)
    ang = jnp.arange(t)[:, None].astype(jnp.float32) * (
        base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))[None, :]
    np.testing.assert_array_equal(
        np.asarray(rope_angles(t, d, 0, base=base)), np.asarray(ang))
    cos = jnp.cos(ang)[None, :, None, :].astype(x.dtype)
    sin = jnp.sin(ang)[None, :, None, :].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    want = jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1)
    for got in (rope(x, 0, base=base),
                rope(x, 0, base=base, rotary_dim=d, yarn=None, attention_factor=1.0)):
        np.testing.assert_array_equal(
            np.asarray(got, np.float32), np.asarray(want, np.float32))


def test_yarn_table_is_the_formulas_written_out():
    """Laguna-XS.2's full layers: rotary width 64, base 500000, factor 64
    over 4096 positions, beta_fast 64, beta_slow 1."""
    from akka_allreduce_tpu.models.transformer import rope, rope_angles

    r, b, factor, big_l = 64, 500000.0, 64.0, 4096
    c = lambda n: r * math.log(big_l / (2 * math.pi * n)) / (2 * math.log(b))  # noqa: E731
    low, high = math.floor(c(64)), math.ceil(c(1))
    assert (low, high) == (5, 16)
    inv = []
    for i in range(r // 2):
        f = b ** (-2 * i / r)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append((f / factor) * ramp + f * (1 - ramp))
    assert inv[3] == b ** (-6 / r)  # below the ramp: as it was
    assert inv[10] == pytest.approx(b ** (-20 / r) * (1 - 5 / 11 + 5 / 11 / 64))
    assert inv[20] == b ** (-40 / r) / 64  # past it: stretched 64-fold
    ang = rope_angles(9, r, 0, base=b, yarn=(factor, big_l, 64.0, 1.0))
    np.testing.assert_allclose(np.asarray(ang[1]), np.asarray(inv, np.float32), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(ang[8]), 8 * np.asarray(inv, np.float32), rtol=2e-6)
    assert ref.yarn_table(_json(REAL)["rope_parameters"]["full_attention"], r) == (inv, 5, 16)
    # the factor is on cos and sin alike; the last 64 columns pass untouched
    factor_a = 0.1 * math.log(64) + 1
    assert factor_a == pytest.approx(1.4158883083359672)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 9, 3, 128))
    got = rope(x, 0, base=b, rotary_dim=r, yarn=(factor, big_l, 64.0, 1.0),
               attention_factor=factor_a)
    np.testing.assert_array_equal(np.asarray(got[..., r:]), np.asarray(x[..., r:]))
    cos, sin = ((factor_a * f(ang))[None, :, None, :] for f in (jnp.cos, jnp.sin))
    x1, x2 = x[..., : r // 2], x[..., r // 2: r]
    _close(got[..., :r], jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), -1), 1e-6)
    # a rotated pair's norm carries the factor, so a score its square
    _close(jnp.linalg.norm(got[..., :r], axis=-1), factor_a * jnp.linalg.norm(x[..., :r], axis=-1),
           1e-5)
    with pytest.raises(ValueError, match="rotary width"):
        rope(x, 0, rotary_dim=130)


def test_reference_rope_is_the_programs(cfg):
    """Both rules of the tiny configuration, the reference's tables against
    the program's."""
    from akka_allreduce_tpu.models.transformer import rope

    x = jax.random.normal(jax.random.PRNGKey(2), (1, 32, 2, 16))
    model, ref = _runner(cfg).build_model(cfg), _ref(cfg)
    for r in model.rope_by_kind:
        got = rope(x, 0, base=r.theta, rotary_dim=r.rotary_dim, yarn=r.yarn,
                   attention_factor=r.attention_factor)
        _close(got, ref.rope(x, cfg["rope_parameters"][r.kind], cfg, ref.REFERENCE), 1e-6)
    # Laguna turns half of a full layer's head, Mellum2 the whole of it
    assert {r.kind: r.rotary_dim for r in model.rope_by_kind} == {
        "full_attention": {"laguna": 8, "mellum": 16}[cfg["model_type"]],
        "sliding_attention": 16}
    assert [r.yarn is not None for r in model.rope_by_kind] == [True, False]


_TURNS = {
    "default": dict(base=10000.0),
    "rotary_below_the_head": dict(base=10000.0, rotary_dim=8),
    "yarn": dict(base=500000.0, rotary_dim=8, yarn=(64.0, 16, 4.0, 1.0)),
    "attention_factor": dict(base=500000.0, rotary_dim=8, yarn=(64.0, 16, 4.0, 1.0),
                             attention_factor=1.4158883083359672),
    "scale_in_the_table": dict(base=10000.0, attention_factor=1.25, scale=16 ** -0.5),
    "scale_and_rotary_below_the_head": dict(base=500000.0, rotary_dim=8, attention_factor=1.4,
                                            scale=16 ** -0.5),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rule", sorted(_TURNS))
def test_heads_first_turn_is_rope_on_the_transposed_input(rule, dtype):
    """``rope_heads_first`` under ``rope_tables`` on (B, H, T, D) against
    ``rope`` on (B, T, H, D), every rule the layer knows and the score scale
    folded into q's table: forward and gradient, f32 to 1e-6, bf16 within
    its roundings of the exact rotation (the scale in the float32 table costs
    none of its own)."""
    from akka_allreduce_tpu.models.transformer import rope, rope_heads_first, rope_tables

    kw = dict(_TURNS[rule])
    scale = kw.pop("scale", 1.0)
    swap = lambda a: a.transpose(0, 2, 1, 3)  # noqa: E731
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 3, 40, 16)).astype(dtype)
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape).astype(dtype)
    x32, probe32 = x.astype(jnp.float32), probe.astype(jnp.float32)  # what bf16 kept of them
    rotary = kw.get("rotary_dim", 16)
    cos, sin = rope_tables(40, 16, 0, scale=scale, **kw)
    assert cos.dtype == sin.dtype == jnp.float32 and cos.shape == sin.shape == (40, 16)
    np.testing.assert_array_equal(np.asarray(cos[:, rotary:]), np.float32(scale))
    np.testing.assert_array_equal(np.asarray(sin[:, rotary:]), 0.0)
    mine = lambda a: rope_heads_first(a, 0, scale=scale, **kw)  # noqa: E731
    todays = lambda a: swap(rope(swap(a), 0, **kw)) * scale  # noqa: E731
    got, pull = jax.vjp(mine, x)
    want, pull_todays = jax.vjp(todays, x32)
    (dx,), (dx_want,) = pull(probe), pull_todays(probe32)
    assert got.dtype == dx.dtype == x.dtype and got.shape == x.shape
    if dtype == "float32":
        _close(got, want, 1e-6)
        _close(dx, dx_want, 1e-6)
        return
    # a bf16 rounding (half an ulp: up to 2 ** -8 of a value) of the table, of
    # the product and of the sum, on each of the two terms a column sums
    for mine16, exact, of in ((got, want, x32), (dx, dx_want, probe32)):
        h = rotary // 2
        partner = jnp.concatenate((of[..., h:rotary], of[..., :h], of[..., rotary:]), -1)
        room = 3 * 2.0 ** -8 * (jnp.abs(of * cos) + jnp.abs(partner * sin)) + 1e-6
        assert bool((jnp.abs(mine16.astype(jnp.float32) - exact) <= room).all())


def test_heads_first_turn_refuses_an_odd_or_too_wide_rotary_width():
    from akka_allreduce_tpu.models.transformer import rope_tables

    for bad in (7, 18, 0):
        with pytest.raises(ValueError, match="rotary width"):
            rope_tables(8, 16, 0, rotary_dim=bad)


# -- the attention layer: gate, head counts, window ---------------------------------


def _attention_layer(cfg, i, seed=0, t=32):
    """Layer ``i``'s attention of the tiny configuration, program and
    reference on the same seeded leaves."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    model, ref = HybridDecoderLM.from_config(cfg), _ref(cfg)
    s = ref.dims(cfg)
    h, d, hd, kv = _heads(cfg, i), s["d"], s["hd"], s["kv"]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    leaves = {
        "q.w": 0.2 * jax.random.normal(k[0], (d, h * hd)),
        "k.w": 0.2 * jax.random.normal(k[1], (d, kv * hd)),
        "v.w": 0.2 * jax.random.normal(k[2], (d, kv * hd)),
        "g.w": 0.5 * jax.random.normal(k[3], (d, h)),
        "o.w": 0.2 * jax.random.normal(k[4], (h * hd, d)),
    }
    if not cfg.get("gating"):
        del leaves["g.w"]
    x = jax.random.normal(k[5], (2, t, d))
    params = {n: {"kernel": leaves[f"{r}.w"]} for n, r in
              (("q", "q"), ("k", "k"), ("v", "v"), ("gate", "g"), ("out", "o"))
              if f"{r}.w" in leaves}
    module, got = _operator_of(model, cfg["layer_types"][i], i, params, x)
    want = ref.attention(x, lambda n: leaves[n], i, cfg, ref.REFERENCE)
    return module, leaves, x, got, want


def _operator_of(model, kind, i, params, x):
    """Layer ``i``'s operator as the model builds it, applied to ``x``: the
    module's fields (a detached copy) and its output."""
    import dataclasses

    import flax.linen as nn

    seen = {}

    class Probe(type(model)):
        @nn.compact
        def operator(self, x):
            op = self._operator(kind, "x_", i)
            seen["fields"] = op.clone(parent=None, name=None)
            return op(x)

    probe = Probe(**{f.name: getattr(model, f.name) for f in dataclasses.fields(model)
                     if f.name not in ("parent", "name")})
    got = probe.apply({"params": {"x_attn": params}}, x, method=Probe.operator)
    return seen["fields"], got


@pytest.mark.parametrize("layer", [0, 1, 2, 3])
def test_attention_layer_matches_the_reference(cfg, layer):
    """Laguna's: full attention at 6 heads with YaRN on half of each head,
    windowed at 8 heads with the default rule: per-layer head counts, both
    masks, the gate. Mellum2's: three windowed layers and a full one at 8
    heads, YaRN over the whole head, no gate. T 32 is four windows deep."""
    if layer >= cfg["num_hidden_layers"]:
        pytest.skip("the Laguna preset has three layers")
    module, leaves, x, got, want = _attention_layer(cfg, layer)
    _close(got, want, 1e-5)
    assert module.n_heads == _heads(cfg, layer)
    assert module.gated == bool(cfg.get("gating")) == ("g.w" in leaves)
    assert (module.window == 8) == (cfg["layer_types"][layer] == "sliding_attention")
    assert module.scope_name == cfg["layer_types"][layer] and not module.qk_norm


def test_the_gate_is_a_sigmoid_per_head_on_the_kernels_output(laguna):
    """With ``W_g`` zero every gate is a half; a column of ``W_g`` pushed far
    negative shuts that head and no other."""
    cfg = laguna
    module, leaves, x, got, _ = _attention_layer(cfg, 1, seed=4)
    w = lambda n, over: over.get(n, leaves[n])  # noqa: E731
    by = lambda over: ref.attention(  # noqa: E731
        x, lambda n: w(n, over), 1, cfg, ref.REFERENCE)
    half = by({"g.w": jnp.zeros_like(leaves["g.w"])})
    ungated = ref.mm(
        ref.masked_attention(
            *(ref.rope(ref.mm(x, leaves[f"{n}.w"]).reshape(2, 32, -1, 16),
                       cfg["rope_parameters"]["sliding_attention"], cfg, ref.REFERENCE)
              if n != "v" else ref.mm(x, leaves["v.w"]).reshape(2, 32, -1, 16)
              for n in ("q", "k", "v")), 8).reshape(2, 32, -1), leaves["o.w"])
    _close(half, 0.5 * ungated, 1e-5)
    shut = leaves["g.w"].at[:, 3].set(0.0)
    x_big = x.at[..., 0].set(50.0)  # a column the gate can read a large value from
    shut = shut.at[0, 3].set(-10.0)
    with_shut = ref.attention(x_big, lambda n: w(n, {"g.w": shut}), 1, cfg, ref.REFERENCE)
    without = ref.attention(
        x_big, lambda n: w(n, {"g.w": shut, "o.w": leaves["o.w"].at[48:64].set(0.0)}),
        1, cfg, ref.REFERENCE)
    _close(with_shut, without, 1e-5)  # head 3's rows of W_o (3 x 16 .. 4 x 16) see nothing


def test_layers_of_a_model_differ_in_head_count(laguna):
    cfg = laguna
    two = dict(cfg, num_hidden_layers=2, layer_types=["full_attention", "sliding_attention"],
               mlp_layer_types=["dense", "sparse"], num_attention_heads_per_layer=[6, 8])
    model = runner.build_model(two)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    p = tree["params"]
    assert p["layers_0_attn"]["q"]["kernel"].shape == (64, 6 * 16)
    assert p["layers_1_attn"]["q"]["kernel"].shape == (64, 8 * 16)
    assert p["layers_0_attn"]["gate"]["kernel"].shape == (64, 6)
    assert p["layers_1_attn"]["out"]["kernel"].shape == (8 * 16, 64)
    assert p["layers_0_attn"]["k"]["kernel"].shape == p["layers_1_attn"]["v"]["kernel"].shape
    assert "q_norm" not in p["layers_0_attn"] and "fixed" not in tree
    assert set(p["layers_1_moe"]) == {"router", "w1", "w2", "w3", "shared"}


def test_mellum2_builds_no_gate_shared_or_mlp_leaf_and_no_gate_pass():
    """``from_config`` on the Mellum2 preset: four leaves an attention layer,
    four an expert layer, nothing else; the lowered forward and backward carry
    the scopes of the layer's kind and none of a gate, a shared expert or a
    dense MLP."""
    cfg = _json(MELLUM_TINY)
    model = mellum_runner.build_model(cfg)
    tokens = jnp.zeros((1, 32), jnp.int32)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    assert set(tree) == {"params"}  # no ``fixed`` collection: no selection bias
    p = tree["params"]
    assert set(p) == {"embed", "head", "final_norm"} | {
        f"layers_{i}_{m}" for i in range(4) for m in ("op_norm", "attn", "ffn_norm", "moe")}
    for i in range(4):
        assert set(p[f"layers_{i}_attn"]) == {"q", "k", "v", "out"}
        assert set(p[f"layers_{i}_moe"]) == {"router", "w1", "w3", "w2"}
        assert p[f"layers_{i}_attn"]["q"]["kernel"].shape == (64, 8 * 16)
        assert p[f"layers_{i}_moe"]["router"].shape == (64, 16)
    grad = jax.grad(lambda v: model.apply(v, tokens)[0].sum())
    text = jax.jit(grad).lower(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), tree)).as_text(debug_info=True)
    for scope in ("sliding_attention/", "full_attention/", "attn_qkv/", "attn_core/",
                  "attn_out/", "moe_route/", "moe_experts/", "moe_combine/"):
        assert scope in text, scope
    for absent in ("/gate/", "shared_expert", "_mlp/"):
        assert absent not in text, absent
    # the Laguna preset's lowering has all four: the probe can see them
    laguna = _json(TINY)
    model = runner.build_model(laguna)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens)
    text = jax.jit(jax.grad(lambda v: model.apply(v, tokens)[0].sum())).lower(
        jax.tree.map(lambda a: jnp.zeros(a.shape, a.dtype), tree)).as_text(debug_info=True)
    for present in ("/gate/", "shared_expert", "_mlp/"):
        assert present in text, present


def test_the_laguna_files_tree_is_what_it_was():
    """``laguna_xs2_d5.json`` through the reader that now takes Mellum2's keys
    too: the same module fields and the same tree, leaf for leaf (names,
    shapes, dtypes), as PR 35 built - written out here."""
    real = _json(REAL)
    model = runner.build_model(real)
    assert (model.heads_per_layer, model.attn_gate, model.shared_width, model.num_dense_layers,
            model.routed_scale, model.sliding_window) == ((48, 64, 64, 64, 48), True, 512, 1, 2.5, 512)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    assert set(tree) == {"params"}
    flat = {"/".join(str(getattr(k, "key", k)) for k in path): (leaf.shape, str(leaf.dtype))
            for path, leaf in jax.tree.leaves_with_path(tree["params"])}
    want = {"embed/embedding": (12544, 2048), "head": (2048, 12544), "final_norm/scale": (2048,)}
    for i, h in enumerate((48, 64, 64, 64, 48)):
        pre = f"layers_{i}_"
        want.update({
            pre + "op_norm/scale": (2048,), pre + "ffn_norm/scale": (2048,),
            pre + "attn/q/kernel": (2048, h * 128), pre + "attn/k/kernel": (2048, 1024),
            pre + "attn/v/kernel": (2048, 1024), pre + "attn/gate/kernel": (2048, h),
            pre + "attn/out/kernel": (h * 128, 2048)})
        if i == 0:
            want.update({pre + "mlp/w1/kernel": (2048, 8192), pre + "mlp/w3/kernel": (2048, 8192),
                         pre + "mlp/w2/kernel": (8192, 2048)})
        else:
            want.update({
                pre + "moe/router": (2048, 256), pre + "moe/w1": (16, 2048, 512),
                pre + "moe/w3": (16, 2048, 512), pre + "moe/w2": (16, 512, 2048),
                pre + "moe/shared/w1/kernel": (2048, 512),
                pre + "moe/shared/w3/kernel": (2048, 512),
                pre + "moe/shared/w2/kernel": (512, 2048)})
    assert flat == {k: (v, "float32") for k, v in want.items()}
    assert sum(int(np.prod(s)) for s, _ in flat.values()) == 490_297_344


def test_lfm2s_attention_keeps_its_tree_and_scope():
    """``GroupedQueryAttention`` as ``lfm2_moe`` builds it: per-head norms, no
    gate, no window, the scope ``attention``."""
    lfm2 = _json(os.path.join(BENCH, "tests", "tiny_lfm2_moe.json"))
    model = spec.load_module("runners", "moe_train").build_model(lfm2)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    x = jnp.zeros((1, 16, 64))
    module, _ = _operator_of(
        model, "full_attention", 1, jax.tree.map(
            lambda a: jnp.zeros(a.shape), tree["params"]["layers_1_attn"]), x)
    assert (module.qk_norm, module.gated, module.window, module.rotary_dim, module.yarn,
            module.attention_factor, module.scope_name) == (
        True, False, None, None, None, 1.0, "attention")
    assert set(tree["params"]["layers_1_attn"]) == {"q", "k", "v", "out", "q_norm", "k_norm"}
    with pytest.raises(ValueError, match="not built"):
        _operator_of(model, "sliding_attention", 1, {}, x)


def _parents_composition(m, params, x):
    """The layer as its parent composed it, kept here as the plain formula:
    ``nn.Dense`` to (B, T, H, D), the per-head norm, ``rope``,
    ``local_attention``, the gate on the kernel's output, ``W_o``."""
    from akka_allreduce_tpu.models.transformer import rope
    from akka_allreduce_tpu.ops.local_attention import local_attention

    b, t, _ = x.shape
    heads = lambda n: (x @ params[n]["kernel"]).reshape(b, t, -1, m.head_dim)  # noqa: E731

    def turned(name, y):
        if m.qk_norm:
            y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + m.norm_eps)
            y = y * params[name]["scale"]
        return rope(y, 0, base=m.rope_theta, rotary_dim=m.rotary_dim, yarn=m.yarn,
                    attention_factor=m.attention_factor)

    out = local_attention(turned("q_norm", heads("q")), turned("k_norm", heads("k")),
                          heads("v"), causal=True, window=m.window)
    if m.gated:
        out = out * jax.nn.sigmoid(x @ params["gate"]["kernel"])[..., None]
    return out.reshape(b, t, -1) @ params["out"]["kernel"]


@pytest.mark.parametrize("which", ["lfm2", "laguna_full", "laguna_sliding",
                                   "mellum_full", "mellum_sliding"])
def test_grouped_query_attention_equals_its_parents_composition(which):
    """Heads-first from the products to ``W_o``, the scale in q's table:
    the same function of the same leaves as the sequence-first composition -
    output, the gradient of ``x`` and of every leaf to 1e-5 in f32, with
    per-head norms (LFM2's), with YaRN on half a head under
    ``attention_factor`` and the gate, and under a window of 8 (Laguna's),
    with YaRN over the whole head and no gate, full and windowed (Mellum2's)."""
    if which == "lfm2":
        config = _json(os.path.join(BENCH, "tests", "tiny_lfm2_moe.json"))
        model, kind, i = spec.load_module("runners", "moe_train").build_model(config), "full_attention", 1
    else:
        cfg = _json(MELLUM_TINY if which.startswith("mellum") else TINY)
        model = _runner(cfg).build_model(cfg)
        kind, i = {"laguna_full": ("full_attention", 0), "laguna_sliding": ("sliding_attention", 1),
                   "mellum_full": ("full_attention", 3), "mellum_sliding": ("sliding_attention", 0)}[which]
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32))
    shapes = tree["params"][f"layers_{i}_attn"]
    leaves, treedef = jax.tree.flatten(shapes)
    keys = jax.random.split(jax.random.PRNGKey(5), len(leaves) + 2)
    params = jax.tree.unflatten(treedef, [
        (1.0 if a.ndim == 1 else 0.0) + 0.3 * jax.random.normal(k, a.shape)
        for a, k in zip(leaves, keys)])
    x = jax.random.normal(keys[-2], (2, 32, 64))
    probe = jax.random.normal(keys[-1], x.shape)
    module, got = _operator_of(model, kind, i, params, x)
    assert (module.qk_norm, module.gated, module.window) == {
        "lfm2": (True, False, None), "laguna_full": (False, True, None),
        "laguna_sliding": (False, True, 8), "mellum_full": (False, False, None),
        "mellum_sliding": (False, False, 8)}[which]
    if which == "laguna_full":
        assert module.rotary_dim == 8 and module.yarn and module.attention_factor > 1.4
    if which == "mellum_full":
        assert module.rotary_dim == 16 and module.yarn and module.attention_factor > 1.27
    if which.startswith("mellum"):
        assert set(shapes) == {"q", "k", "v", "out"}
    mine = lambda p, x: module.apply({"params": p}, x)  # noqa: E731
    _close(mine(params, x), got, 0.0)
    _close(got, _parents_composition(module, params, x), 1e-5)
    loss = lambda f: lambda p, x: (f(p, x) * probe).sum()  # noqa: E731
    g, gx = jax.grad(loss(mine), (0, 1))(params, x)
    w, wx = jax.grad(loss(lambda p, x: _parents_composition(module, p, x)), (0, 1))(params, x)
    _close(gx, wx, 1e-5)
    assert jax.tree.structure(g) == jax.tree.structure(w) == treedef
    for (path, a), b in zip(jax.tree.leaves_with_path(g), jax.tree.leaves(w)):
        assert float(jnp.abs(b).max()) > 0, path  # no leaf is a no-op
        _close(a, b, 1e-5)


# -- the softmax router and the shares ---------------------------------------------


@pytest.mark.parametrize("renormalise,scale", [(True, 2.5), (False, 1.0)])
def test_softmax_route_is_a_plain_top_k(renormalise, scale):
    from akka_allreduce_tpu.ops.moe import softmax_topk_route

    logits = 2.0 * jax.random.normal(jax.random.PRNGKey(0), (50, 16))
    selected, weights = softmax_topk_route(logits, 4, renormalise=renormalise, scale=scale)
    p = np.asarray(jax.nn.softmax(logits, axis=-1), np.float64)
    order = np.argsort(-p, axis=-1, kind="stable")[:, :4]
    np.testing.assert_array_equal(np.asarray(selected), order)
    picked = np.take_along_axis(p, order, axis=-1)
    want = scale * (picked / picked.sum(-1, keepdims=True) if renormalise else picked)
    np.testing.assert_allclose(np.asarray(weights), want, rtol=1e-5)
    assert selected.dtype == jnp.int32 and weights.dtype == jnp.float32


def test_moe_dropless_held_refuses_a_bias_under_softmax():
    from akka_allreduce_tpu.ops.moe import moe_dropless_held

    x, w = jnp.ones((8, 4)), jnp.ones((2, 4, 4))
    with pytest.raises(ValueError, match="not built"):
        moe_dropless_held(x, jnp.ones((4, 4)), jnp.zeros((4,)), w, w, w, k=2, score="softmax")
    with pytest.raises(ValueError, match="not built"):
        moe_dropless_held(x, jnp.ones((4, 4)), None, w, w, w, k=2, score="tanh")


def _layer_inputs(cfg, seed=0, tokens=64):
    s = ref.dims(cfg)
    d, fe, fs, e = s["d"], s["fe"], s["fs"], s["experts"]
    k = jax.random.split(jax.random.PRNGKey(seed), 8)
    return {
        "x": jax.random.normal(k[0], (1, tokens, d)),
        "router.w": 0.3 * jax.random.normal(k[1], (d, e)),
        "experts.w1": 0.2 * jax.random.normal(k[2], (e, d, fe)),
        "experts.w3": 0.2 * jax.random.normal(k[3], (e, d, fe)),
        "experts.w2": 0.2 * jax.random.normal(k[4], (e, fe, d)),
        "shared.w1": 0.2 * jax.random.normal(k[5], (d, fs)),
        "shared.w3": 0.2 * jax.random.normal(k[6], (d, fs)),
        "shared.w2": 0.2 * jax.random.normal(k[7], (fs, d)),
    }


def _reference_layer(cfg, a, held, shared=True):
    w = lambda n: a[n][jnp.asarray(held)] if n.startswith("experts.") else a[n]  # noqa: E731
    return ref.expert_layer(a["x"], w, cfg, jnp.float32, held, shared)[0]


def _program_layer(cfg, a, first, count, shared_width):
    from akka_allreduce_tpu.models.hybrid_decoder import HeldExperts

    s = ref.dims(cfg)
    module = HeldExperts(
        s["experts"], s["k"], s["fe"], first, count, False, True,
        cfg["moe_routed_scaling_factor"], jnp.float32, shared_width, "softmax",
    )
    hold = slice(first, first + count)
    params = {"router": a["router.w"], "w1": a["experts.w1"][hold],
              "w3": a["experts.w3"][hold], "w2": a["experts.w2"][hold]}
    if shared_width:
        params["shared"] = {n: {"kernel": a[f"shared.{n}"]} for n in ("w1", "w3", "w2")}
    y, rows, dropped, _ = module.apply({"params": params}, a["x"])
    return y, rows, dropped


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(laguna):
    """16 experts in 4 shares: every share routes over all the experts by
    softmax scores and computes its own part; the parts of all shares, with
    the shared expert (which every share computes alike) counted once, are
    the whole layer."""
    cfg = laguna
    a = _layer_inputs(cfg, seed=2)
    s = ref.dims(cfg)
    experts, per_share = s["experts"], 4
    whole = _reference_layer(cfg, a, list(range(experts)))
    parts, rows = 0.0, 0
    for first in range(0, experts, per_share):
        # the first share brings the shared expert, the others leave it out
        y, r, dropped = _program_layer(cfg, a, first, per_share, s["fs"] if first == 0 else 0)
        _close(y, _reference_layer(
            cfg, a, list(range(first, first + per_share)), shared=first == 0))
        assert float(dropped) == 0.0
        parts, rows = parts + y, rows + int(r.sum())
    _close(parts, whole)
    assert rows == a["x"].shape[1] * cfg["num_experts_per_tok"]  # each pair once
    # counted in every share the shared expert would be there four times
    every = sum(_program_layer(cfg, a, f, per_share, s["fs"])[0]
                for f in range(0, experts, per_share))
    shared = ref.gated_mlp(a["x"], a["shared.w1"], a["shared.w3"], a["shared.w2"])
    _close(every - whole, 3 * shared, 1e-4)


def test_the_four_shares_of_sixteen_add_up_to_the_uncut_layer_of_sixty_four():
    """Mellum2's layer at its own counts (64 experts, 8 a token, 16 held a
    share, tiny widths): every share routes over all 64 by softmax scores
    renormalised over the picks, with no scale, and computes its own sixteen's
    part; the four parts are the whole layer, every (token, choice) pair
    counted once and nothing counted in every share: there is no shared
    expert."""
    from akka_allreduce_tpu.models.hybrid_decoder import HeldExperts

    cfg = dict(_json(MELLUM_TINY), num_experts=64, router_num_experts=64,
               num_experts_per_tok=8)
    del cfg["held_experts"]
    d, fe, experts, k, tokens = 64, 32, 64, 8, 96
    key = jax.random.split(jax.random.PRNGKey(6), 5)
    a = {"x": jax.random.normal(key[0], (1, tokens, d)),
         "router.w": 0.3 * jax.random.normal(key[1], (d, experts)),
         "experts.w1": 0.2 * jax.random.normal(key[2], (experts, d, fe)),
         "experts.w3": 0.2 * jax.random.normal(key[3], (experts, d, fe)),
         "experts.w2": 0.2 * jax.random.normal(key[4], (experts, fe, d))}

    def reference(held):
        w = lambda n: a[n][jnp.asarray(held)] if n.startswith("experts.") else a[n]  # noqa: E731
        return mellum_ref.expert_layer(a["x"], w, cfg, jnp.float32, held)[0]

    whole, parts, rows = reference(list(range(experts))), 0.0, 0
    for first in range(0, experts, 16):
        module = HeldExperts(experts, k, fe, first, 16, False, True, 1.0, jnp.float32,
                             0, "softmax")
        hold = slice(first, first + 16)
        params = {"router": a["router.w"], "w1": a["experts.w1"][hold],
                  "w3": a["experts.w3"][hold], "w2": a["experts.w2"][hold]}
        y, r, dropped, _ = module.apply({"params": params}, a["x"])
        _close(y, reference(list(range(first, first + 16))))
        assert float(dropped) == 0.0 and r.shape == (16,)
        parts, rows = parts + y, rows + int(r.sum())
    _close(parts, whole)
    assert rows == tokens * k  # each (token, choice) pair in exactly one share
    assert float(jnp.abs(whole).max()) > 0


# -- the whole model against the reference ---------------------------------------


def _controls(cfg):
    """The names of the preset's controls: Mellum2's reference has one more."""
    return ["CONTROL", "NO_WINDOW", "NO_ATTENTION_FACTOR"] + (
        ["HALF_WINDOW"] if cfg["model_type"] == "mellum" else [])


def test_logits_match_the_reference(cfg):
    ref, runner = _ref(cfg), _runner(cfg)
    leaves = ref.init_params(cfg, 3)
    x, _ = _batches(cfg, 3, 1)[0]
    out = runner.build_model(cfg).apply(runner.to_program_tree(leaves, None, cfg), x)
    logits, aux, dropped, rows, buffers = out
    _close(logits, ref.logits(leaves, jnp.asarray(x), cfg))
    assert float(aux) == 0.0 and float(dropped) == 0.0 and logits.dtype == jnp.float32
    moe_layers = len(ref.expert_layers(cfg))
    assert moe_layers == {"laguna": 2, "mellum": 4}[cfg["model_type"]]
    assert rows.shape == (moe_layers, 4) and buffers.tolist() == [256.0] * moe_layers
    # each control is another function of the same leaves
    for control in _controls(cfg):
        other = ref.logits(leaves, jnp.asarray(x), cfg, getattr(ref, control))
        assert float(jnp.abs(other - logits).max()) > 1e-3, control


def test_selections_match_the_reference(cfg):
    ref, runner = _ref(cfg), _runner(cfg)
    leaves = ref.init_params(cfg, 4)
    x, _ = _batches(cfg, 4, 1)[0]
    _, state = runner.build_model(cfg).apply(
        runner.to_program_tree(leaves, None, cfg), x, mutable=["intermediates"])
    got = jnp.stack([
        state["intermediates"][f"layers_{i}_moe"]["selected"][0]
        for i in ref.expert_layers(cfg)])
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.selections(leaves, jnp.asarray(x), cfg)))


def test_three_steps_through_moe_trainer_match_the_reference(cfg):
    """The loss, the first gradient of EVERY leaf (element by element, as
    Adam's first moment holds it) and the parameters' change after three
    steps."""
    ref, runner = _ref(cfg), _runner(cfg)
    seed, names = 11, list(ref.param_shapes(cfg))
    trainer, batches = _trainer(cfg, seed), _batches(cfg, seed)
    m = trainer.train_step(*batches[0])
    mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
    grads = {n: a / (1.0 - cfg["program"]["adam_b1"])
             for n, a in runner.by_reference_name(mu, names).items()}
    leaves = ref.init_params(cfg, seed)
    x, y = (jnp.asarray(a) for a in batches[0])
    loss, want = jax.value_and_grad(ref.mean_loss)(leaves, x, y, cfg)
    assert abs(m.loss - float(loss)) < 1e-5 * float(loss)
    for n in names:
        _close(grads[n], want[n], 1e-4)
        assert float(jnp.abs(want[n]).max()) > 0, n  # no leaf is a no-op
    assert m.dropped == 0.0 and m.aux_loss == 0.0 and m.contributors == 1.0
    moe_layers = len(ref.expert_layers(cfg))
    assert m.expert_rows.shape == (moe_layers, 4) and m.buffer_rows.shape == (moe_layers,)
    assert m.mtp_loss is None
    for b in batches[1:]:
        trainer.train_step(*b)
    got = ref.delta_norms(runner.by_reference_name(trainer.params, names), cfg, seed)
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    for n in names:
        assert abs(got[n] - followed["delta_norms"][n]) <= 1e-3 * followed["delta_norms"][n], n


def test_the_moe_counters_move_by_the_steps_own_metrics(cfg):
    """``trainer.moe.routed_rows`` / ``.buffer_rows`` /
    ``.layers_past_first_rung`` beside ``trainer.steps``: each step adds what
    its ``MoEStepMetrics`` says, nothing else is read from the device."""
    from akka_allreduce_tpu.obs import metrics
    from akka_allreduce_tpu.ops.moe import row_rungs

    names = ("trainer.steps", "trainer.moe.routed_rows", "trainer.moe.buffer_rows",
             "trainer.moe.layers_past_first_rung")
    read = lambda: [metrics.REGISTRY.snapshot().get(n, 0) for n in names]  # noqa: E731
    trainer, before = _trainer(cfg, 17), read()
    steps = [trainer.train_step(*b) for b in _batches(cfg, 17)]
    first = row_rungs(TRAFFIC["batch"] * TRAFFIC["seq_len"] * cfg["num_experts_per_tok"],
                      cfg["num_experts"], cfg["router_num_experts"])[0]
    assert trainer.model.first_rung(TRAFFIC["batch"] * TRAFFIC["seq_len"]) == first
    moved = [a - b for a, b in zip(read(), before)]
    assert moved == [
        3, sum(float(m.expert_rows.sum()) for m in steps),
        sum(float(m.buffer_rows.sum()) for m in steps),
        sum(int((m.buffer_rows > first).sum()) for m in steps)]
    assert moved[1] > 0 and moved[2] >= moved[1] and moved[3] == 0  # one rung at this size


def test_the_moe_counters_count_a_layer_past_the_first_rung(monkeypatch):
    """Of a step whose metrics say two of four layers took a larger buffer
    than the model's first rung at the step's own tokens (8,192 x 8 over 16
    of 64: 20,480), the third counter moves by two. (The skeleton's
    step is stood in for: on the CPU a ladder of several rungs does not pass
    ``shard_map``'s varying-axes check, which the chip's kernels relax.)"""
    from akka_allreduce_tpu.obs import metrics
    from akka_allreduce_tpu.train import MoETrainer
    from akka_allreduce_tpu.train.moe import MoEStepMetrics
    from akka_allreduce_tpu.train.sharded_lm import ShardedLMTrainer

    made = MoEStepMetrics(
        step=1, loss=1.0, aux_loss=0.0, dropped=0.0, contributors=1.0,
        expert_rows=np.full((4, 16), 1500.0),
        buffer_rows=np.asarray([20480.0, 65536.0, 20480.0, 65536.0]))
    monkeypatch.setattr(ShardedLMTrainer, "train_step", lambda self, *a: made)
    trainer = object.__new__(MoETrainer)
    trainer.dp = 1
    asked = []
    trainer.model = types.SimpleNamespace(
        first_rung=lambda tokens: asked.append(tokens) or 20480)
    names = ("trainer.moe.routed_rows", "trainer.moe.buffer_rows",
             "trainer.moe.layers_past_first_rung")
    before = [metrics.counter(n).value for n in names]
    tokens = np.zeros((1, 8192), np.int32)
    assert trainer.train_step(tokens, tokens) is made and asked == [8192]
    assert [metrics.counter(n).value - b for n, b in zip(names, before)] == [
        4 * 16 * 1500.0, 172032.0, 2]
    # a model that reports no rows (``MoETransformerLM``): nothing moves
    monkeypatch.setattr(ShardedLMTrainer, "train_step", lambda self, *a: MoEStepMetrics(
        step=2, loss=1.0, aux_loss=0.0, dropped=0.0, contributors=1.0))
    trainer.train_step(tokens, tokens)
    assert [metrics.counter(n).value - b for n, b in zip(names, before)] == [
        4 * 16 * 1500.0, 172032.0, 2]


@pytest.mark.parametrize("control", ["CONTROL", "NO_WINDOW", "NO_ATTENTION_FACTOR",
                                     "HALF_WINDOW"])
def test_runner_check_passes_sound_and_fails_each_control(cfg, control):
    if control not in _controls(cfg):
        pytest.skip("the halved window is the Mellum2 reference's control")
    ref, runner = _ref(cfg), _runner(cfg)
    compare = spec.load_module("runners", "lm_train").compare
    seed, names = 13, list(ref.param_shapes(cfg))
    batches = _batches(cfg, seed)
    observed = runner.first_steps(_trainer(cfg, seed), ref, cfg, seed, batches, names)
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    assert all(c["ok"] for c in compare(observed, followed, cfg["correct_limits"]))
    wrongly = ref.follow(cfg, cfg["program"], seed, batches, getattr(ref, control))
    assert [c["name"] for c in compare(wrongly, followed, cfg["correct_limits"]) if not c["ok"]]


def test_a_whole_tiny_run_of_the_cell_is_correct(cfg):
    """The cell's own entry in BENCHMARK.json through the harness, tiny, on
    the CPU: units carry both counters, the run is correct."""
    import time

    from harness.cell_run import run_cell

    traffic_cfg = dict(TRAFFIC, loop="closed", unit="train_step", warmup_units=3,
                       trace_seconds=0.5)
    _, _, cell, tiny = PRESETS[cfg["model_type"]]
    result = run_cell(
        cell, 2**31 + 9, 0.4, False, devices=jax.devices(),
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        t_process=time.perf_counter(),
        overrides={"config": _json(tiny), "traffic": traffic_cfg},
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


# -- the configuration file and the dialect ----------------------------------------


def test_from_config_reads_the_dialect(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    m, laguna = HybridDecoderLM.from_config(cfg), cfg["model_type"] == "laguna"
    assert (m.num_experts, m.held_first, m.held_count) == (16, 4, 4)
    assert (m.router_score, m.use_select_bias, m.renormalise) == ("softmax", False, True)
    assert (m.n_kv_heads, m.head_dim, m.sliding_window) == (2, 16, 8)
    assert m.norm_eps == 1e-6 and m.mtp_depth == 0
    if laguna:
        assert m.layer_types == ("full_attention", "sliding_attention", "full_attention")
        assert m.heads_per_layer == (6, 8, 6) and m.num_dense_layers == 1
        assert (m.shared_width, m.routed_scale, m.attn_gate) == (32, 2.5, True)
        assert m.rope_by_kind == (
            ("full_attention", 500000.0, 8, (64.0, 16, 4.0, 1.0), 1.4158883083359672),
            ("sliding_attention", 10000.0, 16, None, 1.0),
        )
        assert HybridDecoderLM.from_config(dict(cfg, gating="per-head")) == m
    else:  # the same reader with four of Laguna's keys absent: each the plain form
        assert m.layer_types == ("sliding_attention",) * 3 + ("full_attention",)
        assert m.heads_per_layer == () and m.n_heads == 8 and m.num_dense_layers == 0
        assert (m.shared_width, m.routed_scale, m.attn_gate) == (0, 1.0, False)
        assert m.rope_by_kind == (
            ("full_attention", 500000.0, 16, (16.0, 16, 4.0, 1.0), 1.2772588722239782),
            ("sliding_attention", 500000.0, 16, None, 1.0),
        )
        assert HybridDecoderLM.from_config(dict(cfg, gating=False)) == m
        gated = HybridDecoderLM.from_config(dict(cfg, gating=True))
        assert gated.attn_gate and gated == m.clone(attn_gate=True)
        # keys of the family's older spelling, read by nothing
        assert HybridDecoderLM.from_config(
            {k: v for k, v in cfg.items() if k not in ("max_window_layers", "use_sliding_window")}
        ) == m
    # the dialect is its keys': under another model's name the same model
    assert HybridDecoderLM.from_config(dict(cfg, model_type="other")) == m
    whole = {k: v for k, v in cfg.items() if k not in ("router_num_experts", "held_experts")}
    m = HybridDecoderLM.from_config(whole)
    assert (m.num_experts, m.held_first, m.held_count) == (4, 0, 4)
    # no attention_factor in the file: YaRN's own, 0.1 ln(factor) + 1
    bare = copy.deepcopy(cfg)
    del bare["rope_parameters"]["full_attention"]["attention_factor"]
    assert HybridDecoderLM.from_config(bare).rope_by_kind[0].attention_factor == pytest.approx(
        0.1 * math.log(64 if laguna else 16) + 1)


def _with_rope_type(cfg, rope_type):
    out = copy.deepcopy(cfg)
    out["rope_parameters"]["full_attention"]["rope_type"] = rope_type
    return out


@pytest.mark.parametrize("key,bad", [
    ("attention_bias", True), ("tie_word_embeddings", True), ("gating", "sigmoid"),
    ("gating", "per-layer"), ("gating_types", ["per_head", "per_layer", "per_head"]),
    ("moe_apply_router_weight_on_input", True), ("moe_router_logit_softcapping", 30.0),
    ("ep_size", 2), ("program", {"remat": "full"}), ("held_experts", [1, 3]),
    ("mlp_layer_types", ["dense", "sparse", "dense"]),
    ("layer_types", ["full_attention", "conv", "full_attention"]),
    ("num_attention_heads_per_layer", [6, 8]), ("rope_type", "linear"), ("rope_type", "llama3"),
])
def test_from_config_refuses_what_is_not_built(cfg, key, bad):
    """On both presets: a spelling of ``gating`` the reader does not know
    among them (false, or the key absent, is no gate)."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    if key in ("mlp_layer_types", "layer_types") and len(bad) != cfg["num_hidden_layers"]:
        bad = bad + bad[-1:]  # Mellum2's preset has four layers
    wrong = _with_rope_type(cfg, bad) if key == "rope_type" else dict(
        copy.deepcopy(cfg), **{key: bad})
    with pytest.raises(ValueError):
        HybridDecoderLM.from_config(wrong)


def test_train_moe_cli_trains_from_the_configuration_file(cfg, capsys):
    from akka_allreduce_tpu.__main__ import main

    rc = main(["train-moe", "--config", PRESETS[cfg["model_type"]][3], "--steps", "3",
               "--batch", "8", "--seq-len", "32", "--lr", "1e-3"])
    out = capsys.readouterr().out
    assert rc == 0 and "experts 4-7 of 16 held, top-4" in out
    kinds = {"laguna": "full/slid/full", "mellum": "slid/slid/slid/full"}[cfg["model_type"]]
    assert kinds in out and "dropped 0.0%" in out and "mtp loss" not in out


def test_the_cells_configuration_counts_as_the_issue_says():
    from harness import laguna_flops, moe_flops

    real = _json(REAL)
    shapes = ref.param_shapes(real)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    held = len(real["held_experts"])
    assert held in (32, 16) and real["num_experts"] == held
    assert real["held_experts"] == list(range(held)) and real["router_num_experts"] == 256
    total = count(lambda n: True)
    assert total == {32: 691_623_936, 16: 490_297_344}[held]
    attn = lambda i: count(lambda n: n.startswith(f"layers.{i}.") and n.split(".")[2] in "qkvgo")  # noqa: E731
    assert attn(0) == attn(4) == 29_458_432 and attn(1) == attn(2) == attn(3) == 37_879_808
    assert count(lambda n: ".mlp." in n) == 50_331_648
    assert count(lambda n: n.startswith("layers.1.") and ".shared." in n) == 3_145_728
    assert count(lambda n: n == "layers.1.experts.w1") == held * 2048 * 512
    # the file: the source's widths uncut, the cuts, the deployment
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "Laguna-XS.2")
        differs = {k for k, v in row["config"].items() if real.get(k, "absent") != v}
        assert differs == set(real["reduced"]) == set(real["reduced_from"])
        assert real["source"] == row["source_url"]
        for key in ("layer_types", "mlp_layer_types", "num_attention_heads_per_layer"):
            assert real[key] == row["config"][key][:5]
    assert real["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                               "num_attention_heads_per_layer", "num_experts", "vocab_size"]
    assert not real["program"]["remat"] and f"EP{256 // held}" in real["stands_for"]
    for key in ("assumed", "departures", "memory_plan", "correct_limits_why"):
        assert real[key], key
    # the benchmark's count: pairs exactly, each layer at its own head count
    assert laguna_flops.pairs(8192) == 8192 * 8193 // 2
    assert laguna_flops.pairs(8192, 512) == sum(min(i + 1, 512) for i in range(8192))
    assert laguna_flops.pairs(300, 512) == 300 * 301 // 2
    assert laguna_flops.attention_layers(real) == [
        (48, None), (64, 512), (64, 512), (64, 512), (48, None)]
    step = laguna_flops.train_flops_per_step(real, 1, 8192, 4 * 8192 * 8 * held / 256)
    assert round(step["attention"] / 1e12, 2) == 6.15
    assert round(step["total"] / 1e12, 1) == {32: 19.7, 16: 19.4}[held]
    band = laguna_flops.attention_train_flops(real, 1, 8192, windowed=True)
    full = laguna_flops.attention_train_flops(real, 1, 8192, windowed=False)
    assert band + full == step["attention"] and round(band / 1e12, 2) == 1.20
    assert laguna_flops.matmul_params(real)["attention"] == 2 * attn(0) + 3 * attn(1)
    # the older readers this cell joins index this dialect's keys as they stand
    need = moe_flops.grouped_products(real, 8192)
    assert need["flops"] == 18 * 8192 * 2048 * 512
    assert need["bytes"] == 3 * (3 * 2 * 8192 * 2560 + 8 * held * 2048 * 512)


def test_the_mellum2_seeded_weights_spread_the_embedding_on_its_own():
    """The reference makes every matrix at ``initializer_range`` and the
    embedding's rows at ``embedding_initializer_range`` where the
    configuration gives one (the cell's file: 8.0, so that a token's router
    reads the token's own vector and the held experts' load does not follow
    the seed); the norm of the parameters' change makes the same weights
    again; a configuration without the key is as before."""
    real, tiny = _json(MELLUM_REAL), _json(MELLUM_TINY)
    assert real["initializer_range"] == 0.02
    assert real["embedding_initializer_range"] == 8.0
    assert mellum_ref.leaf_stds(real) == (0.02, 8.0)
    assert mellum_ref.leaf_stds(tiny) == (0.05, 0.05)
    plain = mellum_ref.init_params(tiny, 3)
    wide = mellum_ref.init_params({**tiny, "embedding_initializer_range": 1.0}, 3)
    for name, leaf in plain.items():
        if name == "embed":
            np.testing.assert_allclose(wide[name], 20.0 * np.asarray(leaf), rtol=1e-6)
            assert abs(float(np.std(wide[name])) - 1.0) < 0.05
        else:
            np.testing.assert_array_equal(wide[name], leaf)
    moved = mellum_ref.delta_norms(
        wide, {**tiny, "embedding_initializer_range": 1.0}, 3)
    assert set(moved) == set(wide) and max(moved.values()) < 1e-5  # rounding's
    assert mellum_ref.delta_norms(wide, tiny, 3)["embed"] > 1.0


def test_the_mellum2_configuration_counts_as_the_issue_says():
    from harness import mellum_flops

    real = _json(MELLUM_REAL)
    shapes = mellum_ref.param_shapes(real)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    assert real["held_experts"] == list(range(16)) and real["num_experts"] == 16
    assert real["router_num_experts"] == 64 and real["vocab_size"] == 98304 // 4
    assert count(lambda n: True) == 595_153_152 == 4 * (
        21_233_664 + 147_456 + 4_608 + 16 * 6_193_152) + 2 * 56_623_104 + 2_304
    attn = lambda i: count(lambda n: n.startswith(f"layers.{i}.") and n.split(".")[2] in "qkvo")  # noqa: E731
    assert [attn(i) for i in range(4)] == [21_233_664] * 4
    assert count(lambda n: n == "layers.2.router.w") == 147_456
    assert count(lambda n: ".experts." in n) == 4 * 16 * 6_193_152  # 66.6 % of all
    assert not any(".mlp." in n or ".shared." in n or n.endswith("g.w") for n in shapes)
    # the file: the source's widths uncut, the cuts, the deployment
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Mellum2-12B-A2.5B-Instruct")
        differs = {k for k, v in row["config"].items() if real.get(k, "absent") != v}
        assert differs == set(real["reduced"]) == set(real["reduced_from"])
        assert real["source"] == row["source_url"]
        for key in ("layer_types", "mlp_layer_types"):
            assert real[key] == row["config"][key][:4]
    assert real["reduced"] == ["num_hidden_layers", "layer_types", "mlp_layer_types",
                               "num_experts", "vocab_size"]
    assert real["layer_types"] == ["sliding_attention"] * 3 + ["full_attention"]
    assert not real["program"]["remat"] and "EP4" in real["stands_for"]
    for key in ("assumed", "departures", "memory_plan", "correct_limits_why"):
        assert real[key], key
    assert real["memory_plan"]["batch1_t8192_gb"]["sum"] <= 14.2
    # the benchmark's count: pairs exactly under each mask
    assert mellum_flops.pairs(8192, 1024) == sum(min(i + 1, 1024) for i in range(8192))
    assert mellum_flops.windows(real) == [1024, 1024, 1024, None]
    step = mellum_flops.train_flops_per_step(real, 1, 8192, 4 * 8192 * 8 * 16 / 64)
    band = mellum_flops.attention_train_flops(real, 1, 8192, windowed=True)
    full = mellum_flops.attention_train_flops(real, 1, 8192, windowed=False)
    assert band + full == step["attention"]
    assert [round(x / 1e12, 2) for x in (band, full, step["experts"], step["total"])] == [
        1.16, 1.65, 2.44, 12.23]
    n = mellum_flops.matmul_params(real)
    assert n == {"attention": 4 * 21_233_664, "router": 4 * 147_456,
                 "head": 56_623_104, "one_expert": 6_193_152}
    need = mellum_flops.grouped_products(real, 16384, 16)
    assert need["flops"] == 18 * 16384 * 2304 * 896
    assert need["bytes"] == 9 * 2 * 16384 * (2304 + 896) + 24 * 16 * 2304 * 896
    assert mellum_flops.grouped_products(real, 100, 1)["bytes"] < need["bytes"] / 16


def _made_up_record(real, tr, scopes):
    rows = [[256.0] * 15 + [512.0]] * 4
    units = [{"t0": i * 0.25, "t1": i * 0.25 + 0.25, "work": 8192, "ok": True,
              "expert_rows": rows, "buffer_rows": [5120.0] * 4,
              **({"op_scopes": scopes} if i == 0 else {})} for i in range(10)]
    return {
        "cell": types.SimpleNamespace(config=real, traffic=tr), "chips": 1,
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }


class _Trace:
    def __init__(self, ops):
        self.ops = ops

    def main_module(self):
        return [(i * 0.25, 0.24) for i in range(10)]

    def matching(self, name=None, kind=None):
        import re

        hits = [v for k, v in self.ops.items() if re.search(name, k)]
        return sum(h[0] for h in hits), sum(h[1] for h in hits)


def test_readers_of_the_new_metrics_on_a_made_up_record():
    from harness import laguna_flops as flops

    real, tr = _json(REAL), _json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))
    pre = "jit(step)/jvp(HybridDecoderLM)/"
    scopes = {
        "fusion.1": pre + "layers_0_attn/full_attention/attn_qkv/q/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_1_attn/"
                    "sliding_attention/attn_out/out/dot_general",
        "fusion.3": pre + "layers_1_attn/sliding_attention/attn_core/mul",
        "reduce.5": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_0_attn/full_attention/"
                    "attn_core/vmap(jit(_splash_attention))/reduce_sum",
        "fusion.4": pre + "layers_0_mlp/w1/dot_general",
        "splash_mha_fwd.1": pre + "layers_1_attn/sliding_attention/attn_core/x",
        "splash_mha_dkv.2": pre + "layers_2_attn/sliding_attention/attn_core/x",
        "splash_mha_fwd.3": pre + "layers_0_attn/full_attention/attn_core/x",
        "cond.9": pre + "layers_1_attn/sliding_attention/attn_core/cond",
    }
    record = _made_up_record(real, tr, scopes)
    ops = {"fusion.1": [10, 0.30, "fusion"], "fusion.2": [10, 0.20, "fusion"],
           "fusion.3": [10, 0.05, "fusion"], "reduce.5": [10, 0.07, "reduce"],
           "fusion.4": [10, 1.00, "fusion"],
           "splash_mha_fwd.1": [30, 0.06, "custom-call"],
           "splash_mha_dkv.2": [30, 0.14, "custom-call"],
           "splash_mha_fwd.3": [20, 0.40, "custom-call"],
           "gmm.3": [360, 0.05, "custom-call"], "tgmm.1": [120, 0.03, "custom-call"],
           # a conditional's event spans its body's events: on neither side
           "cond.9": [10, 0.5, "conditional"]}
    read = lambda n, t=_Trace(ops), r=record: spec.load_module(  # noqa: E731
        "layer_metrics", n).compute(r, t)
    assert read("gqa_proj_ms") == pytest.approx(50.0)
    assert read("gqa_around_kernel_ms") == pytest.approx(12.0)
    # only the kernels under sliding_attention; with the full layers' they
    # are attn_kernel_ms
    assert read("swa_kernel_ms") == pytest.approx(20.0)
    assert read("attn_kernel_ms") == pytest.approx(60.0)
    band = flops.attention_train_flops(real, 1, 8192, windowed=True)
    assert read("swa_kernel_roofline_pct") == pytest.approx(100 * band / 197e12 / 0.020)
    every = flops.attention_train_flops(real, 1, 8192)
    assert read("attn_kernel_roofline_pct.swa") == pytest.approx(100 * every / 197e12 / 0.060)
    routed = 4 * (15 * 256 + 512)
    per_step = flops.train_flops_per_step(real, 1, 8192, routed)["total"]
    assert read("mfu_pct.swa") == pytest.approx(100 * per_step * 4 / 197e12)
    for name in ("mfu_pct.swa", "swa_kernel_roofline_pct", "attn_kernel_roofline_pct.swa"):
        assert 0 < read(name) < 100
    # the older readers the cell joins, from this dialect's keys
    assert read("moe_row_buffer_fill_pct") == pytest.approx(100 * (15 * 256 + 512) / 5120)
    assert read("moe_load_max_over_mean") == pytest.approx(512 * 16 / (15 * 256 + 512))
    # a program without the scopes or the counters, a trace without the
    # kernels: nothing, and no raise
    bare = dict(record, window=dict(record["window"], units=[
        {k: v for k, v in u.items() if k not in ("expert_rows", "buffer_rows", "op_scopes")}
        for u in record["window"]["units"]]))
    empty = _Trace({"fusion.4": [10, 1.0, "fusion"]})
    new = ("mfu_pct.swa", "attn_kernel_roofline_pct.swa", "swa_kernel_ms",
           "swa_kernel_roofline_pct", "gqa_proj_ms", "gqa_around_kernel_ms")
    for name in new:
        assert spec.load_module("layer_metrics", name).compute(bare, empty) is None
    for name in new[2:]:  # the scopes' readers: kernels in the trace, no map
        assert spec.load_module("layer_metrics", name).compute(bare, _Trace(ops)) is None
    unscoped = dict(record, window=dict(record["window"], units=[
        dict(u, op_scopes={k: "" for k in scopes}) if "op_scopes" in u else u
        for u in record["window"]["units"]]))
    for name in new[2:]:
        assert spec.load_module("layer_metrics", name).compute(unscoped, _Trace(ops)) is None
    # LFM2's scope is ``attention``: its kernels are no windowed layer's
    lfm2 = dict(record, window=dict(record["window"], units=[
        dict(u, op_scopes={"splash_mha_fwd.1": pre + "layers_1_attn/attention/attn_core/x"})
        if "op_scopes" in u else u for u in record["window"]["units"]]))
    assert spec.load_module("layer_metrics", "swa_kernel_ms").compute(lfm2, _Trace(ops)) is None


def _recorded_breakdown():
    """``laguna_on_chip.py breakdown``'s recording of ten steps on a v5e as a
    record and a trace the readers take."""
    rec = _json(os.path.join(BENCH, "tests", "data", "laguna_xs2_breakdown_10steps.json"))
    steps = rec["steps"]
    units = [{"t0": i * 0.23, "t1": i * 0.23 + 0.224, "work": 8192, "ok": True,
              "expert_rows": rec["expert_rows"][i], "buffer_rows": rec["buffer_rows"][i],
              **({"op_scopes": {k: v[3] for k, v in rec["ops"].items()}} if i == 0 else {})}
             for i in range(steps)]
    record = {
        "cell": types.SimpleNamespace(
            config=_json(REAL), traffic=_json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))),
        "chips": 1, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }
    trace = _Trace({k: v[:3] for k, v in rec["ops"].items()})
    trace.main_module = lambda: [(0.0, s) for s in rec["step_device_s"]]
    return rec, record, trace


@pytest.mark.parametrize("name", [
    "mfu_pct.swa", "attn_kernel_roofline_pct.swa", "swa_kernel_ms", "swa_kernel_roofline_pct",
    "gqa_proj_ms", "gqa_around_kernel_ms"])
def test_each_new_reader_reads_the_recorded_breakdown(name):
    """Every new reader returns a number on a recording of the cell's own
    steps, the one it returned on the chip; no share passes 100 %."""
    rec, record, trace = _recorded_breakdown()
    value = spec.load_module("layer_metrics", name).compute(record, trace)
    assert value is not None and value > 0
    if name in rec["readers_on_the_chip"]:
        # the recording leaves out ops under 0.02 ms a step: 1.7 % of the time
        assert value == pytest.approx(rec["readers_on_the_chip"][name], rel=3e-2)
    if name.endswith("_pct") or "_pct." in name:
        assert value < 100


def test_windowed_and_full_kernels_are_the_attention_kernels_of_the_recording():
    """``swa_kernel_ms`` counts the kernels under ``sliding_attention`` and no
    other: with the full layers' kernels it is ``attn_kernel_ms`` to 1 %."""
    import re

    rec, record, trace = _recorded_breakdown()
    read = lambda n: spec.load_module("layer_metrics", n).compute(record, trace)  # noqa: E731
    kernels = {k: v for k, v in rec["ops"].items() if re.match(r"splash_m[hq]a", k)}
    assert len(kernels) == 2 * 2 + 3 * 3  # fused backward in the full layers, two kernels in the band
    per_step = lambda pick: 1e3 * sum(  # noqa: E731
        v[1] for v in kernels.values() if pick in v[3]) / rec["steps"]
    assert read("swa_kernel_ms") == pytest.approx(per_step("/sliding_attention/"))
    both = read("swa_kernel_ms") + per_step("/full_attention/")
    assert abs(both - read("attn_kernel_ms")) <= 0.01 * read("attn_kernel_ms")
    assert all("/attn_core/" in v[3] for v in kernels.values())
    for scope in ("attn_qkv", "attn_out", "shared_expert", "moe_route"):
        assert any(f"/{scope}/" in v[3] for v in rec["ops"].values()), scope


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "laguna_xs2_d5", "closed_b1_t8192", 1)
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    # not ``moe_gmm_roofline_pct``: its bytes count every held expert's weights,
    # and this cell's load drains, so experts without a row are never read and
    # the share read 81 / 88 / 96 % on three seeds (PERF.md section 6, PR 35)
    assert listed == {
        "step_ms_p50", "step_ms_p90", "host_gap_ms.train", "device_idle_pct.train",
        "attn_kernel_ms", "moe_gmm_ms", "moe_load_max_over_mean",
        "moe_row_buffer_fill_pct", "mfu_pct.swa", "attn_kernel_roofline_pct.swa",
        "swa_kernel_ms", "swa_kernel_roofline_pct", "gqa_proj_ms", "gqa_around_kernel_ms",
        # PR 37: the readers of the spans inside ``train_step`` (every training
        # cell) and of the step's ``optimizer`` scope (this cell and JoyAI's)
        "step_span_ms_p50", "step_span_ms_p90", "host_gap_ms.around_run",
        "host_gap_ms.caller", "host_gap_ms.place", "slow_steps",
        "slow_step_excess_ms.fetch", "slow_step_excess_ms.host", "optimizer_own_pass_ms"}
    loaded = spec.load_cell(CELL)
    assert loaded.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert set(loaded.per_layer) == listed | {"compile_or_load_s"}
    for name in loaded.per_layer:  # every reader is a file beside the others
        assert hasattr(spec.load_module("layer_metrics", name), "compute")
    # no process of an older cell loads a file PR 35 added (the Mellum2 cell,
    # of the same dialect, joins the readers of its scopes)
    for w in bench["workloads"]:
        # (a later cell of the grouped-query layer joins the readers of its scopes)
        if w["name"] not in (CELL, MELLUM_CELL, "keye_vl2_ep8_train_b1_t8192",
                             "qwen3_next_ep16_train_b1_t8192"):
            older = spec.load_cell(w["name"])
            assert older.config["runner"] != "laguna_moe_train"
            assert not {"swa_kernel_ms", "gqa_proj_ms", "mfu_pct.swa"} & set(older.per_layer)


MELLUM_NEW = ("mfu_pct.mellum", "swa_kernel_roofline_pct.mellum",
              "attn_kernel_roofline_pct.mellum", "moe_gmm_roofline_pct.mellum",
              "moe_path_ms", "moe_past_first_rung_pct", "swa_tile_useful_pct")


def test_the_benchmark_lists_the_mellum2_cell_where_the_issue_says():
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    assert names.index("mellum2_12b_d4") == names.index("laguna_xs2_d5") + 1  # appended then
    config = bench["configs"][names.index("mellum2_12b_d4")]
    assert config["file"] == "benchmarks/configs/mellum2_12b_d4.json"
    assert config["reduced"] == _json(MELLUM_REAL)["reduced"]
    assert config["source"] == _json(MELLUM_REAL)["source"]
    at = [w["name"] for w in bench["workloads"]].index(MELLUM_CELL)
    cell = bench["workloads"][at]
    assert at == 6 and (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        MELLUM_CELL, "mellum2_12b_d4", "closed_b1_t8192", 1)
    assert all(len(x["why"]) <= 200 for x in (config, cell))
    listed = {m["name"] for m in bench["per_layer"] if MELLUM_CELL in m.get("workloads", ())}
    assert listed == set(MELLUM_NEW) | {
        "step_ms_p50", "step_ms_p90", "step_span_ms_p50", "step_span_ms_p90",
        "host_gap_ms.train", "host_gap_ms.around_run", "host_gap_ms.caller",
        "host_gap_ms.place", "slow_steps", "slow_step_excess_ms.fetch",
        "slow_step_excess_ms.host", "device_idle_pct.train", "optimizer_own_pass_ms",
        "attn_kernel_ms", "swa_kernel_ms", "gqa_proj_ms", "gqa_around_kernel_ms",
        "moe_gmm_ms", "moe_load_max_over_mean", "moe_row_buffer_fill_pct"}
    # not the readers silent since PR 31, nor another configuration's
    assert not listed & {"flash_attn_ms", "flash_attn_roofline_pct", "flash_attn_roofline_pct.moe",
                         "mfu_pct.swa", "swa_kernel_roofline_pct", "moe_gmm_roofline_pct"}
    for m in bench["per_layer"]:
        if m["name"] in MELLUM_NEW:  # new then: this cell's first, appended in one run
            assert m["workloads"][0] == MELLUM_CELL and m["moves"] == "train_tokens_per_s"
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(MELLUM_NEW[0])
    assert metrics[first: first + 7] == list(MELLUM_NEW)
    loaded = spec.load_cell(MELLUM_CELL)
    assert loaded.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert set(loaded.per_layer) == listed | {"compile_or_load_s"}
    for name in loaded.per_layer:  # every reader is a file beside the others
        assert hasattr(spec.load_module("layer_metrics", name), "compute")
    # no process of an older cell loads a file that PR added
    for w in bench["workloads"][:at]:
        older = spec.load_cell(w["name"])
        assert older.config["runner"] != "mellum_moe_train"
        assert not set(MELLUM_NEW) & set(older.per_layer)


def _mellum_record(units_extra=None, counters=None):
    real, tr = _json(MELLUM_REAL), _json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))
    pre = "jit(step)/jvp(HybridDecoderLM)/"
    scopes = {
        "fusion.1": pre + "layers_0_attn/sliding_attention/attn_qkv/q/dot_general",
        "fusion.2": pre + "layers_1_moe/moe_route/dot_general",
        "fusion.3": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_1_moe/jit(_rung_backward)/"
                    "moe_experts/gather",
        "fusion.4": pre + "layers_2_moe/jit(_rung_forward)/moe_combine/add",
        "gmm.3": pre + "layers_1_moe/jit(_rung_forward)/moe_experts/gmm/pallas_call",
        "tgmm.1": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_1_moe/moe_experts/tgmm",
        "splash_mha_fwd.1": pre + "layers_1_attn/sliding_attention/attn_core/x",
        "splash_mha_dkv.2": pre + "layers_2_attn/sliding_attention/attn_core/x",
        "splash_mha_fwd.3": pre + "layers_3_attn/full_attention/attn_core/x",
        "cond.9": pre + "layers_1_moe/moe_experts/cond",
        "fusion.7": "jit(step)/optimizer/add",
        # what XLA makes of ``lax.ragged_dot`` on a larger rung: no scope in its name
        "ragged-dot-none.5": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_3_moe/cond/"
                             "branch_1_fun/jit(_rung_backward)/ragged-dot-none",
    }
    rows = [[1024.0] * 15 + [0.0]] * 4  # one held expert without a row
    units = [{"t0": i * 0.2, "t1": i * 0.2 + 0.2, "work": 8192, "ok": True,
              "expert_rows": rows, "buffer_rows": [20480.0, 20480.0, 20480.0, 65536.0],
              **({"op_scopes": scopes, "counters": counters} if i == 0 else {})}
             for i in range(10)]
    record = {
        "cell": types.SimpleNamespace(config=real, traffic=tr), "chips": 1,
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }
    ops = {"fusion.1": [10, 0.30, "fusion"], "fusion.2": [10, 0.04, "fusion"],
           "fusion.3": [10, 0.10, "fusion"], "fusion.4": [10, 0.05, "fusion"],
           "gmm.3": [360, 0.12, "custom-call"], "tgmm.1": [120, 0.06, "custom-call"],
           "splash_mha_fwd.1": [30, 0.06, "custom-call"],
           "splash_mha_dkv.2": [30, 0.14, "custom-call"],
           "splash_mha_fwd.3": [10, 0.13, "custom-call"],
           # a conditional's event spans its body's events: on neither side
           "cond.9": [10, 0.5, "conditional"], "fusion.7": [10, 0.2, "fusion"],
           "ragged-dot-none.5": [10, 0.07, "custom-call"]}
    return real, record, ops


def test_readers_of_the_mellum2_metrics_on_a_made_up_record():
    from harness import mellum_flops as flops

    counters = {"trainer.steps": 10, "trainer.moe.routed_rows": 614400.0,
                "trainer.moe.buffer_rows": 1024000.0,
                "trainer.moe.layers_past_first_rung": 10,
                "attention.band.visited_pairs": 11796480,
                "attention.band.mask_pairs": 7864832}
    real, record, ops = _mellum_record(counters=counters)
    read = lambda n, t=_Trace(ops), r=record: spec.load_module(  # noqa: E731
        "layer_metrics", n).compute(r, t)
    # route 4 + experts 10 + combine 5 + the kernels 12 + 6 + the larger rung's
    # product 7, the conditional left out
    assert read("moe_path_ms") == pytest.approx(44.0)
    assert read("moe_past_first_rung_pct") == pytest.approx(25.0)  # one layer of four
    assert read("swa_tile_useful_pct") == pytest.approx(100 * 7864832 / 11796480)
    band = flops.attention_train_flops(real, 1, 8192, windowed=True)
    assert read("swa_kernel_ms") == pytest.approx(20.0)
    assert read("swa_kernel_roofline_pct.mellum") == pytest.approx(100 * band / 197e12 / 0.020)
    every = flops.attention_train_flops(real, 1, 8192)
    assert read("attn_kernel_roofline_pct.mellum") == pytest.approx(100 * every / 197e12 / 0.033)
    per_step = flops.train_flops_per_step(real, 1, 8192, 4 * 15 * 1024)["total"]
    assert read("mfu_pct.mellum") == pytest.approx(100 * per_step * 5 / 197e12)
    # three layers on the first rung, fifteen experts with a row; the layer past
    # it multiplied through ragged_dot (``_on_rung``: the test below), so its
    # time is not in ``moe_gmm_ms`` and its work is not counted
    need = flops.grouped_products(real, 15 * 1024, 15)
    least = 3 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert need["flops"] / 197e12 > need["bytes"] / 819e9  # compute-bound at this load
    assert read("moe_gmm_roofline_pct.mellum") == pytest.approx(100 * least / 0.018)
    for name in MELLUM_NEW:
        assert 0 < read(name) < 100 or name == "moe_path_ms", name
    # the older readers the cell joins, from these keys
    assert read("gqa_proj_ms") == pytest.approx(30.0)
    assert read("optimizer_own_pass_ms") == pytest.approx(20.0)
    assert read("moe_load_max_over_mean") == pytest.approx(16 / 15)
    fills = [15 * 1024 / 20480] * 3 + [15 * 1024 / 20480]  # the rung the shapes give
    assert read("moe_row_buffer_fill_pct") == pytest.approx(100 * sum(fills) / 4)
    # a program without the counters, the scopes or the gauges, a trace without
    # the kernels: every new reader says nothing, and none raises
    bare = dict(record, window=dict(record["window"], units=[
        {k: v for k, v in u.items()
         if k not in ("expert_rows", "buffer_rows", "op_scopes", "counters")}
        for u in record["window"]["units"]]))
    empty = _Trace({"fusion.9": [10, 1.0, "fusion"]})
    for name in MELLUM_NEW:
        assert spec.load_module("layer_metrics", name).compute(bare, empty) is None, name
    # the parent's program under this PR's runner: units with an empty
    # ``counters`` (no ``trainer.moe.*``, no ``attention.band.*`` in its registry)
    _, parents, _ = _mellum_record(counters={})
    for name in ("moe_past_first_rung_pct", "swa_tile_useful_pct"):
        assert spec.load_module("layer_metrics", name).compute(parents, _Trace(ops)) is None
    _, older, _ = _mellum_record(counters={"trainer.steps": 10})
    assert spec.load_module("layer_metrics", "moe_past_first_rung_pct").compute(
        older, _Trace(ops)) is None


def test_a_rung_past_the_first_multiplies_through_ragged_dot():
    """What ``moe_gmm_roofline_pct.mellum`` rests on when it leaves a layer
    past the first rung out on both sides: only the first rung's body is
    handed the kernels (``gmm`` / ``tgmm``: the ops ``moe_gmm_ms`` matches),
    every other ``lax.ragged_dot``, at this cell's ladder and at JoyAI's."""
    from akka_allreduce_tpu.ops.moe import _on_rung, row_rungs

    for rungs in (row_rungs(8192 * 8, 16, 64), row_rungs(8192 * 8, 8, 256)):
        seen = []

        def body(x, *, rows, keep, impl, seen=seen):
            seen.append((rows, keep, impl))
            return x

        _on_rung(rungs, types.SimpleNamespace(rung=jnp.int32(0)), body, "gmm", jnp.zeros(()))
        assert seen == [(rungs[0], rungs[0], "gmm")] + [
            (r, rungs[0], "ragged_dot") for r in rungs[1:]]


def _mellum_recorded_breakdown():
    """``mellum_on_chip.py breakdown``'s recording of ten steps on a v5e as a
    record and a trace the readers take."""
    rec = _json(os.path.join(BENCH, "tests", "data", "mellum2_breakdown_10steps.json"))
    steps = rec["steps"]
    units = [{"t0": i * 0.19, "t1": i * 0.19 + 0.185, "work": 8192, "ok": True,
              "expert_rows": rec["expert_rows"][i], "buffer_rows": rec["buffer_rows"][i],
              **({"op_scopes": {k: v[3] for k, v in rec["ops"].items()},
                  "counters": rec["counters"]} if i == 0 else {})}
             for i in range(steps)]
    record = {
        "cell": types.SimpleNamespace(
            config=_json(MELLUM_REAL),
            traffic=_json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))),
        "chips": 1, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }
    trace = _Trace({k: v[:3] for k, v in rec["ops"].items()})
    trace.main_module = lambda: [(0.0, s) for s in rec["step_device_s"]]
    return rec, record, trace


@pytest.mark.parametrize("name", MELLUM_NEW + (
    "swa_kernel_ms", "attn_kernel_ms", "gqa_proj_ms", "gqa_around_kernel_ms", "moe_gmm_ms",
    "optimizer_own_pass_ms"))
def test_each_mellum2_reader_reads_the_recorded_breakdown(name):
    """Every reader the cell lists that reads the program's scopes, kernels,
    counters or gauges returns a number on a recording of the cell's own
    steps, the one it returned on the chip; no share passes 100 %."""
    rec, record, trace = _mellum_recorded_breakdown()
    value = spec.load_module("layer_metrics", name).compute(record, trace)
    assert value is not None and value > 0
    if name in rec["readers_on_the_chip"]:
        # the recording leaves out ops under 0.005 ms a step
        assert value == pytest.approx(rec["readers_on_the_chip"][name], rel=3e-2)
    if name.endswith("_pct") or "_pct." in name:
        assert value < 100
    if name == "moe_past_first_rung_pct":  # eleven of the forty layer-steps recorded
        assert value == pytest.approx(27.5)
    if name == "moe_gmm_roofline_pct.mellum":
        # the work of the twenty-nine layer-steps on the first rung over their
        # kernels' time: the eleven past it are in neither
        assert 55 < value < 65


def test_the_recorded_mellum2_steps_hold_what_the_issue_says_of_the_program():
    """Three band layers on two backward kernels each and a full layer on
    the fused one, every kernel under ``attn_core`` of its layer's kind; the
    held experts' path under its three scopes with the grouped products in
    it; no gate, no shared expert, no dense MLP anywhere in the step."""
    import re

    rec, record, trace = _mellum_recorded_breakdown()
    read = lambda n: spec.load_module("layer_metrics", n).compute(record, trace)  # noqa: E731
    kernels = {k: v for k, v in rec["ops"].items() if re.match(r"splash_m[hq]a", k)}
    assert len(kernels) == 3 * 3 + 2
    assert sum("/sliding_attention/attn_core/" in v[3] for v in kernels.values()) == 9
    assert sum("/full_attention/attn_core/" in v[3] for v in kernels.values()) == 2
    per_step = lambda pick: 1e3 * sum(  # noqa: E731
        v[1] for v in kernels.values() if pick in v[3]) / rec["steps"]
    assert read("swa_kernel_ms") == pytest.approx(per_step("/sliding_attention/"))
    assert read("swa_kernel_ms") + per_step("/full_attention/") == pytest.approx(
        read("attn_kernel_ms"), rel=1e-2)
    grouped = [v for k, v in rec["ops"].items() if re.match(r"t?gmm", k)]
    assert grouped and all(re.search(r"moe_(experts|combine)", v[3]) for v in grouped)
    assert read("moe_path_ms") > read("moe_gmm_ms") > 0
    names = " ".join(v[3] for v in rec["ops"].values())
    for scope in ("attn_qkv", "attn_out", "moe_route", "moe_experts", "moe_combine", "optimizer"):
        assert f"{scope}/" in names, scope
    for absent in ("/gate/", "shared_expert", "_mlp/"):
        assert absent not in names, absent


def test_the_mellum2_counter_readers_say_nothing_on_another_programs_recording():
    """The Laguna cell's recorded breakdown (PR 35's program: no
    ``trainer.moe.*`` counter, no ``attention.band.*`` gauge, its runner hands
    no ``counters``): the two readers of them return None; ``moe_path_ms``
    reads that program's own three scopes."""
    _, record, trace = _recorded_breakdown()
    for name in ("moe_past_first_rung_pct", "swa_tile_useful_pct"):
        assert spec.load_module("layer_metrics", name).compute(record, trace) is None
    assert spec.load_module("layer_metrics", "moe_path_ms").compute(record, trace) > 0

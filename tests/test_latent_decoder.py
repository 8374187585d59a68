"""The configuration-built latent-attention decoder with a shared expert and
a multi-token-prediction module (``models/hybrid_decoder.py`` in the
``joyai_llm_flash`` dialect, ``local_attention`` with a values' head size of
its own, ``MoETrainer``'s second loss) against the benchmark's plain
reference ``benchmarks/reference/joyai_mla_moe_plain.py``, at tiny widths on
the CPU, on seeded weights."""

from __future__ import annotations

import copy
import json
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

ref = spec.load_module("reference", "joyai_mla_moe_plain")
runner = spec.load_module("runners", "mla_moe_train")

TRAFFIC = {"batch": 2, "seq_len": 32, "tokens": "copy_half"}
TINY = os.path.join(BENCH, "tests", "tiny_joyai_mla_moe.json")
REAL = os.path.join(BENCH, "configs", "joyai_llm_flash_ep32_d5_mtp1.json")


def _json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    return _json(TINY)


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _batches(cfg, seed, n=3):
    return [traffic.token_batch(TRAFFIC, cfg["vocab_size"], seed, i) for i in range(n)]


def _variables(cfg, seed):
    return runner.to_program_tree(
        ref.init_params(cfg, seed), ref.select_bias(cfg, seed), cfg
    )


_TRAINERS: dict = {}


def _trainer(cfg, seed, variables=None):
    """ONE trainer (one compile of the step) given the seed's weights anew."""
    variables = _variables(cfg, seed) if variables is None else variables
    if "one" not in _TRAINERS:
        _TRAINERS["one"] = runner.build_trainer(
            cfg, TRAFFIC["seq_len"], variables, jax.devices()
        )
    else:
        t = _TRAINERS["one"]
        t.params, t.opt_state = variables, t.tx.init(variables)
    return _TRAINERS["one"]


# -- local_attention with a values' head size of its own --------------------------


def _qkv(b, t, h, h_kv, d, dv, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, t, h, d), dtype),
            jax.random.normal(k[1], (b, t, h_kv, d), dtype),
            jax.random.normal(k[2], (b, t, h_kv, dv), dtype))


def _plain_attention(q, k, v, scale):
    group = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    return ref.causal_attention(q, k, v, scale)


@pytest.mark.parametrize("core,t", [("dense", 64), ("blockwise", 1024)])
def test_local_attention_takes_a_value_head_of_another_size(core, t):
    """192-style query/key heads against narrower values: the dense core
    (short T) and the blockwise one, forward and gradient."""
    from akka_allreduce_tpu.ops.local_attention import local_attention

    q, k, v = _qkv(1, t, 2, 2, 24, 16)
    scale = 24 ** -0.5
    got = local_attention(q, k, v, causal=True, sm_scale=scale)
    assert got.shape == (1, t, 2, 16)
    _close(got, _plain_attention(q, k, v, scale), 1e-5)
    probe = jax.random.normal(jax.random.PRNGKey(7), got.shape)
    grad = lambda f: jax.grad(  # noqa: E731
        lambda q, k, v: (f(q, k, v) * probe).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(
        grad(lambda q, k, v: local_attention(q, k, v, causal=True, sm_scale=scale)),
        grad(lambda q, k, v: _plain_attention(q, k, v, scale)),
    ):
        _close(a, b, 1e-4)


def test_splash_branch_takes_a_value_head_of_another_size():
    """The kernel branch, interpreted (tiny: one 512-tile of two heads), at a
    query/key head past 128 against values of 128, forward and gradient."""
    from akka_allreduce_tpu.ops.local_attention import (
        _splash_attention,
        flash_shapes_ok,
    )

    assert flash_shapes_ok(1024, 192, 128) and not flash_shapes_ok(1024, 192, 100)
    q, k, v = _qkv(1, 1024, 2, 2, 192, 128, seed=3)
    scale = 192 ** -0.5
    kernel = lambda q, k, v: _splash_attention(  # noqa: E731
        q, k, v, causal=True, scale=scale, interpret=True
    )
    got = kernel(q, k, v)
    assert got.shape == (1, 1024, 2, 128)
    _close(got, _plain_attention(q, k, v, scale), 2e-3)
    probe = jax.random.normal(jax.random.PRNGKey(5), got.shape)
    got_g = jax.grad(lambda *a: (kernel(*a) * probe).sum(), (0, 1, 2))(q, k, v)
    want_g = jax.grad(
        lambda *a: (_plain_attention(*a, scale) * probe).sum(), (0, 1, 2)
    )(q, k, v)
    for a, b in zip(got_g, want_g):
        _close(a, b, 5e-3)


@pytest.mark.parametrize("t,d,dv", [(4096, 128, None), (8192, 64, None), (4096, 128, 128)])
def test_splash_blocks_at_the_existing_cells_shapes(t, d, dv):
    """The rule returns the tiles PR 31's sweep chose at StarCoder2's and
    LFM2's attention shapes, whatever it says at a wider head."""
    from akka_allreduce_tpu.ops.local_attention import _splash_blocks

    b = _splash_blocks(t, d, dv)
    assert (b.block_q, b.block_kv, b.block_kv_compute) == (1024, 1024, 512)
    assert (b.block_q_dkv, b.block_kv_dkv, b.block_kv_dkv_compute) == (1024, 1024, 1024)
    assert b.use_fused_bwd_kernel
    wide = _splash_blocks(8192, 192, 128)
    assert 8192 % wide.block_q == 0 and 8192 % wide.block_kv_dkv == 0


@pytest.mark.parametrize("m,k,n", [(5120, 2048, 1536), (5120, 1536, 2048)])
def test_grouped_tiles_at_the_lfm2_cells_shapes(m, k, n):
    """The first rung of the LFM2 cell (5,120 rows over 8 held experts,
    2048 x 1536 and back) keeps the tiles swept there."""
    from akka_allreduce_tpu.ops import moe

    assert moe.grouped_tiles("gmm", m, k, n, 8) == (512, 512, 512)
    assert moe.grouped_tiles("tgmm", m, k, n, 8) == (512, 512, 512)
    assert moe.row_rungs(8192 * 4, 8, 64) == (5120, 8192 * 4)


def test_grouped_tiles_where_an_expert_has_less_than_a_row_tile():
    """The JoyAI cell's first rung: 2,560 rows over 8 held experts, 2048 x
    768 and back - a 256-row tile and the widest tiles that divide the
    matrix, in both kinds; sizes no multiple of 128 divides keep the default."""
    from akka_allreduce_tpu.ops import moe

    assert moe.row_rungs(8192 * 8, 8, 256) == (2560, 10240, 8192 * 8)
    for kind in ("gmm", "tgmm"):
        assert moe.grouped_tiles(kind, 2560, 2048, 768, 8) == (256, 1024, 768)
        assert moe.grouped_tiles(kind, 2560, 768, 2048, 8) == (256, 768, 1024)
        assert moe.grouped_tiles(kind, 384, 64, 32, 4) == (128, 512, 512)
    with pytest.raises(ValueError, match="tiles its rows by 128"):
        moe.grouped_tiles("gmm", 100, 64, 32, 4)


# -- latent attention -----------------------------------------------------------


def _attention_inputs(cfg, seed=0, t=24):
    s = ref.dims(cfg)
    shapes = {n[len("layers.0."):]: shape for n, shape in ref.param_shapes(cfg).items()
              if n.startswith("layers.0.") and n.split(".")[2] in
              ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o")}
    keys = jax.random.split(jax.random.PRNGKey(seed), len(shapes) + 1)
    leaves = {
        n: (1.0 if n.endswith("scale") else 0.0) + 0.2 * jax.random.normal(k, shape)
        for k, (n, shape) in zip(keys, shapes.items())
    }
    return jax.random.normal(keys[-1], (2, t, s["d"])), leaves


def _program_attention(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import LatentAttention

    s = ref.dims(cfg)
    module = LatentAttention(
        s["h"], s["q_rank"], s["kv_rank"], s["nope"], s["rope"], s["vd"],
        float(cfg["rope_theta"]), cfg["rms_norm_eps"], jnp.float32,
    )

    def apply(x, leaves):
        tree = {}
        for name, leaf in leaves.items():
            node, path = tree, runner.program_path("layers.0." + name)[2:]
            for key in path[:-1]:
                node = node.setdefault(key, {})
            node[path[-1]] = leaf
        return module.apply({"params": tree}, x)

    return apply


def test_latent_attention_forward_and_gradient(cfg):
    x, leaves = _attention_inputs(cfg)
    program = _program_attention(cfg)
    plain = lambda x, leaves: ref.latent_attention(x, leaves.__getitem__, cfg)  # noqa: E731
    _close(program(x, leaves), plain(x, leaves))
    probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
    got = jax.grad(lambda *a: (program(*a) * probe).sum(), (0, 1))(x, leaves)
    want = jax.grad(lambda *a: (plain(*a) * probe).sum(), (0, 1))(x, leaves)
    _close(got[0], want[0], 1e-4)
    for n in leaves:
        _close(got[1][n], want[1][n], 1e-4)
        assert float(jnp.abs(want[1][n]).max()) > 0, n
    # causal, and the one rotary key is shared: a later token moves nothing earlier
    later = x.at[:, 13:].set(0.0)
    np.testing.assert_allclose(
        np.asarray(program(later, leaves)[:, :13]),
        np.asarray(program(x, leaves)[:, :13]), atol=1e-6,
    )


def _parents_latent_attention(x, p, *, h, nope, rope_dim, theta, eps):
    """The composition this module had before its products wrote the
    kernel's layout (PR 32), as the plain formula: ONE product per
    up-projection, the activations sliced, ``k_r`` broadcast over the heads,
    ``q * scale``, a dense causal softmax."""
    from akka_allreduce_tpu.models.transformer import rope

    b, t, _ = x.shape
    rms = lambda c, s: c * jax.lax.rsqrt(jnp.mean(c * c, -1, keepdims=True) + eps) * s  # noqa: E731
    c_q, latent = x @ p["q_a"]["kernel"], x @ p["kv_a"]["kernel"]
    rank = p["kv_a_norm"].shape[0]
    q = (rms(c_q, p["q_a_norm"]) @ p["q_b"]).reshape(b, t, h, -1)
    kv = (rms(latent[..., :rank], p["kv_a_norm"]) @ p["kv_b"]).reshape(b, t, h, -1)
    q = jnp.concatenate((q[..., :nope], rope(q[..., nope:], 0, base=theta)), -1)
    k_r = rope(latent[:, :, None, rank:], 0, base=theta)
    k = jnp.concatenate((kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, rope_dim))), -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q * (nope + rope_dim) ** -0.5, k)
    scores = jnp.where(jnp.tril(jnp.ones((t, t), bool)), scores, -jnp.inf)
    out = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
    return out.reshape(b, t, -1) @ p["out"]["kernel"]


@pytest.mark.parametrize("nope,rope_dim,vd", [(128, 64, 128), (24, 8, 16)],
                         ids=["192_128", "32_16"])
def test_latent_attention_equals_its_parents_composition(nope, rope_dim, vd):
    """The weights are split and scaled, not the activations: in float32 the
    output and the gradient of every leaf and of ``x`` are the parent's to
    1e-5, at the cell's head sizes and at a second pair."""
    from akka_allreduce_tpu.models.hybrid_decoder import LatentAttention

    jax.config.update("jax_default_matmul_precision", "highest")
    try:
        h, d, q_rank, kv_rank, t = 3, 40, 48, 32, 24
        module = LatentAttention(h, q_rank, kv_rank, nope, rope_dim, vd, 32e6, 1e-6,
                                 jnp.float32)
        x = jax.random.normal(jax.random.PRNGKey(1), (2, t, d))
        params = module.init(jax.random.PRNGKey(2), x)["params"]
        # norms off one, weights large enough that no gradient is noise
        keys = iter(jax.random.split(jax.random.PRNGKey(3), 16))
        params = jax.tree.map(
            lambda leaf: (1.0 if leaf.ndim == 1 else 0.0)
            + 0.2 * jax.random.normal(next(keys), leaf.shape), params)
        program = lambda x, p: module.apply({"params": p}, x)  # noqa: E731
        parent = lambda x, p: _parents_latent_attention(  # noqa: E731
            x, p, h=h, nope=nope, rope_dim=rope_dim, theta=32e6, eps=1e-6)
        _close(program(x, params), parent(x, params), 1e-5)
        probe = jax.random.normal(jax.random.PRNGKey(4), x.shape)
        got = jax.grad(lambda *a: (program(*a) * probe).sum(), (0, 1))(x, params)
        want = jax.grad(lambda *a: (parent(*a) * probe).sum(), (0, 1))(x, params)
        _close(got[0], want[0], 1e-5)
        flat = lambda tree: dict(jax.tree_util.tree_leaves_with_path(tree))  # noqa: E731
        got_leaves, want_leaves = flat(got[1]), flat(want[1])
        assert got_leaves.keys() == want_leaves.keys() and len(want_leaves) == 7
        for path, leaf in want_leaves.items():
            assert float(jnp.abs(leaf).max()) > 0, path
            _close(got_leaves[path], leaf, 1e-5)
    finally:
        jax.config.update("jax_default_matmul_precision", None)


def test_latent_attention_keeps_its_parameter_tree(cfg):
    """One leaf per up-projection, as the name map, the reference and the
    checkpoint layout have them; the DeepSeek-V3 dialect still builds it."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM, LatentAttention

    module = LatentAttention(32, 1536, 512, 128, 64, 128, 32e6, 1e-6, jnp.bfloat16)
    shapes = jax.eval_shape(
        module.init, jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.bfloat16))["params"]
    assert jax.tree.map(lambda s: (s.shape, s.dtype.name), shapes) == {
        "q_a": {"kernel": ((2048, 1536), "float32")}, "q_a_norm": ((1536,), "float32"),
        "q_b": ((1536, 32 * 192), "float32"),
        "kv_a": {"kernel": ((2048, 576), "float32")}, "kv_a_norm": ((512,), "float32"),
        "kv_b": ((512, 32 * 256), "float32"),
        "out": {"kernel": ((32 * 128, 2048), "float32")},
    }
    model = HybridDecoderLM.from_config(dict(cfg, model_type="deepseek_v3"))
    tokens = jnp.zeros((1, 8), jnp.int32)
    tree = jax.eval_shape(model.init, jax.random.PRNGKey(0), tokens, tokens)["params"]
    s = ref.dims(cfg)
    for prefix in ("layers_0_attn", "layers_1_attn", "mtp_attn"):
        assert set(tree[prefix]) == {"q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "out"}
        assert tree[prefix]["q_b"].shape == (s["q_rank"], s["h"] * (s["nope"] + s["rope"]))
        assert tree[prefix]["kv_b"].shape == (s["kv_rank"], s["h"] * (s["nope"] + s["vd"]))
        assert tree[prefix]["out"]["kernel"].shape == (s["h"] * s["vd"], s["d"])
    # the reference's names still find every leaf
    assert {runner.program_path("layers.0." + n)[2:][0] for n in
            ("q_b.w", "kv_b.w", "q_a_norm.scale", "kv_a_norm.scale")} == {
        "q_b", "kv_b", "q_a_norm", "kv_a_norm"}


# -- the expert layer: shared expert, shares ------------------------------------


def _layer_inputs(cfg, seed=0, tokens=64):
    s = ref.dims(cfg)
    d, fe, fs, e = s["d"], s["fe"], s["fs"], s["experts"]
    k = jax.random.split(jax.random.PRNGKey(seed), 9)
    return {
        "x": jax.random.normal(k[0], (1, tokens, d)),
        "router.w": 0.3 * jax.random.normal(k[1], (d, e)),
        "experts.w1": 0.2 * jax.random.normal(k[2], (e, d, fe)),
        "experts.w3": 0.2 * jax.random.normal(k[3], (e, d, fe)),
        "experts.w2": 0.2 * jax.random.normal(k[4], (e, fe, d)),
        "shared.w1": 0.2 * jax.random.normal(k[5], (d, fs)),
        "shared.w3": 0.2 * jax.random.normal(k[6], (d, fs)),
        "shared.w2": 0.2 * jax.random.normal(k[7], (fs, d)),
        "bias": 0.01 * jax.random.normal(k[8], (e,)),
    }


def _reference_layer(cfg, a, held, shared=True):
    w = lambda n: a[n][jnp.asarray(held)] if n.startswith("experts.") else a[n]  # noqa: E731
    return ref.expert_layer(a["x"], w, a["bias"], cfg, jnp.float32, held, shared)[0]


def _program_layer(cfg, a, first, count, shared_width):
    from akka_allreduce_tpu.models.hybrid_decoder import HeldExperts

    s = ref.dims(cfg)
    module = HeldExperts(
        s["experts"], s["k"], s["fe"], first, count, True,
        cfg["norm_topk_prob"], cfg["routed_scaling_factor"], jnp.float32,
        shared_width,
    )
    hold = slice(first, first + count)
    params = {"router": a["router.w"], "w1": a["experts.w1"][hold],
              "w3": a["experts.w3"][hold], "w2": a["experts.w2"][hold]}
    if shared_width:
        params["shared"] = {n: {"kernel": a[f"shared.{n}"]} for n in ("w1", "w3", "w2")}
    y, rows, dropped, _ = module.apply(
        {"params": params, "fixed": {"select_bias": a["bias"]}}, a["x"]
    )
    return y, rows, dropped


def test_shared_expert_is_added_unweighted_to_every_token(cfg):
    a, fs = _layer_inputs(cfg), ref.dims(cfg)["fs"]
    with_it, _, _ = _program_layer(cfg, a, 4, 4, fs)
    without, _, _ = _program_layer(cfg, a, 4, 4, 0)
    _close(with_it, _reference_layer(cfg, a, [4, 5, 6, 7]))
    _close(without, _reference_layer(cfg, a, [4, 5, 6, 7], shared=False))
    shared = ref.gated_mlp(a["x"], a["shared.w1"], a["shared.w3"], a["shared.w2"])
    _close(with_it - without, shared, 1e-4)


def test_the_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once(cfg):
    """16 experts in 4 shares: every share routes over all the experts and
    computes its own part; the parts of all shares, with the shared expert
    (which every share computes alike) counted once, are the whole layer."""
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


# -- the whole model against the reference ---------------------------------------


def test_logits_of_both_heads_match_the_reference(cfg):
    leaves, bias = ref.init_params(cfg, 3), ref.select_bias(cfg, 3)
    x, y = _batches(cfg, 3, 1)[0]
    out = runner.build_model(cfg).apply(runner.to_program_tree(leaves, bias, cfg), x, y)
    logits, aux, dropped, rows, buffers, mtp_logits = out
    want, want_mtp = ref.logits(leaves, bias, jnp.asarray(x), jnp.asarray(y), cfg)
    _close(logits, want)
    _close(mtp_logits, want_mtp)
    assert float(aux) == 0.0 and float(dropped) == 0.0
    # one expert layer and the prediction module's, counted last
    assert rows.shape == (2, 4) and buffers.tolist() == [256.0] * 2
    assert logits.dtype == mtp_logits.dtype == jnp.float32
    # the module reads the next tokens: other ones, other logits; the main head not
    other = runner.build_model(cfg).apply(
        runner.to_program_tree(leaves, bias, cfg), x, (y + 1) % cfg["vocab_size"])
    np.testing.assert_array_equal(np.asarray(other[0]), np.asarray(logits))
    assert float(jnp.abs(other[5] - mtp_logits).max()) > 1e-3


def test_selections_match_the_reference(cfg):
    leaves, bias = ref.init_params(cfg, 4), ref.select_bias(cfg, 4)
    x, y = _batches(cfg, 4, 1)[0]
    _, state = runner.build_model(cfg).apply(
        runner.to_program_tree(leaves, bias, cfg), x, y, mutable=["intermediates"]
    )
    got = jnp.stack([
        state["intermediates"][m]["selected"][0]
        for m in ("layers_1_moe", "mtp_moe")
    ])
    np.testing.assert_array_equal(
        np.asarray(got),
        np.asarray(ref.selections(leaves, bias, jnp.asarray(x), jnp.asarray(y), cfg)),
    )


def test_three_steps_through_moe_trainer_match_the_reference(cfg):
    """Both losses, the first gradient of EVERY leaf (element by element, as
    Adam's first moment holds it; the embedding's and the head's hold both
    losses' parts) and the parameters' change after three steps."""
    seed, names = 11, list(ref.param_shapes(cfg))
    trainer, batches = _trainer(cfg, seed), _batches(cfg, seed)
    m = trainer.train_step(*batches[0])
    mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
    grads = {n: a / (1.0 - cfg["program"]["adam_b1"])
             for n, a in runner.by_reference_name(mu, names).items()}
    leaves, bias = ref.init_params(cfg, seed), ref.select_bias(cfg, seed)
    x, y = (jnp.asarray(a) for a in batches[0])
    (_, (main, mtp)), want = jax.value_and_grad(ref.mean_loss, has_aux=True)(
        leaves, bias, x, y, cfg)
    assert abs(m.loss - float(main)) < 1e-5 * float(main)
    assert abs(m.mtp_loss - float(mtp)) < 1e-5 * float(mtp)
    for n in names:
        _close(grads[n], want[n], 1e-4)
        assert float(jnp.abs(want[n]).max()) > 0, n  # no leaf is a no-op
    # without the second term the shared leaves' gradients would differ
    only_main = jax.grad(lambda p: ref.both_losses(p, bias, x, y, cfg)[0])(leaves)
    assert float(jnp.abs(only_main["head.w"] - want["head.w"]).max()) > 1e-4
    assert m.dropped == 0.0 and m.aux_loss == 0.0 and m.contributors == 1.0
    assert m.expert_rows.shape == (2, 4) and m.buffer_rows.shape == (2,)

    for b in batches[1:]:
        trainer.train_step(*b)
    got = ref.delta_norms(runner.by_reference_name(trainer.params, names), cfg, seed)
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    assert len(followed["losses"]) == 6  # three main, then the module's three
    for n in names:
        assert abs(got[n] - followed["delta_norms"][n]) <= 1e-3 * followed["delta_norms"][n], n


def test_mtp_loss_leaves_the_last_position_out(cfg):
    """The module's loss is the reference's by hand: T - 1 terms over T."""
    leaves, bias = ref.init_params(cfg, 6), ref.select_bias(cfg, 6)
    x, y = (jnp.asarray(a) for a in _batches(cfg, 6, 1)[0])
    _, mtp_logits = ref.logits(leaves, bias, x, y, cfg)
    logp = jax.nn.log_softmax(mtp_logits[:, :-1], axis=-1)
    by_hand = -jnp.take_along_axis(logp, y[:, 1:, None], axis=-1).sum() / y.size
    assert abs(float(ref.both_losses(leaves, bias, x, y, cfg)[1]) - float(by_hand)) < 1e-5
    m = _trainer(cfg, 6).train_step(np.asarray(x), np.asarray(y))
    assert abs(m.mtp_loss - float(by_hand)) < 1e-5 * float(by_hand)


def test_runner_check_passes_sound_and_fails_the_control(cfg):
    compare = spec.load_module("runners", "lm_train").compare
    seed, names = 13, list(ref.param_shapes(cfg))
    batches = _batches(cfg, seed)
    observed = runner.first_steps(_trainer(cfg, seed), ref, cfg, seed, batches, names)
    assert len(observed["losses"]) == 6
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    assert all(c["ok"] for c in compare(observed, followed, cfg["correct_limits"]))
    control = ref.follow(cfg, cfg["program"], seed, batches, ref.CONTROL)
    assert not all(c["ok"] for c in compare(control, followed, cfg["correct_limits"]))
    # a wrong second loss alone is caught by the one loss gap
    wrong = dict(observed, losses=observed["losses"][:5] + [observed["losses"][5] * 1.01])
    assert not all(c["ok"] for c in compare(wrong, followed, cfg["correct_limits"]))


def test_a_whole_tiny_run_of_the_cell_is_correct(cfg):
    """The cell's own entry in BENCHMARK.json through the harness, tiny, on
    the CPU: units carry both counters, the run is correct."""
    import time

    from harness.cell_run import run_cell

    traffic_cfg = dict(TRAFFIC, loop="closed", unit="train_step", warmup_units=3,
                       trace_seconds=0.5)
    result = run_cell(
        "joyai_ep32_train_b1_t8192", 2**31 + 9, 0.4, False, devices=jax.devices(),
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        t_process=time.perf_counter(),
        overrides={"config": cfg, "traffic": traffic_cfg},
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


# -- the configuration file and the dialect ----------------------------------------


def test_from_config_reads_the_dialect_and_refuses_what_is_not_built(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    m = HybridDecoderLM.from_config(cfg)
    assert (m.num_experts, m.held_first, m.held_count) == (16, 4, 4)
    assert m.layer_types == ("latent_attention",) * 2 and m.num_dense_layers == 1
    assert (m.head_dim, m.rope_head_dim, m.v_head_dim) == (24, 8, 16)
    assert (m.q_lora_rank, m.kv_lora_rank, m.shared_width) == (48, 32, 32)
    assert (m.mtp_depth, m.mtp_weight, m.routed_scale) == (1, 0.3, 2.5)
    assert m.rope_theta == 32e6 and m.norm_eps == 1e-6 and m.use_select_bias
    # the dialect is its keys': the same keys under another model's name build the same
    assert HybridDecoderLM.from_config(dict(cfg, model_type="deepseek_v3")) == m
    whole = {k: v for k, v in cfg.items() if k not in ("router_num_experts", "held_experts")}
    m = HybridDecoderLM.from_config(whole)
    assert (m.num_experts, m.held_first, m.held_count) == (4, 0, 4)
    no_mtp = HybridDecoderLM.from_config(dict(cfg, num_nextn_predict_layers=0))
    assert (no_mtp.mtp_depth, no_mtp.mtp_weight) == (0, 0.0)
    for key, bad in (
        ("n_group", 2), ("topk_group", 2), ("ep_size", 2),
        ("rope_scaling", {"type": "yarn", "factor": 40}),
        ("num_nextn_predict_layers", 2), ("held_experts", [1, 3]),
        ("scoring_func", "softmax"), ("q_lora_rank", None),
        ("program", dict(cfg["program"], remat="full")),
    ):
        with pytest.raises(ValueError):
            HybridDecoderLM.from_config(dict(copy.deepcopy(cfg), **{key: bad}))
    # a module without next tokens has nothing to predict from
    with pytest.raises(ValueError, match="next_tokens"):
        runner.build_model(cfg).apply(_variables(cfg, 1), _batches(cfg, 1, 1)[0][0])


def test_train_moe_cli_trains_from_the_configuration_file(capsys):
    from akka_allreduce_tpu.__main__ import main

    rc = main(["train-moe", "--config", TINY, "--steps", "3", "--batch", "8",
               "--seq-len", "32", "--lr", "1e-3"])
    out = capsys.readouterr().out
    assert rc == 0 and "experts 4-7 of 16 held, top-4" in out
    assert "late/late" in out and "dropped 0.0%" in out and "mtp loss" in out


def test_the_cells_configuration_counts_as_the_issue_says():
    from harness import mla_moe_flops

    real = _json(REAL)
    shapes = ref.param_shapes(real)
    count = lambda keep: sum(  # noqa: E731
        int(np.prod(s)) for n, s in shapes.items() if keep(n))
    total = count(lambda n: True)
    assert total == 491_696_128 and round(total / 1e6, 1) == 491.7
    assert round(100 * count(lambda n: ".experts." in n) / total) == 38
    mla = count(lambda n: n.startswith("layers.0.") and n.split(".")[2] in
                ("q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "o"))
    assert mla == 26_347_520
    assert count(lambda n: n.startswith("layers.0.")) == 70_391_808
    assert count(lambda n: n.startswith("layers.1.") and ".experts." not in n) == 31_594_496
    assert ref.select_bias(real, 0).shape == (5, 256)
    # the file: the source's widths uncut, the three cuts, the deployment
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog, encoding="utf-8") as f:
            row = next(r for r in map(json.loads, f) if r["name"] == "JoyAI-LLM-Flash")
        differs = {k for k, v in row["config"].items() if real.get(k, "absent") != v}
        assert differs == set(real["reduced"]) == set(real["reduced_from"])
        assert real["source"] == row["source_url"]
    assert real["reduced"] == ["num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert real["held_experts"] == list(range(8)) and real["router_num_experts"] == 256
    # the benchmark's count: 27.5 TFLOP a step at a uniform router (one pair
    # in 32 lands here: 8 choices x 5 expert layers / 32 a token)
    per_token = mla_moe_flops.train_flops_per_token(real, 8192, 8 * 5 * 8 / 256)
    assert round(per_token["total"] * 8192 / 1e12, 1) == 27.5
    assert round(per_token["attention"] * 8192 / 1e12, 1) == 12.4
    assert round(per_token["experts"] * 8192 / 1e12, 2) == 0.29
    assert mla_moe_flops.matmul_params(real)["latent_attention"] == 6 * (mla - 2048)
    need = mla_moe_flops.grouped_products(real, 2048)
    assert need["flops"] == 18 * 2048 * 2048 * 768


def test_readers_of_the_new_metrics_on_a_made_up_record():
    real, tr = _json(REAL), _json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))
    rows = [[256.0] * 7 + [512.0]] * 5
    scopes = {
        "fusion.1": "jit(step)/jvp(HybridDecoderLM)/layers_0_attn/mla_down/dot_general",
        "fusion.2": "jit(step)/transpose(jvp(HybridDecoderLM))/mtp/mtp_attn/mla_up/dot_general",
        "fusion.3": "jit(step)/jvp(HybridDecoderLM)/mtp/mtp_moe/shared_expert/w1/dot_general",
        "fusion.4": "jit(step)/jvp(HybridDecoderLM)/layers_0_mlp/w1/dot_general",
        "splash_mha_fwd.1": "jit(step)/jvp(HybridDecoderLM)/mtp/mtp_attn/mla_attention/x",
    }
    units = [{"t0": i * 0.4, "t1": i * 0.4 + 0.4, "work": 8192, "ok": True,
              "expert_rows": rows, "mtp_loss": 9.0,
              # the module's layer took the last rung: ragged_dot, not counted
              "buffer_rows": [2560.0] * 4 + [65536.0],
              **({"op_scopes": scopes} if i == 0 else {})} for i in range(10)]
    record = {
        "cell": types.SimpleNamespace(config=real, traffic=tr), "chips": 1,
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }
    ops = {"fusion.1": [10, 0.30, "fusion"], "fusion.2": [10, 0.20, "fusion"],
           "fusion.3": [10, 0.10, "fusion"], "fusion.4": [10, 1.00, "fusion"],
           "splash_mha_fwd.1": [60, 0.90, "custom-call"], "gmm.3": [450, 0.05, "custom-call"],
           "tgmm.1": [150, 0.03, "custom-call"],
           # a conditional's event spans its body's events: on neither side
           "cond.9": [10, 0.5, "conditional"]}

    class Trace:
        def __init__(self, ops):
            self.ops = ops

        def main_module(self):
            return [(i * 0.4, 0.39) for i in range(10)]

        def matching(self, name=None, kind=None):
            import re

            hits = [v for k, v in self.ops.items() if re.search(name, k)]
            return sum(h[0] for h in hits), sum(h[1] for h in hits)

    read = lambda n, t=Trace(ops): spec.load_module("layer_metrics", n).compute(record, t)  # noqa: E731
    from harness import mla_moe_flops as flops

    assert read("mla_proj_ms") == pytest.approx(50.0)
    assert read("mtp_share_pct") == pytest.approx(100 * 1.2 / 2.58)
    assert read("attn_kernel_roofline_pct.mla") == pytest.approx(
        100 * 3 * 8192 * 8192 * 32 * 320 * 6 / 197e12 / 0.09)
    need = flops.grouped_products(real, 2304, 8)
    least = 4 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("moe_gmm_roofline_pct.mla") == pytest.approx(100 * least / 0.008)
    # four layers on the first rung, the module's on the last
    assert read("moe_row_buffer_fill_pct.mla") == pytest.approx(
        100 * (4 * 2304 / 2560 + 2304 / 65536) / 5)
    # an expert without a row has no tile: its weights are not in the least bytes
    assert flops.grouped_products(real, 2304, 4)["bytes"] < need["bytes"]
    assert flops.grouped_products(real, 0, 0) == {"flops": 0, "bytes": 0}
    per_token = flops.train_flops_per_token(real, 8192, 5 * 2304 / 8192)["total"]
    assert read("mfu_pct.mla") == pytest.approx(100 * per_token * 20480 / 197e12)
    assert 0 < read("mfu_pct.mla") < 100 and 0 < read("attn_kernel_roofline_pct.mla") < 100
    # what latent attention does around its kernels: the ops under the scope
    # that are no kernel (the kernel itself carries the scope too), a wrapper
    # on neither side
    around = Trace(dict(ops, **{"reduce.5": [60, 0.12, "reduce"], "copy.7": [60, 0.03, "copy"],
                                "cond.11": [10, 0.4, "conditional"]}))
    scopes.update({
        "reduce.5": "jit(step)/transpose(jvp(HybridDecoderLM))/layers_0_attn/mla_attention/"
                    "vmap(jit(_splash_attention))/reduce_sum",
        "copy.7": "jit(step)/jvp(HybridDecoderLM)/mtp/mtp_attn/mla_attention/transpose",
        "cond.11": "jit(step)/jvp(HybridDecoderLM)/layers_0_attn/mla_attention/cond",
    })
    assert read("mla_around_kernel_ms", around) == pytest.approx(15.0)
    assert read("mla_proj_ms", around) == pytest.approx(50.0)
    assert read("mla_around_kernel_ms") is None  # the kernel alone under the scope
    # a program without the scopes or the counters, a trace without the
    # kernels: nothing, and no raise
    bare = dict(record, window=dict(record["window"], units=[
        {k: v for k, v in u.items() if k not in ("expert_rows", "buffer_rows", "op_scopes")}
        for u in units]))
    empty = Trace({"fusion.4": [10, 1.0, "fusion"]})
    for name in ("mfu_pct.mla", "attn_kernel_roofline_pct.mla", "moe_gmm_roofline_pct.mla",
                 "moe_row_buffer_fill_pct.mla", "mla_proj_ms", "mtp_share_pct",
                 "mla_around_kernel_ms"):
        assert spec.load_module("layer_metrics", name).compute(bare, empty) is None
    assert spec.load_module("layer_metrics", "mla_around_kernel_ms").compute(bare, around) is None
    unscoped = dict(record, window=dict(record["window"], units=[
        dict(u, op_scopes={k: "" for k in scopes}) if "op_scopes" in u else u
        for u in units]))
    for name in ("mla_proj_ms", "mtp_share_pct", "mla_around_kernel_ms"):
        assert spec.load_module("layer_metrics", name).compute(unscoped, around) is None


def test_op_scopes_gives_a_fusion_the_product_inside_it():
    text = """HloModule jit_step

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  %dot.1 = f32[8,8]{1,0} dot(%p0, %p0), metadata={op_name="jit(step)/layers_0_attn/mla_up/dot_general"}
  ROOT %add.1 = f32[8,8]{1,0} add(%dot.1, %p0), metadata={op_name="jit(step)/adam/add"}
}

ENTRY %main.1 (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %fusion.7 = f32[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.1, metadata={op_name="jit(step)/adam/add"}
  %splash_mha_fwd.3 = f32[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", frontend_attributes={kernel_metadata={
"xprof_metadata":"{\\"block_q\\": 1024}"
}}, metadata={op_name="jit(step)/mtp/mtp_attn/mla_attention/pallas_call" stack_frame_id=2}, backend_config={"x":1}
  ROOT %neg.2 = f32[8,8]{1,0} negate(%fusion.7), metadata={op_name="jit(step)/mtp/neg"}
}
"""
    scopes = runner.op_scopes(text)
    assert scopes["fusion.7"].endswith("mla_up/dot_general")
    assert scopes["neg.2"] == "jit(step)/mtp/neg" and scopes["a"] == ""
    # a kernel's instruction runs over several lines; its metadata is on the last
    assert scopes["splash_mha_fwd.3"] == "jit(step)/mtp/mtp_attn/mla_attention/pallas_call"

"""The configuration-built conv/attention hybrid with held experts
(``models/hybrid_decoder.py``, ``ops/short_conv.py``, the dropless part of
``ops/moe.py``, ``MoETrainer``'s ``model=`` seam) against the benchmark's
plain reference ``benchmarks/reference/lfm2_moe_plain.py``, at tiny widths
on the CPU, on seeded weights."""

from __future__ import annotations

import copy
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec, traffic  # noqa: E402

ref = spec.load_module("reference", "lfm2_moe_plain")
runner = spec.load_module("runners", "moe_train")

TRAFFIC = {"batch": 2, "seq_len": 32, "tokens": "copy_half"}


@pytest.fixture(scope="module")
def cfg():
    path = os.path.join(BENCH, "tests", "tiny_lfm2_moe.json")
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


# -- the short convolution ----------------------------------------------------


def _conv_inputs(seed=0, b=2, t=16, d=8, taps=3):
    k = jax.random.split(jax.random.PRNGKey(seed), 2)
    return jax.random.normal(k[0], (b, t, 3 * d)), jax.random.normal(k[1], (d, taps))


def _plain_conv(bcz, w):
    """The reference's convolution with the projections taken out."""
    d = w.shape[0]
    eye = jnp.eye(3 * d), jnp.eye(d)
    return ref.short_conv(bcz, eye[0], w, eye[1])


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_short_conv_forward(taps):
    from akka_allreduce_tpu.ops.short_conv import gated_short_conv

    bcz, w = _conv_inputs(taps=taps)
    _close(gated_short_conv(bcz, w), _plain_conv(bcz, w))
    # causal: the output at t does not see t + 1
    later = bcz.at[:, 9:].set(0.0)
    np.testing.assert_array_equal(
        gated_short_conv(later, w)[:, :9], gated_short_conv(bcz, w)[:, :9]
    )


@pytest.mark.parametrize("taps", [1, 3])
def test_short_conv_gradient(taps):
    from akka_allreduce_tpu.ops.short_conv import gated_short_conv

    bcz, w = _conv_inputs(seed=1, taps=taps)
    probe = jax.random.normal(jax.random.PRNGKey(9), (2, 16, 8))
    got = jax.grad(lambda a, b: (gated_short_conv(a, b) * probe).sum(), (0, 1))(bcz, w)
    want = jax.grad(lambda a, b: (_plain_conv(a, b) * probe).sum(), (0, 1))(bcz, w)
    _close(got[0], want[0])
    _close(got[1], want[1])


# -- the router ---------------------------------------------------------------


def _route_both(logits, bias, k=2, renormalise=True, scale=1.0):
    from akka_allreduce_tpu.ops.moe import sigmoid_topk_route

    experts = logits.shape[1]
    sel, w = sigmoid_topk_route(
        logits, bias, k, renormalise=renormalise, scale=scale
    )
    dense = jnp.zeros(logits.shape).at[jnp.arange(logits.shape[0])[:, None], sel].add(w)
    cfg = {"num_experts_per_tok": k, "norm_topk_prob": renormalise,
           "routed_scaling_factor": scale}
    # the reference's router on logits handed in as x @ identity
    want, want_sel = ref.routing_weights(
        logits, jnp.eye(experts), bias, cfg, jnp.float32
    )
    return sel, dense, want_sel, want


def _logit(p):
    p = jnp.asarray(p, jnp.float32)
    return jnp.log(p) - jnp.log1p(-p)


ROUTER_CASES = {
    # the bias lifts expert 3 over expert 1; it is weighed by p alone
    "bias_picks_not_weighs": (_logit([[0.6, 0.5, 0.2, 0.45]]), [0.0, 0.0, 0.0, 0.1]),
    # equal scores: the lower index, in both
    "ties": (jnp.zeros((3, 6)), [0.0] * 6),
    "random": (jax.random.normal(jax.random.PRNGKey(2), (64, 16)),
               list(0.01 * np.arange(16))),
}


@pytest.mark.parametrize("case", sorted(ROUTER_CASES))
def test_router_against_reference(case):
    logits, bias = ROUTER_CASES[case]
    sel, dense, want_sel, want = _route_both(logits, jnp.asarray(bias, jnp.float32))
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(want_sel))
    _close(dense, want, 1e-6)


def test_router_selects_by_biased_score_and_weighs_by_p():
    logits, bias = ROUTER_CASES["bias_picks_not_weighs"]
    sel, dense, _, _ = _route_both(logits, jnp.asarray(bias, jnp.float32))
    assert sel.tolist() == [[0, 3]]  # 0.45 + 0.1 beats 0.5
    np.testing.assert_allclose(
        np.asarray(dense[0, [0, 3]]),
        np.array([0.6, 0.45]) / (0.6 + 0.45 + 1e-6), rtol=1e-6,
    )


@pytest.mark.parametrize("renormalise,scale", [(True, 1.0), (False, 1.0), (True, 2.5)])
def test_router_renormalisation_and_scale(renormalise, scale):
    logits, bias = ROUTER_CASES["random"]
    _, dense, _, want = _route_both(
        logits, jnp.asarray(bias, jnp.float32), k=4,
        renormalise=renormalise, scale=scale,
    )
    _close(dense, want, 1e-6)
    if renormalise:
        np.testing.assert_allclose(np.asarray(dense.sum(-1)), scale, rtol=1e-4)


# -- the expert layer: dropless, held subsets ----------------------------------


def _layer_inputs(cfg, seed=0, tokens=64):
    s = ref.dims(cfg)
    d, fe, e = s["d"], s["fe"], s["experts"]
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return {
        "x": jax.random.normal(k[0], (1, tokens, d)),
        "router.w": 0.3 * jax.random.normal(k[1], (d, e)),
        "experts.w1": 0.2 * jax.random.normal(k[2], (e, d, fe)),
        "experts.w3": 0.2 * jax.random.normal(k[3], (e, d, fe)),
        "experts.w2": 0.2 * jax.random.normal(k[4], (e, fe, d)),
        "bias": 0.01 * jax.random.normal(k[5], (e,)),
    }


def _reference_layer(cfg, a, held):
    w = lambda n: a[n][jnp.asarray(held)] if n.startswith("experts.") else a[n]  # noqa: E731
    return ref.expert_layer(a["x"], w, a["bias"], cfg, jnp.float32, held=held)[0]


def _program_layer(cfg, a, first, count, impl="auto"):
    from akka_allreduce_tpu.ops.moe import moe_dropless_held

    hold = slice(first, first + count)
    y, route, dropped = moe_dropless_held(
        a["x"][0], a["router.w"], a["bias"], a["experts.w1"][hold],
        a["experts.w3"][hold], a["experts.w2"][hold],
        k=cfg["num_experts_per_tok"], held_first=first, impl=impl,
    )
    return y[None], route, dropped


def test_the_shares_partial_results_add_up_to_the_uncut_layer(cfg):
    """Every share routes over all the experts and computes its own part;
    the parts of all the shares are the whole layer's result."""
    a = _layer_inputs(cfg)
    experts, per_share = ref.dims(cfg)["experts"], 4
    whole = _reference_layer(cfg, a, list(range(experts)))
    parts, rows = 0.0, 0
    for first in range(0, experts, per_share):
        y, route, dropped = _program_layer(cfg, a, first, per_share)
        _close(y, _reference_layer(cfg, a, list(range(first, first + per_share))))
        assert float(dropped) == 0.0
        parts, rows = parts + y, rows + int(route.group_sizes[:per_share].sum())
    _close(parts, whole)
    assert rows == a["x"].shape[1] * cfg["num_experts_per_tok"]  # each pair once


@pytest.mark.parametrize("favoured", [[5], [4, 5, 6, 7]])
def test_dropless_under_skew(cfg, favoured):
    """All tokens to one held expert, and all tokens' every choice to the
    held experts (the buffer's worst case): nothing dropped, and the result
    is the reference's."""
    a = _layer_inputs(cfg, seed=3)
    tokens, k = a["x"].shape[1], cfg["num_experts_per_tok"]
    # a constant direction in x that the router reads with a large weight
    a["x"] = a["x"].at[..., 0].set(1.0)
    a["router.w"] = a["router.w"].at[0, jnp.asarray(favoured)].add(30.0)
    a["bias"] = jnp.zeros_like(a["bias"])  # the favoured saturate at p = 1
    y, route, dropped = _program_layer(cfg, a, 4, 4)
    sizes = np.asarray(route.group_sizes)
    assert float(dropped) == 0.0
    if len(favoured) == 1:
        assert sizes[1] == tokens  # every token chose expert 5
    else:
        assert sizes[:4].tolist() == [tokens] * 4 and sizes[4] == 0
        assert sizes[:4].sum() == tokens * k  # the whole buffer is filled
    _close(y, _reference_layer(cfg, a, [4, 5, 6, 7]))


# -- the ladder of row buffers ---------------------------------------------------


@pytest.mark.parametrize("rows,held,experts,want", [
    (8192 * 4, 8, 64, (5120, 32768)),  # the LFM2 cell
    # the JoyAI cell: the last rung over 8 x the first, so one between them
    (8192 * 8, 8, 256, (2560, 10240, 65536)),
    (8192 * 8, 8, 128, (5120, 20480, 65536)),
    (8192 * 8, 8, 64, (10240, 65536)),
    (8192 * 4, 64, 64, (32768,)),  # every expert held: the buffer alone
    (8192 * 4, 56, 64, (32768,)),  # a quarter over the expectation is all of it
    (1152, 2, 16, (256, 1152)),
    (1152, 4, 16, (384, 1152)),
    # the Mellum2 cell's shape: a quarter held, the last 3.2 times the first
    (8192 * 8, 16, 64, (20480, 65536)),
    (1024, 4, 16, (512, 1024)),  # the tile is the kernels' at this buffer
    (256, 4, 16, (256,)),
    (100, 1, 16, (100,)),  # no kernel tiles 100 rows
])
def test_row_rungs(rows, held, experts, want):
    from akka_allreduce_tpu.ops.moe import row_rungs

    assert row_rungs(rows, held, experts) == want


def test_row_rungs_at_tiny_shapes_stay_inside_the_buffer_and_on_the_tile():
    from akka_allreduce_tpu.ops.moe import row_rungs

    for rows in range(128, 4097, 128):
        for held, experts in ((1, 64), (2, 16), (4, 16), (8, 8), (3, 7)):
            rungs = row_rungs(rows, held, experts)
            assert 1 <= len(rungs) <= 3 and rungs[-1] == rows
            assert len(rungs) < 3 or rungs[1] == 4 * rungs[0] < rows / 2
            assert list(rungs) == sorted(set(rungs))
            assert all(r % 128 == 0 for r in rungs)
            # the first rung holds the load under uniform routing
            assert rungs[0] >= min(rows, rows * held / experts)


LADDER = (256, 1152)  # 288 tokens x 4 choices, experts 4-5 of 16 held


def _routed_exactly(cfg, all_held, one_held, seed=7):
    """288 tokens of which the first ``all_held`` pick two held experts (4
    and 5: the share holds two, so no token can pick more), the next
    ``one_held`` one (5), and the rest none: ``2 * all_held + one_held``
    rows for the held experts, exactly."""
    a = _layer_inputs(cfg, seed=seed, tokens=288)
    kind = jnp.where(jnp.arange(288) < all_held, 0,
                     jnp.where(jnp.arange(288) < all_held + one_held, 1, 2))
    a["x"] = (0.05 * a["x"]).at[0, :, :3].set(jax.nn.one_hot(kind, 3))
    # each kind of token reads one direction that lifts four experts well
    # over the rest, short of p = 1 (where the router's gradient is zero)
    for direction, favoured in enumerate(([4, 5, 8, 9], [5, 8, 9, 10], [8, 9, 10, 11])):
        a["router.w"] = a["router.w"].at[direction, jnp.asarray(favoured)].add(8.0)
    a["bias"] = jnp.zeros_like(a["bias"])
    return a


def _layer_and_gradients(cfg, a, first, count):
    """``(y, route, dropped)`` and the gradients of a probed sum of ``y``."""
    from akka_allreduce_tpu.ops.moe import moe_dropless_held

    names = ("x", "router.w", "experts.w1", "experts.w3", "experts.w2")
    probe = jax.random.normal(jax.random.PRNGKey(99), a["x"].shape)

    def loss(*leaves):
        y, route, dropped = _program_layer(cfg, dict(a, **dict(zip(names, leaves))),
                                           first, count)
        return (y * probe).sum(), (y, route, dropped)

    grads, out = jax.grad(loss, argnums=range(5), has_aux=True)(*(a[n] for n in names))
    return out, grads


RUNG_CASES = {
    # (tokens picking two held experts, tokens picking one) -> rung taken
    "first_rung": (60, 40, 0),
    "exactly_the_first_rung": (100, 56, 0),
    "one_row_over_the_first_rung": (100, 57, 1),
    "last_rung_half_filled": (280, 8, 1),
    "every_token_picks_both_held": (288, 0, 1),
    "nothing_routed_here": (0, 0, 0),
}


@pytest.mark.parametrize("case", sorted(RUNG_CASES))
def test_each_rung_equals_the_whole_buffer(cfg, case, monkeypatch):
    """Whichever rung the rows routed here ask for, the result, every
    gradient, the counts and the picks are those of the one-rung program
    that moves every assignment."""
    from akka_allreduce_tpu.ops import moe

    all_held, one_held, rung = RUNG_CASES[case]
    a = _routed_exactly(cfg, all_held, one_held)
    assert moe.row_rungs(288 * 4, 2, 16) == LADDER
    (y, route, dropped), grads = _layer_and_gradients(cfg, a, 4, 2)
    routed = 2 * all_held + one_held
    assert int(route.group_sizes[:2].sum()) == routed
    assert (int(route.rung), int(route.buffer_rows)) == (rung, LADDER[rung])
    assert float(dropped) == 0.0
    _close(y, _reference_layer(cfg, a, [4, 5]))

    monkeypatch.setattr(moe, "row_rungs", lambda rows, held, experts: (rows,))
    (y1, route1, dropped1), grads1 = _layer_and_gradients(cfg, a, 4, 2)
    assert int(route1.buffer_rows) == 288 * 4 and float(dropped1) == 0.0
    _close(y, y1)
    for g, g1 in zip(grads, grads1):
        _close(g, g1)
    if routed:
        assert all(float(jnp.abs(g1).max()) > 0 for g1 in grads1)
    np.testing.assert_array_equal(np.asarray(route.group_sizes), np.asarray(route1.group_sizes))
    np.testing.assert_array_equal(np.asarray(route.selected), np.asarray(route1.selected))


@pytest.mark.parametrize("all_held,one_held,rung", [
    (60, 40, 0), (100, 28, 0), (100, 29, 1), (200, 88, 1),
])
def test_the_rung_between_equals_the_whole_buffer(cfg, all_held, one_held, rung, monkeypatch):
    """A share whose last rung is over eight times its first (expert 5 of 16
    alone: 128 rows against 1,152) has a rung between them, and on it too
    the result and every gradient are the one-rung program's."""
    from akka_allreduce_tpu.ops import moe

    ladder = (128, 512, 1152)
    a = _routed_exactly(cfg, all_held, one_held)
    assert moe.row_rungs(288 * 4, 1, 16) == ladder
    (y, route, dropped), grads = _layer_and_gradients(cfg, a, 5, 1)
    assert int(route.group_sizes[0]) == all_held + one_held
    assert (int(route.rung), int(route.buffer_rows)) == (rung, ladder[rung])
    assert float(dropped) == 0.0
    _close(y, _reference_layer(cfg, a, [5]))
    monkeypatch.setattr(moe, "row_rungs", lambda rows, held, experts: (rows,))
    (y1, route1, _), grads1 = _layer_and_gradients(cfg, a, 5, 1)
    assert int(route1.buffer_rows) == 288 * 4
    _close(y, y1)
    for g, g1 in zip(grads, grads1):
        _close(g, g1)
        assert float(jnp.abs(g1).max()) > 0


def test_every_choice_held_takes_the_last_rung(cfg):
    """The ladder's worst case through a share that CAN fill its buffer:
    four of sixteen experts held, every token choosing those four."""
    from akka_allreduce_tpu.ops.moe import row_rungs

    a = _layer_inputs(cfg, seed=3, tokens=288)
    a["x"] = (0.05 * a["x"]).at[..., 0].set(1.0)
    a["router.w"] = a["router.w"].at[0, jnp.asarray([4, 5, 6, 7])].add(8.0)
    a["bias"] = jnp.zeros_like(a["bias"])
    assert row_rungs(288 * 4, 4, 16) == (384, 1152)
    (y, route, dropped), grads = _layer_and_gradients(cfg, a, 4, 4)
    assert (int(route.rung), int(route.buffer_rows)) == (1, 1152)
    assert route.group_sizes.tolist() == [288] * 4 + [0] and float(dropped) == 0.0
    _close(y, _reference_layer(cfg, a, [4, 5, 6, 7]))
    assert all(float(jnp.abs(g).max()) > 0 for g in grads)


@pytest.mark.parametrize("first,count,branches", [(0, 16, False), (4, 2, True)])
def test_a_branch_only_where_experts_are_absent(cfg, first, count, branches):
    """A device that holds every expert has one rung and its program no
    conditional, forward or backward."""
    a = _layer_inputs(cfg, seed=1, tokens=288)

    def grads(x):
        return jax.grad(lambda x: _program_layer(cfg, dict(a, x=x), first, count)[0].sum())(x)

    text = str(jax.make_jaxpr(grads)(a["x"]))
    assert (" cond[" in text or "cond(" in text) == branches, text[:2000]


@pytest.mark.parametrize("tokens,first,count", [
    (64, 8, 4),  # 256 rows, one rung: two 128-row tiles
    (288, 4, 2),  # two rungs, 256 / 1152, the first taken
])
def test_pallas_grouped_products_equal_ragged_dot(cfg, tokens, first, count):
    """The megablox kernels (interpret mode here) against ``lax.ragged_dot``:
    the result and every gradient."""
    a = _layer_inputs(cfg, seed=5, tokens=tokens)

    def loss(impl, x, w1, w3, w2):
        b = dict(a, x=x, **{"experts.w1": w1, "experts.w3": w3, "experts.w2": w2})
        y, route, _ = _program_layer(cfg, b, first, count, impl)
        return (y * y).sum(), route.buffer_rows

    args = (a["x"], a["experts.w1"], a["experts.w3"], a["experts.w2"])
    both = [
        jax.value_and_grad(lambda *p: loss(impl, *p), (0, 1, 2, 3), has_aux=True)(*args)
        for impl in ("ragged_dot", "gmm")
    ]
    ((want, rows), want_grads), ((got, _), got_grads) = both
    assert int(rows) == (256 if count == 4 else LADDER[0])
    _close(got, want)
    for g, w in zip(got_grads, want_grads):
        _close(g, w)
        assert float(jnp.abs(w).max()) > 0


# -- the whole model, through MoETrainer ----------------------------------------


def _batches(cfg, seed, n=3):
    return [traffic.token_batch(TRAFFIC, cfg["vocab_size"], seed, i) for i in range(n)]


def _trainer(cfg, seed):
    variables = runner.to_program_tree(
        ref.init_params(cfg, seed), ref.select_bias(cfg, seed), cfg
    )
    return runner.build_trainer(cfg, TRAFFIC["seq_len"], variables, jax.devices())


def test_logits_match_the_reference(cfg):
    leaves, bias = ref.init_params(cfg, 3), ref.select_bias(cfg, 3)
    tokens, _ = _batches(cfg, 3, 1)[0]
    out = runner.build_model(cfg).apply(
        runner.to_program_tree(leaves, bias, cfg), tokens
    )
    logits, aux, dropped, rows, buffers = out
    _close(logits, ref.logits(leaves, bias, tokens, cfg))
    assert float(aux) == 0.0 and float(dropped) == 0.0
    assert rows.shape == (2, 4) and logits.dtype == jnp.float32
    # 64 tokens x 4 choices: one rung, and each expert layer took it
    assert buffers.tolist() == [256.0, 256.0]


def test_selections_match_the_reference(cfg):
    leaves, bias = ref.init_params(cfg, 4), ref.select_bias(cfg, 4)
    tokens, _ = _batches(cfg, 4, 1)[0]
    _, state = runner.build_model(cfg).apply(
        runner.to_program_tree(leaves, bias, cfg), tokens, mutable=["intermediates"]
    )
    got = jnp.stack([
        state["intermediates"][f"layers_{i}_moe"]["selected"][0]
        for i in ref.expert_layers(cfg)
    ])
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(ref.selections(leaves, bias, tokens, cfg))
    )


def test_three_steps_through_moe_trainer_match_the_reference(cfg):
    """Loss, the first gradient of EVERY leaf (element by element, as Adam's
    first moment holds it) and the parameters' change after three steps."""
    seed, names = 11, list(ref.param_shapes(cfg))
    trainer, batches = _trainer(cfg, seed), _batches(cfg, seed)
    bias_before = jax.tree.map(np.asarray, trainer.params["fixed"])
    m = trainer.train_step(*batches[0])
    mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
    grads = {n: a / (1.0 - cfg["program"]["adam_b1"])
             for n, a in runner.by_reference_name(mu, names).items()}
    leaves, bias = ref.init_params(cfg, seed), ref.select_bias(cfg, seed)
    x, y = (jnp.asarray(a) for a in batches[0])
    want_loss, want = jax.value_and_grad(ref.mean_loss)(leaves, bias, x, y, cfg)
    assert abs(m.loss - float(want_loss)) < 1e-5 * float(want_loss)
    for n in names:
        _close(grads[n], want[n], 1e-4)
        assert float(jnp.abs(want[n]).max()) > 0, n  # no leaf is a no-op
    assert m.dropped == 0.0 and m.aux_loss == 0.0 and m.contributors == 1.0
    tokens = x.size
    assert m.expert_rows.shape == (2, 4)
    assert (m.expert_rows.sum(axis=1) <= tokens * cfg["num_experts_per_tok"]).all()
    assert m.buffer_rows.tolist() == [tokens * cfg["num_experts_per_tok"]] * 2

    for b in batches[1:]:
        trainer.train_step(*b)
    got = ref.delta_norms(runner.by_reference_name(trainer.params, names), cfg, seed)
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    for n in names:
        assert abs(got[n] - followed["delta_norms"][n]) <= 1e-3 * followed["delta_norms"][n], n
    # the selection bias picks only: no gradient reaches it, Adam leaves it
    jax.tree.map(np.testing.assert_array_equal, bias_before,
                 jax.tree.map(np.asarray, trainer.params["fixed"]))


def test_runner_check_passes_sound_and_fails_the_control(cfg):
    """The benchmark's comparison: the program is correct under the tiny
    limits; the reference one step down (bf16 router, bf16 state) is not."""
    compare = spec.load_module("runners", "lm_train").compare
    seed, names = 13, list(ref.param_shapes(cfg))
    batches = _batches(cfg, seed)
    observed = runner.first_steps(_trainer(cfg, seed), ref, cfg, seed, batches, names)
    followed = ref.follow(cfg, cfg["program"], seed, batches)
    assert all(c["ok"] for c in compare(observed, followed, cfg["correct_limits"]))
    control = ref.follow(cfg, cfg["program"], seed, batches, ref.CONTROL)
    assert not all(c["ok"] for c in compare(control, followed, cfg["correct_limits"]))


def test_moe_trainer_inits_a_handed_in_model_jitted_and_refuses_an_expert_axis(cfg):
    from akka_allreduce_tpu.train import MoETrainer

    model = runner.build_model(cfg)
    mesh = jax.make_mesh((2,), ("data",), devices=jax.devices()[:2])
    t = MoETrainer(mesh, model=model, vocab=cfg["vocab_size"], seq_len=32, seed=1)
    assert set(t.params) == {"params", "fixed"}
    x, y = traffic.token_batch(dict(TRAFFIC, batch=4), cfg["vocab_size"], 1, 0)
    first = t.train_step(x, y)
    for _ in range(20):
        last = t.train_step(x, y)
    assert last.loss < first.loss and last.contributors == 2.0
    # rows are summed over the replicas: 4 rows of 32 tokens, 4 choices each
    assert last.expert_rows.sum(axis=1).max() <= 4 * 32 * 4
    # each replica's 64 tokens x 4 choices are one 256-row rung; two replicas
    assert last.buffer_rows.tolist() == [512.0, 512.0]
    if len(jax.devices()) >= 4:
        ep_mesh = jax.make_mesh((2, 2), ("data", "expert"), devices=jax.devices()[:4])
        with pytest.raises(ValueError, match="no exchange"):
            MoETrainer(ep_mesh, model=model, vocab=cfg["vocab_size"], seq_len=32)


def test_from_config_reads_the_share_and_refuses_what_is_not_built(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    m = HybridDecoderLM.from_config(cfg)
    assert (m.num_experts, m.held_first, m.held_count) == (16, 4, 4)
    assert m.head_dim == 16 and m.rope_theta == 1e6 and m.conv_taps == 3
    whole = {k: v for k, v in cfg.items() if k not in ("router_num_experts", "held_experts")}
    m = HybridDecoderLM.from_config(whole)
    assert (m.num_experts, m.held_first, m.held_count) == (4, 0, 4)
    for key, bad in (("conv_bias", True), ("held_experts", [1, 3]),
                     ("num_hidden_layers", 4)):
        with pytest.raises(ValueError):
            HybridDecoderLM.from_config(dict(copy.deepcopy(cfg), **{key: bad}))


def test_train_moe_cli_trains_from_the_configuration_file(cfg, capsys):
    from akka_allreduce_tpu.__main__ import main

    path = os.path.join(BENCH, "tests", "tiny_lfm2_moe.json")
    rc = main(["train-moe", "--config", path, "--steps", "3", "--batch", "8",
               "--seq-len", "32", "--lr", "1e-3"])
    out = capsys.readouterr().out
    assert rc == 0 and "experts 4-7 of 16 held, top-4" in out
    assert "dropped 0.0%" in out


# -- the benchmark's count for the cell ------------------------------------------


def test_the_cells_configuration_counts_as_the_issue_says():
    from harness import moe_flops

    path = os.path.join(BENCH, "configs", "lfm2_24b_a2b_ep8_d5.json")
    with open(path, encoding="utf-8") as f:
        real = json.load(f)
    shapes = ref.param_shapes(real)
    total = sum(int(np.prod(s)) for s in shapes.values())
    experts = sum(int(np.prod(s)) for n, s in shapes.items() if ".experts." in n)
    assert round(total / 1e6, 1) == 486.1 and round(100 * experts / total) == 62
    # half a (token, choice) pair a token and layer lands on the 8 of 64 held
    per_token = moe_flops.train_flops_per_token(real, 8192, 4 * 4 * 8 / 64)
    assert round(per_token["total"] * 8192 / 1e12, 2) == 9.97
    need = moe_flops.grouped_products(real, 4096)
    assert need["flops"] == 18 * 4096 * 2048 * 1536

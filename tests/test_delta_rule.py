"""The gated delta rule in chunked form (``ops/delta_rule.py``) against the
token-by-token recurrence it stands for, forward, final state and every
gradient, at tiny widths on the CPU; the triangular solve it rests on; the
rule's Pallas kernels, interpreted, against that XLA form at heads of 128; the
convolution in front of it (``ops/short_conv.silu_short_conv``). The decoder
built on them: ``tests/test_linear_attention_decoder.py``."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.obs import metrics
from akka_allreduce_tpu.ops import delta_rule
from akka_allreduce_tpu.ops.delta_rule import gated_delta_rule, inverse_unit_lower
from akka_allreduce_tpu.ops.short_conv import silu_short_conv

B, HK, HV, DK, DV, CHUNK = 2, 2, 4, 8, 6, 8  # two value heads on each key head


def recurrence(q, k, v, g, beta):
    """``S_t = a_t S + b_t k_t (v_t - a_t S^T k_t)^T``, ``o_t = S_t^T q_t``."""
    rep = v.shape[1] // q.shape[1]
    q, k = jnp.repeat(q, rep, 1), jnp.repeat(k, rep, 1)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[..., None, None]
        write = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + b_t[..., None, None] * k_t[..., :, None] * write[..., None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    first = jnp.zeros((q.shape[0], v.shape[1], q.shape[-1], v.shape[-1]))
    state, out = lax.scan(step, first, tuple(jnp.moveaxis(x, 2, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(out, 0, 2), state


def operands(t, seed=0, decay="mixed", write="mixed"):
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(key[0], (B, HK, t, DK))) * DK ** -0.5
    k = unit(jax.random.normal(key[1], (B, HK, t, DK)))
    v = jax.random.normal(key[2], (B, HV, t, DV))
    spread = jax.random.uniform(key[3], (B, HV, t))
    g = {"mixed": -2.0 * spread, "alpha_near_0": -5.0 - spread,
         "alpha_near_1": -1e-3 * spread}[decay]
    beta = {"mixed": jax.random.uniform(key[4], (B, HV, t)), "beta_0": jnp.zeros((B, HV, t)),
            "beta_1": jnp.ones((B, HV, t))}[write]
    return q, k, v, g, beta


def weighed(fn):
    """A scalar of both results, so that one gradient holds both paths."""
    key = jax.random.split(jax.random.PRNGKey(99), 2)

    def scalar(*args):
        out, state = fn(*args)
        return (out * jax.random.normal(key[0], out.shape)).sum() + (
            state * jax.random.normal(key[1], state.shape)).sum()

    return scalar


def chunked(*args):
    return gated_delta_rule(*args, chunk=CHUNK)


# one compile a length, whatever the regime
RULE, RECURRENCE = jax.jit(chunked), jax.jit(recurrence)
RULE_GRAD = jax.jit(jax.grad(weighed(chunked), argnums=tuple(range(5))))
RECURRENCE_GRAD = jax.jit(jax.grad(weighed(recurrence), argnums=tuple(range(5))))


def close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want)))
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale + 1e-7


@pytest.mark.parametrize("chunks,decay,write", [
    (1, "mixed", "mixed"), (2, "mixed", "mixed"), (4, "mixed", "mixed"),
    (2, "alpha_near_0", "mixed"), (4, "alpha_near_1", "mixed"),
    (2, "mixed", "beta_0"), (4, "mixed", "beta_1"), (1, "alpha_near_1", "beta_1"),
])
def test_chunked_rule_is_the_recurrence(chunks, decay, write):
    t = chunks * CHUNK
    args = operands(t, chunks, decay, write)
    (out, state), (want, want_state) = RULE(*args), RECURRENCE(*args)
    close(out, want)
    close(state, want_state)
    assert out.shape == (B, HV, t, DV) and state.dtype == jnp.float32
    if write == "beta_0":  # nothing is ever written
        assert float(jnp.abs(out).max()) == 0.0 and float(jnp.abs(state).max()) == 0.0
    got, want = RULE_GRAD(*args), RECURRENCE_GRAD(*args)
    for a, b, name in zip(got, want, "q k v g beta".split()):
        assert float(jnp.abs(b).max()) > 0 or write == "beta_0", name
        close(a, b, 2e-4)


def test_the_state_is_carried_from_chunk_to_chunk():
    """Four chunks: the last chunk's output depends on the first chunk's
    values, and stops depending on them when the decay forgets."""
    t = 4 * CHUNK
    q, k, v, g, beta = operands(t, 5, "alpha_near_1")
    last = lambda v_, g_: RULE(q, k, v_, g_, beta)[0][:, :, -CHUNK:]  # noqa: E731
    moved = v.at[:, :, :CHUNK].add(1.0)
    assert float(jnp.abs(last(moved, g) - last(v, g)).max()) > 1e-2
    forgets = g.at[:, :, CHUNK].set(-1e4)  # alpha = 0 once, after the first chunk
    assert float(jnp.abs(last(moved, forgets) - last(v, forgets)).max()) == 0.0


def test_a_length_the_chunk_does_not_divide_and_the_default_chunk():
    args = operands(20, 7)
    out, state = RULE(*args)
    want, want_state = RECURRENCE(*args)
    close(out, want)
    close(state, want_state)
    args = operands(128, 8)  # the chunk of 64, twice
    out, state = jax.jit(gated_delta_rule)(*args)
    want, want_state = RECURRENCE(*args)
    close(out, want)
    close(state, want_state)


def test_alike_keys_in_a_chunk_keep_float32():
    """One key and beta 1 throughout, no decay: ``I + A`` is all ones below the
    diagonal, its inverse a bidiagonal of 1 and -1, and the 64-wide nilpotent
    product would sum terms of 1e17 to get there."""
    q, k, v, _, _ = operands(64, 9)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    flat = jnp.zeros((B, HV, 64))
    out, state = jax.jit(gated_delta_rule)(q, k, v, flat, flat + 1.0)
    want, want_state = RECURRENCE(q, k, v, flat, flat + 1.0)
    close(out, want, 1e-3)
    close(state, want_state, 1e-3)


def test_inverse_unit_lower_and_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (3, 64, 64)) * 0.3, -1)
    eye = jnp.eye(64)
    close(inverse_unit_lower(a), jnp.linalg.inv(eye + a), 1e-4)
    w = jax.random.normal(jax.random.PRNGKey(4), (3, 64, 64))
    got = jax.grad(lambda a_: (inverse_unit_lower(a_) * w).sum())(a)
    want = jax.grad(lambda a_: (jnp.linalg.inv(eye + a_) * w).sum())(a)
    close(jnp.tril(got, -1), jnp.tril(want, -1), 1e-3)
    ones = jnp.tril(jnp.ones((64, 64)), -1)
    close(inverse_unit_lower(ones), eye - jnp.eye(64, k=-1), 1e-5)


def test_bf16_operands_keep_a_float32_state():
    args = operands(2 * CHUNK, 11)
    low = tuple(x.astype(jnp.bfloat16) for x in args[:3]) + args[3:]
    out, state = RULE(*low)
    want, want_state = RECURRENCE(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    close(out.astype(jnp.float32), want, 5e-2)
    close(state, want_state, 5e-2)


def test_inside_shard_map_with_the_varying_axes_checked():
    """As ``MoETrainer`` wraps the step: rows sharded over ``data``, the
    check on; the scan's zero state has to vary as the chunks do."""
    mesh = jax.make_mesh((2,), ("data",))
    args = operands(2 * CHUNK, 12)
    spec = P("data")

    def local(*a):
        out, state = gated_delta_rule(*a, chunk=CHUNK)
        return out, state

    mapped = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(spec,) * 5, out_specs=(spec, spec), check_vma=True))
    out, state = mapped(*args)
    want, want_state = RECURRENCE(*args)
    close(out, want)
    close(state, want_state)
    grad = jax.jit(jax.grad(lambda *a: mapped(*a)[0].sum(), argnums=(0, 3)))(*args)
    want = jax.grad(lambda *a: recurrence(*a)[0].sum(), argnums=(0, 3))(*args)
    close(grad[0], want[0], 2e-4)
    close(grad[1], want[1], 2e-4)


def test_shapes_that_disagree_are_refused():
    q, k, v, g, beta = operands(CHUNK)
    with pytest.raises(ValueError, match="beta"):
        gated_delta_rule(q, k, v, g, beta[:, :2])
    with pytest.raises(ValueError):
        gated_delta_rule(q, k, v[:, :3], g[:, :3], beta[:, :3])  # 3 heads on 2


# -- the rule's kernels, interpreted ---------------------------------------------------

KERNEL_T = 256  # four chunks of 64: two grid steps of two chunks, the state carried across both
GAUGE, UNWRITTEN = "linear_attention.rule.kernel_chunks", -1


def kernel_operands(seed=0, decay="mixed", write="mixed", heads=(1, 2), t=KERNEL_T, d=128,
                    dtype=jnp.bfloat16):
    """As :func:`operands` at heads the kernels take: two value heads a key
    head, 128 columns, bf16."""
    hk, hv = heads
    key = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(key[0], (1, hk, t, d))) * d ** -0.5
    k = unit(jax.random.normal(key[1], (1, hk, t, d)))
    v = jax.random.normal(key[2], (1, hv, t, d))
    spread = jax.random.uniform(key[3], (1, hv, t))
    g = {"mixed": -0.8 * spread, "alpha_near_0": -5.0 - spread,
         "alpha_near_1": -1e-3 * spread}[decay]
    beta = {"mixed": jax.random.uniform(key[4], (1, hv, t)), "beta_0": jnp.zeros((1, hv, t)),
            "beta_1": jnp.ones((1, hv, t))}[write]
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


@pytest.fixture
def kernels_interpreted(monkeypatch):
    """The kernels where the chip would run them, interpreted, two chunks a
    grid step; the gauge at a value no call writes, its writer's memory of
    the shapes it has seen cleared."""
    monkeypatch.setattr(delta_rule, "_on_chip", lambda *arrays: True)
    monkeypatch.setattr(delta_rule, "BLOCK_CHUNKS", 2)
    delta_rule._gauge_kernel_chunks.cache_clear()
    metrics.gauge(GAUGE).set(UNWRITTEN)
    yield
    delta_rule._gauge_kernel_chunks.cache_clear()


def on_the_xla_form(*args):
    real, delta_rule._on_chip = delta_rule._on_chip, lambda *arrays: False
    try:
        return gated_delta_rule(*args)
    finally:
        delta_rule._on_chip = real


@pytest.mark.parametrize("heads,decay,write", [
    ((1, 2), "mixed", "mixed"), ((2, 4), "mixed", "mixed"),
    ((1, 2), "alpha_near_0", "mixed"), ((1, 2), "alpha_near_1", "mixed"),
    ((1, 2), "mixed", "beta_0"), ((1, 2), "mixed", "beta_1"),
])
def test_kernels_interpreted_match_the_xla_form(heads, decay, write, kernels_interpreted):
    """``gated_delta_rule_fwd`` / ``_bwd`` in interpret mode against the XLA
    form on the same bf16 operands: ``o`` and the final state (the kernels
    make the same products in the same precisions), and all five gradients of
    a loss that also reads the final state, so that the backward's ``dS``
    starts from a cotangent. The gradients are held to the XLA form's on
    float32 copies of the operands, which the kernels' (float32 between their
    products) are about as near as the XLA form's own in bf16 (within a bf16 rounding of
    the largest entry)."""
    args = kernel_operands(heads[0] + len(decay), decay, write, heads)
    # a function of its own: a trace jit has kept would not write the gauge again
    out, state = jax.jit(lambda *a: gated_delta_rule(*a))(*args)
    want, want_state = jax.jit(on_the_xla_form)(*args)
    assert out.dtype == jnp.bfloat16 and state.dtype == jnp.float32
    assert metrics.REGISTRY.snapshot()[GAUGE] == heads[1] * KERNEL_T // 64
    close(out.astype(jnp.float32), want.astype(jnp.float32), 1e-2)
    close(state, want_state, 1e-4)
    grad = lambda fn: jax.jit(jax.grad(weighed(  # noqa: E731
        lambda *a: tuple(x.astype(jnp.float32) for x in fn(*a))), argnums=tuple(range(5))))
    got, in_bf16 = grad(gated_delta_rule)(*args), grad(on_the_xla_form)(*args)
    exact = grad(on_the_xla_form)(*(x.astype(jnp.float32) for x in args))
    for a, b, c, name in zip(got, in_bf16, exact, "q k v g beta".split()):
        a, b = a.astype(jnp.float32), b.astype(jnp.float32)
        if write == "beta_0":  # nothing is written: only beta has a gradient
            assert name == "beta" or float(jnp.abs(a).max()) == 0.0, name
        scale = float(jnp.abs(c).max())
        assert scale > 0 or write == "beta_0", name
        err = lambda x: float(jnp.abs(x - c).max())  # noqa: E731
        assert err(a) <= max(2.0 * err(b), 1e-2 * scale) + 1e-7, (name, err(a), err(b), scale)


def test_kernels_keep_float32_where_a_chunks_keys_are_alike(kernels_interpreted):
    """The regime of :func:`test_alike_keys_in_a_chunk_keep_float32`: the
    kernels' solve is the halving too, so they are as near the recurrence
    as the XLA form is; and the packed solve alone on ``I + A`` all ones below the
    diagonal (both heads' halves) gives the bidiagonal of 1 and -1."""
    q, k, v, _, _ = kernel_operands(9, t=128)
    k = jnp.broadcast_to(k[:, :, :1], k.shape)
    flat = jnp.zeros((1, 2, 128))
    args = (q, k, v, flat, flat + 1.0)
    exact = RECURRENCE(*(x.astype(jnp.float32) for x in args))
    got = jax.jit(lambda *a: gated_delta_rule(*a))(*args)
    xla = jax.jit(on_the_xla_form)(*args)
    for a, b, c in zip(got, xla, exact):  # bf16 products around a float32 solve, both
        err = lambda x: float(jnp.abs(x.astype(jnp.float32) - c).max())  # noqa: E731
        close(a.astype(jnp.float32), c, 5e-2)
        assert err(a) <= 1.5 * err(b)
    ones = jnp.tile(jnp.tril(jnp.ones((64, 64)), -1), (1, 2))
    solved, = delta_rule._solve([ones])
    close(solved, jnp.tile(jnp.eye(64) - jnp.eye(64, k=-1), (1, 2)), 1e-5)
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (2, 64, 64)) * 0.3, -1)
    solved, = delta_rule._solve([jnp.concatenate((a[0], a[1]), axis=1)])
    close(solved, jnp.concatenate(tuple(inverse_unit_lower(a)), axis=1), 1e-5)


@pytest.mark.parametrize("why,t,d,dtype", [
    ("a ragged T", 200, 128, jnp.bfloat16), ("heads of 64", KERNEL_T, 64, jnp.bfloat16),
    ("float32 operands", KERNEL_T, 128, jnp.float32),
])
def test_a_shape_the_kernels_refuse_takes_the_xla_form(why, t, d, dtype, kernels_interpreted,
                                                       monkeypatch):
    def no_kernels(*args):
        raise AssertionError(why + " reached the kernels")

    monkeypatch.setattr(delta_rule, "_rule_by_kernels", no_kernels)
    args = kernel_operands(4, t=t, d=d, dtype=dtype)
    out, state = gated_delta_rule(*args)
    want, want_state = RECURRENCE(*(x.astype(jnp.float32) for x in args))
    close(out.astype(jnp.float32), want, 5e-2)
    close(state, want_state, 5e-2)
    assert metrics.REGISTRY.snapshot()[GAUGE] == UNWRITTEN


def test_the_kernels_take_the_cells_shape_and_nothing_off_the_chip(
        kernels_interpreted, monkeypatch):
    takes = delta_rule.takes_delta_rule
    monkeypatch.setattr(delta_rule, "BLOCK_CHUNKS", 16)  # as the module has it
    assert takes(8192, 128, 128, 16, 32, jnp.bfloat16)
    assert not takes(8192 + 64, 128, 128, 16, 32, jnp.bfloat16)  # a chunk past a grid step
    assert not takes(8192, 128, 128, 16, 16, jnp.bfloat16)  # one value head a key head
    assert not takes(8192, 128, 256, 16, 32, jnp.bfloat16)
    assert not takes(8192, 128, 128, 16, 32, jnp.float32)
    delta_rule._gauge_kernel_chunks(1, 32, 8192)
    assert metrics.REGISTRY.snapshot()[GAUGE] == 4096
    # off the chip the platform alone decides: the XLA form, the gauge untouched
    monkeypatch.undo()
    metrics.gauge(GAUGE).set(UNWRITTEN)
    q, k, v, g, beta = (jax.ShapeDtypeStruct(x.shape, x.dtype) for x in kernel_operands())
    text = jax.jit(gated_delta_rule).lower(q, k, v, g, beta).as_text()
    assert "gated_delta_rule_fwd" not in text and "while" in text
    assert metrics.REGISTRY.snapshot()[GAUGE] == UNWRITTEN


# -- the convolution in front of it -------------------------------------------------


@pytest.mark.parametrize("taps", [1, 3, 4])
def test_silu_short_conv_and_its_written_out_backward(taps):
    key = jax.random.split(jax.random.PRNGKey(taps), 3)
    x = jax.random.normal(key[0], (2, 11, 5))
    w = jax.random.normal(key[1], (5, taps))
    weigh = jax.random.normal(key[2], (2, 11, 5))

    def plain(x, w):  # tap j weighs position t - (L - 1) + j
        padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
        return jax.nn.silu(sum(w[:, j] * padded[:, j: j + 11] for j in range(taps)))

    close(silu_short_conv(x, w), plain(x, w), 1e-6)
    got = jax.grad(lambda x, w: (silu_short_conv(x, w) * weigh).sum(), argnums=(0, 1))(x, w)
    want = jax.grad(lambda x, w: (plain(x, w) * weigh).sum(), argnums=(0, 1))(x, w)
    close(got[0], want[0], 1e-5)
    close(got[1], want[1], 1e-5)
    # causal: the output up to a position does not see what follows it
    later = x.at[:, 6:].add(3.0)
    np.testing.assert_array_equal(
        np.asarray(silu_short_conv(later, w)[:, :6]), np.asarray(silu_short_conv(x, w)[:, :6]))

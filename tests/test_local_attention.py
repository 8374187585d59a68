"""Blockwise (memory-efficient) attention vs the dense oracle.

Covers values AND gradients (the jax.checkpoint'd scan path), causal and
bidirectional, ragged K lengths (padding-tail masking), and global offsets
(the windows ring attention hands in).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.ops import (
    attention_reference,
    blockwise_attention,
    local_attention,
)


def _qkv(b=2, tq=96, tk=96, h=2, d=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (
        jax.random.normal(ks[0], (b, tq, h, d), jnp.float32),
        jax.random.normal(ks[1], (b, tk, h, d), jnp.float32),
        jax.random.normal(ks[2], (b, tk, h, d), jnp.float32),
    )


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tk", [96, 100, 33])
def test_blockwise_matches_dense(causal, tk):
    q, k, v = _qkv(tk=tk)
    want = attention_reference(q, k, v, causal=causal)
    got = blockwise_attention(q, k, v, causal=causal, block_k=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_blockwise_grads_match_dense():
    q, k, v = _qkv(tq=64, tk=64)

    def loss(fn, q, k, v):
        return (fn(q, k, v, causal=True) ** 2).sum()

    g_ref = jax.grad(lambda *a: loss(attention_reference, *a), argnums=(0, 1, 2))(
        q, k, v
    )
    g_blk = jax.grad(
        lambda *a: loss(
            lambda q, k, v, **kw: blockwise_attention(q, k, v, block_k=16, **kw),
            *a,
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ref, g_blk):
        np.testing.assert_allclose(np.asarray(b), np.asarray(a), atol=3e-4)


def test_blockwise_with_offsets_matches_windowed_dense():
    """Ring-attention-style global windows: q rows 32.., k rows 64.."""
    q, k, v = _qkv(tq=32, tk=32, seed=3)
    want = attention_reference(
        q, k, v, causal=True, q_offset=64, k_offset=32
    )
    got = blockwise_attention(
        q, k, v, causal=True, q_offset=64, k_offset=32, block_k=8
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_blockwise_fully_masked_rows_are_zero():
    """A query window entirely BEFORE its key window (no visible keys under
    causal masking) must produce zero rows — padding and masked entries
    contribute exactly nothing, never a bogus uniform average."""
    q, k, v = _qkv(tq=8, tk=5, seed=7)
    out = np.asarray(
        blockwise_attention(
            q, k, v, causal=True, q_offset=0, k_offset=32, block_k=4
        )
    )
    np.testing.assert_array_equal(out, np.zeros_like(out))


def test_local_attention_dispatches_and_matches():
    # short: dense path; long: blockwise path (CPU backend) — same numbers
    q, k, v = _qkv(tq=64, tk=64, seed=5)
    np.testing.assert_allclose(
        np.asarray(local_attention(q, k, v, causal=True)),
        np.asarray(attention_reference(q, k, v, causal=True)),
        atol=2e-5,
    )
    q, k, v = _qkv(tq=768, tk=768, h=1, d=8, seed=6)
    np.testing.assert_allclose(
        np.asarray(local_attention(q, k, v, causal=True)),
        np.asarray(attention_reference(q, k, v, causal=True)),
        atol=2e-5,
    )


@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize(
    "h,h_kv,d", [(4, 2, 64), (4, 4, 128), (6, 2, 128)],
    ids=["h4_kv2_d64", "h4_kv4_d128", "h6_kv2_d128"],
)
def test_splash_branch_matches_dense(h, h_kv, d, causal):
    """The function ``local_attention``'s TPU branch calls, in interpret mode
    (``local_attention`` itself never takes the branch off the chip): compact
    K/V go in, ``dk``/``dv`` come back at H_kv heads, and output and all three
    gradients agree with the dense oracle over the expanded K/V."""
    from akka_allreduce_tpu.ops.local_attention import _splash_attention
    from akka_allreduce_tpu.ops.ring_attention import repeat_kv

    t, scale = 1024, 0.17  # a scale of its own: the kernel has none, q carries it
    keys = jax.random.split(jax.random.PRNGKey(h * d + causal), 3)
    q = jax.random.normal(keys[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, h_kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, t, h_kv, d), jnp.float32)

    def value_and_grads(attention):
        def loss(q, k, v):
            out = attention(q, k, v)
            return (out ** 2).sum(), out

        return jax.jit(
            jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        )(q, k, v)

    (_, got), got_grads = value_and_grads(
        lambda q, k, v: _splash_attention(
            q, k, v, causal=causal, scale=scale, interpret=True
        )
    )
    (_, want), want_grads = value_and_grads(
        lambda q, k, v: attention_reference(
            q, repeat_kv(k, h), repeat_kv(v, h), causal=causal, sm_scale=scale
        )
    )
    assert [g.shape for g in got_grads] == [q.shape, k.shape, v.shape]
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(w), atol=2e-5 * float(jnp.abs(w).max())
        )


def test_local_attention_expands_grouped_kv_off_the_kernel():
    """The dense and blockwise cores still take grouped K/V: the expansion
    lives in them now, not in front of the dispatch."""
    from akka_allreduce_tpu.ops.ring_attention import repeat_kv

    for t in (64, 768):  # dense, then blockwise on the CPU
        q, k, v = _qkv(tq=t, tk=t, h=4, seed=t)
        k, v = k[:, :, :2], v[:, :, :2]
        np.testing.assert_allclose(
            np.asarray(local_attention(q, k, v, causal=True)),
            np.asarray(attention_reference(
                q, repeat_kv(k, 4), repeat_kv(v, 4), causal=True
            )),
            atol=2e-5,
        )


@pytest.mark.parametrize(
    "h,h_kv,d", [(24, 2, 128), (32, 8, 64)], ids=["starcoder2", "lfm2"]
)
def test_the_two_entries_into_the_kernel_are_one_kernel(h, h_kv, d):
    """The benchmark's grouped-query shapes through ``_splash_attention``
    ((B, T, H, D) in, the scale folded into q) and through the heads-first
    core it is written on, called directly on the transposed, scaled inputs:
    the same bits, output and all three gradients."""
    from akka_allreduce_tpu.ops.local_attention import (
        _splash_attention,
        _splash_heads_first,
    )

    t, scale = 1024, d ** -0.5
    keys = jax.random.split(jax.random.PRNGKey(h), 4)
    q = jax.random.normal(keys[0], (1, t, h, d), jnp.float32)
    k = jax.random.normal(keys[1], (1, t, h_kv, d), jnp.float32)
    v = jax.random.normal(keys[2], (1, t, h_kv, d), jnp.float32)
    probe = jax.random.normal(keys[3], (1, t, h, d), jnp.float32)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731

    def through_entry(q, k, v):
        out = _splash_attention(q, k, v, causal=True, scale=scale, interpret=True)
        return (out * probe).sum(), out

    def through_core(q, k, v):
        out = swap(_splash_heads_first(
            swap(q * scale), swap(k), swap(v), causal=True, interpret=True
        ))
        return (out * probe).sum(), out

    run = lambda f: jax.jit(  # noqa: E731
        jax.value_and_grad(f, argnums=(0, 1, 2), has_aux=True)
    )(q, k, v)
    (_, got), got_grads = run(through_entry)
    (_, want), want_grads = run(through_core)
    assert got.shape == q.shape and float(jnp.abs(got).max()) > 0
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("t", [64, 768], ids=["dense", "blockwise"])
def test_heads_first_attention_off_the_kernel(t):
    """A caller that holds heads-first operands with the scale already in q:
    off the chip the portable cores answer, on the sequence-first views, with
    grouped K/V and a values' head size of its own."""
    from akka_allreduce_tpu.ops.local_attention import heads_first_attention
    from akka_allreduce_tpu.ops.ring_attention import repeat_kv

    keys = jax.random.split(jax.random.PRNGKey(t), 3)
    q = jax.random.normal(keys[0], (2, 4, t, 24), jnp.float32)
    k = jax.random.normal(keys[1], (2, 2, t, 24), jnp.float32)
    v = jax.random.normal(keys[2], (2, 2, t, 16), jnp.float32)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    got = heads_first_attention(q * 0.2, k, v, causal=True)
    want = attention_reference(
        swap(q), repeat_kv(swap(k), 4), repeat_kv(swap(v), 4), causal=True,
        sm_scale=0.2,
    )
    assert got.shape == (2, 4, t, 16)
    np.testing.assert_allclose(np.asarray(swap(got)), np.asarray(want), atol=2e-5)

"""Attention under a mask a learned indexer makes (``ops/sparse_attention.py``,
``local_attention.heads_first_attention(mask=)``, multimodal RoPE in
``models/transformer.py``) and the configuration-built decoder that runs it
(``models/hybrid_decoder.py`` from the Qwen3-MoE keys with ``sa_config``:
``KeyeVL2``'s language model) with its indexer's own loss in ``MoETrainer``,
against formulas written out here, ``lax.top_k``, and the benchmark's plain
reference ``benchmarks/reference/keye_moe_plain.py``, at tiny widths on the
CPU, on seeded weights."""

from __future__ import annotations

import copy
import functools
import json
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import lax

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec  # noqa: E402

from akka_allreduce_tpu.ops import sparse_attention as sa  # noqa: E402

ref = spec.load_module("reference", "keye_moe_plain")

REAL = os.path.join(BENCH, "configs", "keye_vl2_30b_a3b_ep8.json")


def _json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _unequal_rows(t):
    """Three rows of positions that differ, as an image's patches give them."""
    i = jnp.arange(t)
    return jnp.stack([i, i // 3, (2 * i) % 7]).astype(jnp.int32)


# -- the selection -----------------------------------------------------------------


def _top_k_mask(scores, topk, row0):
    """The oracle: ``lax.top_k`` over the row with the keys after t at minus
    infinity (the lower index first among equals), cut to the causal keys."""
    r, c = scores.shape
    causal = jnp.arange(c)[None, :] <= (row0 + jnp.arange(r))[:, None]
    scores = jnp.where(scores == 0, 0.0, scores)  # -0.0 equals 0.0: the lower index first
    _, best = lax.top_k(jnp.where(causal, scores, -jnp.inf), min(topk, c))
    picked = jnp.zeros((r, c), bool).at[jnp.arange(r)[:, None], best].set(True)
    return picked & causal


@pytest.mark.parametrize("ties", ["none", "zeros", "signed_zeros", "few_values", "all_equal"])
@pytest.mark.parametrize("rows,cols,row0,topk", [(24, 24, 0, 8), (16, 40, 24, 8), (8, 64, 56, 64)])
def test_selection_is_top_k_with_ties_to_the_lower_index(ties, rows, cols, row0, topk):
    scores = jax.random.normal(jax.random.PRNGKey(rows + cols), (rows, cols))
    if ties == "zeros":  # what relu leaves: many exact zeros around the threshold
        scores = jnp.where(scores < 0.6, 0.0, scores)
    elif ties == "signed_zeros":
        scores = jnp.where(scores < 0.6, jnp.where(scores < 0, -0.0, 0.0), scores)
    elif ties == "few_values":
        scores = jnp.round(scores * 2) / 2
    elif ties == "all_equal":
        scores = jnp.full_like(scores, -1.5)
    got = sa.select_keys(scores, topk, row0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(_top_k_mask(scores, topk, row0)))
    t = row0 + np.arange(rows)
    np.testing.assert_array_equal(np.asarray(got.sum(axis=1)), np.minimum(t + 1, topk))
    assert not bool(jnp.any(got & (jnp.arange(cols)[None, :] > t[:, None])))


def _written_scores(q_i, k_i, w):
    return jnp.einsum("tj,jts->ts", w.astype(jnp.float32), jax.nn.relu(
        jnp.einsum("jtd,sd->jts", q_i, k_i, preferred_element_type=jnp.float32)))


def _score_kernels_interpreted(monkeypatch, q_tile, k_tile):
    """The index scores' kernels where the chip would run them, interpreted,
    at tiles a tiny shape holds: the platform question answered yes."""
    monkeypatch.setattr(sa, "_on_chip", lambda *arrays: True)
    monkeypatch.setattr(sa, "SCORE_Q", q_tile)
    monkeypatch.setattr(sa, "SCORE_K", k_tile)


SCORES_BY = pytest.mark.parametrize("scores_by", ["einsums", "kernels"])


@SCORES_BY
def test_indexer_mask_by_blocks_is_the_selection_of_the_whole(scores_by, monkeypatch):
    """Runs of blocks through their loops (8 rows at a time, four runs) give
    the mask one block of all rows gives; rows inside ``topk`` see every
    causal key. On the kernels the entries after a tile's queries are zeros
    the selection never reads."""
    t, j, d, topk = 64, 4, 8, 16
    if scores_by == "kernels":
        _score_kernels_interpreted(monkeypatch, 8, 16)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    q_i, k_i = jax.random.normal(k[0], (j, t, d)), jax.random.normal(k[1], (t, d))
    w = jax.random.normal(k[2], (t, j))
    whole = sa.indexer_mask(q_i, k_i, w, topk)  # 64 rows: one block of all
    assert sa._stages(t, 8) == [(0, 16, 8), (16, 32, 8), (32, 48, 8), (48, 64, 8)]
    assert sa._stages(640, 512) == [(0, 640, 640)] and sa._stages(32, 512) == [(0, 32, 32)]
    monkeypatch.setattr(sa, "INDEX_ROWS", 8)
    np.testing.assert_array_equal(
        np.asarray(sa.indexer_mask(q_i, k_i, w, topk)), np.asarray(whole))
    want = _top_k_mask(sa.index_scores(q_i, k_i, w), topk, 0)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(want, np.int8))
    assert whole.dtype == jnp.int8 and int(whole.sum()) == 16 * 17 // 2 + 48 * 16
    _close(jnp.tril(sa.index_scores(q_i, k_i, w)), jnp.tril(_written_scores(q_i, k_i, w)))


# -- attention under an array mask ------------------------------------------------


def _qkv(b, t, h, h_kv, d, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(k[0], (b, h, t, d)) * d ** -0.5,
            jax.random.normal(k[1], (b, h_kv, t, d)),
            jax.random.normal(k[2], (b, h_kv, t, d)))


def _random_mask(b, t, topk, seed=5):
    scores = jax.random.normal(jax.random.PRNGKey(seed), (b, t, t))
    return jnp.stack([sa.select_keys(s, topk, 0) for s in scores]).astype(jnp.int8)


def _dense_under_mask(q, k, v, mask):
    """Written out: softmax over the keys the mask keeps, heads-first."""
    group = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest")
    scores = jnp.where(mask[:, None] != 0, scores, -jnp.inf)
    out = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(scores, axis=-1), v, precision="highest")
    return out, jax.nn.logsumexp(scores, axis=-1)


def _small_tiles(monkeypatch):
    monkeypatch.setattr(sa, "BLOCK_Q", 64)
    monkeypatch.setattr(sa, "BLOCK_K", 128)
    monkeypatch.setattr(sa, "SOFTMAX_ROWS", 32)


@functools.lru_cache(maxsize=None)
def _kernels_and_the_core(heads, kv_heads, mask_is, b, t, topk, with_core=True):
    """The output with the rows' log-sum-exp and the three operands'
    gradients, ``{what: (the kernels', the portable core's, the softmax's
    written out)}``, made once a shape for the cases that each hold one of
    them: the masked kernels interpreted at (64, 128) tiles, the forward's
    softmax 32 rows of a head at a time; ``blockwise_attention`` with the
    mask (where asked for, else the written-out side again)."""
    from akka_allreduce_tpu.ops.local_attention import blockwise_attention

    d = 32
    q, k, v = _qkv(b, t, heads, kv_heads, d)
    mask = within = _random_mask(b, t, topk)
    if mask_is == "past_the_diagonal":
        stray = jax.random.bernoulli(jax.random.PRNGKey(6), 0.3, mask.shape)
        mask = mask | jnp.triu(stray, 1).astype(jnp.int8)
        assert int(mask.sum()) > int(within.sum())
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    weight = jax.random.normal(jax.random.PRNGKey(9), q.shape)

    def portable(q, k, v):
        out, lse = blockwise_attention(
            swap(q), swap(k), swap(v), causal=True, sm_scale=1.0, mask=mask,
            with_lse=True, block_k=64)
        return swap(out), lse

    def kernels(q, k, v):
        out, lse = sa.sparse_attention(q, k, v, mask, True)
        return out, lse.reshape(b, heads, t)

    def all_of(f):
        def weighted(*operands):
            out = f(*operands)
            return (out[0] * weight).sum(), out

        (_, out), grads = jax.value_and_grad(weighted, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return dict(zip(("out_and_lse", "dq", "dk", "dv"), (out, *grads)))

    with pytest.MonkeyPatch.context() as patch:
        _small_tiles(patch)
        want = all_of(lambda *a: _dense_under_mask(*a, within))
        sides = [all_of(kernels), all_of(portable) if with_core else want, want]
    return {what: tuple(side[what] for side in sides) for what in sides[0]}


def _kernels_against_the_core(what, *shape):
    got, portable, want = _kernels_and_the_core(*shape)[what]
    if what == "out_and_lse":
        for side in (got, portable):
            _close(side[0], want[0])
            _close(side[1], want[1])
        return
    _close(got, want, 1e-4)
    _close(portable, want, 1e-4)


MASK_IS = pytest.mark.parametrize("mask_is", ["causal", "past_the_diagonal"])
# one K/V head under all the heads, and two: the backward's resident pair is
# zeroed at a head's first step and written out at its last
HEADS = pytest.mark.parametrize("heads,kv_heads", [(4, 2), (8, 1), (8, 2)])


@pytest.mark.parametrize("what", ["out_and_lse", "dq", "dk", "dv"])
@HEADS
@MASK_IS
def test_masked_kernels_interpreted_match_the_portable_core(what, heads, kv_heads, mask_is):
    """``flash_mha_sparse_fwd`` / ``_bwd`` in interpret mode against
    ``blockwise_attention`` with the mask and against the softmax written
    out. A mask with ones past the diagonal is cut by the causal rule, in the
    kernels' straddling tiles as in the portable core."""
    _kernels_against_the_core(what, heads, kv_heads, mask_is, 2, 256, 48)


@pytest.mark.parametrize("what", ["dq", "dk", "dv"])
@HEADS
@MASK_IS
def test_the_one_backward_kernel_sums_both_ways_across_tiles(
    what, heads, kv_heads, mask_is, monkeypatch
):
    """T of four key tiles and eight query tiles: ``flash_mha_sparse_bwd``
    sums a query tile's ``dq`` over up to four key tiles, and in its resident
    pair of a K/V head a key tile's ``dk`` and ``dv`` over up to eight query
    tiles (the first key tile takes every query tile's, the last the last
    two's), the group's heads into the one head they share."""
    _small_tiles(monkeypatch)
    assert sa._last_key_tile(7) == 3 and sa._last_key_tile(1) == 0  # the eighth row of tiles has four
    _kernels_against_the_core(what, heads, kv_heads, mask_is, 1, 512, 96, False)


@pytest.mark.parametrize("keys,heads,kv_heads", [
    ("a_band_behind_the_query", 4, 2), ("a_band_behind_the_query", 8, 1),
    ("a_band_behind_the_query", 8, 2), ("the_queries_own_tile_alone", 8, 2),
])
def test_a_rows_first_seen_key_wipes_what_its_unseen_tiles_left(keys, heads, kv_heads, monkeypatch):
    """The forward takes ``exp(s - m)`` without a select beside it
    (``_weights``). Rows whose kept keys all lie past their first key tile
    run that tile, and every one before the first with a kept key, at the mask
    value as their maximum: weights of 1 on unseen keys, sums of garbage in
    ``l`` and ``acc``. The first kept key's ``fade = exp(mask value - m)`` is
    exactly 0, so the output and the log-sum-exp are BIT-equal to the kernel
    with the select kept, and close to the softmax written out."""
    _small_tiles(monkeypatch)
    b, t, d = 1, 512, 32
    q, k, v = _qkv(b, t, heads, kv_heads, d, seed=3)
    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    if keys == "a_band_behind_the_query":  # 40 keys: past row 168 none in key tile 0
        mask = (cols <= rows) & (cols > rows - 40)
    else:  # only keys of the tile astride the diagonal, the row's last
        mask = (cols <= rows) & (cols >= rows // 128 * 128) & ((rows - cols) % 3 == 0)
    mask = mask[None].astype(jnp.int8)
    first_kept = jnp.argmax(mask[0] != 0, axis=1)
    assert int(jnp.sum(first_kept >= 128)) >= t - 168  # rows with no kept key in their first tile
    assert int(jnp.sum(first_kept >= 384)) >= 80  # rows with three such tiles before their first key
    got = sa.sparse_attention(q, k, v, mask, True)
    with monkeypatch.context() as m:
        m.setattr(sa, "_weights", lambda s, at: jnp.where(
            s > sa._MASK_VALUE, jnp.exp(s - at), 0.0))  # the select kept
        kept = sa.sparse_attention(q, k, v, mask, True)
    np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(kept[0]))
    np.testing.assert_array_equal(np.asarray(got[1]), np.asarray(kept[1]))
    want = _dense_under_mask(q, k, v, mask)
    _close(got[0], want[0])
    _close(got[1].reshape(b, heads, t), want[1])


def test_a_small_step_lowers_to_the_two_kernels():
    """Forward and backward of a layer's attention under a mask, lowered for
    the chip: one ``flash_mha_sparse_fwd``, ONE ``flash_mha_sparse_bwd``, and
    neither of the two kernels it took the place of."""
    import re

    t, h, h_kv, d = 2 * sa.BLOCK_K, 8, 2, 128
    assert sa.takes_sparse(t, d, d)

    def loss(q, k, v, mask):
        out, lse = sa.sparse_attention(q, k, v, mask, False)
        return out.astype(jnp.float32).sum() + lax.stop_gradient(lse).sum()

    sds = jax.ShapeDtypeStruct
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
        sds((1, h, t, d), jnp.bfloat16), sds((1, h_kv, t, d), jnp.bfloat16),
        sds((1, h_kv, t, d), jnp.bfloat16), sds((1, t, t), jnp.int8),
    ).lower(lowering_platforms=("tpu",)).as_text()
    assert text.count("tpu_custom_call") == 2
    assert sorted(set(re.findall(r"flash_mha_sparse_\w+", text))) == [
        "flash_mha_sparse_bwd", "flash_mha_sparse_fwd"]
    assert "_dq" not in text and "_dkv" not in text


def test_a_sequence_whose_sums_do_not_fit_goes_to_the_portable_core(monkeypatch):
    """The backward keeps a K/V head's float32 ``dk`` and ``dv`` of the whole
    sequence in VMEM: ``takes_sparse`` refuses a T past what is left beside
    the tiles, and ``heads_first_attention(mask=)`` then runs the portable
    core, on the chip too (the platform question answered yes here)."""
    from akka_allreduce_tpu.ops import _platform
    from akka_allreduce_tpu.ops.local_attention import heads_first_attention

    room = sa._VMEM_LIMIT - sa._TILE_ROOM
    assert sa.takes_sparse(16384, 128, 128) and 16384 * 256 * 12 <= room
    assert not sa.takes_sparse(32768, 128, 128) and 32768 * 256 * 12 > room
    assert sa.takes_sparse(32768, 64, 64)  # the bound is on T x (D + Dv)

    def no_kernels(*a, **kw):
        raise AssertionError("the kernels were asked for a shape they do not take")

    monkeypatch.setattr(_platform, "interpret_default", lambda *arrays: False)
    monkeypatch.setattr(sa, "sparse_attention", no_kernels)
    _small_tiles(monkeypatch)
    monkeypatch.setattr(sa, "_TILE_ROOM", sa._VMEM_LIMIT - 128 * 32 * 12)
    t = 256
    assert sa.takes_sparse(128, 16, 16) and not sa.takes_sparse(t, 16, 16)
    q, k, v = _qkv(1, t, 4, 2, 16)
    mask = _random_mask(1, t, 24)
    grad = lambda f: jax.grad(lambda *a: (f(*a)[0] ** 2).sum(), argnums=(0, 1, 2))(q, k, v)  # noqa: E731
    out, lse = heads_first_attention(q, k, v, causal=True, mask=mask)
    want = _dense_under_mask(q, k, v, mask)
    _close(out, want[0])
    _close(lse.reshape(1, 4, t), want[1])
    for got, ref_ in zip(grad(lambda *a: heads_first_attention(*a, causal=True, mask=mask)),
                         grad(lambda *a: _dense_under_mask(*a, mask))):
        _close(got, ref_, 1e-4)


@pytest.mark.parametrize("t", [64, 640])
def test_heads_first_attention_takes_a_mask_off_the_chip(t):
    """The blockwise core, in one block of keys up to 512 positions and in
    several past them, against the softmax written out; the rows' log-sum-exp
    comes back in the kernels' layout."""
    from akka_allreduce_tpu.ops.local_attention import heads_first_attention

    q, k, v = _qkv(1, t, 4, 2, 16)
    mask = _random_mask(1, t, 24)
    out, lse = heads_first_attention(q, k, v, causal=True, mask=mask)
    want = _dense_under_mask(q, k, v, mask)
    _close(out, want[0])
    assert lse.shape == (1, 2, 2, t) and lse.dtype == jnp.float32
    _close(lse.reshape(1, 4, t), want[1])


def test_a_mask_is_for_causal_attention_without_a_window():
    from akka_allreduce_tpu.ops.local_attention import heads_first_attention

    q, k, v = _qkv(1, 32, 2, 1, 8)
    mask = _random_mask(1, 32, 8)
    with pytest.raises(ValueError, match="causal attention without a window"):
        heads_first_attention(q, k, v, causal=False, mask=mask)
    with pytest.raises(ValueError, match="causal attention without a window"):
        heads_first_attention(q, k, v, causal=True, window=8, mask=mask)
    # without a mask the call is today's: one array back
    assert heads_first_attention(q, k, v, causal=True).shape == q.shape


def test_the_kernels_take_the_cells_shape_and_the_gauges_say_what_they_run():
    from akka_allreduce_tpu.obs import metrics
    from akka_allreduce_tpu.ops.local_attention import _gauge_sparse

    assert sa.takes_sparse(8192, 128, 128) and not sa.takes_sparse(8192 + 256, 128, 128)
    assert not sa.takes_sparse(8192, 192, 128)
    _gauge_sparse(8192)  # the kernels' wrapper: what their grid runs
    sa._gauge_selected(8192, 2048)  # the selection: what it keeps of any scores
    now = metrics.REGISTRY.snapshot()
    assert now["attention.sparse.mask_pairs"] == 14_681_088  # ISSUE 41's count
    # every tile that holds a causal pair, whole: the causal pairs and the
    # tiles' overhang past the diagonal
    assert now["attention.sparse.visited_pairs"] == sa.visited_pairs(8192)
    causal = 8192 * 8193 // 2
    assert causal < sa.visited_pairs(8192) <= causal + 8192 * sa.BLOCK_K


@SCORES_BY
def test_the_indexers_loss_and_its_gradient_made_in_the_forward_pass(scores_by, monkeypatch):
    """``indexer_kl`` against the loss written out, and its ``custom_vjp``
    (gradients made block by block in the forward pass, scaled by the
    cotangent) against autodiff of the written-out loss. On the kernels the
    scores' own ``custom_vjp`` runs under ``jax.vjp`` inside the runs' scans."""
    t, j, d, h, h_kv, hd, topk = 32, 4, 8, 4, 2, 16, 8
    if scores_by == "kernels":
        _score_kernels_interpreted(monkeypatch, 8, 8)
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q_i, k_i = jax.random.normal(ks[0], (j, t, d)), jax.random.normal(ks[1], (t, d))
    w = jax.random.normal(ks[2], (t, j))
    q, k, v = _qkv(1, t, h, h_kv, hd, seed=4)
    mask = sa.indexer_mask(q_i, k_i, w, topk)
    _, lse = _dense_under_mask(q, k, v, mask[None])
    lse = lse.reshape(h_kv, h // h_kv, t)

    def written(q_i, k_i, w):
        scores = jnp.einsum("tj,jts->ts", w, jax.nn.relu(jnp.einsum("jtd,sd->jts", q_i, k_i)))
        log_q = jax.nn.log_softmax(jnp.where(mask != 0, scores, -jnp.inf), axis=-1)
        s = jnp.einsum("hqd,hkd->hqk", q[0], jnp.repeat(k[0], h // h_kv, axis=0))
        p = jax.nn.softmax(jnp.where(mask != 0, s, -jnp.inf), axis=-1).mean(axis=0)
        return jnp.sum(jnp.where(mask != 0, p * (jnp.log(jnp.where(p > 0, p, 1.0)) - log_q), 0.0))

    for rows in (sa.INDEX_ROWS, 8):
        monkeypatch.setattr(sa, "INDEX_ROWS", rows)
        loss = lambda *a: 0.25 * sa.indexer_kl(*a, mask, q[0], k[0], lse)  # noqa: E731
        got, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(q_i, k_i, w)
        want, want_grads = jax.value_and_grad(
            lambda *a: 0.25 * written(*a), argnums=(0, 1, 2))(q_i, k_i, w)
        assert abs(float(got) - float(want)) < 1e-5 * abs(float(want)) and float(want) > 0
        for a, b in zip(grads, want_grads):
            _close(a, b, 1e-4)
        _close(sa.indexer_kl(q_i, k_i, w, mask, q[0], k[0], lse), written(q_i, k_i, w))


# -- the index scores' kernels --------------------------------------------------------


def _index_operands(dtype, rows, cols, heads=4, d=8, seed=3):
    """A block's operands with what the kernels have to get right in them:
    weights of both signs, and pre-activations at exactly 0 (a query and a
    key of zeros)."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q_i = jax.random.normal(ks[0], (heads, rows, d)).at[1, 5].set(0.0).astype(dtype)
    k_i = jax.random.normal(ks[1], (cols, d)).at[7].set(0.0).astype(dtype)
    w = jax.random.normal(ks[2], (rows, heads))
    assert bool((w < 0).any()) and bool((w > 0).any())
    return q_i, k_i, w


# (first row, rows, keys): the sequence's start; a block astride the diagonal
# whose keys end before the sequence does; a block's last rows
BLOCKS = {"at_the_start": (0, 32, 96), "astride_with_keys_left": (32, 32, 64),
          "last_rows": (64, 32, 96)}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("what", ["scores", "dq_i", "dk_i", "dw"])
@pytest.mark.parametrize("block", sorted(BLOCKS))
def test_score_kernels_interpreted_match_the_two_einsums(block, what, dtype, monkeypatch):
    """``index_scores_fwd`` / ``index_scores_bwd`` in interpret mode, two
    tiles of queries and four or six of keys, against the einsums that stay
    in the module: the scores on every tile that holds a causal pair (zeros
    on the others), and each gradient under a random float32 cotangent on the
    causal pairs, as ``_kl_block`` hands it. Both forms accumulate in
    float32, so from equal operands the scores and ``dw`` agree to a
    re-ordered sum in either dtype; from bf16 operands the kernel's gate and
    weighted query enter the MXU in bf16, as the masked kernels' ``ds`` does."""
    row0, rows, cols = BLOCKS[block]
    q_i, k_i, w = _index_operands(dtype, rows, cols)
    einsums = lambda *a: sa.index_scores(*a, row0)  # noqa: E731
    want, pull_einsums = jax.vjp(einsums, q_i, k_i, w)
    _close(want, _written_scores(q_i, k_i, w))  # the yardstick is the formula
    assert bool((want == 0).any())  # the zeroed query and key: pre-activations at 0
    _score_kernels_interpreted(monkeypatch, 16, 16)
    assert sa.takes_index_scores(rows, cols, 8, 4)
    got, pull_kernels = jax.vjp(lambda *a: sa.index_scores(*a, jnp.int32(row0)), q_i, k_i, w)
    run = np.repeat(np.repeat(  # the tiles the grid's rule runs
        np.arange(cols // 16)[None, :] <= np.asarray(
            [sa._last_score_tile(row0, i, 16, 16) for i in range(rows // 16)])[:, None],
        16, axis=0), 16, axis=1)
    causal = np.arange(cols)[None, :] <= (row0 + np.arange(rows))[:, None]
    assert run[causal].all() and not run.all()
    if what == "scores":
        assert got.dtype == jnp.float32
        _close(jnp.where(run, got, 0.0), jnp.where(run, want, 0.0))
        assert not bool(jnp.any(jnp.where(run, 0.0, got)))
        return
    cotangent = jnp.where(causal, jax.random.normal(jax.random.PRNGKey(11), (rows, cols)), 0.0)
    at = ("dq_i", "dk_i", "dw").index(what)
    mine, theirs = pull_kernels(cotangent)[at], pull_einsums(cotangent)[at]
    assert mine.dtype == theirs.dtype == (jnp.float32 if what == "dw" else dtype)
    exact = dtype == jnp.float32 or what == "dw"
    _close(mine.astype(jnp.float32), theirs.astype(jnp.float32), 2e-5 if exact else 2e-2)


def test_the_score_kernels_take_the_cells_blocks_and_the_gauges_say_what_they_run():
    from akka_allreduce_tpu.obs import metrics

    for cols in (4096, 6144, 8192):  # 512 rows of 16 heads of 64 against a run's keys
        assert sa.takes_index_scores(512, cols, 64, 16)
    assert not sa.takes_index_scores(512, 4096 + 256, 64, 16)  # keys the tile does not divide
    assert not sa.takes_index_scores(640, 8192, 64, 16)  # rows it does not
    assert not sa.takes_index_scores(512, 8192, 128, 16)  # more than the backward holds
    # the grid's rule: of a block at row0 the key tiles up to its last row's, whole;
    # the mask pass makes no score for the rows inside topk
    tiles = lambda blocks: sum(b + 1 for b in blocks)  # noqa: E731
    assert sa.scored_pairs(8192, 2048) == 512 * 512 * (tiles(range(4, 16)) + tiles(range(16)))
    sa._gauge_scored.__wrapped__(8192, 2048)
    now = metrics.REGISTRY.snapshot()
    causal = 8192 * 8193 // 2
    assert now["attention.indexer.causal_pairs"] == causal
    assert now["attention.indexer.scored_pairs"] == sa.scored_pairs(8192, 2048)
    # two passes, the first without the 2,048 rows every causal key of which is kept
    assert 1.0 < sa.scored_pairs(8192, 2048) / (2 * causal - 2048 * 2049 // 2) < 1.06
    # a program on the einsum form writes neither
    q_i, k_i, w = _index_operands(jnp.float32, 64, 64)
    sa.indexer_mask(q_i, k_i, w, 16)
    assert metrics.REGISTRY.snapshot()["attention.indexer.scored_pairs"] == sa.scored_pairs(8192, 2048)


# -- multimodal RoPE -----------------------------------------------------------------


def test_bf16_operands_move_the_index_keys_gradient_by_percents_and_its_norm_by_far_less():
    """What ``correct_limits.grad_norm_gap`` of the cell's file is set by
    (``benchmarks/tests/keye_index_key_tail.py``): the indexer's loss is a mean
    over queries of a KL each, so its gradient to the key is decided by the
    first few queries, and bf16 operands move it by percents as a vector on
    every seed while its norm, which the check compares, moves far less."""
    tests = os.path.join(BENCH, "tests")
    if tests not in sys.path:
        sys.path.insert(0, tests)
    import keye_index_key_tail as tail

    for seed in (0, 1):
        norm_gap, vector_gap = (float(g) for g in tail.gaps(jax.random.PRNGKey(seed)))
        assert 0.01 < vector_gap < 0.2, vector_gap
        assert norm_gap < 0.2 * vector_gap, (norm_gap, vector_gap)


@pytest.mark.parametrize("t,d,base", [(64, 16, 1e7), (8192, 128, 1e7), (4096, 64, 1e6)])
def test_mrope_tables_on_equal_rows_are_bit_equal_to_the_one_row_tables(t, d, base):
    from akka_allreduce_tpu.models.transformer import rope_tables

    sections = (d // 8, d // 8 + d // 16, d // 2 - 2 * (d // 8) - d // 16)
    rows = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32), (3, t))
    one = rope_tables(t, d, 0, base=base, scale=d ** -0.5)
    three = rope_tables(t, d, 0, base=base, scale=d ** -0.5, positions=rows, sections=sections)
    for a, b in zip(one, three):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_mrope_turns_each_section_by_its_own_row():
    """``rope_heads_first`` with three unequal rows against the formula
    written out (column i of the first half with i + D/2, angle ``pos[s(i), t]
    theta^(-2i/D)``) and against the reference's own ``mrope``."""
    from akka_allreduce_tpu.models.transformer import rope_heads_first, rope_tables

    t, h, d, theta, sections = 32, 3, 16, 1e7, (2, 3, 3)
    pos = _unequal_rows(t)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, h, t, d))
    got = rope_heads_first(x, 0, base=theta, positions=pos, sections=sections)
    row_of = np.repeat(np.arange(3), sections)
    ang = np.asarray(pos, np.float64)[row_of].T * theta ** (-np.arange(0, d, 2) / d)
    x1, x2 = np.asarray(x)[..., : d // 2], np.asarray(x)[..., d // 2:]
    want = np.concatenate(
        (x1 * np.cos(ang) - x2 * np.sin(ang), x1 * np.sin(ang) + x2 * np.cos(ang)), axis=-1)
    _close(got, jnp.asarray(want, jnp.float32), 1e-5)
    _close(got[0].transpose(1, 0, 2), ref.mrope(x[0].transpose(1, 0, 2), pos, theta, sections), 1e-5)
    equal = rope_heads_first(x, 0, base=theta)
    assert float(jnp.abs(got - equal).max()) > 1e-2  # the rows do differ
    with pytest.raises(ValueError, match="positions"):
        rope_tables(t, d, 0, positions=pos, sections=(2, 3, 4))


# -- the benchmark's count and readers -------------------------------------------------


def test_the_count_of_a_step_is_the_issues():
    from harness import keye_flops

    real = _json(REAL)
    assert keye_flops.selected_pairs(real, 8192) == 14_681_088
    flops = keye_flops.train_flops_per_step(real, 1, 8192, 5 * 8192)
    tera = {k: round(v / 1e12, 2) for k, v in flops.items()}
    assert tera == {"always": 6.99, "experts": 1.16, "attention": 3.61, "index_scores": 1.03,
                    "target": 0.6, "total": 13.39}
    assert keye_flops.attention_train_flops(real, 1, 8192) == 12 * 14_681_088 * 32 * 128 * 5
    assert 14_681_088 / (8192 * 8193 // 2) == pytest.approx(0.437, abs=1e-3)


class _Trace:
    """Three steps; the ops named as a v5e trace names them."""

    def __init__(self, ops):
        self.ops = ops

    def main_module(self):
        return [(0.0, 0.1), (0.1, 0.1), (0.2, 0.1)]

    def matching(self, name=None, kind=None):
        import re

        hit = [v for k, v in self.ops.items() if re.search(name, k)]
        return sum(v[0] for v in hit), sum(v[1] for v in hit)


def test_readers_of_the_new_metrics_on_a_made_up_record():
    from harness import keye_flops

    real, tr = _json(REAL), _json(os.path.join(BENCH, "traffic", "closed_b1_t8192.json"))
    pre = "jit(step)/sparse_attention/"
    scopes = {
        "fusion.1": pre + "attn_indexer/indexer_proj/dot", "fusion.2": pre + "attn_indexer/indexer_scores/dot",
        "fusion.3": pre + "attn_indexer/while/body/indexer_select/reduce",
        "fusion.4": pre + "attn_indexer/indexer_target/exp", "fusion.5": pre + "attn_core/mul",
        "flash_mha_sparse_fwd.1": pre + "attn_core/pallas", "flash_mha_sparse_bwd.1": pre + "attn_core/pallas",
        "while.1": pre + "attn_indexer/while",
    }
    ops = {n: (3, 0.003 * (i + 1), "while" if n.startswith("while") else "fusion")
           for i, n in enumerate(scopes)}
    ops.update({"gmm.1": (3, 0.030, "fusion"), "tgmm": (3, 0.012, "fusion")})
    units = [{"t0": 0.1 * i, "t1": 0.1 * i + 0.1, "work": 8192, "ok": True,
              "expert_rows": [[512.0] * 16] * 5, "buffer_rows": [10240.0] * 5} for i in range(3)]
    units[0]["op_scopes"] = scopes
    units[0]["counters"] = {"attention.sparse.mask_pairs": 14_681_088,
                            "attention.sparse.visited_pairs": sa.visited_pairs(8192)}
    record = {
        "cell": types.SimpleNamespace(config=real, traffic=tr), "chips": 1,
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }
    trace = _Trace(ops)
    read = lambda name: spec.load_module("layer_metrics", name).compute(record, trace)  # noqa: E731
    assert read("indexer_ms") == pytest.approx(1e3 * (0.003 + 0.006 + 0.009 + 0.012) / 3)
    assert read("indexer_select_ms") == pytest.approx(3.0)
    assert read("indexer_target_ms") == pytest.approx(4.0)
    kernel_ms = 1e3 * (0.018 + 0.021) / 3
    assert read("attn_kernel_ms") == pytest.approx(kernel_ms)
    assert read("gqa_around_kernel_ms") == pytest.approx(5.0)
    assert read("attn_kernel_roofline_pct.keye") == pytest.approx(
        100 * 12 * 14_681_088 * 32 * 128 * 5 / 197e12 / (1e-3 * kernel_ms))
    assert read("sparse_tile_useful_pct") == pytest.approx(
        100 * 14_681_088 / sa.visited_pairs(8192))
    assert 40 < read("sparse_tile_useful_pct") < 43.7
    assert read("mfu_pct.keye") == pytest.approx(100 * 13.39e12 * 10 / 197e12, rel=1e-3)
    # five layers on the first rung, sixteen experts with 512 rows each
    need = keye_flops.grouped_products(real, 16 * 512, 16)
    assert need == {"flops": 18 * 8192 * 2048 * 768,
                    "bytes": 9 * 2 * 8192 * (2048 + 768) + 24 * 16 * 2048 * 768}
    # 512 rows an expert: the weights' bytes (read twice, written once in f32)
    # outweigh the products by a little, so the layer is held to the HBM rate
    assert 1.0 < need["bytes"] / 819e9 / (need["flops"] / 197e12) < 1.1
    assert read("moe_gmm_ms") == pytest.approx(14.0)
    assert read("moe_gmm_roofline_pct.keye") == pytest.approx(
        100 * 5 * need["bytes"] / 819e9 / 0.014)
    # an expert no row reached is never read
    assert keye_flops.grouped_products(real, 16 * 512, 8)["bytes"] < need["bytes"]
    # a program without the scopes, the gauges or the key set: nothing, and no raise
    bare = copy.deepcopy(record)
    for u in bare["window"]["units"]:
        u.pop("op_scopes", None), u.pop("counters", None), u.pop("expert_rows", None)
    for name in ("indexer_ms", "indexer_select_ms", "indexer_target_ms",
                 "sparse_tile_useful_pct", "mfu_pct.keye", "moe_gmm_roofline_pct.keye"):
        assert spec.load_module("layer_metrics", name).compute(bare, _Trace({})) is None, name
    assert spec.load_module("layer_metrics", "attn_kernel_roofline_pct.keye").compute(
        bare, _Trace({})) is None


def test_reader_of_the_index_scores_on_a_made_up_record():
    """``indexer_scores_ms`` counts what runs under ``indexer_scores``, XLA's
    ops and the program's own kernels alike; ``indexer_ms`` counts the
    kernels too, ``attn_kernel_ms`` does not."""
    pre = "jit(step)/sparse_attention/attn_indexer/"
    scopes = {
        "fusion.1": pre + "indexer_proj/dot", "fusion.2": pre + "while/body/indexer_scores/reduce",
        "index_scores_fwd.1": pre + "while/body/indexer_scores/pallas",
        "index_scores_bwd.1": pre + "while/body/indexer_scores/pallas",
        "fusion.3": pre + "while/body/indexer_select/reduce", "while.1": pre + "while",
        "flash_mha_sparse_fwd.1": "jit(step)/sparse_attention/attn_core/pallas",
    }
    ops = {n: (3, 0.003 * (i + 1), "while" if n.startswith("while") else "fusion")
           for i, n in enumerate(scopes)}
    units = [{"t0": 0.1 * i, "t1": 0.1 * i + 0.1, "work": 8192, "ok": True} for i in range(3)]
    units[0]["op_scopes"] = scopes
    record = {"window": {"units": units, "start": 0.0, "paused": 0.0}}
    trace = _Trace(ops)
    read = lambda name: spec.load_module("layer_metrics", name).compute(record, trace)  # noqa: E731
    assert read("indexer_scores_ms") == pytest.approx(1e3 * (0.006 + 0.009 + 0.012) / 3)
    assert read("indexer_ms") == pytest.approx(1e3 * (0.003 + 0.006 + 0.009 + 0.012 + 0.015) / 3)
    assert read("indexer_select_ms") == pytest.approx(5.0)
    assert read("attn_kernel_ms") == pytest.approx(7.0)  # the masked kernel alone
    # a program without the scope (or a runner without the map): nothing, and no raise
    units[0]["op_scopes"] = {"fusion.1": "jit(step)/attention/attn_core/dot"}
    assert read("indexer_scores_ms") is None
    del units[0]["op_scopes"]
    assert read("indexer_scores_ms") is None
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    entry = next(m for m in bench["per_layer"] if m["name"] == "indexer_scores_ms")
    assert entry["layer"] == "the indexer" and entry["workloads"] == ["keye_vl2_ep8_train_b1_t8192"]

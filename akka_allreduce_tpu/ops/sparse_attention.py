"""Attention under a mask that arrives as an array, and the learned indexer
that makes one (DeepSeek-V3.2-Exp's sparse attention, as ``sa_config`` of
``KeyeVL2`` sizes it): every query keeps the ``topk`` keys at or before it
that an index score ranks highest, and attends to those alone.

Four parts, each a function of its own:

- :func:`index_scores`: ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``,
  float32, from the operands' dtype into the MXU. On the chip, on the shapes
  :func:`takes_index_scores` takes, a ``custom_vjp`` over two Pallas TPU
  kernels of the repo's own: ``index_scores_fwd`` runs a tile of (queries,
  keys) through the 16 heads' products, ReLU and the weighted sum and writes
  the float32 tile once; ``index_scores_bwd`` keeps a block's rows resident,
  steps over tiles of keys, makes the pre-activations again and from them
  ``dq_I``, ``dk_I`` and ``dw``. The per-head pre-activations live in VMEM
  only; the key tiles wholly after a block's rows are skipped from the grid
  position. Anywhere else two einsums, the portable form and the tests'
  yardstick.
- :func:`select_keys`: scores in, a mask out with exactly ``min(t + 1,
  topk)`` keys a row among the keys ``s <= t``, ties to the lower index. The
  mathematics needs the SET, not its order, so no row is sorted: the
  ``topk``-th largest value of a row is found by bisection over the 32 bits
  of a key that orders as the floats do (32 counting passes over the block),
  and the keys tied with it are ranked by position only where a row has more
  of them than it may keep.
- :func:`sparse_attention`: two Pallas TPU kernels of the repo's own,
  ``flash_mha_sparse_fwd`` and ``flash_mha_sparse_bwd``, in
  ``ops/band_attention.py``'s manner (a group's query heads stacked as rows
  of one product, f32 scores and statistics, the operands' dtype into the
  MXU) with what a mask known only at run time asks for beside it: a loop
  over K/V tiles with a running maximum, the mask an int8 operand read a
  (queries, keys) tile at a time, the tiles above the diagonal skipped from
  the grid position, the causal rule taken from iotas beside the mask.
  The forward makes a tile's scores in one product and then the softmax's
  chain a head's :data:`SOFTMAX_ROWS` rows at a time, so that the chain
  lives in registers between the scores' load and the weights' store (two
  products a tile), and returns the rows' log-sum-exp beside the output: the
  indexer's loss reads it. The backward is ONE kernel, query-major as the
  forward: a visited tile's scores, probabilities and ``ds`` are made once
  and feed all three gradient products (five products a tile), ``dq`` summed
  over a row of key tiles, ``dk`` and ``dv`` of a K/V head's whole sequence
  summed in a float32 (T, D) + (T, Dv) pair resident in VMEM that leaves the
  chip's fast memory once, at the head's last step (K/V are compact, 4 heads
  for 32: 8 MiB at T 8,192; :func:`takes_sparse` bounds T by it).
- :func:`indexer_kl`: the indexer's own loss, ``sum_t KL(pbar_t || softmax
  over S_t of I[t, .])`` with ``pbar`` the head mean of the main attention's
  probabilities on the selected set, a block of query rows at a time so that
  no (heads, T, T) array is ever whole. Its gradient reaches ``q_I``, ``k_I``
  and ``w`` alone and is made in the forward pass, block by block (the
  block's scores are alive then anyway), and kept as the residual: three
  small arrays where the scores of every block would have been.

The kernels take T that their tiles divide and the backward's accumulators
fit, and heads of up to 128 (:func:`takes_sparse`); any other shape goes to
the portable core (``local_attention._blockwise_olm`` with the mask).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from akka_allreduce_tpu.ops._platform import interpret_default
from akka_allreduce_tpu.ops.ring_attention import _MASK_VALUE

#: queries and keys a grid step of each kernel: a group's eight heads stack
#: ``BLOCK_Q`` rows each into one product against ``BLOCK_K`` keys
BLOCK_Q = 512
BLOCK_K = 512
#: rows of a head whose softmax the forward kernel makes at a time
SOFTMAX_ROWS = 256
#: query rows of index scores (and of the loss's target) alive at once, and
#: the runs of such blocks that share their columns (:func:`_stages`)
INDEX_ROWS = 512
STAGES = 4
#: query rows and keys a grid step of the index scores' forward kernel; the
#: backward keeps a block's rows resident and steps over ``SCORE_K`` keys
SCORE_Q = 512
SCORE_K = 512
#: elements of a block's index queries the backward kernel holds beside their
#: gradient (16 heads x 512 rows x 64: what a 16 MB scope of VMEM has room for)
_RESIDENT = 16 * 512 * 64
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_LIMIT = 100 * 1024 * 1024  # of a v5e core's 128 MiB
#: of that, what a grid step's tiles take: the masked kernels' blocks, buffered
#: twice, and the (heads, queries, keys) f32 intermediates of the backward
_TILE_ROOM = 40 * 1024 * 1024


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


# -- the indexer's scores and the selection ---------------------------------------


def _on_chip(*arrays) -> bool:
    """The platform question of :func:`index_scores`, as
    ``local_attention._masked_heads_first`` asks it of its operands."""
    return not interpret_default(*arrays)


def _by_kernels(q_i, k_i, rows: int, cols: int) -> bool:
    """Do ``rows`` of these index queries against ``cols`` of these keys go
    to the kernels? By the platform and the shape, nothing else."""
    return _on_chip(q_i, k_i) and takes_index_scores(rows, cols, q_i.shape[2], q_i.shape[0])


def index_scores(q_i: jax.Array, k_i: jax.Array, w: jax.Array, row0=0) -> jax.Array:
    """``q_i`` (J, R, D), ``k_i`` (C, D), ``w`` (R, J) -> float32 (R, C):
    ``sum_j w[r, j] relu(q_i[j, r] . k_i[c])``, the queries at positions
    ``row0 .. row0 + R - 1``. The kernels (on the chip, on a shape
    :func:`takes_index_scores` takes) skip the tiles of keys wholly after
    their queries and write zeros there, entries no caller reads; no gradient
    comes back from them."""
    if _by_kernels(q_i, k_i, q_i.shape[1], k_i.shape[0]):
        return _scores_by_kernels(
            q_i, k_i, w.astype(jnp.float32), jnp.asarray(row0, jnp.int32),
            interpret_default(q_i, k_i),
        )
    pre = jnp.einsum("jrd,cd->jrc", q_i, k_i, preferred_element_type=jnp.float32)
    return jnp.einsum("jrc,rj->rc", jax.nn.relu(pre), w.astype(jnp.float32))


def _ordered_bits(x: jax.Array) -> jax.Array:
    """uint32 keys that order as the float32 ``x`` do (-0.0 as +0.0)."""
    bits = lax.bitcast_convert_type(jnp.where(x == 0, 0.0, x), jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_keys(scores: jax.Array, topk: int, row0: int = 0) -> jax.Array:
    """float32 ``scores`` (R, C) of the queries at positions ``row0 .. row0 +
    R - 1`` against the keys at 0 .. C - 1 -> bool (R, C): for the query at
    ``t`` the ``min(t + 1, topk)`` keys ``s <= t`` of largest score, of equal
    scores the lower ``s`` first. No gradient passes."""
    r, c = scores.shape
    t = row0 + jnp.arange(r, dtype=jnp.int32)
    causal = jnp.arange(c, dtype=jnp.int32)[None, :] <= t[:, None]
    want = jnp.minimum(t + 1, topk)
    # a key no causal entry has: below every float, NaN apart
    key = jnp.where(causal, _ordered_bits(lax.stop_gradient(scores)), jnp.uint32(0))

    def at_least(v):
        return jnp.sum(key >= v[:, None], axis=1, dtype=jnp.int32)

    def bit(i, v):  # the largest v with ``want`` keys at or above it, bit by bit
        cand = v | (jnp.uint32(1 << 31) >> i.astype(jnp.uint32))
        return jnp.where(at_least(cand) >= want, cand, v)

    # from the keys, so that the carry varies over a mesh as they do
    kth = lax.fori_loop(0, 32, bit, key[:, 0] & jnp.uint32(0))

    def ranked():  # some row holds more keys tied at its threshold than it may keep
        above, tied = key > kth[:, None], key == kth[:, None]
        room = want - jnp.sum(above, axis=1, dtype=jnp.int32)
        return above | (tied & (jnp.cumsum(tied, axis=1, dtype=jnp.int32) <= room[:, None]))

    return lax.cond(
        jnp.all(at_least(kth) == want), lambda: key >= kth[:, None], ranked
    )


def _stages(t: int, rows: int) -> list[tuple[int, int, int]]:
    """``(first row, end, rows a block)`` of up to :data:`STAGES` runs of
    whole blocks of ``rows`` query rows. A run's blocks go through one loop,
    each against the keys before the run's end: one compiled body a run, one
    block alive at a time, and of the pairs above the diagonal only those
    inside a run's own columns are made. T that ``rows`` does not divide (or
    does not pass) is one block of all its rows."""
    if t <= rows or t % rows:
        return [(0, t, t)]
    blocks = t // rows
    per = -(-blocks // STAGES)
    return [(b * rows, min(b + per, blocks) * rows, rows) for b in range(0, blocks, per)]


def _rows_at(a, start, rows: int, axis: int):
    return lax.dynamic_slice_in_dim(a, start, rows, axis=axis)


@functools.lru_cache(maxsize=None)
def _gauge_selected(t: int, topk: int) -> None:
    """The pairs a sequence's mask keeps, once a shape, to the gauge
    ``attention.sparse.mask_pairs`` (OBSERVABILITY.md): ``sum_t min(t + 1,
    topk)``, what :func:`select_keys` keeps of ANY scores."""
    from akka_allreduce_tpu.obs import metrics as obs_metrics

    keys = min(topk, t)
    obs_metrics.gauge("attention.sparse.mask_pairs").set(
        keys * (keys + 1) // 2 + (t - keys) * keys
    )


@functools.lru_cache(maxsize=None)
def _gauge_scored(t: int, topk: int) -> None:
    """What the index scores' kernels run of a sequence, once a shape, to the
    gauges ``attention.indexer.scored_pairs`` / ``.causal_pairs``
    (OBSERVABILITY.md)."""
    from akka_allreduce_tpu.obs import metrics as obs_metrics

    obs_metrics.gauge("attention.indexer.scored_pairs").set(scored_pairs(t, topk))
    obs_metrics.gauge("attention.indexer.causal_pairs").set(t * (t + 1) // 2)


def indexer_mask(q_i, k_i, w, topk: int):
    """One sequence's mask from its indexer's operands (``q_i`` (J, T, D),
    ``k_i`` (T, D), ``w`` (T, J)): int8 (T, T), 1 where the query of the row
    sees the key of the column. :data:`INDEX_ROWS` query rows at a time
    (:func:`_stages`); a run wholly inside the first ``topk`` positions sees
    every causal key and makes no score."""
    t = k_i.shape[0]
    _gauge_selected(t, topk)
    stages = _stages(t, INDEX_ROWS)
    if all(_by_kernels(q_i, k_i, n, r1) for _, r1, n in stages):
        _gauge_scored(t, topk)
    out = []
    for r0, r1, n in stages:
        if r1 <= topk:
            seen = (jnp.arange(r1)[None, :] <= jnp.arange(r0, r1)[:, None]).astype(jnp.int8)
        else:
            keys = k_i[:r1]

            def block(start, n=n, keys=keys):
                with jax.named_scope("indexer_scores"):
                    scores = index_scores(
                        _rows_at(q_i, start, n, 1), keys, _rows_at(w, start, n, 0), start
                    )
                with jax.named_scope("indexer_select"):
                    return select_keys(scores, topk, start).astype(jnp.int8)

            seen = lax.map(block, jnp.arange(r0, r1, n, dtype=jnp.int32))
            seen = seen.reshape(r1 - r0, r1)
        out.append(jnp.pad(seen, ((0, 0), (0, t - r1))))
    return jnp.concatenate(out, axis=0)


# -- the indexer's loss -------------------------------------------------------------


def _target_block(q, k, lse, seen):
    """Head mean of the main attention's probabilities, (R, C) float32:
    ``q`` (H_kv, G, R, D) with the score scale in it, ``k`` (H_kv, C, D),
    ``lse`` (H_kv, G, R) the rows' log-sum-exp over their selected keys."""
    s = jnp.einsum("kgrd,kcd->kgrc", q, k, preferred_element_type=jnp.float32)
    p = jnp.exp(s - lse[..., None]).sum(axis=(0, 1)) / (q.shape[0] * q.shape[1])
    return jnp.where(seen, p, 0.0)


def _kl_block(q_i, k_i, w, seen, target, row0):
    """``sum_r KL(target_r || softmax over the seen keys of I[r, .])``, the
    block's first query at ``row0``."""
    scores = jnp.where(seen, index_scores(q_i, k_i, w, row0), -jnp.inf)
    log_q = scores - jax.nn.logsumexp(scores, axis=1, keepdims=True)
    held = seen & (target > 0)
    log_p = jnp.log(jnp.where(held, target, 1.0))
    return jnp.sum(jnp.where(held, target * (log_p - log_q), 0.0))


def _kl_blocks(q_i, k_i, w, mask, q, k, lse, with_grads: bool):
    """The loss, and with it ``(dq_i, dk_i, dw)`` where asked for: a block of
    rows at a time through each run's loop, the keys' gradient carried."""
    h_kv, g = lse.shape[:2]
    t = k_i.shape[0]
    q = q.reshape(h_kv, g, t, -1)
    # zeros made from the operands, so that the carries vary over a mesh as they do
    dk = k_i.astype(jnp.float32) * 0.0
    total = dk[0, 0]
    dq, dw = [], []
    for r0, r1, n in _stages(t, INDEX_ROWS):
        keys_i, keys = k_i[:r1], k[:, :r1]

        def block(carry, start, n=n, keys_i=keys_i, keys=keys, r1=r1):
            seen = _rows_at(mask, start, n, 0)[:, :r1] != 0
            with jax.named_scope("indexer_target"):
                target = _target_block(
                    _rows_at(q, start, n, 2), keys, _rows_at(lse, start, n, 2), seen
                )
            kl_of = functools.partial(_kl_block, seen=seen, target=target, row0=start)
            operands = (_rows_at(q_i, start, n, 1), keys_i, _rows_at(w, start, n, 0))
            with jax.named_scope("indexer_scores"):
                if not with_grads:
                    return (carry[0] + kl_of(*operands), carry[1]), None
                kl, pull = jax.vjp(kl_of, *operands)
                dq_b, dk_b, dw_b = pull(kl * 0.0 + 1.0)  # a one that varies as kl
            return (carry[0] + kl, carry[1] + dk_b.astype(jnp.float32)), (dq_b, dw_b)

        (total, dk_run), grads = lax.scan(
            block, (total, dk[:r1]), jnp.arange(r0, r1, n, dtype=jnp.int32)
        )
        if with_grads:
            dk = dk.at[:r1].set(dk_run)
            dq.append(grads[0].transpose(1, 0, 2, 3).reshape(q_i.shape[0], r1 - r0, -1))
            dw.append(grads[1].reshape(r1 - r0, -1))
    if not with_grads:
        return total
    return total, (jnp.concatenate(dq, axis=1), dk.astype(k_i.dtype), jnp.concatenate(dw))


@jax.custom_vjp
def indexer_kl(q_i, k_i, w, mask, q, k, lse):
    """One sequence's ``sum_t KL(pbar_t || softmax over S_t of I[t, .])``,
    float32: ``q_i`` (J, T, D), ``k_i`` (T, D), ``w`` (T, J) the indexer's
    operands; ``mask`` int8 (T, T) the selection ``S``; ``q`` (H, T, D) WITH
    the score scale, ``k`` (H_kv, T, D) and ``lse`` (H_kv, G, T) the main
    attention's, through which no gradient passes. :data:`INDEX_ROWS` query
    rows at a time (:func:`_stages`)."""
    return _kl_blocks(q_i, k_i, w, mask, q, k, lse, False)


def _indexer_kl_fwd(q_i, k_i, w, mask, q, k, lse):
    return _kl_blocks(q_i, k_i, w, mask, q, k, lse, True)


def _indexer_kl_bwd(grads, g):
    dq, dk, dw = grads
    scaled = lambda a: (g * a.astype(jnp.float32)).astype(a.dtype)  # noqa: E731
    return scaled(dq), scaled(dk), scaled(dw), None, None, None, None


indexer_kl.defvjp(_indexer_kl_fwd, _indexer_kl_bwd)


# -- the indexer's scores: the kernels ----------------------------------------------


def takes_index_scores(rows: int, cols: int, d: int, heads: int) -> bool:
    """Do the index scores' kernels take a block of ``rows`` queries of
    ``heads`` index heads against ``cols`` keys? Rows and columns the tiles
    divide, and a block the backward kernel can keep resident."""
    return rows % SCORE_Q == 0 and cols % SCORE_K == 0 and heads * rows * d <= _RESIDENT


def _last_score_tile(row0, i, q_tile: int, k_tile: int):
    """The last tile of ``k_tile`` keys that holds a key at or before a query
    of tile i (``q_tile`` rows) of a block whose first query is at ``row0``."""
    return (row0 + (i + 1) * q_tile - 1) // k_tile


def scored_pairs(t: int, topk: int) -> int:
    """(query, key) pairs the forward kernel's tiles run over a sequence's
    mask pass (the runs past ``topk``: :func:`indexer_mask`) and loss pass
    (every run: :func:`indexer_kl`), by the rule its grid skips by."""
    return sum(
        (1 + (r1 > topk)) * SCORE_Q * SCORE_K * (_last_score_tile(start, i, SCORE_Q, SCORE_K) + 1)
        for r0, r1, n in _stages(t, INDEX_ROWS)
        for start in range(r0, r1, n)
        for i in range(n // SCORE_Q)
    )


def _scores_fwd_kernel(row0_ref, q_ref, k_ref, w_ref, o_ref):
    i, j = pl.program_id(0), pl.program_id(1)
    last = _last_score_tile(row0_ref[0], i, *o_ref.shape)

    @pl.when(j <= last)
    def _():
        k, w = k_ref[...], w_ref[...]
        acc = jnp.zeros(o_ref.shape, jnp.float32)
        for h in range(q_ref.shape[0]):
            acc = acc + w[:, h:h + 1] * jnp.maximum(_dot(q_ref[h], k, _NT), 0.0)
        o_ref[...] = acc

    @pl.when(j > last)  # wholly after the tile's queries: entries no caller reads
    def _():
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)


def _scores_bwd_kernel(row0_ref, q_ref, qt_ref, k_ref, wt_ref, di_ref,
                       dqt_ref, dkt_ref, dw_ref, dqt_scr, dw_scr):
    """A tile of keys against the block's rows, head by head: the gate
    ``m = dI (pre > 0)`` is the (K, N) operand of both gradient products,
    which come out transposed, 64 rows each (``dq_I[j]^T = k_I^T m^T``,
    ``dk_I^T += (w[:, j] q_I[j])^T m``): a product 64 wide runs the MXU half
    empty, one 64 rows tall does not. The head's weight multiplies after the
    product where it is a factor of the rows (``dq_I``) and the small operand
    where it is not (``dk_I``)."""
    heads, r, d = q_ref.shape
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        dqt_scr[...] = jnp.zeros(dqt_scr.shape, jnp.float32)
        dw_scr[...] = jnp.zeros(dw_scr.shape, jnp.float32)

    live = j * k_ref.shape[0] <= row0_ref[0] + r - 1  # the tile holds a key a row may see

    @pl.when(live)
    def _():
        k, di, wt = k_ref[...], di_ref[...], wt_ref[...]
        k_t = k.T
        head_of = lax.broadcasted_iota(jnp.int32, dw_scr.shape, 1)
        dk_t = jnp.zeros(dkt_ref.shape, jnp.float32)
        dw = dw_scr[...]
        for h in range(heads):
            pre = _dot(q_ref[h], k, _NT)
            m = jnp.where(pre > 0, di, 0.0)
            gate, w_h = m.astype(k.dtype), wt[h:h + 1, :]
            dqt_scr[h] += _dot(k_t, gate, _NT) * w_h
            dk_t = dk_t + _dot((qt_ref[h] * w_h).astype(k.dtype), gate)
            dw = jnp.where(head_of == h, dw + (m * pre).sum(axis=1, keepdims=True), dw)
        dw_scr[...] = dw
        dkt_ref[...] = dk_t.astype(dkt_ref.dtype)

    @pl.when(jnp.logical_not(live))
    def _():
        dkt_ref[...] = jnp.zeros(dkt_ref.shape, dkt_ref.dtype)

    @pl.when(j == pl.num_programs(0) - 1)
    def _():
        dqt_ref[...] = dqt_scr[...].astype(dqt_ref.dtype)
        dw_ref[...] = dw_scr[...]


# Both calls are jitted on their own: a step traces each of them dozens of
# times (five layers, seven runs of blocks a layer, the primal, the forward
# rule and the loops' own passes over their bodies), and a jitted function is
# traced and lowered once a shape (four: the runs' keys), which is what a warm
# start pays. The tiles are arguments so that the cache sees them.
@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _scores_forward(q_i, k_i, w, row0, interpret, q_tile, k_tile):
    heads, r, d = q_i.shape
    c = k_i.shape[0]
    return pl.pallas_call(
        _scores_fwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(r // q_tile, c // k_tile),
            in_specs=[
                pl.BlockSpec((heads, q_tile, d), lambda i, j, row0: (0, i, 0)),
                # a skipped step fetches nothing: its tile is the last one run
                pl.BlockSpec((k_tile, d), lambda i, j, row0: (
                    jnp.minimum(j, _last_score_tile(row0[0], i, q_tile, k_tile)), 0)),
                pl.BlockSpec((q_tile, heads), lambda i, j, row0: (i, 0)),
            ],
            out_specs=pl.BlockSpec((q_tile, k_tile), lambda i, j, row0: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((r, c), jnp.float32),
        name="index_scores_fwd", interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
    )(row0.reshape(1), q_i, k_i, w)


@functools.partial(jax.jit, static_argnums=(5, 6))
def _scores_backward(q_i, k_i, w, d_scores, row0, interpret, k_tile):
    heads, r, d = q_i.shape
    c = k_i.shape[0]
    whole = lambda *shape: pl.BlockSpec(shape, lambda j, row0: (0,) * len(shape))  # noqa: E731
    tile = lambda j, row0: jnp.minimum(j, (row0[0] + r - 1) // k_tile)  # noqa: E731
    dq_t, dk_t, dw = pl.pallas_call(
        _scores_bwd_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(c // k_tile,),
            in_specs=[
                whole(heads, r, d), whole(heads, d, r),
                pl.BlockSpec((k_tile, d), lambda j, row0: (tile(j, row0), 0)),
                whole(heads, r),
                pl.BlockSpec((r, k_tile), lambda j, row0: (0, tile(j, row0))),
            ],
            out_specs=[
                whole(heads, d, r), pl.BlockSpec((d, k_tile), lambda j, row0: (0, j)),
                whole(r, heads),
            ],
            scratch_shapes=[
                pltpu.VMEM((heads, d, r), jnp.float32), pltpu.VMEM((r, heads), jnp.float32)
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((heads, d, r), q_i.dtype),
            jax.ShapeDtypeStruct((d, c), k_i.dtype),
            jax.ShapeDtypeStruct((r, heads), jnp.float32),
        ],
        name="index_scores_bwd", interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",)),
    )(row0.reshape(1), q_i, q_i.transpose(0, 2, 1), k_i, w.T, d_scores)
    return dq_t.transpose(0, 2, 1), dk_t.T, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _scores_by_kernels(q_i, k_i, w, row0, interpret):
    """:func:`index_scores` on the kernels: float32 ``w``, ``row0`` an int32
    scalar (a loop's counter: it reaches the kernels as a prefetched scalar).
    The residuals are the operands; the backward makes the pre-activations
    again, a tile at a time."""
    return _scores_forward(q_i, k_i, w, row0, interpret, SCORE_Q, SCORE_K)


def _scores_kernels_fwd(q_i, k_i, w, row0, interpret):
    out = _scores_forward(q_i, k_i, w, row0, interpret, SCORE_Q, SCORE_K)
    return out, (q_i, k_i, w, row0)


def _scores_kernels_bwd(interpret, residuals, d_scores):
    q_i, k_i, w, row0 = residuals
    return (*_scores_backward(q_i, k_i, w, d_scores, row0, interpret, SCORE_K), None)


_scores_by_kernels.defvjp(_scores_kernels_fwd, _scores_kernels_bwd)


# -- attention under the mask: the kernels ---------------------------------------


def takes_sparse(t: int, d: int, dv: int) -> bool:
    """Do the kernels take the shape? T that both tiles divide, heads of up
    to 128 (a group's stacked scores against a tile of keys fit VMEM), and a
    sequence whose ``dk`` and ``dv`` the backward keeps resident beside its
    tiles: a K/V head's float32 (T, D + Dv) sums and the blocks they leave
    through, buffered twice, at up to four bytes an element, twelve bytes in
    all, in the 60 MB that :data:`_VMEM_LIMIT` has past :data:`_TILE_ROOM`.
    At heads of 128 that is T up to 16,384 (the cell's 8,192 takes 25 MB); a
    longer sequence goes where every shape the kernels do not take goes, to
    the portable core."""
    resident = t * (d + dv) * (4 + 2 * 4)
    return (
        t % BLOCK_Q == 0 and t % BLOCK_K == 0 and max(d, dv) <= 128
        and resident <= _VMEM_LIMIT - _TILE_ROOM
    )


def visited_pairs(t: int) -> int:
    """(query, key) pairs a head's tiles run, by the rule the kernels' grids
    skip by (:func:`_last_key_tile`): every tile that holds a causal pair,
    whole. A tile without a selected key is not skipped (on a learned mask
    over 8,192 keys a 512 x 512 tile without one is not to be had)."""
    return sum(
        BLOCK_Q * BLOCK_K * (_last_key_tile(i) + 1) for i in range(t // BLOCK_Q)
    )


def _last_key_tile(i):
    """The last tile of keys that holds a key at or before a query of tile i."""
    return ((i + 1) * BLOCK_Q - 1) // BLOCK_K


def _visits(i, j):
    """Does the tile (query tile ``i``, key tile ``j``) hold a causal pair?
    The grid steps past a row's last such tile run nothing and, their blocks
    clamped onto that tile's (:func:`_specs`), fetch nothing."""
    return j <= _last_key_tile(i)


def _seen(mask_ref, i, j):
    """Which pairs of the tile at (query tile ``i``, key tile ``j``) are
    seen, (rows, keys) bool, made once a tile for the group's heads: where
    the mask says so AND the key is at or before the query. The causal rule
    is taken from iotas on every visited tile, not only on the one astride
    the diagonal: an int32 compare and an AND a tile, beside products that
    set the pace, are under 2 % of either kernel's schedule and nothing on
    the chip, and one compiled body a kernel in place of two halves the
    kernels' code, which a warm start pays for (PERF.md section 6, PR 44)."""
    kept = mask_ref[...].astype(jnp.int32)
    rows = i * BLOCK_Q + lax.broadcasted_iota(jnp.int32, kept.shape, 0)
    keys = j * BLOCK_K + lax.broadcasted_iota(jnp.int32, kept.shape, 1)
    return (kept != 0) & (keys <= rows)


def _weights(s, m):
    """``exp(s - m)``, a key's weight against the row's running maximum,
    WITHOUT a select beside it. An unseen key's score is the mask value, so
    its weight is exactly 0 once ``m`` is a seen key's score. Before a row
    has seen a key, ``m`` is the mask value too and the weight of its unseen
    keys is 1: garbage in ``l`` and ``acc`` that the row's first seen key
    wipes, ``fade = exp(mask value - m)`` being exactly 0 then, and every
    query sees a key (:func:`sparse_attention`)."""
    return jnp.exp(s - m)


def _fwd_kernel(q_ref, k_ref, v_ref, mask_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, fade_scr, p_scr):
    """A visited tile: the group's stacked scores in one product, then the
    softmax's chain (mask, running maximum, weights, row sum, the cast) a
    head's :data:`SOFTMAX_ROWS` rows at a time, each block's weights written
    to ``p_scr`` in the operands' dtype as soon as they are made, and the
    second product from ``p_scr`` whole. A block's chain lives in registers
    between its scores' load and its weights' store; as whole-array
    operations over (G, rows, keys) every step of it goes through VMEM, and
    the one vector store a bundle then sets the kernel's pace."""
    g, b, d = q_ref.shape
    rows = min(b, SOFTMAX_ROWS)
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _():
        m_scr[...] = jnp.full(m_scr.shape, _MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    @pl.when(_visits(i, j))
    def _():
        seen = _seen(mask_ref, i, j)
        s = _dot(q_ref[...].reshape(g * b, d), k_ref[...], _NT)
        for h in range(g):
            for r in range(0, b, rows):
                part, stacked = slice(r, r + rows), slice(h * b + r, h * b + r + rows)
                s_b = jnp.where(seen[part], s[stacked], _MASK_VALUE)
                m_old = m_scr[h, part]
                m_new = jnp.maximum(m_old, s_b.max(axis=-1, keepdims=True))
                p = _weights(s_b, m_new)
                fade = jnp.exp(m_old - m_new)
                l_scr[h, part] = fade * l_scr[h, part] + p.sum(axis=-1, keepdims=True)
                m_scr[h, part] = m_new
                fade_scr[h, part] = fade
                p_scr[stacked, :] = p.astype(p_scr.dtype)
        pv = _dot(p_scr[...], v_ref[...]).reshape(acc_scr.shape)
        acc_scr[...] = fade_scr[...] * acc_scr[...] + pv

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        l = l_scr[...]
        o_ref[...] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[...] = (m_scr[...] + jnp.log(l))[..., 0]


def _bwd_kernel(q_ref, k_ref, v_ref, mask_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr):
    """A visited tile's scores, probabilities and ``ds`` made ONCE, and the
    three gradient products from them. ``dq`` of the query tile is summed in
    ``dq_scr`` over its row of key tiles; ``dk`` and ``dv`` of the K/V head's
    WHOLE sequence in ``dk_scr`` / ``dv_scr``, float32 (T, D), a key tile's
    rows taking the query tiles' contributions in the order of the grid."""
    g, b, d = q_ref.shape
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when((i == 0) & (j == 0))
    def _():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    @pl.when(j == 0)
    def _():
        dq_scr[...] = jnp.zeros(dq_scr.shape, jnp.float32)

    @pl.when(_visits(i, j))
    def _():
        k = k_ref[...]
        q, do = q_ref[...].reshape(g * b, d), do_ref[...].reshape(g * b, -1)
        # the unseen scores are at the mask value and every row's log-sum-exp
        # is finite (every query sees a key): their probability is exactly 0
        s = _dot(q, k, _NT).reshape(g, b, -1)
        s = jnp.where(_seen(mask_ref, i, j)[None], s, _MASK_VALUE)
        p = jnp.exp(s - lse_ref[...][..., None])
        dp = _dot(do, v_ref[...], _NT).reshape(p.shape)
        ds = p * (dp - delta_ref[...][..., None])
        p, ds = p.reshape(g * b, -1), ds.reshape(g * b, -1)
        dq_scr[...] += _dot(ds.astype(k.dtype), k).reshape(g, b, d)
        # rows are the group's stacked queries: contracting them sums the
        # group's heads into the one K/V head
        keys = pl.ds(pl.multiple_of(j * BLOCK_K, BLOCK_K), BLOCK_K)
        dv_scr[keys, :] += _dot(p.astype(do.dtype), do, _TN)
        dk_scr[keys, :] += _dot(ds.astype(q.dtype), q, _TN)

    @pl.when(j == pl.num_programs(3) - 1)
    def _():
        dq_ref[...] = dq_scr[...].astype(dq_ref.dtype)

    @pl.when((i == pl.num_programs(2) - 1) & (j == pl.num_programs(3) - 1))
    def _():
        dk_ref[...] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_scr[...].astype(dv_ref.dtype)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, scratch, interpret, query_tiles):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", query_tiles, "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
    )


# Index maps. Both kernels run (n, h, i, j), query-major, the key tiles above
# the diagonal clamped onto the last one below it, so that the skipped steps
# fetch nothing.
def _specs(g: int):
    key_tile = lambda i, j: jnp.minimum(j, _last_key_tile(i))  # noqa: E731
    queries = lambda w: pl.BlockSpec(  # noqa: E731
        (None, g, BLOCK_Q, w), lambda n, h, i, j: (n, h, i, 0)
    )
    keys = lambda w: pl.BlockSpec(  # noqa: E731
        (None, None, BLOCK_K, w), lambda n, h, i, j: (n, h, key_tile(i, j), 0)
    )
    mask = pl.BlockSpec((None, BLOCK_Q, BLOCK_K), lambda n, h, i, j: (n, i, key_tile(i, j)))
    rows = pl.BlockSpec((None, None, g, BLOCK_Q), lambda n, h, i, j: (n, h, 0, i))
    return queries, keys, mask, rows


def _forward(q, k, v, mask, interpret):
    n, h, t, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    g = h // h_kv
    queries, keys, tile, rows = _specs(g)
    stat = pltpu.VMEM((g, BLOCK_Q, 1), jnp.float32)
    return _call(
        _fwd_kernel, "flash_mha_sparse_fwd", (n, h_kv, t // BLOCK_Q, t // BLOCK_K),
        [queries(d), keys(d), keys(dv), tile], [queries(dv), rows],
        [jax.ShapeDtypeStruct((n, h, t, dv), q.dtype),
         jax.ShapeDtypeStruct((n, h_kv, g, t), jnp.float32)],
        [stat, stat, pltpu.VMEM((g, BLOCK_Q, dv), jnp.float32), stat,
         pltpu.VMEM((g * BLOCK_Q, BLOCK_K), v.dtype)], interpret, "parallel",
    )(q, k, v, mask)


def _backward(q, k, v, mask, do, lse, delta, interpret):
    """``(dq, dk, dv)`` from one kernel: the query tiles of a K/V head run in
    order (``"arbitrary"``), because the head's ``dk`` and ``dv`` are summed
    across them in VMEM and leave it once, at the head's last step."""
    n, h, t, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    g = h // h_kv
    queries, keys, tile, rows = _specs(g)
    whole = lambda w: pl.BlockSpec(  # noqa: E731
        (None, None, t, w), lambda n, h, i, j: (n, h, 0, 0)
    )
    return _call(
        _bwd_kernel, "flash_mha_sparse_bwd", (n, h_kv, t // BLOCK_Q, t // BLOCK_K),
        [queries(d), keys(d), keys(dv), tile, queries(dv), rows, rows],
        [queries(d), whole(d), whole(dv)],
        [jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
         jax.ShapeDtypeStruct(v.shape, v.dtype)],
        [pltpu.VMEM((g, BLOCK_Q, d), jnp.float32), pltpu.VMEM((t, d), jnp.float32),
         pltpu.VMEM((t, dv), jnp.float32)], interpret, "arbitrary",
    )(q, k, v, mask, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def sparse_attention(q, k, v, mask, interpret: bool = False):
    """Attention of ``q`` (B, H, T, D) WITH the score scale in it against
    compact ``k`` (B, H_kv, T, D) and ``v`` (B, H_kv, T, Dv) under ``mask``,
    int8 (B, T, T): query t sees key s iff ``mask[b, t, s] != 0`` and ``s <=
    t`` (a tile above the diagonal is not read, the others are cut by the
    rule beside the mask), and every query sees a key (the forward leans on it:
    :func:`_weights`; so does the backward, whose probabilities are taken
    against a finite log-sum-exp).
    Returns ``(o (B, H, T, Dv), lse float32 (B, H_kv, G, T))``; the gradient
    is the output's alone (``lse`` is a statistic here, not a path). Two
    kernels: ``flash_mha_sparse_fwd`` (two products a visited tile) and ONE
    backward, ``flash_mha_sparse_bwd`` (five: the tile's scores, weights and
    ``ds`` made once for ``dq``, ``dk`` and ``dv``), which keeps a K/V head's
    float32 ``dk`` / ``dv`` of the whole sequence resident in VMEM, the bound
    on T that :func:`takes_sparse` states."""
    return _forward(q, k, v, mask, interpret)


def _sparse_fwd(q, k, v, mask, interpret):
    o, lse = _forward(q, k, v, mask, interpret)
    return (o, lse), (q, k, v, mask, o, lse)


def _sparse_bwd(interpret, residuals, cotangents):
    q, k, v, mask, o, lse = residuals
    do = cotangents[0]
    delta = (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(axis=-1).reshape(lse.shape)
    return (*_backward(q, k, v, mask, do, lse, delta, interpret), None)


sparse_attention.defvjp(_sparse_fwd, _sparse_bwd)

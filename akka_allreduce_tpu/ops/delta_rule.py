"""The gated delta rule in chunked form: the mixer of linear-attention layers
that carry a matrix state along the sequence (Gated DeltaNet, arXiv
2412.06464).

Per value head, with a state ``S`` (d_k, d_v), ``S_0 = 0``, a log decay ``g_t
<= 0`` (``alpha_t = exp(g_t)``) and a write strength ``beta_t``:

    S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
    o_t = S_t^T q_t

Token by token that is T dependent steps of rank-one updates. In chunks of
``C`` positions (64, the published implementation's) the dependence inside a
chunk is a unit lower-triangular system, solved for all chunks at once, and
only the (d_k, d_v) state is carried from chunk to chunk by ONE ``lax.scan``.
With ``gamma_i`` the cumulative ``g`` inside a chunk, ``S`` the state the
chunk starts from and ``u_j = beta_j (v_j - alpha_j S_{j-1}^T k_j)`` what
position j writes:

    A[i, j] = beta_i exp(gamma_i - gamma_j) (k_i . k_j)   for j < i, else 0
    (I + A) U = beta V - (beta exp(gamma) K) S
      =>  U = u - w S,   u = T (beta V),  w = T (beta exp(gamma) K),
          T = (I + A)^-1
    O   = (exp(gamma) Q) S + (Q K^T * exp(gamma_i - gamma_j), j <= i) U
    S'  = exp(gamma_C) S + (exp(gamma_C - gamma) K)^T U

Every decay is the exponential of a DIFFERENCE of cumulative sums taken where
the difference is <= 0 (masked before the exponential), so nothing overflows
however long the memory. ``T`` is made in float32 by halving (:func:
`inverse_unit_lower`): 16 x 16 diagonal blocks by the nilpotent product ``(I -
A)(I + A^2)(I + A^4)(I + A^8)``, then ``[[T1, 0], [-T2 A21 T1, T2]]`` twice;
the whole 64-wide product ``(I - A) ... (I + A^32)`` sums binomially large
terms of both signs when a chunk's keys are alike and loses float32 there.

Precisions: ``g``, the cumulative decays, ``T`` and the state are float32; the
products take their operands in ``v``'s dtype (bf16 in a bf16 program, as the
published kernels do) and accumulate in float32.

Two forms of the one algorithm, and the shape decides (:func:`gated_delta_rule`
asks the platform and :func:`takes_delta_rule`; no option):

- **The XLA form**, everywhere but on the chip and for every shape the
  kernels refuse (a ragged T, float32 operands, heads not 128 wide): all
  chunks' systems at once, then the scan; the portable form and the tests'
  yardstick. Its backward is autodiff through it: the scan keeps each chunk's
  incoming state and what the body made of it (over a GB a layer at 8,192
  positions and 32 heads of 128); ``T``'s own backward is written out
  (``-T^T dT T^T``), so the halving is not differentiated through.
- **Two Pallas TPU kernels** under one ``custom_vjp``, on the chip:
  ``gated_delta_rule_fwd`` walks a key head's chunks in order on a sequential
  grid axis, ``BLOCK_CHUNKS`` a grid step, its two value heads' (d_k, d_v)
  float32 states resident in VMEM from the first chunk to the last; a chunk's
  Gram matrices (once a key head), decays, ``a``, ``T`` (the halving, in
  float32 by six bf16 passes a product), ``w``, ``u``, its output and the
  states' update are made in VMEM from q, k, v read heads-first through the
  ``BlockSpec``, and ``o`` is written once. As a ``custom_vjp``'s forward rule
  it also writes each chunk's incoming states (float32) and ``T``; the primal
  writes neither. ``gated_delta_rule_bwd`` walks the same grid from the last
  chunk to the first with the states' cotangent resident, makes a chunk's small
  matrices again from its operands, its incoming states and its ``T``, and
  writes ``dq``, ``dk`` (summed over the key head's value heads), ``dv`` and
  the gradients of the cumulative decays and ``beta``. Around them XLA keeps
  the cumulative sum of ``g`` inside each chunk, two layouts of it and of
  ``beta`` (a MB each) and the reverse sum that turns the cumulative decays'
  gradient into ``g``'s.

What either costs is read by the benchmark's ``gdn_core_ms`` against a count
of the operation that does not depend on the form
(``benchmarks/harness/qwen3_next_flops.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from akka_allreduce_tpu.ops._platform import interpret_default

CHUNK = 64
_BLOCK = 16  # diagonal blocks inverted by the nilpotent product

_mm = functools.partial(jnp.matmul, precision=lax.Precision.HIGHEST)


def _halving_inverse(a: jax.Array) -> jax.Array:
    n = a.shape[-1]
    if n <= _BLOCK or n % 2:
        # a^n = 0: (I + a)^-1 = sum_m (-a)^m = (I - a)(I + a^2)(I + a^4) ...
        out, power, reach = jnp.eye(n, dtype=a.dtype) - a, a, 1
        while 2 * reach < n:  # ``out`` holds the powers below 2 reach
            power, reach = _mm(power, power), 2 * reach
            out = out + _mm(out, power)
        return out
    h = n // 2
    t1, t2 = _halving_inverse(a[..., :h, :h]), _halving_inverse(a[..., h:, h:])
    low = -_mm(t2, _mm(a[..., h:, :h], t1))
    return jnp.concatenate(
        (jnp.concatenate((t1, jnp.zeros_like(low)), axis=-1),
         jnp.concatenate((low, t2), axis=-1)),
        axis=-2,
    )


@jax.custom_vjp
def inverse_unit_lower(a: jax.Array) -> jax.Array:
    """``(I + a)^-1`` for strictly lower-triangular ``a`` (..., n, n),
    float32 at the highest precision."""
    return _halving_inverse(a)


def _inverse_fwd(a):
    t = _halving_inverse(a)
    return t, t


def _inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    return (-_mm(tt, _mm(dt, tt)),)


inverse_unit_lower.defvjp(_inverse_fwd, _inverse_bwd)


def gated_delta_rule(
    q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array, beta: jax.Array,
    *, chunk: int = CHUNK,
) -> tuple[jax.Array, jax.Array]:
    """``q``, ``k`` (B, H_k, T, d_k); ``v`` (B, H_v, T, d_v) with ``H_v`` a
    multiple of ``H_k``, key head j serving the ``H_v / H_k`` consecutive
    value heads from ``j H_v / H_k``; ``g`` (log decay, <= 0) and ``beta``
    (B, H_v, T). Whatever scale or norm q and k take is the caller's.
    Returns ``(o (B, H_v, T, d_v) in v's dtype, the final state (B, H_v, d_k,
    d_v) float32)``. A T that ``chunk`` does not divide is filled up with
    positions that neither decay nor write."""
    b, hk, t, dk = q.shape
    hv, dv = v.shape[1], v.shape[-1]
    if hv % hk or k.shape != q.shape or g.shape != (b, hv, t) or beta.shape != g.shape:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}, g {g.shape}, beta {beta.shape}"
        )
    if chunk == CHUNK and _by_kernels(q, k, v):
        _gauge_kernel_chunks(b, hv, t)
        return _rule_by_kernels(
            q, k, v, g.astype(jnp.float32), beta.astype(jnp.float32), interpret_default(q, k, v))
    dt, f32 = v.dtype, jnp.float32
    fill = -t % chunk
    if fill:  # g = 0 keeps the state, beta = 0 writes nothing
        q, k, v, g, beta = (
            jnp.pad(x, ((0, 0), (0, 0), (0, fill)) + ((0, 0),) * (x.ndim - 3))
            for x in (q, k, v, g, beta)
        )
    n = (t + fill) // chunk
    q, k, v, g, beta = (
        x.reshape(*x.shape[:2], n, chunk, *x.shape[3:]) for x in (q, k, v, g, beta)
    )
    g, beta = g.astype(f32), beta.astype(f32)
    products = functools.partial(jnp.einsum, preferred_element_type=f32)
    per_value_head = lambda x: jnp.repeat(x, hv // hk, axis=1)  # noqa: E731

    gamma = jnp.cumsum(g, axis=-1)  # (B, H_v, n, C)
    ones = jnp.ones((chunk, chunk), bool)
    at, before = jnp.tril(ones), jnp.tril(ones, -1)  # j <= i, j < i
    decay = jnp.exp(  # exp(gamma_i - gamma_j) where j <= i, else 0
        jnp.where(at, gamma[..., :, None] - gamma[..., None, :], -jnp.inf)
    )
    kk = per_value_head(products("bhnik,bhnjk->bhnij", k, k))
    qk = per_value_head(products("bhnik,bhnjk->bhnij", q, k))
    solved = inverse_unit_lower(
        jnp.where(before, beta[..., None] * kk * decay, 0.0)
    ).astype(dt)
    q, k = per_value_head(q), per_value_head(k)
    in_chunk = jnp.exp(gamma)  # from the chunk's start to each position
    to_end = jnp.exp(gamma[..., -1:] - gamma)  # from each position to its end
    scaled = lambda x, s: (x.astype(f32) * s[..., None]).astype(dt)  # noqa: E731
    w = products("bhnij,bhnjk->bhnik", solved, scaled(k, beta * in_chunk)).astype(dt)
    u = products("bhnij,bhnjv->bhniv", solved, scaled(v, beta))
    per_chunk = tuple(
        jnp.moveaxis(x, 2, 0)
        for x in (w, u, (qk * decay).astype(dt), scaled(q, in_chunk),
                  scaled(k, to_end), in_chunk[..., -1])
    )

    def one_chunk(state, xs):
        w_c, u_c, scores, q_c, k_c, kept = xs
        read = state.astype(dt)
        new = u_c - products("bhck,bhkv->bhcv", w_c, read)  # what the chunk writes
        new_dt = new.astype(dt)
        out = products("bhck,bhkv->bhcv", q_c, read) + products(
            "bhcj,bhjv->bhcv", scores, new_dt
        )
        state = state * kept[..., None, None] + products(
            "bhck,bhcv->bhkv", k_c, new_dt
        )
        return state, out.astype(dt)

    state = jnp.zeros((b, hv, dk, dv), f32)
    # under shard_map a constant carry has to vary as the body's result does
    varying = tuple(sorted(set().union(*(jax.typeof(x).vma for x in per_chunk))))
    if varying:
        state = lax.pcast(state, varying, to="varying")
    state, out = lax.scan(one_chunk, state, per_chunk)
    out = jnp.moveaxis(out, 0, 2).reshape(b, hv, t + fill, dv)
    return out[:, :, :t], state


# -- the rule on the chip: Pallas kernels of the repo's own ----------------------
#
# One key head's two value heads share a visit (``H_v = 2 H_k``), and a chunk's
# two (C, C) matrices of a kind sit side by side in the 128 lanes of ONE (C, 2C)
# array, "packed": column ``h C + j`` is head h's column j. A product of two
# packed matrices is ``x @ block_diagonal(y)``, one 128-wide product for both
# heads; elementwise work fills whole registers.

#: chunks a grid step of both kernels walks, in a loop inside the step
BLOCK_CHUNKS = 16
#: chunks whose per-position columns share one (C, 128) tile: 64 x 2 heads
_TILE_CHUNKS = 64
#: chunks whose Gram matrices and solves a step of the forward's first loop
#: makes together
_GROUP = 4
_LANES = 2 * CHUNK
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_TN = (((0,), (0,)), ((), ()))  # a.T @ b
_VMEM_LIMIT = 64 * 1024 * 1024  # of a v5e core's 128 MiB


def takes_delta_rule(t: int, d_k: int, d_v: int, h_k: int, h_v: int, dtype) -> bool:
    """Do the kernels take the shape? A T that a grid step's chunks divide,
    heads of 128 columns (the state of a value head is one MXU tile square),
    two value heads a key head (their chunk matrices fill the 128 lanes
    together) and bf16 operands."""
    return (
        t % (BLOCK_CHUNKS * CHUNK) == 0 and d_k == 128 and d_v == 128
        and h_v == 2 * h_k and jnp.dtype(dtype) == jnp.bfloat16
    )


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _pieces(x):
    """A float32 array as three bf16 whose sum it is (8 + 8 + 8 bits)."""
    f32, bf16 = jnp.float32, jnp.bfloat16
    high = x.astype(bf16)
    rest = x - high.astype(f32)
    middle = rest.astype(bf16)
    return high, middle, (rest - middle.astype(f32)).astype(bf16)


def _dot32(a, b, dims=(((1,), (0,)), ((), ()))):
    """A float32 product of ``a`` and ``b``, each three bf16 pieces, by the six
    bf16 passes that ``Precision.HIGHEST`` is on this chip (the pairs of
    pieces whose weight is at least 2^-16 of the product), written out: ``b``'s
    piece is loaded into the MXU once for all of ``a``'s pieces it meets (the
    compiler's own float32 product pushes 128 float32 registers of weights
    where this pushes 24 of bf16). The small terms are summed first."""
    free = 1 - dims[0][0][0]  # the dimension of ``a`` that stays: its pieces stack along it
    n = a[0].shape[free]
    part = lambda x, i: lax.slice_in_dim(x, i * n, (i + 1) * n, axis=0)  # noqa: E731
    by_high = _dot(jnp.concatenate(a, axis=free), b[0], dims)
    by_middle = _dot(jnp.concatenate(a[:2], axis=free), b[1], dims)
    by_low = _dot(a[0], b[2], dims)
    small = (part(by_high, 2) + by_low) + part(by_middle, 1)
    return part(by_high, 0) + ((part(by_high, 1) + part(by_middle, 0)) + small)


def _iota(shape, axis):
    return lax.broadcasted_iota(jnp.int32, shape, axis)


def _first_head(shape=(CHUNK, _LANES)):
    """Is a lane in the first value head's half of a packed array?"""
    return _iota(shape, 1) < CHUNK


def _block_diagonal(xp):
    """Packed (C, 2C) -> (2C, 2C): the two heads' matrices on the diagonal."""
    shape = (_LANES, _LANES)
    return jnp.where(
        (_iota(shape, 0) < CHUNK) == _first_head(shape), jnp.concatenate((xp, xp), axis=0),
        jnp.zeros((), xp.dtype),
    )


def _wide(pieces):
    """:func:`_block_diagonal` of each piece."""
    return tuple(_block_diagonal(x) for x in pieces)


def _by_head(xp):
    """Packed (C, 2C) -> (2C, 2C): rows of head h keep only head h's columns,
    so ``_by_head(x) @ [y_0; y_1]`` is ``[x_0 y_0; x_1 y_1]``."""
    first = _first_head()
    zero = jnp.zeros_like(xp)
    return jnp.concatenate((jnp.where(first, xp, zero), jnp.where(first, zero, xp)), axis=0)


def _head_rows(x, h: int):
    """Head h's rows of an array that stacks the two heads' (C, n) blocks."""
    return x[h * CHUNK:(h + 1) * CHUNK]


def _same_block(size: int):
    """Packed mask: row i and a head's column j in the same ``size`` block."""
    row, col = _iota((CHUNK, _LANES), 0), _iota((CHUNK, _LANES), 1) % CHUNK
    return row // size == col // size


def _merged(aps, outs, size: int):
    """A merge of :func:`_solve`: ``outs`` hold the inverses of the diagonal
    blocks of ``size / 2``, and come back holding those of ``size``. Only the
    lower half of each ``size`` block moves, so both products take those rows
    alone (half the pushes into and the pops out of the MXU, whose
    instructions are what the solve is bound by)."""
    half, tops = size // 2, range(0, CHUNK, size)

    def lower(x):  # the rows that move, block after block: (C / 2, 2C)
        return jnp.concatenate([x[r + half:r + size] for r in tops], axis=0)

    def back(x):  # those rows in their places again, zeros above them
        zeros = jnp.zeros((half, _LANES), x.dtype)
        return jnp.concatenate(
            sum(([zeros, x[n * half:(n + 1) * half]] for n in range(len(tops))), []), axis=0)

    brought_in = _same_block(size) & ~_same_block(half)
    offs = [_pieces(lower(jnp.where(brought_in, ap, 0.0))) for ap in aps]
    pieces = [_pieces(out) for out in outs]
    inner = [_wide(_pieces(back(_dot32(off, _wide(piece))))) for off, piece in zip(offs, pieces)]
    return [out - back(_dot32(tuple(lower(x) for x in piece), m))
            for out, piece, m in zip(outs, pieces, inner)]


def _solve(aps):
    """``(I + a)^-1`` of both heads' strictly lower-triangular ``a``, packed,
    for each of ``aps`` (chunks that do not depend on one another), in float32
    (:func:`_dot32`), by :func:`_halving_inverse`'s steps: the 16 x 16
    diagonal blocks by the nilpotent product (block-diagonal matrices stay so
    under products), then two merges, ``T - T (off T)`` with ``off`` the
    blocks a merge brings in (below the diagonal: the upper half of a merged
    block stays as it is). Every step is written for all of ``aps`` before
    the next one: the MXU runs its products in the program's order, so it is
    this order that lets one chunk's products fill another's waits."""
    row, col = _iota((CHUNK, _LANES), 0), _iota((CHUNK, _LANES), 1) % CHUNK
    each = lambda fn, *lists: [fn(*xs) for xs in zip(*lists)]  # noqa: E731
    powers = each(lambda ap: jnp.where(_same_block(_BLOCK), ap, 0.0), aps)
    outs = each(lambda power: jnp.where(row == col, 1.0, 0.0) - power, powers)
    powers, reach = each(_pieces, powers), 1
    while 2 * reach < _BLOCK:
        powers = each(lambda power: _pieces(_dot32(power, _wide(power))), powers)
        outs = each(lambda out, power: out + _dot32(_pieces(out), _wide(power)), outs, powers)
        reach *= 2
    size = 2 * _BLOCK
    while size <= CHUNK:
        outs = _merged(aps, outs, size)
        size *= 2
    return outs


def _columns(tile, at):
    """The two heads' columns (C, 1) of chunk ``at`` of a tile (lane ``2 at +
    h``)."""
    return tuple(
        pltpu.roll(tile, (_LANES - (2 * at + h)) % _LANES, 1)[:, :1] for h in range(2)
    )


def _chunk_columns(cols_ref, block, chunks: int, c):
    """``(gamma, beta)`` of chunk ``c`` of grid step ``block``, each the two
    heads' (C, 1) columns; ``gamma`` from the chunk's start to each position."""
    in_tile = (block * chunks + c) % _TILE_CHUNKS
    return _columns(cols_ref[0], in_tile), _columns(cols_ref[1], in_tile)


def _chunk_matrices(q_ref, k_ref, rows_ref, columns, c):
    """A chunk's (C, C) matrices, packed, none of which waits for the state:
    the decays, both Gram matrices (each twice, side by side), ``beta`` as
    the rows' factor and the strictly lower-triangular ``a``."""
    at = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
    q, k = q_ref[at, :], k_ref[at, :]
    first = _first_head()
    gamma, beta = columns
    gamma_p = jnp.where(first, gamma[0], gamma[1])
    beta_p = jnp.where(first, beta[0], beta[1])
    row, col = _iota((CHUNK, _LANES), 0), _iota((CHUNK, _LANES), 1) % CHUNK
    decay = jnp.exp(jnp.where(col <= row, gamma_p - rows_ref[pl.ds(c, 1), :], -jnp.inf))
    gram = _dot(jnp.concatenate((k, q), axis=0), jnp.concatenate((k, k), axis=0), _NT)
    kk, qk = gram[:CHUNK], gram[CHUNK:]
    return dict(decay=decay, kk=kk, qk=qk, beta_p=beta_p,
                a=jnp.where(col < row, beta_p * kk * decay, 0.0))


def _chunk_scaled(q_ref, k_ref, v_ref, columns, c):
    """A chunk's operands and their copies scaled by the decays and ``beta``,
    a value head each."""
    f32 = jnp.float32
    at = pl.ds(pl.multiple_of(c * CHUNK, CHUNK), CHUNK)
    q, k = q_ref[at, :], k_ref[at, :]
    v = (v_ref[0, at, :], v_ref[1, at, :])
    gamma, beta = columns
    in_chunk = tuple(jnp.exp(x) for x in gamma)
    last = tuple(x[CHUNK - 1:] for x in gamma)  # (1, 1): the chunk's whole log decay
    to_end = tuple(jnp.exp(e - x) for e, x in zip(last, gamma))
    kept = tuple(jnp.exp(e)[0, 0] for e in last)  # scalars: they scale whole states
    scaled = lambda x, s: (x.astype(f32) * s).astype(x.dtype)  # noqa: E731
    kb = tuple(scaled(k, b * s) for b, s in zip(beta, in_chunk))
    vb = tuple(scaled(x, b) for x, b in zip(v, beta))
    qs = tuple(scaled(q, s) for s in in_chunk)
    ke = tuple(scaled(k, s) for s in to_end)
    return dict(at=at, q=q, k=k, v=v, beta=beta, in_chunk=in_chunk, to_end=to_end, kept=kept,
                kb=kb, vb=vb, qs=qs, ke=ke)


def _scaled_rows(x):
    """``[kb | vb]`` of head 0 over that of head 1: what ``T`` multiplies."""
    return jnp.concatenate(
        tuple(jnp.concatenate(pair, axis=1) for pair in zip(x["kb"], x["vb"])), axis=0)


def _written(x, solved, scaled_rows):
    """``(w, u)``, a head each, from the packed bf16 ``T``: ``w`` in the
    operands' dtype, ``u`` float32."""
    dt, d_k = x["q"].dtype, x["q"].shape[1]
    wu = _dot(_by_head(solved), scaled_rows)
    w = tuple(_head_rows(wu, h)[:, :d_k].astype(dt) for h in range(2))
    u = tuple(_head_rows(wu, h)[:, d_k:] for h in range(2))
    return w, u


def _fwd_kernel(q_ref, k_ref, v_ref, rows_ref, cols_ref, o_ref, final_ref, *rest, keep):
    """A grid step: ``rows_ref.shape[0]`` chunks of one key head's two value
    heads. First what no state is needed for, ``_GROUP`` chunks at a time:
    each chunk's scores and its ``T`` into VMEM. Then the chunks one after
    another against the states, which stay in ``state`` from the head's first
    step to its last. With ``keep`` two more outputs: each chunk's incoming
    states, and its float32 ``T`` (written where the primal has a scratch)."""
    if keep:
        states_ref, solved_ref, state, scores_ref = rest
    else:
        state, scores_ref, solved_ref = rest
    dt, block, chunks = q_ref.dtype, pl.program_id(2), rows_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros(state.shape, jnp.float32)

    together = math.gcd(_GROUP, chunks)

    def group(i, carry):
        cs = [i * together + u for u in range(together)]
        made = [_chunk_matrices(q_ref, k_ref, rows_ref,
                                _chunk_columns(cols_ref, block, chunks, c), c) for c in cs]
        for c, m in zip(cs, made):
            scores_ref[c] = (m["qk"] * m["decay"]).astype(dt)
        for c, solved in zip(cs, _solve([m["a"] for m in made])):
            solved_ref[c] = solved
        return carry

    lax.fori_loop(0, chunks // together, group, 0)

    def chunk(c, carry):
        x = _chunk_scaled(q_ref, k_ref, v_ref, _chunk_columns(cols_ref, block, chunks, c), c)
        w, u = _written(x, solved_ref[c].astype(dt), _scaled_rows(x))
        new, read = [], []
        for h in range(2):
            s = state[h]
            if keep:
                states_ref[h, c] = s
            both = _dot(jnp.concatenate((w[h], x["qs"][h]), axis=0), s.astype(dt))
            new.append((u[h] - both[:CHUNK]).astype(dt))
            read.append(both[CHUNK:])
        inside = _dot(_by_head(scores_ref[c]), jnp.concatenate(new, axis=0))
        for h in range(2):
            o_ref[h, x["at"], :] = (read[h] + _head_rows(inside, h)).astype(dt)
            state[h] = state[h] * x["kept"][h] + _dot(x["ke"][h], new[h], _TN)
        return carry

    lax.fori_loop(0, chunks, chunk, 0)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _():
        final_ref[...] = state[...]


def _bwd_kernel(q_ref, k_ref, v_ref, rows_ref, cols_ref, states_ref, solved_ref, do_ref,
                dfinal_ref, dq_ref, dk_ref, dv_ref, dgamma_ref, dbeta_ref, dstate):
    """The forward's grid walked from the last chunk to the first: ``dstate``
    holds the cotangent of the states a chunk hands on; a chunk's small
    matrices are made again from its operands, its incoming states and its
    saved ``T``."""
    f32, dt = jnp.float32, q_ref.dtype
    chunks, block = rows_ref.shape[0], pl.num_programs(2) - 1 - pl.program_id(2)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = dfinal_ref[...]

    def chunk(step, _):
        c = chunks - 1 - step
        columns = _chunk_columns(cols_ref, block, chunks, c)
        x = {**_chunk_matrices(q_ref, k_ref, rows_ref, columns, c),
             **_chunk_scaled(q_ref, k_ref, v_ref, columns, c)}
        q, k, at = x["q"], x["k"], x["at"]
        q32, k32 = q.astype(f32), k.astype(f32)
        first = _first_head()
        row, col = _iota((CHUNK, _LANES), 0), _iota((CHUNK, _LANES), 1) % CHUNK
        solved = solved_ref[c]
        solved_dt = solved.astype(dt)
        written = _scaled_rows(x)
        w, u = _written(x, solved_dt, written)
        scores = x["qk"] * x["decay"]
        do = (do_ref[0, at, :], do_ref[1, at, :])
        # what reaches ``new`` through the output inside the chunk, both heads
        through_scores = _dot(_by_head(scores.astype(dt)), jnp.concatenate(do, axis=0), _TN)
        # a step for both heads before the next one: the MXU runs products in
        # the program's order, and a head's next product waits for its last
        heads = range(2)
        s = [states_ref[h, c] for h in heads]
        ds = [dstate[h] for h in heads]
        s_dt, ds_dt = [x.astype(dt) for x in s], [x.astype(dt) for x in ds]
        read = [_dot(w[h], s_dt[h]) for h in heads]
        through_state = [_dot(x["ke"][h], ds_dt[h]) for h in heads]
        new = [(u[h] - read[h]).astype(dt) for h in heads]
        d_new = [(_head_rows(through_scores, h) + through_state[h]).astype(dt) for h in heads]
        d_ke = [_dot(new[h], ds_dt[h], _NT) for h in heads]
        stacked = jnp.concatenate(new, axis=0)
        d_scores = jnp.where(  # (C, 2C) against both heads' ``new``: a head keeps its half
            col <= row, jnp.where(first, _dot(do[0], stacked, _NT), _dot(do[1], stacked, _NT)), 0.0)
        both = [_dot(jnp.concatenate((do[h], d_new[h]), axis=0), s_dt[h], _NT) for h in heads]
        for h in heads:
            dstate[h] = ds[h] * x["kept"][h] + _dot(
                jnp.concatenate((x["qs"][h], w[h]), axis=0),
                jnp.concatenate((do[h], -d_new[h]), axis=0), _TN)
        d_wu = [jnp.concatenate(((-both[h][CHUNK:]).astype(dt), d_new[h]), axis=1) for h in heads]
        # the cotangents of q and k through their scaled copies, then what the
        # same copies hand the decays and beta
        t_q = [both[h][:CHUNK] * x["in_chunk"][h] for h in heads]
        t_ke = [d_ke[h] * x["to_end"][h] for h in heads]
        from_end = [jnp.sum(k32 * t_ke[h], axis=1, keepdims=True) for h in heads]
        last = [jnp.sum(from_end[h], axis=0, keepdims=True)
                + x["kept"][h] * jnp.sum(s[h] * ds[h], axis=(0, 1), keepdims=True) for h in heads]
        dq, dk = t_q[0] + t_q[1], t_ke[0] + t_ke[1]
        # ``T``'s cotangent, packed, and what ``T`` hands ``kb`` and ``vb``
        d_solved = jnp.where(
            first, _dot(d_wu[0], written, _NT), _dot(d_wu[1], written, _NT))
        d_written = _dot(_by_head(solved_dt), jnp.concatenate(d_wu, axis=0), _TN)
        # -T^T dT T^T, packed: T^T of both heads from ONE transpose of the
        # block-diagonal matrix, whose halves fold back into a packed one
        transposed = _block_diagonal(solved).T
        inner = _dot32(_pieces(d_solved), _wide(_pieces(solved)), _NT)
        inner = _dot32(_pieces(transposed[:CHUNK] + transposed[CHUNK:]), _wide(_pieces(inner)))
        d_a = jnp.where(col < row, -inner, 0.0)
        d_gram = jnp.concatenate(
            ((d_a * x["beta_p"] * x["decay"]).astype(dt), (d_scores * x["decay"]).astype(dt)),
            axis=0)
        straight = _dot(d_gram, jnp.concatenate((k, k), axis=0))  # [dKK k; dQK k]
        turned = _dot(d_gram, jnp.concatenate((k, q), axis=0), _TN)  # dKK^T k + dQK^T q, a head
        dq = dq + straight[CHUNK:]
        dk = dk + straight[:CHUNK] + turned[:CHUNK] + turned[CHUNK:]
        through_decay = d_a * x["a"] + d_scores * scores  # d decay * decay, packed
        beta_from_a = d_a * x["kk"] * x["decay"]
        gamma_rows, beta_rows = [], []
        for h in heads:
            d_kb, d_vb = jnp.split(_head_rows(d_written, h), [q.shape[1]], axis=1)
            t_kb = d_kb * (x["beta"][h] * x["in_chunk"][h])
            dk = dk + t_kb
            dv_ref[h, at, :] = (d_vb * x["beta"][h]).astype(dt)
            mine = first if h == 0 else ~first
            gamma_rows.append(
                jnp.sum(q32 * t_q[h] + k32 * t_kb + jnp.where(mine, through_decay, 0.0),
                        axis=1, keepdims=True) - from_end[h])
            beta_rows.append(jnp.sum(
                k32 * d_kb * x["in_chunk"][h] + d_vb * x["v"][h].astype(f32)
                + jnp.where(mine, beta_from_a, 0.0), axis=1, keepdims=True))
        dq_ref[at, :] = dq.astype(dt)
        dk_ref[at, :] = dk.astype(dt)
        # columns (a number a position, down the sublanes) to packed rows
        on_diagonal = row == col
        to_row = lambda cols: jnp.sum(  # noqa: E731
            jnp.where(on_diagonal, jnp.where(first, cols[0], cols[1]), 0.0), axis=0, keepdims=True)
        at_end = jnp.where(_first_head((1, _LANES)), last[0], last[1])
        dgamma_ref[pl.ds(c, 1), :] = (
            to_row(gamma_rows) - jnp.sum(through_decay, axis=0, keepdims=True)
            + jnp.where(_iota((1, _LANES), 1) % CHUNK == CHUNK - 1, at_end, 0.0))
        dbeta_ref[pl.ds(c, 1), :] = to_row(beta_rows)
        return 0

    lax.fori_loop(0, chunks, chunk, 0)


def _rows_layout(x, h_k: int):
    """(B, H_v, T) -> (B, H_k, chunks, 2C): a chunk's numbers of a key head's
    two value heads side by side, the packed arrays' lane order."""
    b, h_v, t = x.shape
    x = x.reshape(b, h_k, 2, t // CHUNK, CHUNK)
    return x.transpose(0, 1, 3, 2, 4).reshape(b, h_k, t // CHUNK, _LANES)


def _from_rows(x, t: int):
    """:func:`_rows_layout` undone: (B, H_k, chunks, 2C) -> (B, H_v, T)."""
    b, h_k, n, _ = x.shape
    return x.reshape(b, h_k, n, 2, CHUNK).transpose(0, 1, 3, 2, 4).reshape(b, 2 * h_k, t)


def _columns_layout(x, h_k: int):
    """(B, H_v, T) -> (B, H_k, tiles, C, 128): position i of chunk c of head h
    at [c // 64, i, 2 (c % 64) + h], so that a chunk's numbers run down the
    sublanes, as the rows of q, k and v they scale do."""
    b, h_v, t = x.shape
    n = t // CHUNK
    tiles = -(-n // _TILE_CHUNKS)
    x = x.reshape(b, h_k, 2, n, CHUNK)
    x = jnp.pad(x, ((0, 0), (0, 0), (0, 0), (0, tiles * _TILE_CHUNKS - n), (0, 0)))
    x = x.reshape(b, h_k, 2, tiles, _TILE_CHUNKS, CHUNK).transpose(0, 1, 3, 5, 4, 2)
    return x.reshape(b, h_k, tiles, CHUNK, _LANES)


def _prepared(g, beta, h_k: int):
    """The decays' cumulative sums inside each chunk, as packed rows, and
    with ``beta`` as tiles of columns."""
    b, h_v, t = g.shape
    gamma = jnp.cumsum(g.reshape(b, h_v, t // CHUNK, CHUNK), axis=-1).reshape(b, h_v, t)
    cols = jnp.stack((_columns_layout(gamma, h_k), _columns_layout(beta, h_k)), axis=2)
    return _rows_layout(gamma, h_k), cols


def _specs(block: int, d_k: int, d_v: int, steps: int, reverse: bool):
    """Index maps of both kernels on the grid (batch, key head, block of
    chunks); the backward walks the blocks from the last to the first."""
    at = (lambda s: steps - 1 - s) if reverse else (lambda s: s)
    rows = block * CHUNK
    keys = pl.BlockSpec((None, None, rows, d_k), lambda n, j, s: (n, j, at(s), 0))
    values = pl.BlockSpec((None, 2, rows, d_v), lambda n, j, s: (n, j, at(s), 0))
    packed_rows = pl.BlockSpec((None, None, block, _LANES), lambda n, j, s: (n, j, at(s), 0))
    cols = pl.BlockSpec(
        (None, None, 2, None, CHUNK, _LANES),
        lambda n, j, s: (n, j, 0, at(s) * block // _TILE_CHUNKS, 0, 0))
    head_states = pl.BlockSpec((None, 2, d_k, d_v), lambda n, j, s: (n, j, 0, 0))
    states = pl.BlockSpec((None, 2, block, d_k, d_v), lambda n, j, s: (n, j, at(s), 0, 0))
    solved = pl.BlockSpec((None, None, block, CHUNK, _LANES), lambda n, j, s: (n, j, at(s), 0, 0))
    return keys, values, packed_rows, cols, head_states, states, solved


_PARAMS = dict(
    compiler_params=pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_VMEM_LIMIT,
    )
)


# Both calls are jitted on their own, as the index scores' are: traced and
# lowered once a shape, not once a call site (three layers, the primal, the
# forward rule and the recomputed pass), which is what a warm start pays.
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _rule_forward(q, k, v, g, beta, keep: bool, interpret: bool, block: int):
    """``(o, final state)`` and, with ``keep``, each chunk's incoming states
    (B, H_v, chunks, d_k, d_v) and its packed float32 ``T`` (B, H_k, chunks,
    C, 2C) for the backward."""
    b, h_k, t, d_k = q.shape
    h_v, d_v = v.shape[1], v.shape[-1]
    n = t // CHUNK
    steps = n // block
    keys, values, packed_rows, cols, head_states, states, solved = _specs(
        block, d_k, d_v, steps, False)
    f32 = jnp.float32
    out_specs = [values, head_states] + ([states, solved] if keep else [])
    out_shape = [jax.ShapeDtypeStruct(v.shape, v.dtype),
                 jax.ShapeDtypeStruct((b, h_v, d_k, d_v), f32)] + ([
        jax.ShapeDtypeStruct((b, h_v, n, d_k, d_v), f32),
        jax.ShapeDtypeStruct((b, h_k, n, CHUNK, _LANES), f32)] if keep else [])
    return pl.pallas_call(
        functools.partial(_fwd_kernel, keep=keep), grid=(b, h_k, steps),
        in_specs=[keys, keys, values, packed_rows, cols], out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((2, d_k, d_v), f32), pltpu.VMEM((block, CHUNK, _LANES), v.dtype)]
        + ([] if keep else [pltpu.VMEM((block, CHUNK, _LANES), f32)]),
        name="gated_delta_rule_fwd", interpret=interpret, **_PARAMS,
    )(q, k, v, *_prepared(g, beta, h_k))


@functools.partial(jax.jit, static_argnums=(9, 10))
def _rule_backward(q, k, v, g, beta, states, solved, do, dfinal, interpret: bool, block: int):
    """``(dq, dk, dv, dg, dbeta)`` from the operands, what the forward rule
    kept and the cotangents of ``o`` and of the final state."""
    b, h_k, t, d_k = q.shape
    h_v, d_v = v.shape[1], v.shape[-1]
    n = t // CHUNK
    steps = n // block
    keys, values, packed_rows, cols, head_states, kept_states, kept_solved = _specs(
        block, d_k, d_v, steps, True)
    f32 = jnp.float32
    rows = jax.ShapeDtypeStruct((b, h_k, n, _LANES), f32)
    dq, dk, dv, dgamma, dbeta = pl.pallas_call(
        _bwd_kernel, grid=(b, h_k, steps),
        in_specs=[keys, keys, values, packed_rows, cols, kept_states, kept_solved, values,
                  head_states],
        out_specs=[keys, keys, values, packed_rows, packed_rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype), rows, rows],
        scratch_shapes=[pltpu.VMEM((2, d_k, d_v), f32)],
        name="gated_delta_rule_bwd", interpret=interpret, **_PARAMS,
    )(q, k, v, *_prepared(g, beta, h_k), states, solved, do, dfinal)
    # g reaches a position's cumulative sum and every later one of its chunk
    dgamma = _from_rows(dgamma, t).reshape(b, h_v, n, CHUNK)
    dg = jnp.flip(jnp.cumsum(jnp.flip(dgamma, -1), axis=-1), -1).reshape(b, h_v, t)
    return dq, dk, dv, dg, _from_rows(dbeta, t)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_by_kernels(q, k, v, g, beta, interpret):
    """:func:`gated_delta_rule` on the kernels: float32 ``g`` and ``beta``.
    The primal writes no residual; the forward rule (under a caller's
    ``jax.checkpoint``, the pass made again) also writes each chunk's
    incoming states and its ``T``, alive until the backward has read them."""
    return tuple(_rule_forward(q, k, v, g, beta, False, interpret, BLOCK_CHUNKS))


def _rule_kernels_fwd(q, k, v, g, beta, interpret):
    o, final, states, solved = _rule_forward(q, k, v, g, beta, True, interpret, BLOCK_CHUNKS)
    return (o, final), (q, k, v, g, beta, states, solved)


def _rule_kernels_bwd(interpret, residuals, cotangents):
    do, dfinal = cotangents
    return _rule_backward(*residuals, do, dfinal, interpret, BLOCK_CHUNKS)


_rule_by_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


def _on_chip(*arrays) -> bool:
    """The platform question, as ``sparse_attention.index_scores`` asks it."""
    return not interpret_default(*arrays)


def _by_kernels(q, k, v) -> bool:
    """Does the rule of these operands go to the kernels? By the platform and
    the shape, nothing else."""
    return _on_chip(q, k, v) and q.dtype == k.dtype == v.dtype and takes_delta_rule(
        q.shape[2], q.shape[3], v.shape[3], q.shape[1], v.shape[1], v.dtype)


@functools.lru_cache(maxsize=None)
def _gauge_kernel_chunks(b: int, h_v: int, t: int) -> None:
    """The (value head, chunk) visits a call hands to the kernels, once a
    shape, to the gauge ``linear_attention.rule.kernel_chunks``
    (OBSERVABILITY.md)."""
    from akka_allreduce_tpu.obs import metrics as obs_metrics

    obs_metrics.gauge("linear_attention.rule.kernel_chunks").set(b * h_v * (t // CHUNK))

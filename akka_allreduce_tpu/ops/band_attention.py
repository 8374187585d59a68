"""A Pallas TPU kernel for causal attention under a short window (a band).

Query ``i`` sees key ``j`` iff ``0 <= i - j < window``. All the keys a tile
of ``TILE`` queries may see are ``window + TILE`` CONTIGUOUS keys, a slab
that fits VMEM at once for windows up to 1,024, so a tile needs no loop over
K/V blocks, no running maximum and no rescaled accumulator: one score product
against the slab, one mask (the same parallelogram on every tile but the
first few, whose slab is clamped at position 0), a plain softmax, one value
product. The query heads of one K/V head's group share the slab: they are
stacked as rows of one product, so k and v are read once a group. The
library's splash kernel skips whole blocks only: its 512 tiles run a 512-wide
band half masked out and a 1,024-wide one a third; these run a fifth and a
ninth.

Three ``pallas_call``s, each writing whole outputs (:func:`band_attention`
is their ``custom_vjp``): ``flash_mha_band_fwd`` and ``flash_mha_band_dq``
query-major over the key slab, ``flash_mha_band_dkv`` key-major over the
slab of the ``window + TILE`` queries that may see a tile of keys, summed
over the group's heads inside the kernel. The operands' dtype (bf16) into the
MXU, f32 scores, statistics and accumulation; the residuals are the output
and the rows' log-sum-exp, f32 (B, H_kv, G, T). ``delta = rowsum(do * o)`` is
XLA's.

The sweep behind ``TILE`` and the stacking (128 against 256 and 512 in each
kernel, the group stacked against looped, per window) is in CHANGES.md, PR 40.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from akka_allreduce_tpu.ops.ring_attention import _MASK_VALUE

#: queries (forward, dq) or keys (dkv) a grid step; the MXU's width, and the
#: least a tile can run of masked-out pairs: ``TILE / (window + TILE)``
TILE = 128
#: query rows of a group's heads stacked into one product (forward, dq): eight
#: heads of a tile; a wider group goes through in parts, the slab staying put
_STACK_ROWS = 1024
_NT = (((1,), (1,)), ((), ()))  # a @ b.T
_VMEM_LIMIT = 100 * 1024 * 1024  # of a v5e core's 128 MiB


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _in_band(rows: int, cols: int, low, window: int):
    """(rows, cols) bool: ``low <= r - c < low + window``."""
    diff = (lax.broadcasted_iota(jnp.int32, (rows, cols), 0)
            - lax.broadcasted_iota(jnp.int32, (rows, cols), 1))
    return (diff >= low) & (diff < low + window)


def _seen_keys(b: int, slab: int, window: int):
    """(1, b, slab) bool: which keys of its slab a query tile's rows see. The
    slab starts at ``max(q0 - window, 0)``: the first tiles' is clamped, so
    query r of the tile and key c of the slab are ``shift + r - c`` apart."""
    shift = jnp.minimum(pl.program_id(2) * b, window)
    return _in_band(b, slab, -shift, window)[None]


def _stacks(g: int):
    """The group's heads in parts of at most ``_STACK_ROWS`` stacked rows."""
    n = max(1, _STACK_ROWS // TILE)
    return [slice(i, min(i + n, g)) for i in range(0, g, n)]


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, window: int):
    g, b, d = q_ref.shape
    slab = k_ref.shape[0]
    seen = _seen_keys(b, slab, window)
    k, v = k_ref[...], v_ref[...]
    for hs in _stacks(g):
        n = hs.stop - hs.start
        s = _dot(q_ref[hs].reshape(n * b, d), k, _NT).reshape(n, b, slab)
        s = jnp.where(seen, s, _MASK_VALUE)
        m = s.max(axis=-1, keepdims=True)
        p = jnp.exp(s - m)
        l = p.sum(axis=-1, keepdims=True)
        o = _dot(p.reshape(n * b, slab).astype(v.dtype), v)
        o_ref[hs] = (o.reshape(n, b, -1) / l).astype(o_ref.dtype)
        lse_ref[hs] = (m + jnp.log(l))[..., 0]


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref, *, window: int):
    g, b, d = q_ref.shape
    slab = k_ref.shape[0]
    seen = _seen_keys(b, slab, window)
    k, v = k_ref[...], v_ref[...]
    for hs in _stacks(g):
        n = hs.stop - hs.start
        s = _dot(q_ref[hs].reshape(n * b, d), k, _NT).reshape(n, b, slab)
        p = jnp.exp(jnp.where(seen, s, _MASK_VALUE) - lse_ref[hs][..., None])
        dp = _dot(do_ref[hs].reshape(n * b, -1), v, _NT).reshape(n, b, slab)
        ds = p * (dp - delta_ref[hs][..., None])
        dq = _dot(ds.reshape(n * b, slab).astype(k.dtype), k)
        dq_ref[hs] = dq.reshape(n, b, d).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref, dv_ref,
                *, window: int, seq_len: int):
    g, slab, _ = q_ref.shape
    b = k_ref.shape[0]
    # the queries' slab starts at min(k0, T - slab): the last tiles' is
    # clamped, so key r of the tile and query c of the slab are c - r + shift apart
    k0 = pl.program_id(2) * b
    shift = jnp.minimum(k0, seq_len - slab) - k0
    seen = _in_band(b, slab, shift - window + 1, window)
    k, v = k_ref[...], v_ref[...]
    dk = jnp.zeros(dk_ref.shape, jnp.float32)
    dv = jnp.zeros(dv_ref.shape, jnp.float32)
    for h in range(g):  # rows are keys: a head's statistics broadcast down them
        q, do = q_ref[h], do_ref[h]
        s = jnp.where(seen, _dot(k, q, _NT), _MASK_VALUE)
        p = jnp.exp(s - lse_ref[h][None, :])
        dv += _dot(p.astype(do.dtype), do)
        ds = p * (_dot(v, do, _NT) - delta_ref[h][None, :])
        dk += _dot(ds.astype(q.dtype), q)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dv.astype(dv_ref.dtype)


def _call(kernel, name, grid, in_specs, out_specs, out_shape, interpret):
    return pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, name=name, interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
    )


def _tile(g: int | None, width: int):
    """A tile of positions of one group's ``g`` heads, (B, H, T, width), or
    (``g`` None) of one K/V head."""
    return pl.BlockSpec((None, g, TILE, width), lambda n, h, i: (n, h, i, 0))


def _rows(g: int):
    """The same tile of a row statistic, f32 (B, H_kv, G, T): a group's heads
    are a whole dimension, so any group size is a legal block."""
    return pl.BlockSpec((None, None, g, TILE), lambda n, h, i: (n, h, 0, i))


def _key_slab(window: int, width: int):
    """The ``window + TILE`` keys (or values) a tile of queries may see, of
    one K/V head, from ``max(q0 - window, 0)``: indexed by element, since the
    slabs of neighbouring tiles overlap."""
    return pl.BlockSpec(
        (None, None, pl.Element(window + TILE), pl.Element(width)),
        lambda n, h, i: (n, h, jnp.maximum(i - window // TILE, 0) * TILE, 0),
    )


def _forward(q, k, v, window, interpret):
    n, h, t, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    g = h // h_kv
    return _call(
        functools.partial(_fwd_kernel, window=window), "flash_mha_band_fwd",
        (n, h_kv, t // TILE),
        [_tile(g, d), _key_slab(window, d), _key_slab(window, dv)],
        [_tile(g, dv), _rows(g)],
        [jax.ShapeDtypeStruct((n, h, t, dv), q.dtype),
         jax.ShapeDtypeStruct((n, h_kv, g, t), jnp.float32)],
        interpret,
    )(q, k, v)


def _dq(q, k, v, do, lse, delta, window, interpret):
    n, h, t, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    g = h // h_kv
    return _call(
        functools.partial(_dq_kernel, window=window), "flash_mha_band_dq",
        (n, h_kv, t // TILE),
        [_tile(g, d), _key_slab(window, d), _key_slab(window, dv),
         _tile(g, dv), _rows(g), _rows(g)],
        _tile(g, d), jax.ShapeDtypeStruct(q.shape, q.dtype), interpret,
    )(q, k, v, do, lse, delta)


def _dkv(q, k, v, do, lse, delta, window, interpret):
    n, h, t, d = q.shape
    h_kv, dv = k.shape[1], v.shape[-1]
    g, slab = h // h_kv, window + TILE
    # the queries that may see a tile of keys, of the group's heads, from
    # min(k0, T - slab)
    start = lambda i: jnp.minimum(i, (t - slab) // TILE) * TILE  # noqa: E731
    queries = lambda width: pl.BlockSpec(  # noqa: E731
        (None, pl.Element(g), pl.Element(slab), pl.Element(width)),
        lambda n, h, i: (n, h * g, start(i), 0),
    )
    rows = pl.BlockSpec(
        (None, None, pl.Element(g), pl.Element(slab)), lambda n, h, i: (n, h, 0, start(i))
    )
    return _call(
        functools.partial(_dkv_kernel, window=window, seq_len=t), "flash_mha_band_dkv",
        (n, h_kv, t // TILE),
        [queries(d), _tile(None, d), _tile(None, dv), queries(dv), rows, rows],
        [_tile(None, d), _tile(None, dv)],
        [jax.ShapeDtypeStruct(k.shape, k.dtype), jax.ShapeDtypeStruct(v.shape, v.dtype)],
        interpret,
    )(q, k, v, do, lse, delta)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def band_attention(q, k, v, window: int, interpret: bool = False):
    """Causal attention under ``window``: ``q`` (B, H, T, D) WITH the score
    scale in it against compact ``k`` (B, H_kv, T, D) and ``v`` (B, H_kv, T,
    Dv), to (B, H, T, Dv). ``TILE`` divides the window and T, and T holds a
    window and a tile (``local_attention._takes_band`` is the rule)."""
    return _forward(q, k, v, window, interpret)[0]


def _band_fwd(q, k, v, window, interpret):
    o, lse = _forward(q, k, v, window, interpret)
    return o, (q, k, v, o, lse)


def _band_bwd(window, interpret, residuals, do):
    q, k, v, o, lse = residuals
    delta = (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(axis=-1).reshape(lse.shape)
    dq = _dq(q, k, v, do, lse, delta, window, interpret)
    dk, dv = _dkv(q, k, v, do, lse, delta, window, interpret)
    return dq, dk, dv


band_attention.defvjp(_band_fwd, _band_bwd)

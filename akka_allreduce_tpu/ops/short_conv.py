"""Gated short convolution: the token mixer of conv/attention hybrids.

    s = B * z;   c_t = sum_{j<L} w[:, j] * s_{t-(L-1)+j};   out = C * c

``B``, ``C`` and ``z`` are the three d-wide thirds of the input projection,
``w`` is one L-tap filter per channel (depthwise), the convolution is causal
with zeros to the left. Everything is elementwise over (batch, time,
channel) but the L shifted reads, so the op is bound by memory traffic, not
by the MXU: plain XLA, which fuses the taps into one pass.

The backward is written out (``jax.custom_vjp``) so that only the projection
``bcz`` is kept for it: ``s`` and ``c`` are made again from it, two (B, T, d)
tensors a layer that autodiff would have saved. The filter's gradient is
accumulated in float32.

The same taps serve a second caller, the convolution in front of a
linear-attention mixer (:func:`silu_short_conv`): ``out = silu(c)`` with ``c``
the causal filter of the input itself, no gate; its backward too keeps only
the input and makes ``c`` again.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _shift_right(x: jax.Array, n: int) -> jax.Array:
    """``x`` delayed by ``n`` steps along axis 1, zeros entering."""
    if n == 0:
        return x
    return jnp.pad(x, ((0, 0), (n, 0), (0, 0)))[:, : x.shape[1]]


def _shift_left(x: jax.Array, n: int) -> jax.Array:
    if n == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, n), (0, 0)))[:, n:]


def _causal_taps(s: jax.Array, w: jax.Array) -> jax.Array:
    taps = w.shape[1]
    wc = w.astype(s.dtype)
    return sum(wc[:, j] * _shift_right(s, taps - 1 - j) for j in range(taps))


@jax.custom_vjp
def gated_short_conv(bcz: jax.Array, w: jax.Array) -> jax.Array:
    """``bcz``: (batch, T, 3d), the thirds in the order B, C, z; ``w``: (d, L).
    Returns (batch, T, d) in ``bcz``'s dtype."""
    b, c, z = jnp.split(bcz, 3, axis=-1)
    return c * _causal_taps(b * z, w)


def _fwd(bcz, w):
    return gated_short_conv(bcz, w), (bcz, w)


def _taps_backward(dc: jax.Array, s: jax.Array, w: jax.Array):
    """``(ds, dw)`` from the cotangent ``dc`` of ``_causal_taps(s, w)``."""
    taps = w.shape[1]
    wc = w.astype(s.dtype)
    # the transpose of a causal filter reads the future
    ds = sum(wc[:, j] * _shift_left(dc, taps - 1 - j) for j in range(taps))
    dw = jnp.stack(
        [
            jnp.sum(
                dc.astype(jnp.float32)
                * _shift_right(s, taps - 1 - j).astype(jnp.float32),
                axis=(0, 1),
            )
            for j in range(taps)
        ],
        axis=1,
    ).astype(w.dtype)
    return ds, dw


def _bwd(res, dy):
    bcz, w = res
    b, c, z = jnp.split(bcz, 3, axis=-1)
    s = b * z
    ds, dw = _taps_backward(dy * c, s, w)  # the cotangent of the filter's output
    dbcz = jnp.concatenate(
        (ds * z, dy * _causal_taps(s, w), ds * b), axis=-1
    )
    return dbcz, dw


gated_short_conv.defvjp(_fwd, _bwd)


@jax.custom_vjp
def silu_short_conv(x: jax.Array, w: jax.Array) -> jax.Array:
    """``silu`` of the causal depthwise filter of ``x`` (batch, T, d) by ``w``
    (d, L), zeros to the left, in ``x``'s dtype."""
    return jax.nn.silu(_causal_taps(x, w))


def _silu_fwd(x, w):
    return silu_short_conv(x, w), (x, w)


def _silu_bwd(res, dy):
    x, w = res
    _, through_silu = jax.vjp(jax.nn.silu, _causal_taps(x, w))
    return _taps_backward(through_silu(dy)[0], x, w)


silu_short_conv.defvjp(_silu_fwd, _silu_bwd)

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
published kernels do) and accumulate in float32. The backward is autodiff
through this form: the scan keeps each chunk's incoming state and what the
body made of it (over a GB a layer at 8,192 positions and 32 heads of 128, so
the caller that trains at that size makes the rule again on its backward
pass: ``models.hybrid_decoder.GatedDeltaNet``); ``T``'s own backward is
written out (``-T^T dT T^T``), so the halving is not differentiated through.
Plain XLA, no kernel: what it costs is read by the benchmark's ``gdn_core_ms``
against a count of the operation that does not depend on this form
(``benchmarks/harness/qwen3_next_flops.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

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

"""Sequence/context parallelism: ring attention and all-to-all (Ulysses).

The reference has no long-context support at all (SURVEY.md §6 "Long-context /
sequence parallelism: ABSENT") — this module is where the TPU rebuild goes
beyond parity. Both strategies shard the *sequence* dimension across a mesh
axis so attention over sequences far larger than one chip's HBM runs at full
MXU utilization:

- :func:`ring_attention` — blockwise attention with online (flash-style)
  softmax. Each device keeps its Q shard resident and rotates K/V shards
  around the ICI ring via ``lax.ppermute``, accumulating ``(m, l, o)`` running
  statistics. Communication is the same neighbor-ring schedule as the
  framework's ring allreduce (ops/ring.py), so it rides ICI links the same
  way; compute per step is a dense (T_local x T_local) block that XLA tiles
  onto the MXU.
- :func:`ulysses_attention` — DeepSpeed-Ulysses-style: two ``lax.all_to_all``
  collectives re-shard from sequence-parallel to head-parallel, run full-
  sequence attention on ``H / n`` heads per device, and re-shard back. Cheaper
  in collective steps (2 vs n-1) when heads divide evenly; ring wins when
  H < n or when overlap with the MXU matters.

Both are pure functions to call INSIDE ``shard_map`` with the sequence mesh
axis name, and both match the dense :func:`attention_reference` oracle to
float tolerance (tests/test_ring_attention.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

# Finite stand-in for -inf: keeps the online-softmax recurrence NaN-free when
# an entire (causal-masked) block is invisible to a query row — the bogus
# exp(0)=1 contributions such a block accumulates are wiped by the correction
# factor exp(m_old - m_new) = 0 the moment a real block arrives, and every row
# sees at least its own diagonal block, so the final (l, o) are exact.
_MASK_VALUE = -1e30


def repeat_kv(k: jax.Array, n_heads: int) -> jax.Array:
    """Expand grouped-query K/V (B, T, H_kv, D) to ``n_heads`` by repeating
    each KV head over its query group (GQA; H_kv == 1 is MQA). The repeat
    happens at the COMPUTE site only — sequence-parallel schedules move the
    compact (B, T, H_kv, D) form over the wire, which is where GQA's
    bandwidth saving lives."""
    h_kv = k.shape[2]
    if h_kv == n_heads:
        return k
    if n_heads % h_kv:
        raise ValueError(f"{n_heads=} not divisible by kv heads {h_kv}")
    return jnp.repeat(k, n_heads // h_kv, axis=2)


def online_softmax_update(olm, qf, kk, vv, scale, mask):
    """One flash-style block fold: merge K/V block (kk, vv) into the running
    ``(o, l, m)`` statistics for queries ``qf`` (all fp32).

    ``mask``: boolean (Tq, Tk_block) visibility (or (B, 1, Tq, Tk_block),
    one a sample), or None for a fully visible block. Masked positions contribute EXACTLY zero — including the corner
    case where a whole row has seen nothing yet (m still at the sentinel):
    there ``exp(score - m) = 1`` would otherwise leak mask/padding entries
    into ``l``. Shared by ring attention (cross-device blocks) and the
    single-device blockwise path so the numerically delicate recurrence
    exists once.
    """
    o, l, m = olm
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kk.astype(jnp.float32)) * scale
    if mask is not None and mask.ndim == 2:
        mask = mask[None, None]
    if mask is not None:
        scores = jnp.where(mask, scores, _MASK_VALUE)
    m_new = jnp.maximum(m, scores.max(axis=-1))
    p = jnp.exp(scores - m_new[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1)
    o = o * corr[..., None] + jnp.einsum(
        "bhqk,bkhd->bhqd", p, vv.astype(jnp.float32)
    )
    return o, l, m_new


def attention_reference(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
    window: int | None = None,
) -> jax.Array:
    """Dense softmax attention; the single-device oracle and the Ulysses core.

    Shapes: ``q`` (B, Tq, H, D); ``k``/``v`` (B, Tk, H, D). Offsets give the
    global positions of the local windows for causal masking under sharding.
    With ``window`` (causal only) query ``i`` sees key ``j`` iff
    ``0 <= i - j < window``: itself and the ``window - 1`` before it.
    """
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32
    ) * scale
    if window is not None and not causal:
        raise ValueError("a window is built for causal attention only")
    if causal:
        q_pos = q_offset + jnp.arange(q.shape[1])
        k_pos = k_offset + jnp.arange(k.shape[1])
        mask = q_pos[:, None] >= k_pos[None, :]
        if window is not None:
            mask &= q_pos[:, None] - k_pos[None, :] < window
        scores = jnp.where(mask[None, None], scores, _MASK_VALUE)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum(
        "bhqk,bkhd->bqhd", probs, v, preferred_element_type=jnp.float32
    )
    return out.astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """Blockwise ring attention over sequence shards; call inside ``shard_map``.

    ``q``/``k``/``v``: this device's sequence shard, (B, T_local, H, D); the
    global sequence is the concatenation along ``axis_name`` in mesh order
    (``n = lax.axis_size(axis_name)`` shards). Returns this device's
    (B, T_local, H, D) shard of the attention output, exactly as if dense
    attention ran over the full sequence.

    K/V rotate one neighbor per step (device i -> i+1), so after step ``s``
    device ``i`` holds the shard originating at ``(i - s) mod n``; the online
    softmax makes the result order-independent and numerically stable in fp32.
    The last block is consumed outside the loop so no final (discarded)
    rotation crosses the ICI.

    Grouped-query attention: ``k``/``v`` may carry fewer heads than ``q``
    (H_kv dividing H) — the COMPACT form rotates around the ring, so the
    per-step ICI bytes shrink by H/H_kv, and each block expands KV locally
    just before its score matmul (:func:`repeat_kv`).
    """
    n = lax.axis_size(axis_name)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    if n == 1:
        from akka_allreduce_tpu.ops.local_attention import local_attention

        return local_attention(q, k, v, causal=causal, sm_scale=scale)
    b, t, h, d = q.shape
    my = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]

    qf = q.astype(jnp.float32)
    q_pos = my * t + jnp.arange(t)

    def block_update(olm, src, kk, vv):
        """Fold the K/V shard that originated on device `src` into (o, l, m)."""
        if causal:
            k_pos = src * t + jnp.arange(t)
            mask = q_pos[:, None] >= k_pos[None, :]
        else:
            mask = None
        kk, vv = repeat_kv(kk, h), repeat_kv(vv, h)
        return online_softmax_update(olm, qf, kk, vv, scale, mask)

    def step(s, carry):
        o, l, m, kk, vv = carry
        o, l, m = block_update((o, l, m), jnp.mod(my - s, n), kk, vv)
        kk = lax.ppermute(kk, axis_name, fwd)
        vv = lax.ppermute(vv, axis_name, fwd)
        return o, l, m, kk, vv

    # Derive inits from q so they carry q's full device-varying spec (seq axis
    # plus any batch axes of an enclosing 2D mesh); constant zeros would make
    # the fori_loop carry types mismatch (unvarying in, varying out).
    o0 = jnp.swapaxes(qf, 1, 2) * 0.0  # (b, h, t, d)
    l0 = o0[..., 0]  # (b, h, t)
    m0 = l0 + _MASK_VALUE
    o, l, m, kk, vv = lax.fori_loop(0, n - 1, step, (o0, l0, m0, k, v))
    o, l, _ = block_update((o, l, m), jnp.mod(my - (n - 1), n), kk, vv)
    out = o / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
) -> jax.Array:
    """All-to-all sequence parallelism; call inside ``shard_map``.

    Re-shards (B, T/n, H, D) -> (B, T, H/n, D) with one ``all_to_all``, runs
    full-sequence attention on the local head group, and re-shards back.
    Requires ``H % lax.axis_size(axis_name) == 0``.

    Grouped-query attention: when the KV head count also divides the axis
    size, K/V cross the all_to_all in COMPACT form (wire bytes shrink by
    H/H_kv) and the local core expands them; otherwise they are expanded
    before the exchange (correct, no bandwidth saving — noted so callers
    pick H_kv >= the seq-axis size when they want the win).
    """
    from akka_allreduce_tpu.ops.local_attention import local_attention

    n = lax.axis_size(axis_name)
    h = q.shape[2]
    if n == 1:
        return local_attention(q, k, v, causal=causal, sm_scale=sm_scale)
    if h % n:
        raise ValueError(
            f"ulysses needs heads ({h}) divisible by axis size {n}"
        )
    if k.shape[2] % n:
        k, v = repeat_kv(k, h), repeat_kv(v, h)

    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qg, kg, vg = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    # full-sequence local core: memory-efficient/flash, not dense
    out = local_attention(qg, kg, vg, causal=causal, sm_scale=sm_scale)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2, tiled=True)

"""Single-device attention cores: blockwise (flash-style) + kernel dispatch.

Dense attention materializes the (B, H, T, T) score matrix — ~1 GB per layer
at T=2048/B=8/H=8 fp32 — so every long-context path that lands on ONE device
(the sp=1 fast path of ring attention, and Ulysses' full-sequence local core)
was HBM-bound on score traffic, not FLOPs. Two fixes, dispatched by
:func:`local_attention`:

- :func:`blockwise_attention` — portable memory-efficient attention: an
  online-softmax ``lax.scan`` over K/V blocks (the same recurrence ring
  attention runs across devices, applied within one device), with
  ``jax.checkpoint`` on the block step so autodiff RECOMPUTES block scores in
  the backward pass instead of saving them — O(T·block) live memory for
  forward+backward instead of O(T^2).
- the library's Pallas TPU splash-attention kernel
  (``jax.experimental.pallas.ops.tpu.splash_attention``) when running on a
  real TPU backend and the shape fits its tiling (:func:`_splash_attention`):
  the mask is an object, so only the blocks the causal diagonal cuts pay for
  masking and the blocks it empties are skipped; the backward is ONE fused
  pass (``dq``, ``dk``, ``dv`` from one recomputation of the scores); K/V go
  in at their own head count and ``dk``/``dv`` come back at it. Under a
  window of up to 1,024 keys the same branch calls the repo's own band
  kernel instead (``ops/band_attention.py``, :func:`_takes_band`): the
  library skips whole blocks only, so its tiles run a band half masked out.

What the kernel costs is measured in the benchmark's training cells
(``attn_kernel_ms``, PERF.md section 5); the block-size sweep behind
:func:`_splash_blocks` is in CHANGES.md, PR 31.

Convention for a query row with NO visible keys (fully-causal-masked or
all-padding window): the output row is zero — masked positions contribute
exactly nothing (``online_softmax_update``), unlike a dense softmax which
would fall back to a uniform average of whatever it was given.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.ops.ring_attention import (
    attention_reference,
    online_softmax_update,
    repeat_kv,
)

# dense is fine (and fastest) below this sequence length: the score block
# fits comfortably in VMEM-scale working sets
_DENSE_MAX_T = 512


def _blockwise_olm(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    q_offset,
    k_offset,
    block_k: int,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    vary_axes: tuple = (),
    window: int | None = None,
    mask: jax.Array | None = None,
):
    """Blockwise online-softmax PARTIALS ``(o, l, m)`` over a local K/V
    slice — the un-normalized core of :func:`blockwise_attention`, also
    the memory-safe local stage of the seq-sharded split-K merge.

    With ``k_scale``/``v_scale`` (int8 cache), ``k``/``v`` are int8
    payloads dequantized ONE BLOCK AT A TIME inside the scan — live
    full-precision memory stays O(block), never the whole slice.
    ``vary_axes``: mesh axes the K/V slice is device-varying over when
    called inside ``shard_map`` — the scan's zero-initialized carry must
    be pcast to match, or the vma typecheck rejects the loop (the axes the
    operands' own types show are added to them).
    ``mask`` (B, Tq, Tk), nonzero where the query sees the key: a key is
    visible iff the mask AND the causal rule (where asked for) say so.
    """
    from akka_allreduce_tpu.ops.ring_attention import _MASK_VALUE, repeat_kv

    h = q.shape[2]
    if k.shape[2] != h:  # grouped-query K/V expand at compute
        group = h // k.shape[2]
        k, v = repeat_kv(k, h), repeat_kv(v, h)
        if k_scale is not None:
            k_scale = jnp.repeat(k_scale, group, axis=2)
            v_scale = jnp.repeat(v_scale, group, axis=2)
    b, tq, h, d = q.shape
    tk, dv = k.shape[1], v.shape[-1]  # the values' head size may differ
    nb = -(-tk // block_k)
    pad = nb * block_k - tk
    kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    # (nb, B, block, H, D) so scan carries one block per step
    kb = kp.reshape(b, nb, block_k, h, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nb, block_k, h, dv).transpose(1, 0, 2, 3, 4)
    blk = (jnp.arange(nb), kb, vb)
    if k_scale is not None:
        sb = lambda s: jnp.pad(s, ((0, 0), (0, pad), (0, 0))).reshape(  # noqa: E731
            b, nb, block_k, h
        ).transpose(1, 0, 2, 3)
        blk = blk + (sb(k_scale), sb(v_scale))
    if mask is not None:  # (nb, B, 1, Tq, block): one block of keys a step
        seen = jnp.pad(mask != 0, ((0, 0), (0, 0), (0, pad)))
        blk = blk + (seen.reshape(b, 1, tq, nb, block_k).transpose(3, 0, 1, 2, 4),)

    qf = q.astype(jnp.float32)
    q_pos = q_offset + jnp.arange(tq)

    def block_step(olm, blk):
        if mask is not None:
            *blk, seen = blk
        if k_scale is not None:
            idx, kk, vv, ks, vs = blk
            kk = kk.astype(jnp.float32) * ks[..., None]
            vv = vv.astype(jnp.float32) * vs[..., None]
        else:
            idx, kk, vv = blk
        k_pos = k_offset + idx * block_k + jnp.arange(block_k)
        valid = k_pos < k_offset + tk  # mask the zero-padding tail
        if causal:
            valid = valid[None, :] & (q_pos[:, None] >= k_pos[None, :])
            if window is not None:  # a K/V block wholly outside adds nothing
                valid &= q_pos[:, None] - k_pos[None, :] < window
        else:
            valid = jnp.broadcast_to(valid[None, :], (tq, block_k))
        if mask is not None:
            valid = valid[None, None] & seen
        return online_softmax_update(olm, qf, kk, vv, scale, valid), None

    o0 = jnp.zeros((b, h, tq, dv), jnp.float32)
    l0 = jnp.zeros((b, h, tq), jnp.float32)
    m0 = jnp.full((b, h, tq), _MASK_VALUE, jnp.float32)
    # besides the axes named, those the operands are seen to vary over (a
    # caller under shard_map that names none: attention under an array mask)
    operands = (q, k, v) if mask is None else (q, k, v, mask)
    seen_to_vary = set().union(*(jax.typeof(a).vma for a in operands))
    vary_axes = tuple(vary_axes) + tuple(sorted(seen_to_vary - set(vary_axes)))
    if vary_axes:
        o0, l0, m0 = (
            lax.pcast(x, vary_axes, to="varying") for x in (o0, l0, m0)
        )
    # checkpoint: backward recomputes each block's scores instead of storing
    # them — this is what keeps live memory O(T * block) through autodiff
    step = jax.checkpoint(block_step)
    (o, l, m), _ = lax.scan(step, (o0, l0, m0), blk)
    return o, l, m


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    q_offset: int | jax.Array = 0,
    k_offset: int | jax.Array = 0,
    block_k: int = 512,
    window: int | None = None,
    mask: jax.Array | None = None,
    with_lse: bool = False,
):
    """Memory-efficient attention over K/V blocks; same result as
    :func:`attention_reference` to float tolerance.

    Shapes: ``q`` (B, Tq, H, D); ``k`` (B, Tk, H, D); ``v`` (B, Tk, H, Dv),
    Dv = D or not (latent attention: 192 against 128); the result has Dv.
    Offsets position the local windows globally for causal masking (as in
    ring attention); ``window`` as :func:`attention_reference` has it.
    ``mask`` (B, Tq, Tk), nonzero where a query sees a key, narrows what the
    other rules leave (the portable core of attention under a mask that
    arrives as an array). ``with_lse``: also the rows' float32 log-sum-exp
    over their visible keys, (B, H, Tq).
    """
    if window is not None and not causal:
        raise ValueError("a window is built for causal attention only")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    o, l, m = _blockwise_olm(
        q, k, v, causal=causal, scale=scale,
        q_offset=q_offset, k_offset=k_offset, block_k=block_k, window=window,
        mask=mask,
    )
    l = jnp.maximum(l, 1e-30)
    out = (o / l[..., None]).transpose(0, 2, 1, 3).astype(q.dtype)
    return (out, m + jnp.log(l)) if with_lse else out


def flash_shapes_ok(t: int, d: int, dv: int | None = None) -> bool:
    """Would the Pallas TPU splash kernel take (T=t, head_dim=d), the values'
    head size ``dv`` (``d`` where left out)?

    Conservative static gate (:func:`_splash_blocks` hands out blocks of 512
    and up, and every block must divide T); also the question trainers ask to
    decide whether shard_map's vma check must be relaxed (the kernel's
    outputs carry no varying-axes annotation).
    """
    return t > _DENSE_MAX_T and t % 512 == 0 and d % 32 == 0 and (dv or d) % 32 == 0


def flash_vma_relax(
    seq_len: int, head_dim: int, *, sp: int = 1, seq_impl: str = "ring"
) -> bool:
    """True when the Pallas splash kernel CAN dispatch inside a trainer's
    step for this attention configuration on this backend. shard_map
    callers must then set ``check_vma=False``: the kernel's outputs carry
    no varying-axes annotation, so the static replication checker cannot
    type them (the trainers' shared gate — LongContext/MoE/Pipeline/FSDP).

    A FULL single-device attention runs at the whole ``seq_len`` when the
    sequence is unsharded (``sp == 1``) or under Ulysses (the all-to-all
    reassembles full T locally); ring attention never runs one, so the
    kernel never dispatches there.
    """
    local_t = seq_len if (sp == 1 or seq_impl == "ulysses") else 0
    return (
        jax.default_backend() == "tpu"
        and local_t > 0
        and flash_shapes_ok(local_t, head_dim)
    )


def _flash_ok(
    q: jax.Array, k: jax.Array, v: jax.Array, q_offset, k_offset, seq_axis: int = 1
) -> bool:
    """Shape/placement gate for the Pallas TPU splash kernel; ``seq_axis`` is
    where the operands hold T (2 for heads-first ones)."""
    from akka_allreduce_tpu.ops._platform import interpret_default

    if interpret_default(q, k):
        return False
    if not (isinstance(q_offset, int) and q_offset == 0):
        return False
    if not (isinstance(k_offset, int) and k_offset == 0):
        return False
    tq = q.shape[seq_axis]
    return tq == k.shape[seq_axis] and flash_shapes_ok(tq, q.shape[-1], v.shape[-1])


def _splash_blocks(
    t: int, d: int, dv: int | None = None, itemsize: int = 2,
    window: int | None = None,
):
    """The splash kernel's tiles for (T=t, head_dim=d, values' head size
    ``dv``, bytes an element), read from sweeps of {512, 1024, 2048} (compute
    blocks also 256) on a v5e at the benchmark's attention shapes: (T 4096,
    head 128, 24 heads on 2) and (T 8192, head 64, 32 on 8) — the table is in
    CHANGES.md, PR 31 — and latent attention's (T 8192, 32 heads, 192
    against 128; CHANGES.md, PR 32). The same tiles won at all three: 1024 x
    1024 everywhere, the forward's scores computed 512 K/V rows at a time.
    Larger K/V blocks run more of the diagonal's masked half (and 2048 x 2048
    no longer fits VMEM); smaller ones re-read q and, in the fused backward,
    write one more bf16 partial ``dq`` of q's size per K/V block for XLA to
    sum. Neither head 64 nor 192 against 128 moved the choice. Past 128,
    float32 inputs at 1024 overrun VMEM in the described-v5e compile (bf16
    ones fit, and ran), so those keep 512, which :func:`flash_shapes_ok`
    guarantees divides T. q, k AND v at 256 (T 8192, 16 heads on 2, bf16;
    CHANGES.md, PR 46): 1024 tiles 4.38 ms forward and 13.91 forward +
    backward a layer, 512 tiles 4.76 and 15.88, both compiled for the
    described v5e first: 1024 again. ``window``: a causal band :func:`_takes_band` left
    to the library (the band kernel's tile does not divide it, or it is past
    1,024)."""
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    fits = max(d, dv or d) <= 128 or itemsize <= 2
    b = 1024 if t % 1024 == 0 and fits else 512
    fused = True
    if window is not None and window <= 1024:
        # The fused backward runs its whole (K/V block, q block) grid whatever
        # the mask and writes a zero ``dq`` partial for every K/V block the
        # band leaves empty; ``dkv`` and ``dq`` apart run shrunk grids. At
        # windows 512 and 1,024, 512 tiles and two kernels won (CHANGES.md,
        # PRs 35 and 39); past 1,024 not swept: the tiles of no window.
        b, fused = 512, False
    return BlockSizes(
        block_q=b, block_kv=b, block_kv_compute=512,
        block_q_dkv=b, block_kv_dkv=b, block_kv_dkv_compute=b,
        block_q_dq=None if fused else b, block_kv_dq=None if fused else b,
        use_fused_bwd_kernel=fused,
    )


@functools.lru_cache(maxsize=None)
def _splash_kernel(
    t: int, h: int, causal: bool, blocks, interpret: bool,
    window: int | None = None,
):
    """The library's kernel object for one shape, built once: the mask's
    block tables are host numpy work (0.3-0.6 s at the benchmark's shapes),
    and a step traces this call once per attention layer. With ``window`` the
    mask is the band ``0 <= i - j < window`` and the K/V blocks it leaves
    empty on either side are skipped."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        CausalMask,
        FullMask,
        LocalMask,
        MultiHeadMask,
        make_splash_mha,
    )

    if window is not None:
        mask = LocalMask((t, t), window_size=(window - 1, 0), offset=0)
    else:
        mask = (CausalMask if causal else FullMask)((t, t))
    # the tables become device arrays inside the library; built under a
    # trace they would be that trace's tracers, and the cache would leak them
    with jax.ensure_compile_time_eval():
        return make_splash_mha(
            MultiHeadMask([mask] * h), head_shards=1, q_seq_shards=1,
            block_sizes=blocks, interpret=interpret,
        )


def _takes_band(t: int, d: int, dv: int, window: int | None) -> bool:
    """Does the repo's band kernel (``ops/band_attention.py``) take the shape?
    A causal window of up to 1,024 keys that its tile divides, as it does T,
    T holding a window and a tile, heads of up to 128: the slab of ``window +
    TILE`` keys and a group's scores against it fit VMEM at once. On shapes
    alone. At both windows the cells run (512 on 64 heads of 8, 1,024 on 32 of
    4) it took 4.6 and 4.1 ms a layer where the library's 512 tiles took 8.5
    and 6.5 (CHANGES.md, PR 40)."""
    if window is None or window > 1024 or max(d, dv) > 128:
        return False
    from akka_allreduce_tpu.ops.band_attention import TILE

    return window % TILE == 0 and t % TILE == 0 and t >= window + TILE


@functools.lru_cache(maxsize=None)
def _gauge_band(t: int, window: int) -> None:
    """What the band kernel's tiles run of the mask, once a shape, to the
    gauges ``attention.band.*`` (a head's; every head has the same;
    OBSERVABILITY.md): ``visited_pairs``, each tile of queries against its
    slab of ``window + TILE`` keys, and ``mask_pairs``, those of them the band
    leaves, ``sum_i min(i + 1, window)``."""
    from akka_allreduce_tpu.obs import metrics as obs_metrics
    from akka_allreduce_tpu.ops.band_attention import TILE

    obs_metrics.gauge("attention.band.visited_pairs").set(t * (window + TILE))
    obs_metrics.gauge("attention.band.mask_pairs").set(
        window * (window + 1) // 2 + (t - window) * window
    )


def _splash_heads_first(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """The kernel on the layout it reads: ``q`` (B, H, T, D) against COMPACT
    ``k`` (B, H_kv, T, D) and ``v`` (B, H_kv, T, Dv), H_kv dividing H, to
    (B, H, T, Dv) — the kernel reads each K/V head for its whole query group
    and accumulates ``dk``/``dv`` over the group itself. It has no scale
    argument: ``q`` comes with the scale in it. Both entries end here:
    :func:`_splash_attention` for callers that hold (B, T, H, D),
    :func:`heads_first_attention` for one whose products wrote this layout.
    Under a window :func:`_takes_band` takes, the repo's own band kernel
    (``ops/band_attention.py``); else the library's splash kernel."""
    _, h, t, d = q.shape
    if _takes_band(t, d, v.shape[-1], window):
        from akka_allreduce_tpu.ops.band_attention import band_attention

        _gauge_band(t, window)
        return band_attention(q, k, v, window, interpret)
    blocks = _splash_blocks(t, d, v.shape[-1], q.dtype.itemsize, window)
    return jax.vmap(_splash_kernel(t, h, causal, blocks, interpret, window))(q, k, v)


def _splash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    scale: float,
    interpret: bool = False,
    window: int | None = None,
) -> jax.Array:
    """:func:`local_attention`'s kernel branch: ``q`` (B, T, H, D) against
    COMPACT ``k``/``v`` (B, T, H_kv, D). The scale is folded into ``q``
    (exact at head size 64, one more rounding of ``q`` in its own dtype
    otherwise). ``interpret`` is for the CPU test of these numbers."""
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    out = _splash_heads_first(
        heads_first(q * scale), heads_first(k), heads_first(v),
        causal=causal, interpret=interpret, window=window,
    )
    return heads_first(out)


def _scaled_masked_scores(q, k, k_scale, scale, q_offset, k_offset):
    """f32 (B, H, Tq, L) causally-masked scores of ``q`` against a local
    K slice: GQA heads repeat at the compute site, and (for an int8
    cache) the per-row scales fold into the scores (q·(k·s) = (q·k)·s) so
    no dequantized copy of the slice is materialized. THE one copy of the
    score/mask convention for the dense cache-attention paths
    (:func:`quantized_cache_attention`, :func:`seq_decode_attention`)."""
    from akka_allreduce_tpu.ops.ring_attention import _MASK_VALUE, repeat_kv

    h = q.shape[2]
    kc = repeat_kv(k.astype(q.dtype), h)  # convert fuses into the dot
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q, kc, preferred_element_type=jnp.float32
    )
    if k_scale is not None:
        ks = jnp.repeat(k_scale, h // k.shape[2], axis=2)  # (B, L, H)
        scores = scores * (ks.transpose(0, 2, 1)[:, :, None, :] * scale)
    else:
        scores = scores * scale
    q_pos = q_offset + jnp.arange(q.shape[1])
    k_pos = k_offset + jnp.arange(k.shape[1])
    mask = q_pos[:, None] >= k_pos[None, :]
    return jnp.where(mask[None, None], scores, _MASK_VALUE)


def _weighted_v(p, v, v_scale):
    """(B, H, Tq, L) weights × local V slice -> (B, H, Tq, D) f32, with
    int8 row scales folded into the weights (Σ p·s·v = (p·s)·v); the
    sibling of :func:`_scaled_masked_scores` for the V side."""
    from akka_allreduce_tpu.ops.ring_attention import repeat_kv

    h = p.shape[1]
    vc = repeat_kv(v, h)
    if v_scale is not None:
        vs = jnp.repeat(v_scale, h // v.shape[2], axis=2)
        p = p * vs.transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum(
        "bhqk,bkhd->bhqd",
        p.astype(jnp.float32),
        vc.astype(jnp.float32),
        preferred_element_type=jnp.float32,
    )


def quantized_cache_attention(
    q: jax.Array,
    k_q: jax.Array,
    k_scale: jax.Array,
    v_q: jax.Array,
    v_scale: jax.Array,
    *,
    q_offset,
    sm_scale: float | None = None,
) -> jax.Array:
    """Causal attention over an int8-quantized KV cache WITHOUT
    materializing the dequantized cache: per-row scales fold into the
    score matrix and the probability weights (see
    :func:`_scaled_masked_scores` / :func:`_weighted_v`), so the only
    full-cache reads are the int8 payloads — the bandwidth the
    quantization was bought for.

    Shapes: ``q`` (B, Tq, H, D); ``k_q``/``v_q`` (B, L, H_kv, D) int8 with
    (B, L, H_kv) f32 scales. Built for the decode shape (small Tq over a
    long cache); scores are (B, H, Tq, L) — tiny for Tq of a few.
    """
    import math as _math

    scale = sm_scale if sm_scale is not None else 1.0 / _math.sqrt(q.shape[-1])
    scores = _scaled_masked_scores(q, k_q, k_scale, scale, q_offset, 0)
    probs = jax.nn.softmax(scores, axis=-1)
    out = _weighted_v(probs, v_q, v_scale)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def seq_decode_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis_name: str,
    *,
    q_offset,
    k_offset,
    k_scale: jax.Array | None = None,
    v_scale: jax.Array | None = None,
    sm_scale: float | None = None,
    extra_vary_axes: tuple = (),
) -> jax.Array:
    """Decode attention over a SEQUENCE-SHARDED KV cache (VERDICT r4 #5).

    ``extra_vary_axes``: further mesh axes the inputs are device-varying
    over (the ``model`` axis under TP-composed decode) — the blockwise
    branch's scan carry must be typed varying over every such axis.

    Each shard holds its (B, L_local, H_kv, D) slice of the cache;
    ``q`` (B, Tq, H, D) is replicated over ``axis_name``. The shard
    computes a dense partial softmax against its local keys (causal vs
    GLOBAL positions: ``k_offset`` is this shard's first cache slot), and
    the partials merge with one ``pmax`` + two ``psum``s — flash-decoding's
    split-K reduction expressed as XLA collectives riding the ICI ring.

    With ``k_scale``/``v_scale`` (int8 cache), ``k``/``v`` are the int8
    payloads and the per-row scales fold into the scores and weights
    exactly like :func:`quantized_cache_attention` — no dequantized copy
    of the local slice is materialized.

    Local partials dispatch on the score-block size like
    :func:`local_attention`: dense for the decode shape (small Tq), the
    blockwise online-softmax scan (:func:`_blockwise_olm`) when a large
    prefill chunk over a long local slice would otherwise materialize
    (B, H, Tq, L_local) f32 scores. Accumulation is float32 throughout —
    the merge must be exact across shards regardless of compute dtype.
    """
    import math as _math

    scale = sm_scale if sm_scale is not None else 1.0 / _math.sqrt(q.shape[-1])
    if q.shape[1] * k.shape[1] <= _DENSE_MAX_T * _DENSE_MAX_T:
        # dense local partial: take the GLOBAL max before exponentiating
        # (one pmax), so every shard's p uses the same reference — the
        # same rounding as a single-device softmax
        scores = _scaled_masked_scores(
            q, k, k_scale, scale, q_offset, k_offset
        )
        m_g = lax.pmax(jnp.max(scores, axis=-1), axis_name)  # (B, H, Tq)
        p = jnp.exp(scores - m_g[..., None])  # masked slots: exp(-huge)=0
        l_g = lax.psum(jnp.sum(p, axis=-1), axis_name)
        o_g = lax.psum(_weighted_v(p, v, v_scale), axis_name)
    else:
        # blockwise local partials (large prefill chunk x long slice):
        # each shard's (o, l, m) rescale to the global max at merge time
        o, l, m = _blockwise_olm(
            q, k, v, causal=True, scale=scale,
            q_offset=q_offset, k_offset=k_offset, block_k=512,
            k_scale=k_scale, v_scale=v_scale,
            vary_axes=(axis_name,) + tuple(extra_vary_axes),
        )
        m_g = lax.pmax(m, axis_name)
        corr = jnp.exp(m - m_g)
        l_g = lax.psum(l * corr, axis_name)
        o_g = lax.psum(o * corr[..., None], axis_name)
    out = o_g / jnp.maximum(l_g, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def local_attention(
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
    """Best single-device attention for the shape/backend at hand.

    Dispatch: dense for short sequences (fastest, fits on chip), the Pallas
    TPU splash kernel when on TPU with kernel-friendly shapes, else the
    portable blockwise path. All three agree with the dense oracle.

    Grouped-query K/V (fewer heads than ``q``) go into the kernel compact;
    the dense and blockwise cores expand them at their score matmul.

    ``window`` (causal only): query ``i`` sees key ``j`` iff
    ``0 <= i - j < window``, in all three cores; a row always sees itself.
    """
    if window is not None and not causal:
        raise ValueError("a window is built for causal attention only")
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(q.shape[-1])
    # dense is gated on the SCORE MATRIX size, not the raw lengths: a
    # short query block over a long K/V (the decode-over-cache shape,
    # Tq=1) has a tiny (B, H, Tq, Tk) score tensor, and the blockwise
    # scan would be pure launch overhead for it
    if q.shape[1] * k.shape[1] <= _DENSE_MAX_T * _DENSE_MAX_T:
        h = q.shape[2]  # repeat_kv is the identity at equal head counts
        return attention_reference(
            q, repeat_kv(k, h), repeat_kv(v, h), causal=causal,
            sm_scale=scale, q_offset=q_offset, k_offset=k_offset, window=window,
        )
    if _flash_ok(q, k, v, q_offset, k_offset):
        return _splash_attention(q, k, v, causal=causal, scale=scale, window=window)
    return blockwise_attention(
        q, k, v, causal=causal, sm_scale=scale,
        q_offset=q_offset, k_offset=k_offset, window=window,
    )


@functools.lru_cache(maxsize=None)
def _gauge_sparse(t: int) -> None:
    """What the masked kernels' tiles run, once a shape, to the gauge
    ``attention.sparse.visited_pairs`` (a head's; OBSERVABILITY.md): every
    tile the kernels' own grid rule does not skip, whole. The pairs the mask
    keeps, ``attention.sparse.mask_pairs``, are written where the mask is
    made (``sparse_attention.indexer_mask``)."""
    from akka_allreduce_tpu.obs import metrics as obs_metrics
    from akka_allreduce_tpu.ops.sparse_attention import visited_pairs

    obs_metrics.gauge("attention.sparse.visited_pairs").set(visited_pairs(t))


def _masked_heads_first(q, k, v, mask):
    """:func:`heads_first_attention` under ``mask``: ``(out, lse)`` with
    ``lse`` float32 (B, H_kv, G, T). The repo's masked kernels
    (``ops/sparse_attention.py``) where they take the shape, engaged by the
    presence of a mask alone; else the portable core."""
    from akka_allreduce_tpu.ops._platform import interpret_default
    from akka_allreduce_tpu.ops.sparse_attention import sparse_attention, takes_sparse

    b, h, t, _ = q.shape
    h_kv = k.shape[1]
    if not interpret_default(q, k) and takes_sparse(t, q.shape[-1], v.shape[-1]):
        _gauge_sparse(t)
        return sparse_attention(q, k, v, mask.astype(jnp.int8), False)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    out, lse = blockwise_attention(
        swap(q), swap(k), swap(v), causal=True, sm_scale=1.0, mask=mask,
        with_lse=True, block_k=min(t, 512),
    )
    return swap(out), lse.reshape(b, h_kv, h // h_kv, t)


def heads_first_attention(
    q: jax.Array, k: jax.Array, v: jax.Array, *, causal: bool = False,
    window: int | None = None, mask: jax.Array | None = None,
):
    """:func:`local_attention` for a caller whose products already wrote the
    kernel's layout: ``q`` (B, H, T, D) WITH the score scale in it (folded
    into the weight that made it), ``k`` (B, H_kv, T, D), ``v`` (B, H_kv, T,
    Dv); returns (B, H, T, Dv). Where the kernel takes the shape nothing is
    transposed, scaled or copied on the way in or out; elsewhere the portable
    cores get the sequence-first views.

    ``mask`` (B, T, T), nonzero where a query sees a key (causal attention
    only: a key is seen iff the mask AND the causal rule say so; every query
    sees a key): attention under a mask that arrives as an array. The result
    is then ``(out, lse)``, the rows' float32 log-sum-exp (B, H_kv, G, T)
    beside the output."""
    if mask is not None:
        if not causal or window is not None:
            raise ValueError("an array mask is built for causal attention without a window")
        return _masked_heads_first(q, k, v, mask)
    if _flash_ok(q, k, v, 0, 0, seq_axis=2):
        return _splash_heads_first(q, k, v, causal=causal, window=window)
    swap = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    return swap(
        local_attention(
            swap(q), swap(k), swap(v), causal=causal, sm_scale=1.0, window=window
        )
    )

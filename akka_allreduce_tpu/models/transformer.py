"""Decoder-only Transformer LM with pluggable sequence/context parallelism.

A model family the reference does not have (its workloads stop at MLP and
ResNet-50 — SURVEY.md §2 L5); it exists here because long-context is
first-class in the TPU rebuild. Designed TPU-first:

- all heavy math is batched matmul (MXU-shaped), optional bfloat16 compute
  with fp32 params/logits;
- rotary position embeddings, so a sequence-sharded device needs only its
  integer global offset — no position-table gather crossing shards;
- attention dispatches on ``seq_axis``: ``None`` -> dense single-device;
  otherwise ring attention or Ulysses all-to-all over that mesh axis
  (ops/ring_attention.py), making the SAME module runnable under ``shard_map``
  with the sequence dimension sharded across the ICI ring. The shard count is
  read from the mesh itself (``lax.axis_size``), so the module cannot drift
  out of sync with the mesh it runs under.

When ``seq_axis`` is set the module must be applied inside ``shard_map`` with
that axis in scope; ``__call__`` then takes this device's (B, T_local) token
shard.
"""

from __future__ import annotations

import math

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from akka_allreduce_tpu.ops.ring_attention import (
    attention_reference,
    ring_attention,
    ulysses_attention,
)


def rope_angles(
    t: int,
    d: int,
    offset: jax.Array | int,
    *,
    base: float,
    yarn: tuple[float, int, float, float] | None = None,
    positions: jax.Array | None = None,
    sections: tuple[int, ...] | None = None,
):
    """Position x frequency, float32 (T, d/2), for positions offset + arange(T)
    and a rotary width ``d``: position precision is what long-context rope
    depends on, so the angles and the tables made of them are float32.

    ``positions`` (S, T) with ``sections`` (S column counts that sum to d/2)
    is multimodal RoPE: frequency ``i`` turns by the row of positions whose
    section holds it, the sections lying one after another (text has equal
    rows, and its angles are bit-equal to the one-row ones).

    ``yarn`` = ``(factor, original positions L, beta_fast, beta_slow)``
    blends the frequencies as YaRN does (Peng et al. 2023, section 3.2, as
    transformers computes it). ``c(n) = d ln(L / (2 pi n)) / (2 ln base)`` is
    the column that makes ``n`` turns over ``L`` positions: the columns up to
    ``floor(c(beta_fast))`` keep their frequency, those from
    ``ceil(c(beta_slow))`` on (both clipped to [0, d - 1]) have it divided by
    ``factor``, and a linear ramp lies between."""
    pos = offset + jnp.arange(t)
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    if yarn is not None:
        factor, original, beta_fast, beta_slow = yarn

        def column(turns: float) -> float:
            return d * math.log(original / (turns * 2 * math.pi)) / (2 * math.log(base))

        low = max(math.floor(column(beta_fast)), 0)
        high = min(math.ceil(column(beta_slow)), d - 1)
        if high == low:
            high += 0.001  # transformers' guard against a ramp of no width
        ramp = jnp.clip(
            (jnp.arange(d // 2, dtype=jnp.float32) - low) / (high - low), 0, 1
        )
        freqs = (freqs / factor) * ramp + freqs * (1 - ramp)
    if positions is not None:
        if positions.shape != (len(sections), t) or sum(sections) != d // 2:
            raise ValueError(
                f"positions {positions.shape} and sections {sections} for "
                f"{t} positions and {d // 2} frequencies"
            )
        ends = np.cumsum(sections)
        return jnp.concatenate(
            [
                row[:, None].astype(jnp.float32) * freqs[None, end - n: end]
                for row, n, end in zip(positions, sections, ends)
            ],
            axis=-1,
        )
    return pos[:, None].astype(jnp.float32) * freqs[None, :]


def rope(
    x: jax.Array,
    offset: jax.Array | int,
    *,
    base: float = 10000.0,
    rotary_dim: int | None = None,
    yarn: tuple[float, int, float, float] | None = None,
    attention_factor: float = 1.0,
):
    """Rotary embedding over the last (even) dim; positions = offset + arange(T).

    ``x``: (B, T, H, D). Pure elementwise after a cos/sin table build, so XLA
    fuses it into the surrounding projections.

    The ANGLES (position · frequency) and the trig tables are always
    computed in float32 — position precision is what long-context rope
    depends on — but the elementwise rotation runs in ``x``'s own dtype:
    under bf16 compute the (B, T, H, D) tensors would otherwise make four
    f32 round trips per projection, a measured ~2.8 ms/step of pure cast
    traffic at the MoE bench shape (BENCHMARKS.md round 4).

    With ``rotary_dim`` below D the first ``rotary_dim`` columns of each head
    rotate (in halves of that width) and the rest pass as they are. ``yarn``
    blends the frequencies (:func:`rope_angles`) and ``attention_factor``
    multiplies cos and sin alike in float32, so the rotated columns' part of a
    score carries its square.
    """
    d = x.shape[-1]
    if d % 2:
        raise ValueError(f"rope needs an even head dim, got {d}")
    if rotary_dim is not None and rotary_dim != d:
        if rotary_dim % 2 or not 0 < rotary_dim < d:
            raise ValueError(f"rotary width {rotary_dim} of a head of {d}")
        turned = rope(
            x[..., :rotary_dim], offset, base=base, yarn=yarn,
            attention_factor=attention_factor,
        )
        return jnp.concatenate((turned, x[..., rotary_dim:]), axis=-1)
    ang = rope_angles(x.shape[1], d, offset, base=base, yarn=yarn)

    def table(fn):
        y = fn(ang) if attention_factor == 1.0 else fn(ang) * attention_factor
        return y[None, :, None, :].astype(x.dtype)

    cos, sin = table(jnp.cos), table(jnp.sin)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1
    )


def rope_tables(
    t: int,
    d: int,
    offset: jax.Array | int,
    *,
    base: float = 10000.0,
    rotary_dim: int | None = None,
    yarn: tuple[float, int, float, float] | None = None,
    attention_factor: float = 1.0,
    scale: float = 1.0,
    positions: jax.Array | None = None,
    sections: tuple[int, ...] | None = None,
):
    """:func:`rope`'s rule as two float32 (T, d) tables ``cos``, ``sin`` over
    the WHOLE head, as :func:`rope_heads_first` takes it: ``scale * rope(x) = x * cos
    + partner(x) * sin``. In the ``rotary_dim`` columns that rotate, ``cos``
    and ``sin`` of :func:`rope_angles` times ``attention_factor * scale``, the
    sine negative in their first half (``x1 cos - x2 sin``); in the columns
    that pass, ``cos = scale`` and ``sin = 0``. ``scale`` is where a caller
    puts the score scale of q: in float32, before the table's one cast.
    ``positions`` / ``sections`` as :func:`rope_angles` takes them."""
    r = d if rotary_dim is None else rotary_dim
    if r % 2 or not 0 < r <= d:
        raise ValueError(f"rotary width {r} of a head of {d}")
    ang = rope_angles(
        t, r, offset, base=base, yarn=yarn, positions=positions, sections=sections
    )
    cos, sin = (fn(ang) * (attention_factor * scale) for fn in (jnp.cos, jnp.sin))
    return (
        jnp.concatenate((cos, cos, jnp.full((t, d - r), scale, jnp.float32)), axis=-1),
        jnp.concatenate((-sin, sin, jnp.zeros((t, d - r), jnp.float32)), axis=-1),
    )


def rope_heads_first(
    x: jax.Array,
    offset: jax.Array | int,
    *,
    base: float = 10000.0,
    rotary_dim: int | None = None,
    yarn: tuple[float, int, float, float] | None = None,
    attention_factor: float = 1.0,
    scale: float = 1.0,
    positions: jax.Array | None = None,
    sections: tuple[int, ...] | None = None,
) -> jax.Array:
    """``scale *`` :func:`rope` on ``x`` (B, H, T, D), the layout the
    attention kernel reads: ``x * cos + partner(x) * sin`` in ``x``'s dtype
    under :func:`rope_tables`' tables. With ``h`` half the rotary width,
    column ``j``'s partner is ``j + h`` in the first ``h`` columns and ``j -
    h`` in the next; past them ``sin`` is 0.

    The partners are brought over by a product with the (D, D) permutation,
    not by slices: one term a column, so exact, and the rest is that
    product's elementwise epilogue - ONE read and one write of ``x`` each
    way whatever the rule, where the halves of a 128-lane head sliced apart
    and put together again are passes of their own on the chip (CHANGES.md,
    PR 36)."""
    t, d = x.shape[-2:]
    r = d if rotary_dim is None else rotary_dim
    cos, sin = rope_tables(
        t, d, offset, base=base, rotary_dim=r, yarn=yarn,
        attention_factor=attention_factor, scale=scale,
        positions=positions, sections=sections,
    )
    j = np.arange(d)
    swap = np.zeros((d, d), np.float32)
    swap[np.where(j < r // 2, j + r // 2, np.where(j < r, j - r // 2, j)), j] = 1.0
    partner = jnp.einsum(
        "...d,de->...e", x, jnp.asarray(swap, x.dtype), precision=lax.Precision.HIGHEST
    )
    return x * cos.astype(x.dtype) + partner * sin.astype(x.dtype)


class Attention(nn.Module):
    """Causal multi-head self-attention with RoPE, SP and TP dispatch.

    Tensor parallelism (``model_axis``/``tp_size``): each shard projects and
    attends ``n_heads / tp_size`` heads (the kernels' head dims are the
    sharded dims), the out-projection produces a partial sum, and ONE psum
    over ``model_axis`` completes it — Megatron-style column/row split, with
    the output bias added AFTER the psum so it is applied exactly once.

    Grouped-query attention (``n_kv_heads`` < ``n_heads``; 1 = MQA): K/V
    project to ``n_kv_heads`` heads and stay COMPACT until the compute
    site — under ring attention the ppermute wire bytes shrink by
    H/H_kv, under Ulysses the K/V all_to_all does (ops/ring_attention.py).

    Autoregressive decoding (``decode=True``): a "cache" variable
    collection holds the K/V written so far — shaped
    (B, max_decode_len, H_kv, D), so GQA shrinks the cache (its main
    inference win) — and each call appends its chunk at the running
    ``cache_index`` and attends over the whole cache causally. Init the
    cache with ``model.init`` on any-length tokens; apply with
    ``mutable=["cache"]``. Composes with tensor parallelism (each model
    shard caches its kv_local heads — run inside shard_map over the
    ``model`` axis) AND with sequence sharding (``seq_axis`` set while
    decoding: each seq shard owns a contiguous ``max_decode_len / n``
    slice of the cache SLOTS, writes scatter to the owning shard, and
    attention merges the shards' partial softmaxes split-K style over the
    axis — ``ops.local_attention.seq_decode_attention``; VERDICT r4 #5).

    ``cache_quant="int8"`` stores the cache quantized per (token, head)
    row — int8 payload + one f32 scale per row, ~4× fewer cache bytes
    than f32 (2× vs bf16) at ~0.4 % per-element quantization error — the
    inference twin of the training wire's int8 ring compression.
    """

    n_heads: int
    n_kv_heads: int | None = None  # None = n_heads (standard MHA)
    seq_axis: str | None = None
    seq_impl: str = "ring"  # "ring" | "ulysses"
    compute_dtype: jnp.dtype = jnp.float32
    model_axis: str | None = None
    tp_size: int = 1
    decode: bool = False  # KV-cache autoregressive mode
    max_decode_len: int = 0  # cache capacity (decode=True only)
    cache_quant: str | None = None  # None = compute dtype; "int8" quantized

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        if d_model % self.n_heads:
            raise ValueError(f"{d_model=} not divisible by {self.n_heads=}")
        if self.n_heads % self.tp_size:
            raise ValueError(f"{self.n_heads=} not divisible by {self.tp_size=}")
        kv_heads = (
            self.n_heads if self.n_kv_heads is None else self.n_kv_heads
        )
        if kv_heads < 1:
            raise ValueError(f"n_kv_heads must be >= 1, got {kv_heads}")
        if self.n_heads % kv_heads:
            raise ValueError(
                f"{self.n_heads=} not divisible by n_kv_heads={kv_heads}"
            )
        if kv_heads % self.tp_size:
            raise ValueError(
                f"n_kv_heads={kv_heads} not divisible by {self.tp_size=}"
            )
        if self.decode and self.max_decode_len < 1:
            raise ValueError("decode=True needs max_decode_len >= 1")
        head = d_model // self.n_heads
        heads_local = self.n_heads // self.tp_size
        kv_local = kv_heads // self.tp_size
        dense = lambda name, hh: nn.DenseGeneral(  # noqa: E731
            (hh, head),
            dtype=self.compute_dtype,
            name=name,
        )
        q = dense("q", heads_local)(x)
        k = dense("k", kv_local)(x)
        v = dense("v", kv_local)(x)

        if self.decode:
            if self.cache_quant not in (None, "int8"):
                raise ValueError(
                    f"cache_quant must be None or 'int8', got "
                    f"{self.cache_quant!r}"
                )
            quant = self.cache_quant == "int8"
            b, t = x.shape[0], x.shape[1]
            if self.seq_axis is not None:
                # SEQUENCE-SHARDED cache (VERDICT r4 #5): each shard of
                # the seq axis owns a contiguous L/n_sh slice of the cache
                # slots; decode attention merges the shards' partial
                # softmaxes split-K style (seq_decode_attention). Composes
                # with TP (heads shard on model, slots on seq).
                n_sh = lax.axis_size(self.seq_axis)
                if self.max_decode_len % n_sh:
                    raise ValueError(
                        f"max_decode_len={self.max_decode_len} not "
                        f"divisible by the {n_sh}-shard seq axis"
                    )
                l_local = self.max_decode_len // n_sh
                k_off = lax.axis_index(self.seq_axis) * l_local
            else:
                l_local = self.max_decode_len
                k_off = 0
            kv_shape = (b, l_local, kv_local, head)
            cache_dt = jnp.int8 if quant else k.dtype
            ck = self.variable("cache", "cached_k", jnp.zeros, kv_shape, cache_dt)
            cv = self.variable("cache", "cached_v", jnp.zeros, kv_shape, cache_dt)
            if quant:
                cks = self.variable(
                    "cache", "k_scale", jnp.zeros, kv_shape[:3], jnp.float32
                )
                cvs = self.variable(
                    "cache", "v_scale", jnp.zeros, kv_shape[:3], jnp.float32
                )
            ci = self.variable(
                "cache", "cache_index", lambda: jnp.zeros((), jnp.int32)
            )
            offset = ci.value  # global position of this chunk's first token
        elif self.seq_axis is None:
            offset = 0
        else:
            offset = lax.axis_index(self.seq_axis) * x.shape[1]
        q, k = rope(q, offset), rope(k, offset)

        if self.decode:
            from akka_allreduce_tpu.ops.local_attention import (
                _DENSE_MAX_T,
                local_attention,
                quantized_cache_attention,
                seq_decode_attention,
            )

            # append this chunk's K/V at the running index; slots past
            # offset + t hold zeros and are causally invisible (their
            # k_pos exceeds every live q_pos)
            if self.seq_axis is not None:
                # scatter each token to the shard that owns its slot:
                # indices outside this shard's [k_off, k_off + l_local)
                # range are clamped to l_local and DROPPED by the scatter
                pos = offset + jnp.arange(t) - k_off
                idx = jnp.where((pos >= 0) & (pos < l_local), pos, l_local)

                def write(cache, chunk):
                    cache.value = cache.value.at[:, idx].set(
                        chunk, mode="drop"
                    )

            else:

                def write(cache, chunk):
                    cache.value = lax.dynamic_update_slice(
                        cache.value,
                        chunk,
                        (0, offset) + (0,) * (chunk.ndim - 2),
                    )

            if quant:
                def quantize(x_):
                    # per (token, head) row: one f32 scale over the D dim
                    s = jnp.max(jnp.abs(x_), axis=-1) / 127.0
                    s = jnp.maximum(s, 1e-8).astype(jnp.float32)
                    q_ = jnp.clip(
                        jnp.round(x_ / s[..., None].astype(x_.dtype)),
                        -127, 127,
                    ).astype(jnp.int8)
                    return q_, s

                kq, ks = quantize(k)
                vq, vs = quantize(v)
                write(ck, kq), write(cv, vq)
                write(cks, ks), write(cvs, vs)
                ci.value = offset + t
                # decode (small Tq over the long cache): attend directly
                # over the int8 payloads — the scales fold into the
                # scores/weights, so no dequantized full-precision copy of
                # the cache is ever materialized (the bandwidth the
                # quantization was bought for). Prefill (large Tq) would
                # make the dense (B,H,Tq,L) f32 scores the memory hog
                # instead; there, dequantize once and take
                # local_attention's blockwise/flash dispatch. Gate on the
                # per-key byte costs of the two branches (both scale with
                # L, so L cancels): fused scores cost 4·H·Tq bytes/key,
                # dequant costs itemsize·2·H_kv·D bytes/key (K and V) —
                # Tq=1 over any cache length stays fused.
                score_b = 4 * heads_local * t
                dequant_b = 2 * kv_local * head * k.dtype.itemsize
                # t == 1 is unconditional: the dequant branch would also
                # WRITE and re-read the full-precision copy (its per-key
                # cost is ~3x dequant_b in practice), so token-by-token
                # decode must never take it even at extreme GQA ratios
                # where the byte model above tips the other way
                if self.seq_axis is not None:
                    # sharded cache: local partial over this shard's slots
                    # (scales fold in, like quantized_cache_attention),
                    # split-K merge over the seq axis; under TP the inputs
                    # are ALSO model-varying — the blockwise carry must
                    # carry that typing
                    out = seq_decode_attention(
                        q, ck.value, cv.value, self.seq_axis,
                        q_offset=offset, k_offset=k_off,
                        k_scale=cks.value, v_scale=cvs.value,
                        extra_vary_axes=(
                            (self.model_axis,) if self.model_axis else ()
                        ),
                    )
                elif (
                    t == 1
                    or score_b <= dequant_b
                    or t * self.max_decode_len <= _DENSE_MAX_T * _DENSE_MAX_T
                ):
                    out = quantized_cache_attention(
                        q, ck.value, cks.value, cv.value, cvs.value,
                        q_offset=offset,
                    )
                else:
                    dq = lambda c, s: (  # noqa: E731
                        c.value.astype(k.dtype)
                        * s.value[..., None].astype(k.dtype)
                    )
                    out = local_attention(
                        q, dq(ck, cks), dq(cv, cvs),
                        causal=True, q_offset=offset,
                    )
            else:
                write(ck, k), write(cv, v)
                ci.value = offset + t
                if self.seq_axis is not None:
                    out = seq_decode_attention(
                        q, ck.value, cv.value, self.seq_axis,
                        q_offset=offset, k_offset=k_off,
                        extra_vary_axes=(
                            (self.model_axis,) if self.model_axis else ()
                        ),
                    )
                else:
                    out = local_attention(
                        q, ck.value, cv.value, causal=True, q_offset=offset,
                    )
        elif self.seq_axis is None:
            # dense single-device form: dispatch to the best local core
            # (flash kernel on TPU, blockwise off-chip for long T)
            from akka_allreduce_tpu.ops.local_attention import local_attention

            out = local_attention(q, k, v, causal=True)
        elif self.seq_impl == "ring":
            out = ring_attention(q, k, v, self.seq_axis, causal=True)
        elif self.seq_impl == "ulysses":
            out = ulysses_attention(q, k, v, self.seq_axis, causal=True)
        else:
            raise ValueError(f"unknown seq_impl {self.seq_impl!r}")
        y = nn.DenseGeneral(
            d_model,
            axis=(-2, -1),
            dtype=self.compute_dtype,
            name="out",
            use_bias=False,  # partial sum under TP; bias goes after the psum
        )(out)
        if self.model_axis is not None:
            y = lax.psum(y, self.model_axis)
        bias = self.param("out_bias", nn.initializers.zeros, (d_model,))
        return y + bias.astype(y.dtype)


class Block(nn.Module):
    n_heads: int
    n_kv_heads: int | None = None
    mlp_ratio: int = 4
    seq_axis: str | None = None
    seq_impl: str = "ring"
    compute_dtype: jnp.dtype = jnp.float32
    model_axis: str | None = None
    tp_size: int = 1
    decode: bool = False
    max_decode_len: int = 0
    cache_quant: str | None = None

    @nn.compact
    def __call__(self, x):
        d_model = x.shape[-1]
        hidden = self.mlp_ratio * d_model
        if hidden % self.tp_size:
            raise ValueError(
                f"mlp hidden {hidden} not divisible by {self.tp_size=}"
            )
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        x = x + Attention(
            self.n_heads,
            n_kv_heads=self.n_kv_heads,
            seq_axis=self.seq_axis,
            seq_impl=self.seq_impl,
            compute_dtype=self.compute_dtype,
            model_axis=self.model_axis,
            tp_size=self.tp_size,
            decode=self.decode,
            max_decode_len=self.max_decode_len,
            cache_quant=self.cache_quant,
        )(h)
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        # TP: hidden dim column-split on the up projection, row-split on the
        # down projection; one psum completes the partial products, and the
        # down bias lands after it (applied once)
        h = nn.Dense(
            hidden // self.tp_size, dtype=self.compute_dtype, name="mlp_up"
        )(h)
        h = nn.gelu(h)
        y = nn.Dense(
            d_model, dtype=self.compute_dtype, name="mlp_down", use_bias=False
        )(h)
        if self.model_axis is not None:
            y = lax.psum(y, self.model_axis)
        bias = self.param("mlp_bias", nn.initializers.zeros, (d_model,))
        return x + y + bias.astype(y.dtype)


class TransformerLM(nn.Module):
    """Tokens (B, T_local) int32 -> logits (B, T_local, vocab) fp32."""

    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int | None = None  # GQA: fewer K/V heads (1 = MQA)
    n_layers: int = 2
    mlp_ratio: int = 4
    seq_axis: str | None = None
    seq_impl: str = "ring"
    compute_dtype: jnp.dtype = jnp.float32
    model_axis: str | None = None  # tensor-parallel mesh axis (None = no TP)
    tp_size: int = 1  # shards per TP group; kernels declare LOCAL head/hidden
    # rematerialize each block on the backward pass (jax.checkpoint): trades
    # one extra forward of FLOPs for O(layers) activation memory — the knob
    # that lets long sequences fit in HBM
    remat: bool = False
    decode: bool = False  # KV-cache autoregressive mode (models/generate.py)
    max_decode_len: int = 0
    cache_quant: str | None = None  # "int8" = quantized KV cache

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.d_model, dtype=self.compute_dtype)(tokens)
        block_cls = nn.remat(Block) if self.remat else Block
        for i in range(self.n_layers):
            # explicit names: nn.remat would otherwise rename the scope to
            # CheckpointBlock_i, forking the param tree from the non-remat
            # (and init-twin) layout — remat must change memory, not params
            x = block_cls(
                self.n_heads,
                n_kv_heads=self.n_kv_heads,
                mlp_ratio=self.mlp_ratio,
                seq_axis=self.seq_axis,
                seq_impl=self.seq_impl,
                compute_dtype=self.compute_dtype,
                model_axis=self.model_axis,
                tp_size=self.tp_size,
                decode=self.decode,
                max_decode_len=self.max_decode_len,
                cache_quant=self.cache_quant,
                name=f"Block_{i}",
            )(x)
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        logits = nn.Dense(self.vocab, dtype=self.compute_dtype)(x)
        return logits.astype(jnp.float32)


class MoEBlock(nn.Module):
    """Transformer block whose MLP is a Switch-routed mixture of experts.

    With ``expert_axis``/``ep_size`` set, each device owns
    ``n_experts / ep_size`` experts (the w1/b1/w2 leading dims are the
    sharded dims — see :func:`ep_param_specs`) and tokens reach their expert
    through the all_to_all pair in ``ops.moe``. The router is replicated:
    every device routes its own tokens over the FULL expert set.
    Returns ``(x, aux, dropped)`` — the Switch load-balancing loss and the
    fraction of tokens dropped past capacity ride alongside (the drop
    fraction is the signal for tuning ``capacity_factor``).
    """

    n_heads: int
    n_kv_heads: int | None = None
    n_experts: int = 4
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    compute_dtype: jnp.dtype = jnp.float32
    expert_axis: str | None = None
    ep_size: int = 1
    router_topk: int = 1  # 1 = Switch, 2 = GShard top-2
    seq_axis: str | None = None  # sequence-parallel axis (ring/Ulysses attn)
    seq_impl: str = "ring"
    dispatch_impl: str = "auto"  # "einsum" | "scatter" | "auto" (ops.moe)

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.ops.moe import moe_dispatch_compute

        d_model = x.shape[-1]
        hidden = self.mlp_ratio * d_model
        if self.n_experts % self.ep_size:
            raise ValueError(
                f"{self.n_experts=} not divisible by {self.ep_size=}"
            )
        e_local = self.n_experts // self.ep_size
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        x = x + Attention(
            self.n_heads,
            n_kv_heads=self.n_kv_heads,
            seq_axis=self.seq_axis,
            seq_impl=self.seq_impl,
            compute_dtype=self.compute_dtype,
        )(h)
        h = nn.LayerNorm(dtype=self.compute_dtype)(x)
        router = self.param(
            "router", nn.initializers.lecun_normal(), (d_model, self.n_experts)
        )
        w1 = self.param(
            "moe_w1", nn.initializers.lecun_normal(), (e_local, d_model, hidden)
        )
        b1 = self.param("moe_b1", nn.initializers.zeros, (e_local, hidden))
        w2 = self.param(
            "moe_w2", nn.initializers.lecun_normal(), (e_local, hidden, d_model)
        )
        flat = h.reshape(-1, d_model)
        y, aux, dropped = moe_dispatch_compute(
            flat,
            router,
            w1,
            b1,
            w2,
            n_experts=self.n_experts,
            capacity_factor=self.capacity_factor,
            expert_axis=self.expert_axis if self.ep_size > 1 else None,
            router_topk=self.router_topk,
            seq_axis=self.seq_axis,
            dispatch_impl=self.dispatch_impl,
        )
        return x + y.reshape(x.shape), aux, dropped


class MoETransformerLM(nn.Module):
    """Decoder-only LM with Switch-MoE MLPs:
    tokens -> (logits, aux_loss, dropped_fraction)."""

    vocab: int = 256
    d_model: int = 128
    n_heads: int = 4
    n_kv_heads: int | None = None
    n_layers: int = 2
    n_experts: int = 4
    mlp_ratio: int = 4
    capacity_factor: float = 1.25
    compute_dtype: jnp.dtype = jnp.float32
    expert_axis: str | None = None
    ep_size: int = 1
    router_topk: int = 1  # 1 = Switch, 2 = GShard top-2
    seq_axis: str | None = None  # sequence-parallel axis (ring/Ulysses attn)
    seq_impl: str = "ring"
    dispatch_impl: str = "auto"  # "einsum" | "scatter" | "auto" (ops.moe)

    @nn.compact
    def __call__(self, tokens):
        x = nn.Embed(self.vocab, self.d_model, dtype=self.compute_dtype)(tokens)
        aux_total = jnp.float32(0.0)
        dropped_total = jnp.float32(0.0)
        for _ in range(self.n_layers):
            x, aux, dropped = MoEBlock(
                self.n_heads,
                n_kv_heads=self.n_kv_heads,
                n_experts=self.n_experts,
                mlp_ratio=self.mlp_ratio,
                capacity_factor=self.capacity_factor,
                compute_dtype=self.compute_dtype,
                expert_axis=self.expert_axis,
                ep_size=self.ep_size,
                router_topk=self.router_topk,
                seq_axis=self.seq_axis,
                seq_impl=self.seq_impl,
                dispatch_impl=self.dispatch_impl,
            )(x)
            aux_total = aux_total + aux
            dropped_total = dropped_total + dropped
        x = nn.LayerNorm(dtype=self.compute_dtype)(x)
        logits = nn.Dense(self.vocab, dtype=self.compute_dtype)(x)
        return (
            logits.astype(jnp.float32),
            aux_total / self.n_layers,
            dropped_total / self.n_layers,
        )


def ep_param_specs(tree, expert_axis: str):
    """PartitionSpec pytree for expert parallelism: the moe_w1/b1/w2 leaves
    shard their leading (expert) dim over ``expert_axis``; the router and
    everything else replicate. Same path-rule mechanism as
    :func:`tp_param_specs`, so it also shards optax moment trees."""
    import jax

    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        joined = "/".join(str(n) for n in names)
        if joined.endswith("moe_w1") or joined.endswith("moe_w2"):
            return P(expert_axis, None, None)
        if joined.endswith("moe_b1"):
            return P(expert_axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, tree)


def tp_param_specs(tree, model_axis: str):
    """PartitionSpec pytree for Megatron-style TP over ``model_axis``.

    Matches the layout the modules above declare: q/k/v kernels and biases
    shard on the HEAD dim, the out-projection kernel on its head input dim,
    the MLP up projection on the hidden (output) dim and the down projection
    on the hidden (input) dim. Everything else — embeddings, norms, the
    post-psum biases, the LM head — replicates. Apply to FULL-shape params
    (``tp_size=1`` geometry); ``shard_map`` in_specs then deliver each shard
    its local slice, matching the ``tp_size>1`` module's declared shapes.

    Works on any tree whose leaf PATHS embed the param names — the params
    themselves, or an optax state (adam's mu/nu mirror the param tree, so
    the same path rules shard the optimizer moments identically; scalars
    like adam's step count match no rule and replicate).
    """
    import jax

    from jax.sharding import PartitionSpec as P

    def spec(path, leaf):
        names = [getattr(k, "key", getattr(k, "name", str(k))) for k in path]
        joined = "/".join(str(n) for n in names)
        if "/q/" in joined or "/k/" in joined or "/v/" in joined:
            if joined.endswith("kernel"):
                return P(None, model_axis, None)
            return P(model_axis, None)  # bias (heads, head_dim)
        if joined.endswith("out/kernel"):
            return P(model_axis, None, None)
        if joined.endswith("mlp_up/kernel"):
            return P(None, model_axis)
        if joined.endswith("mlp_up/bias"):
            return P(model_axis)
        if joined.endswith("mlp_down/kernel"):
            return P(model_axis, None)
        return P()

    return jax.tree_util.tree_map_with_path(spec, tree)

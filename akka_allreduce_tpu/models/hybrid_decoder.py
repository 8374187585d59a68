"""A decoder built from a configuration: conv/attention hybrids with dense
and expert feed-forward layers (the ``lfm2_moe`` family), latent-attention
decoders with a shared expert and a multi-token-prediction module (the
DeepSeek-V3 dialect, ``joyai_llm_flash``), and decoders that mix windowed and
full attention by the layer's kind, at one head count or at one per layer
(the ``laguna`` dialect, which ``mellum`` writes too), and decoders whose
attention runs under a mask a learned indexer makes from the data (the
Qwen3-MoE key set with ``sa_config``, ``KeyeVL2``'s language model), and
decoders whose layers are linear attention with a matrix state (the gated
delta rule) three to one with gated full attention (``qwen3_next``).

Layer ``i``:  ``h = x + Op_i(RMSNorm(x))``,  ``y = h + FF_i(RMSNorm(h))``.
``Op_i`` is a gated short convolution where ``layer_types[i] == "conv"``,
grouped-query attention (:class:`GroupedQueryAttention`: rotate-half RoPE,
per-head RMSNorm on q and k or none, a per-head output gate or none) where
``"full_attention"``, the same under a causal window where
``"sliding_attention"``, latent attention (:class:`LatentAttention`) where
``"latent_attention"``, and a Gated DeltaNet mixer (:class:`GatedDeltaNet`)
where ``"linear_attention"``; ``FF_i`` is a gated SiLU MLP for the first
``num_dense_layers`` layers and a dropless expert layer after them, routed
by sigmoid or softmax scores, with a shared expert beside the routed ones
where ``shared_width`` says so (under its own sigmoid gate where
``shared_gate``). A final RMSNorm, then an untied head. No
bias anywhere.

With ``mtp_depth == 1`` one more layer predicts the token after the next
(DeepSeek-V3's report, section 2.2): ``h' = [RMSNorm_e(Emb(t_{i+1})) |
RMSNorm_h(h_i)] W_eh`` with ``h_i`` the last layer's output before the final
norm, a latent-attention + expert layer over ``h'``, its own final RMSNorm,
the SAME embedding and head. The model then takes the next tokens beside the
tokens and returns those logits last; the trainer weighs their loss.

The expert layers are told which contiguous range of the experts this device
holds (``held_first``, ``held_count``): they route over all ``num_experts``
and compute the held experts' part of the result (``ops.moe``). The router's
selection bias lives in the ``"fixed"`` collection: it picks but does not
weigh, so it has no gradient and no optimizer moves it.

``TransformerLM`` / ``MoETransformerLM`` (LayerNorm, GELU, capacity routing)
are a separate path; this module is imported where it is used, not from the
package's ``__init__``.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


class RotaryRule(NamedTuple):
    """How one kind of attention layer rotates q and k, as
    :func:`models.transformer.rope` takes it."""

    kind: str  # the ``layer_types`` entry it serves
    theta: float
    rotary_dim: int  # columns of each head that rotate
    yarn: tuple[float, int, float, float] | None
    attention_factor: float


class IndexerRule(NamedTuple):
    """The learned indexer of sparse attention (``sa_config``): ``heads``
    index heads of ``head_dim`` on ONE key head of that width, and the
    ``topk`` keys a query keeps."""

    heads: int
    head_dim: int
    topk: int


class LinearAttentionRule(NamedTuple):
    """The sizes of a Gated DeltaNet mixer: ``key_heads`` heads of
    ``key_dim`` for q and k, each serving ``value_heads / key_heads``
    consecutive value heads of ``value_dim``, behind a causal depthwise
    convolution of ``conv_taps`` taps."""

    key_heads: int
    value_heads: int
    key_dim: int
    value_dim: int
    conv_taps: int


def _dense(features: int, dtype, name: str) -> nn.Dense:
    return nn.Dense(features, use_bias=False, dtype=dtype, name=name)


class ShortConv(nn.Module):
    d_model: int
    taps: int
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.ops.short_conv import gated_short_conv

        with jax.named_scope("short_conv"):
            bcz = _dense(3 * self.d_model, self.compute_dtype, "in_proj")(x)
            w = self.param(
                "conv", nn.initializers.normal(0.02), (self.d_model, self.taps)
            )
            y = gated_short_conv(bcz, w)
            return _dense(self.d_model, self.compute_dtype, "out_proj")(y)


class _HeadsIn(nn.Module):
    """``nn.Dense`` to ``heads`` heads of ``head_dim``, written heads-first:
    the kernel ``(features, heads x head_dim)`` of a ``Dense``, applied to (B,
    T, features) as ``btd,dhk->bhtk``, so the product itself writes the
    layout the attention kernel reads. With ``head_dim`` None one number a
    head, (B, H, T)."""

    heads: int
    head_dim: int | None
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(),
            (x.shape[-1], self.heads * (self.head_dim or 1)),
        )
        x, kernel = x.astype(self.dtype), kernel.astype(self.dtype)
        if self.head_dim is None:
            return jnp.einsum("btd,dh->bht", x, kernel)
        return jnp.einsum(
            "btd,dhk->bhtk", x, kernel.reshape(-1, self.heads, self.head_dim)
        )


class _HeadsOut(nn.Module):
    """``nn.Dense`` over the heads' values, taken heads-first: the kernel
    ``(H v, features)`` of a ``Dense`` on (B, T, H v), applied to (B, H, T,
    v) as ``bhtv,hvd->btd`` so that no transposed copy of the attention's
    output is asked for."""

    features: int
    dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        _, h, _, v = x.shape
        kernel = self.param(
            "kernel", nn.initializers.lecun_normal(), (h * v, self.features)
        )
        return jnp.einsum(
            "bhtv,hvd->btd", x.astype(self.dtype),
            kernel.reshape(h, v, self.features).astype(self.dtype),
        )


class GroupedQueryAttention(nn.Module):
    """``n_heads`` queries on ``n_kv_heads`` keys and values, rotate-half
    RoPE, causal. As ``lfm2_moe`` has it: per-head RMSNorm on q and k, one
    ``rope_theta`` over the whole head. The further fields are the ``laguna``
    dialect's: no such norm; ``gated`` true, a per-head ``sigmoid(x W_g)`` (in
    float32, read from the layer's normed input) on the kernel's output
    before ``W_o``, and ``gated`` ``"element"`` (``qwen3_next``) one gate an
    element of that output, read from a ``W_q`` of twice the width: a head's
    ``head_dim`` query columns, then its ``head_dim`` gate columns; ``window``, query ``i`` sees keys ``i - window + 1 .. i``;
    ``rotary_dim`` / ``yarn`` / ``attention_factor`` as
    :func:`models.transformer.rope` takes them; ``scope_name``, the named scope
    around the layer.

    Between the layer's input and ``W_o``'s output every activation is
    heads-first, (B, H, T, D), the layout the attention kernel reads and
    writes, and each is written once: by its product (:class:`_HeadsIn`), by
    the one pass that norms and turns it (:func:`models.transformer.
    rope_heads_first`; the score scale is in q's float32 table, so q is never
    scaled in its own dtype), by the kernel, by the gate; ``W_o`` reads it
    heads-first (:class:`_HeadsOut`).

    ``mrope_sections`` with ``positions`` (3, T) in the call is multimodal
    RoPE (:func:`models.transformer.rope_angles`); without positions, text:
    the rows are equal and the one-row tables are the same tables.

    ``indexer`` (an :class:`IndexerRule`; off for every configuration without
    ``sa_config``) is DeepSeek-V3.2-Exp's sparse attention: on the layer's
    input with its gradient stopped, ``q_I = x W_qI`` (heads, head_dim),
    ``k_I = LayerNorm(x W_kI)`` one key head, ``w = x W_w * heads^-0.5 *
    head_dim^-0.5``, rotate-half RoPE over the whole index head by the
    temporal row; ``I[t, s] = sum_j w[t, j] relu(q_I[t, j] . k_I[s])``; query
    t keeps the ``min(t + 1, topk)`` keys ``s <= t`` of largest ``I`` (ties:
    the lower s) and attends to those alone (``ops/sparse_attention.py``).
    The call then returns ``(out, kl, pairs)``: the indexer's loss ``mean_t
    KL(pbar_t || softmax over the kept keys of I[t, .])`` against the head
    mean ``pbar`` of this layer's own attention probabilities, whose gradient
    reaches the indexer's leaves alone as the cross-entropy's reaches none of
    them (a hard top-k passes none), and the pairs the mask keeps. Scopes:
    ``attn_indexer`` beside ``attn_core``, in it ``indexer_proj``,
    ``indexer_scores``, ``indexer_select``, ``indexer_target``."""

    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    compute_dtype: jnp.dtype
    qk_norm: bool = True
    gated: bool | str = False  # no gate | one a head | "element": one an element
    window: int | None = None
    rotary_dim: int | None = None
    yarn: tuple[float, int, float, float] | None = None
    attention_factor: float = 1.0
    scope_name: str = "attention"
    mrope_sections: tuple[int, ...] | None = None
    indexer: IndexerRule | None = None

    def _index_operands(self, x, positions):
        """``(q_I (B, J, T, D), k_I (B, T, D), w (B, T, J))`` from the layer's
        normed input, through which no gradient passes back."""
        from akka_allreduce_tpu.models.transformer import rope_heads_first

        rule, dt = self.indexer, self.compute_dtype
        x = lax.stop_gradient(x)
        q_i = _HeadsIn(rule.heads, rule.head_dim, dt, name="index_q")(x)
        k_i = nn.LayerNorm(epsilon=self.norm_eps, dtype=dt, name="index_k_norm")(
            _dense(rule.head_dim, dt, "index_k")(x)
        )
        w = _dense(rule.heads, dt, "index_w")(x).astype(jnp.float32) * (
            rule.heads ** -0.5 * rule.head_dim ** -0.5
        )
        by_time = {} if positions is None else {  # the temporal row, the whole head
            "positions": positions[:1], "sections": (rule.head_dim // 2,)
        }
        turn = functools.partial(rope_heads_first, offset=0, base=self.rope_theta, **by_time)
        return turn(q_i), turn(k_i[:, None])[:, 0], w

    @nn.compact
    def __call__(self, x, positions=None):
        from akka_allreduce_tpu.models.transformer import rope_heads_first
        from akka_allreduce_tpu.ops.local_attention import heads_first_attention

        d = x.shape[-1]
        dt, hd = self.compute_dtype, self.head_dim
        mrope = {} if positions is None else {
            "positions": positions, "sections": self.mrope_sections
        }

        def turned(name: str, y, scale: float):
            if self.qk_norm:
                y = nn.RMSNorm(epsilon=self.norm_eps, dtype=dt, name=name)(y)
            return rope_heads_first(
                y, 0, base=self.rope_theta, rotary_dim=self.rotary_dim,
                yarn=self.yarn, attention_factor=self.attention_factor, scale=scale,
                **mrope,
            )

        if self.gated not in (False, True, "element"):
            raise ValueError(f"gated = {self.gated!r}: False, True (a head) or 'element'")
        by_element = self.gated == "element"
        with jax.named_scope(self.scope_name):
            with jax.named_scope("attn_qkv"):
                q = _HeadsIn(self.n_heads, hd * (1 + by_element), dt, name="q")(x)
                k = _HeadsIn(self.n_kv_heads, hd, dt, name="k")(x)
                v = _HeadsIn(self.n_kv_heads, hd, dt, name="v")(x)
                if by_element:
                    q, gate = q[..., :hd], q[..., hd:]
                elif self.gated:
                    gate = _HeadsIn(self.n_heads, None, dt, name="gate")(x)
            if self.indexer is not None:
                return self._under_learned_mask(x, q, k, v, turned, positions)
            with jax.named_scope("attn_core"):
                q, k = turned("q_norm", q, hd ** -0.5), turned("k_norm", k, 1.0)
                out = heads_first_attention(q, k, v, causal=True, window=self.window)
                if self.gated:
                    gate = jax.nn.sigmoid(gate.astype(jnp.float32)).astype(dt)
                    out = out * (gate if by_element else gate[..., None])
            with jax.named_scope("attn_out"):
                return _HeadsOut(d, dt, name="out")(out)

    def _under_learned_mask(self, x, q, k, v, turned, positions):
        """The rest of a layer whose mask the indexer makes (inside the
        layer's scope): ``(out, kl, pairs)``."""
        from akka_allreduce_tpu.ops.local_attention import heads_first_attention
        from akka_allreduce_tpu.ops.sparse_attention import indexer_kl, indexer_mask

        if self.gated or self.window is not None:
            raise ValueError("the indexer is built for ungated causal attention")
        rule, dt = self.indexer, self.compute_dtype
        samples = range(x.shape[0])
        with jax.named_scope("attn_indexer"):
            with jax.named_scope("indexer_proj"):
                q_i, k_i, w = self._index_operands(x, positions)
            mask = jnp.stack(
                [indexer_mask(q_i[b], k_i[b], w[b], rule.topk) for b in samples]
            )
        with jax.named_scope("attn_core"):
            q = turned("q_norm", q, self.head_dim ** -0.5)
            k = turned("k_norm", k, 1.0)
            out, lse = heads_first_attention(q, k, v, causal=True, mask=mask)
        with jax.named_scope("attn_indexer"):
            qs, ks, lse = (lax.stop_gradient(a) for a in (q, k, lse))
            kl = sum(
                indexer_kl(q_i[b], k_i[b], w[b], mask[b], qs[b], ks[b], lse[b])
                for b in samples
            ) / (x.shape[0] * x.shape[1])
            pairs = jnp.sum(mask, dtype=jnp.int32).astype(jnp.float32)
        self.sow("intermediates", "mask", mask)
        with jax.named_scope("attn_out"):
            return _HeadsOut(x.shape[-1], dt, name="out")(out), kl, pairs


def _rms(x, scale, eps: float):
    """RMSNorm over the last axis, reduced in float32, in ``x``'s dtype."""
    x32 = x.astype(jnp.float32)
    y = x32 * lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _latent_up(c_q, c_kv, k_r, q_scale, kv_scale, w_qb, w_kvb, *, heads: int,
               nope: int, rope_theta: float, eps: float):
    """From the latents to the heads, in the layout the attention kernel
    reads: ``q`` and ``k`` (B, H, T, nope + 2 rope), ``v`` (B, H, T, v), each
    written once, heads-first, by ONE matrix product. What has to be split,
    scaled or rotated is split, scaled or rotated in the float32 WEIGHTS
    before their cast (``w_qb`` (q_rank, H (nope + rope)) and ``w_kvb``
    (kv_rank, H (nope + v)) stay one leaf each), never in the (B, T, H, .)
    activations:

    ``RoPE(x) = x cos + rotate_half(x) sin`` and ``rotate_half(x) = x P`` for
    a signed permutation ``P`` of the columns, so with ``x = c_q W_r`` and
    ``k' = RoPE(k_r)`` a head's rotary score is ``(c_q W_r cos) . k' + (c_q
    W_r P sin) . k'``. ``q = c_q [W_nope | W_r | W_r P] * [1 | cos | sin]``
    against ``k = [k_nope | k' | k']`` has exactly the scores of
    ``[q_nope | RoPE(q_r)]`` against ``[k_nope | k']``; the table is an
    elementwise factor of the product's output (its epilogue), and ``k``
    comes from ``[c_kv | k'] x [[W_k_nope(h), 0, 0], [0, I, I]]``, the
    identity blocks copying the one rotary key into every head. At 128 + 64
    the heads are 256 wide where they were 192: the same two 128-lane tiles
    in memory and through the MXU."""
    from akka_allreduce_tpu.models.transformer import rope, rope_angles

    dt = c_q.dtype
    t, rot = c_q.shape[1], k_r.shape[-1]
    up = lambda c, w: jnp.einsum("btr,rhd->bhtd", c, w.astype(dt))  # noqa: E731
    w_q = w_qb.reshape(w_qb.shape[0], heads, -1) * (nope + rot) ** -0.5
    w_kv = w_kvb.reshape(w_kvb.shape[0], heads, -1)
    c_q, c_kv = _rms(c_q, q_scale, eps), _rms(c_kv, kv_scale, eps)
    w_r = w_q[..., nope:]
    w_q = jnp.concatenate(
        (w_q, -w_r[..., rot // 2:], w_r[..., : rot // 2]), axis=-1
    )
    ang = rope_angles(t, rot, 0, base=rope_theta)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    table = jnp.concatenate((jnp.ones((t, nope)), cos, cos, sin, sin), axis=-1)
    q = up(c_q, w_q) * table.astype(dt)
    eye = jnp.broadcast_to(
        jnp.eye(rot, dtype=w_kv.dtype)[:, None], (rot, heads, rot)
    )
    w_k = jnp.concatenate(
        (jnp.pad(w_kv[..., :nope], ((0, 0), (0, 0), (0, 2 * rot))),
         jnp.pad(jnp.concatenate((eye, eye), axis=-1), ((0, 0), (0, 0), (nope, 0)))),
        axis=0,
    )
    k_r = rope(k_r[:, :, None, :], 0, base=rope_theta)[:, :, 0, :]
    k = up(jnp.concatenate((c_kv, k_r), axis=-1), w_k)
    return q, k, up(c_kv, w_kv[..., nope:])


class LatentAttention(nn.Module):
    """Multi-head latent attention (DeepSeek-V2/V3): low-rank queries
    ``c_q = RMSNorm(x W_qa)``, ``[q_nope | q_r] = c_q W_qb`` per head; a
    compressed K/V latent ``[c_kv | k_r] = x W_kva``, ``c_kv = RMSNorm(c_kv)``,
    ``[k_nope | v] = c_kv W_kvb`` per head; ``q = [q_nope | RoPE(q_r)]``,
    ``k = [k_nope | RoPE(k_r)]`` with the one ``k_r`` shared by all heads;
    scores scaled by ``(nope + rope) ** -0.5``; causal softmax; ``W_o`` over
    the heads' values, whose size differs from the queries'. Between the
    latents and ``W_o`` everything is heads-first (:func:`_latent_up`)."""

    n_heads: int
    q_rank: int
    kv_rank: int
    nope_dim: int
    rope_dim: int
    v_dim: int
    rope_theta: float
    norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.ops.local_attention import heads_first_attention

        d = x.shape[-1]
        dt, h = self.compute_dtype, self.n_heads
        init, ones = nn.initializers.normal(0.02), nn.initializers.ones
        with jax.named_scope("mla_down"):
            c_q = _dense(self.q_rank, dt, "q_a")(x)
            latent = _dense(self.kv_rank + self.rope_dim, dt, "kv_a")(x)
        with jax.named_scope("mla_up"):
            q, k, v = _latent_up(
                c_q, latent[..., : self.kv_rank], latent[..., self.kv_rank:],
                self.param("q_a_norm", ones, (self.q_rank,)),
                self.param("kv_a_norm", ones, (self.kv_rank,)),
                self.param(
                    "q_b", init, (self.q_rank, h * (self.nope_dim + self.rope_dim))
                ),
                self.param(
                    "kv_b", init, (self.kv_rank, h * (self.nope_dim + self.v_dim))
                ),
                heads=h, nope=self.nope_dim,
                rope_theta=self.rope_theta, eps=self.norm_eps,
            )
        with jax.named_scope("mla_attention"):
            out = heads_first_attention(q, k, v, causal=True)
        with jax.named_scope("mla_out"):
            return _HeadsOut(d, dt, name="out")(out)


def _decay_rate_init(key, shape, dtype=jnp.float32):
    """``A_log`` as the Gated DeltaNet reference layer seeds it: ``log A``,
    ``A ~ U(0, 16)`` held away from 0."""
    return jnp.log(jnp.maximum(jax.random.uniform(key, shape, dtype, 0.0, 16.0), 1e-3))


def _step_bias_init(key, shape, dtype=jnp.float32):
    """``dt_bias`` as that layer seeds it: the inverse softplus of a step
    log-uniform in [1e-3, 1e-1], so that with ``A`` above a token's log decay
    starts in about (-1.6, 0) and the state carries memory across chunks."""
    step = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3), math.log(1e-1)))
    return step + jnp.log(-jnp.expm1(-step))


def _filter_init(key, shape, dtype=jnp.float32):
    """A depthwise ``Conv1d``'s default: uniform within ``taps^-0.5``."""
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer of ``qwen3_next`` (Gated DeltaNet, arXiv
    2412.06464), ``rule`` a :class:`LinearAttentionRule`: ``[q | k | v | z] =
    x W_qkvz``, ``[b | a] = x W_ba`` (one of each a value head); ``[q | k |
    v] <- silu(conv([q | k | v]))``, causal and depthwise; ``q <- l2norm(q)
    key_dim^-0.5``, ``k <- l2norm(k)`` (epsilon 1e-6 under the root); in
    float32 ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a + dt_bias)``;
    the gated delta rule (``ops/delta_rule.py``) from a zero state; ``y =
    RMSNorm(o) (*) silu(z)`` over each head's ``value_dim`` columns, the norm
    first; ``W_o``. Returns ``(out, the mean of g, the root-mean-square of
    the final state)``. Scopes: ``linear_attention`` around ``gdn_in``,
    ``gdn_conv``, ``gdn_core`` (the two l2-norms, ``beta``, ``g`` and the
    rule, nothing else) and ``gdn_out``."""

    rule: LinearAttentionRule
    norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.ops.delta_rule import gated_delta_rule
        from akka_allreduce_tpu.ops.short_conv import silu_short_conv

        rule, dt, f32 = self.rule, self.compute_dtype, jnp.float32
        b, t, d = x.shape
        hk, hv, dk, dv = rule.key_heads, rule.value_heads, rule.key_dim, rule.value_dim
        keys, values = hk * dk, hv * dv

        def heads_first(y, heads):
            return y.reshape(b, t, heads, -1).transpose(0, 2, 1, 3)

        def l2norm(y, scale):
            y = y.astype(f32)
            y = y * lax.rsqrt(jnp.sum(jnp.square(y), axis=-1, keepdims=True) + 1e-6)
            return (y * scale).astype(dt)

        with jax.named_scope("linear_attention"):
            with jax.named_scope("gdn_in"):
                qkvz = _dense(2 * keys + 2 * values, dt, "qkvz")(x)
                ba = _dense(2 * hv, dt, "ba")(x)
            taps = self.param("conv", _filter_init, (2 * keys + values, rule.conv_taps))
            a_log = self.param("A_log", _decay_rate_init, (hv,))
            dt_bias = self.param("dt_bias", _step_bias_init, (hv,))
            scale = self.param("norm", nn.initializers.ones, (dv,))

            # Between the two projections everything is made again on the
            # backward pass from ``qkvz`` and ``ba``: what the chunked rule
            # keeps for its own backward is over a GB a layer at 8,192
            # positions in the XLA form (each chunk's state and what the
            # scan's body made of it) and 0.34 GB under the kernels (each
            # chunk's incoming states and its ``T``, written by the pass made
            # again and read once by the backward kernel); the convolution's
            # output, the heads-first copies and the rule's output are 0.4 GB
            # more; the two projections' outputs are 0.2
            @jax.checkpoint
            def mixed(qkvz, ba, taps, a_log, dt_bias, scale):
                with jax.named_scope("gdn_conv"):
                    qkv = silu_short_conv(qkvz[..., : 2 * keys + values], taps)
                with jax.named_scope("gdn_core"):
                    q = l2norm(heads_first(qkv[..., :keys], hk), dk ** -0.5)
                    k = l2norm(heads_first(qkv[..., keys: 2 * keys], hk), 1.0)
                    v = heads_first(qkv[..., 2 * keys:], hv)
                    ba = ba.astype(f32).transpose(0, 2, 1)  # (B, 2 H_v, T)
                    beta = jax.nn.sigmoid(ba[:, :hv])
                    g = -jnp.exp(a_log)[:, None] * jax.nn.softplus(
                        ba[:, hv:] + dt_bias[:, None]
                    )
                    o, state = gated_delta_rule(q, k, v, g, beta)
                with jax.named_scope("gdn_out"):
                    z = heads_first(qkvz[..., 2 * keys + values:], hv)
                    y = _rms(o, scale, self.norm_eps) * jax.nn.silu(z)
                return y, jnp.mean(g), jnp.sqrt(jnp.mean(jnp.square(state)))

            y, log_decay, state_rms = mixed(qkvz, ba, taps, a_log, dt_bias, scale)
            with jax.named_scope("gdn_out"):
                out = _HeadsOut(d, dt, name="out")(y)
        return out, log_decay, state_rms


class GatedMLP(nn.Module):
    width: int
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        dt = self.compute_dtype
        gate = _dense(self.width, dt, "w1")(x)
        up = _dense(self.width, dt, "w3")(x)
        return _dense(x.shape[-1], dt, "w2")(jax.nn.silu(gate) * up)


class HeldExperts(nn.Module):
    """The expert layer of one device: returns ``(y, rows, dropped, taken)``
    with ``rows`` the (held_count,) rows each held expert received. With
    ``shared_width`` a shared expert, a gated MLP every token passes through
    and every device computes alike, is added: unweighted, or with
    ``shared_gate`` times ``sigmoid(x w_sg)``, one number a token, computed
    alike on every device as the shared expert is."""

    num_experts: int
    experts_per_token: int
    width: int
    held_first: int
    held_count: int
    use_select_bias: bool
    renormalise: bool
    scale: float
    compute_dtype: jnp.dtype
    shared_width: int = 0
    score: str = "sigmoid"  # the router's score function, or "softmax"
    shared_gate: bool = False

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.ops.moe import moe_dropless_held

        d, h, f = x.shape[-1], self.held_count, self.width
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, self.num_experts))
        w1 = self.param("w1", init, (h, d, f))
        w3 = self.param("w3", init, (h, d, f))
        w2 = self.param("w2", init, (h, f, d))
        bias = None
        if self.use_select_bias:
            bias = self.variable(
                "fixed", "select_bias", jnp.zeros, (self.num_experts,),
                jnp.float32,
            ).value
        y, route, dropped = moe_dropless_held(
            x.reshape(-1, d).astype(self.compute_dtype), router, bias,
            w1, w3, w2, k=self.experts_per_token, held_first=self.held_first,
            renormalise=self.renormalise, scale=self.scale, score=self.score,
        )
        self.sow("intermediates", "selected", route.selected)
        y = y.reshape(x.shape)
        if self.shared_width:
            with jax.named_scope("shared_expert"):
                shared = GatedMLP(self.shared_width, self.compute_dtype, name="shared")(x)
                if self.shared_gate:
                    w_sg = self.param("shared_gate", init, (d, 1))
                    gate = jnp.einsum(
                        "...d,de->...e", x.astype(self.compute_dtype),
                        w_sg.astype(self.compute_dtype),
                        preferred_element_type=jnp.float32,
                    )
                    shared = shared * jax.nn.sigmoid(gate).astype(shared.dtype)
                y = y + shared
        return y, route.group_sizes[:h], dropped, route.buffer_rows


class HybridDecoderLM(nn.Module):
    """``tokens -> (logits, aux, dropped, expert_rows, buffer_rows)``:
    float32 logits, ``aux`` always 0 (no auxiliary loss), ``dropped`` the
    mean of the expert layers' (0 by construction), ``expert_rows`` (expert
    layers, held_count) float32 counts and ``buffer_rows`` (expert layers,)
    the rows of the row buffer each layer took for them — the tuple
    ``MoETrainer`` takes. With ``mtp_depth`` it is called with the next
    tokens too, ``(tokens, next_tokens)``, counts the prediction module's
    expert layer last in both counters and returns that module's float32
    logits (position i: the token after ``next_tokens[i]``) as a sixth. With
    ``indexer`` two more come last: the indexer's loss (the layers' mean
    KL summed over the layers, a scalar: the trainer adds it to its
    total) and the (query, key) pairs each layer's mask keeps,
    (layers,) float32. ``positions`` (3, T): the temporal, height and width
    rows of multimodal RoPE where the model has ``mrope_sections``; left
    out, text (three equal rows 0 .. T - 1). With ``linear_attention`` two
    scalars come last of all: the mean log decay ``g`` over tokens, heads
    and linear layers, and the largest root-mean-square of such a layer's
    final state."""

    vocab: int
    d_model: int
    layer_types: tuple[str, ...]
    num_dense_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int  # of the queries and keys (latent attention: nope + rope)
    intermediate_size: int
    moe_intermediate_size: int
    num_experts: int  # the router's width: every expert of the model
    experts_per_token: int
    held_first: int  # this device's experts: held_first .. held_first +
    held_count: int  # held_count - 1
    conv_taps: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    use_select_bias: bool = True
    renormalise: bool = True
    routed_scale: float = 1.0
    compute_dtype: jnp.dtype = jnp.float32
    shared_width: int = 0  # the shared expert's, 0: none
    # latent attention: ranks of the two latents, the rotary part of a head
    # and the values' head size (head_dim - rope_head_dim is the rest of q/k)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    rope_head_dim: int = 0
    v_head_dim: int = 0
    mtp_depth: int = 0  # multi-token-prediction modules (0 or 1)
    mtp_weight: float = 0.0  # of that module's loss in the trainer's total
    # attention that differs from layer to layer (the ``laguna`` dialect)
    heads_per_layer: tuple[int, ...] = ()  # (): ``n_heads`` in every layer
    sliding_window: int | None = None  # of the "sliding_attention" layers
    # per kind of attention layer, where given; such a layer has no per-head
    # norm and is scoped by its kind
    rope_by_kind: tuple[RotaryRule, ...] = ()
    # a sigmoid gate on attention's output: False, true (one a head) or
    # "element" (:class:`GroupedQueryAttention`'s ``gated``)
    attn_gate: bool | str = False
    router_score: str = "sigmoid"
    # attention under a learned mask (``sa_config``) and multimodal RoPE
    indexer: IndexerRule | None = None
    mrope_sections: tuple[int, ...] | None = None
    # ``qwen3_next``: the "linear_attention" layers' sizes, the columns of a
    # full-attention head that rotate (None: all), a gate on the shared expert
    linear_attention: LinearAttentionRule | None = None
    rotary_dim: int | None = None
    shared_gate: bool = False

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "HybridDecoderLM":
        """From the keys of a ``config.json``, in the dialect its keys are
        of: DeepSeek-V3's where it has ``kv_lora_rank`` (``deepseek_v3``,
        ``joyai_llm_flash``); ``laguna``'s where ``rope_parameters`` holds its
        rules under the kinds of ``layer_types`` (``laguna``, ``mellum``:
        :func:`_from_laguna` says which keys may be absent); Qwen3-MoE's
        where it has ``sa_config``, or ``decoder_sparse_step`` with
        ``mlp_only_layers`` and ``moe_intermediate_size`` (``KeyeVL2``'s
        language model: :func:`_from_qwen3_moe_keys` says what it builds and
        what it refuses by name), unless it has ``linear_num_value_heads``
        (``qwen3_next``, whose file has those three keys too and is asked for
        first: :func:`_from_qwen3_next`); else ``lfm2_moe``'s. The key that counts the experts (``num_experts`` /
        ``n_routed_experts``) counts the experts HELD when
        ``router_num_experts`` states the model's own count beside it (a
        chip's share, ``held_experts`` its ids); otherwise all experts are
        held."""
        if "kv_lora_rank" in cfg:
            read = _from_deepseek_v3_keys
        elif _rope_by_layer_kind(cfg):
            read = _from_laguna
        elif "linear_num_value_heads" in cfg:
            read = _from_qwen3_next
        elif "sa_config" in cfg or all(
            key in cfg
            for key in ("decoder_sparse_step", "mlp_only_layers", "moe_intermediate_size")
        ):
            read = _from_qwen3_moe_keys
        else:
            read = _from_lfm2_moe
        kw = read(cfg)
        if len(kw["layer_types"]) != int(cfg["num_hidden_layers"]):
            raise ValueError("layer_types and num_hidden_layers disagree")
        kw.update(overrides)
        return cls(**kw)

    def first_rung(self, tokens: int) -> int:
        """The smallest row buffer an expert layer of this model takes at
        ``tokens`` tokens a replica: the first of the ladder
        ``moe_dropless_held`` builds from the same three sizes. What
        ``buffer_rows`` reads while a layer's load is near the uniform one."""
        from akka_allreduce_tpu.ops.moe import row_rungs

        return row_rungs(
            tokens * self.experts_per_token, self.held_count, self.num_experts
        )[0]

    def _operator(self, kind: str, pre: str, index: int):
        dt = self.compute_dtype
        if kind == "conv":
            return ShortConv(self.d_model, self.conv_taps, dt, name=pre + "conv")
        if kind in ("full_attention", "sliding_attention") and self.rope_by_kind:
            rule = next(r for r in self.rope_by_kind if r.kind == kind)
            return GroupedQueryAttention(
                self.heads_per_layer[index] if self.heads_per_layer else self.n_heads,
                self.n_kv_heads, self.head_dim, rule.theta, self.norm_eps, dt,
                qk_norm=False, gated=self.attn_gate,
                window=self.sliding_window if kind == "sliding_attention" else None,
                rotary_dim=rule.rotary_dim, yarn=rule.yarn,
                attention_factor=rule.attention_factor,
                scope_name=kind, name=pre + "attn",
            )
        if kind == "full_attention":
            return GroupedQueryAttention(
                self.n_heads, self.n_kv_heads, self.head_dim,
                self.rope_theta, self.norm_eps, dt, name=pre + "attn",
                scope_name="sparse_attention" if self.indexer else "attention",
                mrope_sections=self.mrope_sections, indexer=self.indexer,
                gated=self.attn_gate, rotary_dim=self.rotary_dim,
            )
        if kind == "linear_attention":
            return GatedDeltaNet(
                self.linear_attention, self.norm_eps, dt, name=pre + "linear"
            )
        if kind == "latent_attention":
            return LatentAttention(
                self.n_heads, self.q_lora_rank, self.kv_lora_rank,
                self.head_dim - self.rope_head_dim, self.rope_head_dim,
                self.v_head_dim, self.rope_theta, self.norm_eps, dt,
                name=pre + "attn",
            )
        raise ValueError(f"layer type {kind!r} is not built")

    @nn.compact
    def __call__(self, tokens, next_tokens=None, positions=None):
        dt = self.compute_dtype
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=dt, name=name
        )
        rows, dropped, buffers, index_kl, index_pairs = [], [], [], [], []
        decays, state_rms = [], []
        if positions is not None and not self.mrope_sections:
            raise ValueError("positions are taken by a model with mrope_sections")

        def layer(x, pre: str, index: int, dense: bool):
            kind = self.layer_types[index]
            op = self._operator(kind, pre, index)
            extra = (positions,) if self.indexer or self.mrope_sections else ()
            y = op(norm(pre + "op_norm")(x), *extra)
            if kind == "linear_attention":
                y, decay, rms = y
                decays.append(decay)
                state_rms.append(rms)
            elif self.indexer:
                y, kl, pairs = y
                index_kl.append(kl)
                index_pairs.append(pairs)
            x = x + y
            h = norm(pre + "ffn_norm")(x)
            if dense:
                return x + GatedMLP(self.intermediate_size, dt, name=pre + "mlp")(h)
            y, r, dr, taken = HeldExperts(
                self.num_experts, self.experts_per_token,
                self.moe_intermediate_size, self.held_first, self.held_count,
                self.use_select_bias, self.renormalise, self.routed_scale,
                dt, self.shared_width, self.router_score, self.shared_gate,
                name=pre + "moe",
            )(h)
            rows.append(r)
            dropped.append(dr)
            buffers.append(taken)
            return x + y

        embed = nn.Embed(self.vocab, self.d_model, dtype=dt, name="embed")
        head = self.param(
            "head", nn.initializers.normal(0.02), (self.d_model, self.vocab)
        )

        def to_logits(x):
            return lax.dot_general(
                x, head.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )

        x = embed(tokens)
        for i in range(len(self.layer_types)):
            x = layer(x, f"layers_{i}_", i, i < self.num_dense_layers)
        logits, mtp_logits = to_logits(norm("final_norm")(x)), ()
        if self.mtp_depth:
            if next_tokens is None and self.is_initializing():
                next_tokens = tokens  # ``init(key, tokens)`` as any model's
            if self.mtp_depth != 1 or next_tokens is None:
                raise ValueError(
                    "one prediction module, called with (tokens, next_tokens)"
                )
            with jax.named_scope("mtp"):
                merged = jnp.concatenate(
                    (norm("mtp_enorm")(embed(next_tokens)), norm("mtp_hnorm")(x)),
                    axis=-1,
                )
                x = _dense(self.d_model, dt, "mtp_eh_proj")(merged)
                x = layer(x, "mtp_", len(self.layer_types) - 1, False)
                mtp_logits = (to_logits(norm("mtp_final_norm")(x)),)
        n = max(len(rows), 1)
        return (
            logits,
            jnp.float32(0.0),
            sum(dropped, jnp.float32(0.0)) / n,
            jnp.stack(rows).astype(jnp.float32) if rows
            else jnp.zeros((0, self.held_count), jnp.float32),
            jnp.stack(buffers).astype(jnp.float32) if buffers
            else jnp.zeros((0,), jnp.float32),
            *mtp_logits,
            *((sum(index_kl), jnp.stack(index_pairs)) if self.indexer else ()),
            *((jnp.mean(jnp.stack(decays)), jnp.max(jnp.stack(state_rms)))
              if decays else ()),
        )


def _held_share(cfg: dict, count_key: str) -> tuple[int, int, int]:
    """``(router width, first held expert, held count)``."""
    total = int(cfg.get("router_num_experts", cfg[count_key]))
    held = list(cfg.get("held_experts", range(int(cfg[count_key]))))
    if held != list(range(held[0], held[0] + len(held))):
        raise ValueError(f"held experts must be one range, got {held}")
    return total, held[0], len(held)


def _from_lfm2_moe(cfg: dict) -> dict:
    if cfg.get("conv_bias"):
        raise ValueError("conv_bias is not built")
    total, first, count = _held_share(cfg, "num_experts")
    heads = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return dict(
        vocab=int(cfg["vocab_size"]), d_model=d,
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=int(cfg["num_dense_layers"]),
        n_heads=heads, n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg.get("head_dim") or d // heads),
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=total,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        held_first=first, held_count=count,
        conv_taps=int(cfg["conv_L_cache"]),
        norm_eps=float(cfg["norm_eps"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        use_select_bias=bool(cfg["use_expert_bias"]),
        renormalise=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
    )


def _from_deepseek_v3_keys(cfg: dict) -> dict:
    """DeepSeek-V3's keys: latent attention in every layer, the first
    ``first_k_dense_replace`` feed-forwards dense, sigmoid scores picked by
    ``p + bias`` in one group (``noaux_tc``), ``n_shared_experts`` shared
    experts as one MLP of their summed width, ``num_nextn_predict_layers``
    prediction modules. ``program.mtp_loss_weight`` weighs the second loss."""
    refused = {
        "n_group": 1, "topk_group": 1, "rope_scaling": None, "ep_size": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "attention_bias": False, "hidden_act": "silu", "moe_layer_freq": 1,
        "tie_word_embeddings": False,
    }
    for key, built in refused.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"{key} = {cfg[key]!r} is not built (only {built!r})")
    mtp = int(cfg.get("num_nextn_predict_layers", 0))
    if mtp > 1:
        raise ValueError(f"{mtp} prediction modules: one is built")
    if not cfg.get("q_lora_rank"):
        raise ValueError("full-rank queries (no q_lora_rank) are not built")
    total, first, count = _held_share(cfg, "n_routed_experts")
    layers = int(cfg["num_hidden_layers"])
    program = cfg.get("program", {})
    if program.get("remat"):
        raise ValueError(f"program.remat {program['remat']!r}: recomputation is not built")
    nope, rot = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    heads = int(cfg["num_attention_heads"])
    return dict(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        layer_types=("latent_attention",) * layers,
        num_dense_layers=int(cfg["first_k_dense_replace"]),
        n_heads=heads, n_kv_heads=heads, head_dim=nope + rot,
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=total,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        held_first=first, held_count=count,
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        use_select_bias=True,
        renormalise=bool(cfg["norm_topk_prob"]),
        routed_scale=float(cfg["routed_scaling_factor"]),
        shared_width=int(cfg["n_shared_experts"]) * int(cfg["moe_intermediate_size"]),
        q_lora_rank=int(cfg["q_lora_rank"]), kv_lora_rank=int(cfg["kv_lora_rank"]),
        rope_head_dim=rot, v_head_dim=int(cfg["v_head_dim"]),
        mtp_depth=mtp,
        mtp_weight=float(program.get("mtp_loss_weight", 0.3)) if mtp else 0.0,
    )


def _rope_by_layer_kind(cfg: dict) -> bool:
    """Does ``rope_parameters`` hold rules under the kinds of
    ``layer_types`` (and not one rule for the model)?"""
    rules = cfg.get("rope_parameters")
    return isinstance(rules, dict) and any(
        isinstance(rules.get(kind), dict) for kind in cfg.get("layer_types", ())
    )


def _from_laguna(cfg: dict) -> dict:
    """``laguna``'s keys, which ``mellum`` shares: grouped-query attention
    whose mask (``layer_types``: ``full_attention`` | ``sliding_attention``
    under ``sliding_window``) and rotary rule (``rope_parameters[kind]``:
    ``default`` | ``yarn``, a ``partial_rotary_factor`` or the whole head) go
    by the layer's kind, then expert layers with softmax scores renormalised
    over the picks and no selection bias. Four keys may be absent, each
    meaning the plain form: ``num_attention_heads_per_layer`` (absent:
    ``num_attention_heads`` in every layer), ``gating`` (absent or false: no
    output gate; true or "per-head": a sigmoid gate per head),
    ``shared_expert_intermediate_size`` (absent or 0: no shared expert),
    ``moe_routed_scaling_factor`` (absent: 1); and ``mlp_layer_types`` may
    be ``sparse`` throughout (no leading dense feed-forward)."""
    refused = {
        "attention_bias": False, "tie_word_embeddings": False,
        "moe_apply_router_weight_on_input": False,
        "moe_router_logit_softcapping": 0, "hidden_act": "silu", "ep_size": 1,
    }
    for key, built in refused.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"{key} = {cfg[key]!r} is not built (only {built!r})")
    gating = cfg.get("gating", False)
    if gating not in (False, True, "per-head") or any(
        g != "per_head" for g in cfg.get("gating_types", ())
    ):
        raise ValueError(
            f"gating = {gating!r} / {cfg.get('gating_types')!r} is not built "
            "(a gate per head, or none)"
        )
    program = cfg.get("program", {})
    if program.get("remat"):
        raise ValueError(f"program.remat {program['remat']!r}: recomputation is not built")
    layers, kinds = int(cfg["num_hidden_layers"]), tuple(cfg["layer_types"])
    heads = tuple(int(h) for h in cfg.get("num_attention_heads_per_layer", ()))
    mlps = list(cfg["mlp_layer_types"])
    dense = mlps.index("sparse") if "sparse" in mlps else len(mlps)
    if mlps != ["dense"] * dense + ["sparse"] * (len(mlps) - dense):
        raise ValueError(f"mlp_layer_types {mlps}: dense layers lead, sparse ones follow")
    if len(mlps) != layers or len(heads) not in (0, layers):
        raise ValueError("the per-layer lists and num_hidden_layers disagree")
    head_dim = int(cfg["head_dim"])
    rules = []
    for kind in sorted(set(kinds)):
        if kind not in ("full_attention", "sliding_attention"):
            raise ValueError(f"layer type {kind!r} is not built")
        r = cfg["rope_parameters"][kind]
        rope_type, yarn, factor = r.get("rope_type", "default"), None, 1.0
        if rope_type == "yarn":
            yarn = (
                float(r["factor"]), int(r["original_max_position_embeddings"]),
                float(r.get("beta_fast", 32)), float(r.get("beta_slow", 1)),
            )
            factor = float(
                r.get("attention_factor") or 0.1 * math.log(yarn[0]) + 1.0
            )
        elif rope_type != "default":
            raise ValueError(f"rope_type {rope_type!r} is not built (default, yarn)")
        part = float(r.get("partial_rotary_factor", cfg.get("partial_rotary_factor", 1)))
        rules.append(
            RotaryRule(kind, float(r["rope_theta"]), int(head_dim * part), yarn, factor)
        )
    total, first, count = _held_share(cfg, "num_experts")
    return dict(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        layer_types=kinds, num_dense_layers=dense,
        n_heads=int(cfg["num_attention_heads"]), heads_per_layer=heads,
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=head_dim,
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=total,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        held_first=first, held_count=count,
        norm_eps=float(cfg["rms_norm_eps"]),
        sliding_window=int(cfg["sliding_window"]),
        rope_by_kind=tuple(rules), attn_gate=bool(gating),
        use_select_bias=False, router_score="softmax",
        renormalise=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("moe_routed_scaling_factor", 1.0)),
        shared_width=int(cfg.get("shared_expert_intermediate_size", 0)),
    )


def _from_qwen3_moe_keys(cfg: dict) -> dict:
    """Qwen3-MoE's keys, as ``KeyeVL2``'s language model writes them: causal
    grouped-query attention with a learned per-head RMSNorm on q and k, one
    ``rope_theta`` over the whole head, turned by three rows of positions
    where ``rope_scaling.mrope_section`` says so (consecutive sections);
    every layer an expert layer (``decoder_sparse_step`` 1, ``mlp_only_layers``
    empty) with softmax scores renormalised over the picks where
    ``norm_topk_prob``, no selection bias, no shared expert, no scale. With
    ``sa_config`` attention runs under the mask its indexer learns
    (:class:`IndexerRule`); without it the same reader builds plain causal
    attention. Refused by name, because not built: a sliding window in
    use, a ``rope_scaling`` type other than ``default``, interleaved
    sections (``mrope_interleaved``), a dense layer among the expert layers
    (``decoder_sparse_step`` != 1, a non-empty ``mlp_only_layers``), more
    than one index key head, biases, tied embeddings, recomputation, a shared
    expert (``shared_expert_intermediate_size``), a ``partial_rotary_factor``
    other than 1 and any ``linear_*`` / ``full_attention_interval`` key, so
    that no file is built as plain attention without what those keys ask."""
    refused = {
        "use_sliding_window": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
        "attention_bias": False, "tie_word_embeddings": False, "hidden_act": "silu",
        "shared_expert_intermediate_size": 0, "partial_rotary_factor": 1,
    }
    for key, built in refused.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"{key} = {cfg[key]!r} is not built (only {built!r})")
    for key in cfg:  # ``qwen3_next``'s, which :func:`_from_qwen3_next` reads
        if key.startswith("linear_") or key == "full_attention_interval":
            raise ValueError(f"{key} is not built by the Qwen3-MoE reader")
    scaling = cfg.get("rope_scaling") or {}
    for key in ("rope_type", "type"):
        if scaling.get(key, "default") != "default":
            raise ValueError(
                f"rope_scaling.{key} = {scaling[key]!r} is not built (only 'default')"
            )
    if "mrope_interleaved" in scaling or "mrope_interleaved" in cfg:
        raise ValueError(
            "mrope_interleaved is not built (consecutive mrope_section columns only)"
        )
    program = cfg.get("program", {})
    if program.get("remat"):
        raise ValueError(f"program.remat {program['remat']!r}: recomputation is not built")
    head_dim = int(cfg["head_dim"])
    sections = tuple(int(n) for n in scaling.get("mrope_section", ()))
    if sections and sum(sections) != head_dim // 2:
        raise ValueError(f"mrope_section {sections} does not fill half a head of {head_dim}")
    indexer, sa = None, cfg.get("sa_config")
    if sa is not None:
        if int(sa.get("indexer_num_kv_heads", 1)) != 1:
            raise ValueError(
                f"sa_config.indexer_num_kv_heads = {sa['indexer_num_kv_heads']!r} "
                "is not built (only one index key head)"
            )
        indexer = IndexerRule(
            int(sa["indexer_num_heads"]), int(sa["indexer_head_dim"]), int(sa["topk"])
        )
    total, first, count = _held_share(cfg, "num_experts")
    layers = int(cfg["num_hidden_layers"])
    return dict(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        layer_types=("full_attention",) * layers, num_dense_layers=0,
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=head_dim,
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=total,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        held_first=first, held_count=count,
        norm_eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
        use_select_bias=False, router_score="softmax",
        renormalise=bool(cfg["norm_topk_prob"]), routed_scale=1.0,
        indexer=indexer, mrope_sections=sections or None,
    )


def _from_qwen3_next(cfg: dict) -> dict:
    """``qwen3_next``'s keys (entered by ``linear_num_value_heads``): layer i
    is ``full_attention`` where ``(i + 1) % full_attention_interval == 0`` and
    ``linear_attention`` elsewhere (or as ``layer_types`` says where the file
    has it); the linear layers a Gated DeltaNet mixer at the ``linear_*``
    sizes; the full layers grouped-query attention with a learned RMSNorm on
    each head of q and k, rotate-half RoPE on the first
    ``partial_rotary_factor`` of a head and a sigmoid gate an element from a
    doubled ``W_q``; every layer an expert layer with softmax scores
    renormalised over the picks where ``norm_topk_prob``, no selection bias,
    no scale, and a shared expert of ``shared_expert_intermediate_size``
    under its own sigmoid gate. Refused by name, because not built: a dense
    layer among the expert layers (``decoder_sparse_step`` != 1, a non-empty
    ``mlp_only_layers``), a sliding window in use, a ``rope_scaling``,
    biases, tied embeddings, an activation other than ``silu``, value heads
    that are no multiple of the key heads, recomputation."""
    refused = {
        "use_sliding_window": False, "decoder_sparse_step": 1, "mlp_only_layers": [],
        "rope_scaling": None, "attention_bias": False, "tie_word_embeddings": False,
        "hidden_act": "silu",
    }
    for key, built in refused.items():
        if cfg.get(key, built) != built:
            raise ValueError(f"{key} = {cfg[key]!r} is not built (only {built!r})")
    program = cfg.get("program", {})
    if program.get("remat"):
        raise ValueError(f"program.remat {program['remat']!r}: recomputation is not built")
    rule = LinearAttentionRule(
        int(cfg["linear_num_key_heads"]), int(cfg["linear_num_value_heads"]),
        int(cfg["linear_key_head_dim"]), int(cfg["linear_value_head_dim"]),
        int(cfg["linear_conv_kernel_dim"]),
    )
    if rule.value_heads % rule.key_heads:
        raise ValueError(
            f"linear_num_value_heads = {rule.value_heads} is not built (only a "
            f"multiple of linear_num_key_heads = {rule.key_heads})"
        )
    layers, every = int(cfg["num_hidden_layers"]), int(cfg["full_attention_interval"])
    kinds = tuple(cfg.get("layer_types") or (
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(layers)
    ))
    for kind in set(kinds) - {"full_attention", "linear_attention"}:
        raise ValueError(f"layer type {kind!r} is not built")
    total, first, count = _held_share(cfg, "num_experts")
    head_dim = int(cfg["head_dim"])
    return dict(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        layer_types=kinds, num_dense_layers=0,
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]), head_dim=head_dim,
        intermediate_size=int(cfg["intermediate_size"]),
        moe_intermediate_size=int(cfg["moe_intermediate_size"]),
        num_experts=total,
        experts_per_token=int(cfg["num_experts_per_tok"]),
        held_first=first, held_count=count,
        norm_eps=float(cfg["rms_norm_eps"]), rope_theta=float(cfg["rope_theta"]),
        use_select_bias=False, router_score="softmax",
        renormalise=bool(cfg["norm_topk_prob"]), routed_scale=1.0,
        shared_width=int(cfg["shared_expert_intermediate_size"]), shared_gate=True,
        attn_gate="element",
        rotary_dim=int(head_dim * float(cfg.get("partial_rotary_factor", 1))),
        linear_attention=rule,
    )

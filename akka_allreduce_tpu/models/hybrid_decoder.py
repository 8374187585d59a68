"""A decoder built from a configuration: conv/attention hybrids with dense
and expert feed-forward layers (the ``lfm2_moe`` family).

Layer ``i``:  ``h = x + Op_i(RMSNorm(x))``,  ``y = h + FF_i(RMSNorm(h))``.
``Op_i`` is a gated short convolution where ``layer_types[i] == "conv"`` and
grouped-query attention (per-head RMSNorm on q and k, rotate-half RoPE) where
``"full_attention"``; ``FF_i`` is a gated SiLU MLP for the first
``num_dense_layers`` layers and a sigmoid-routed, dropless expert layer after
them. A final RMSNorm, then an untied head. No bias anywhere.

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

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax


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


class GroupedQueryAttention(nn.Module):
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float
    norm_eps: float
    compute_dtype: jnp.dtype

    @nn.compact
    def __call__(self, x):
        from akka_allreduce_tpu.models.transformer import rope
        from akka_allreduce_tpu.ops.local_attention import local_attention

        b, t, d = x.shape
        dt, hd = self.compute_dtype, self.head_dim
        head_norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=dt, name=name
        )
        with jax.named_scope("attention"):
            q = _dense(self.n_heads * hd, dt, "q")(x).reshape(b, t, -1, hd)
            k = _dense(self.n_kv_heads * hd, dt, "k")(x).reshape(b, t, -1, hd)
            v = _dense(self.n_kv_heads * hd, dt, "v")(x).reshape(b, t, -1, hd)
            q = rope(head_norm("q_norm")(q), 0, base=self.rope_theta)
            k = rope(head_norm("k_norm")(k), 0, base=self.rope_theta)
            out = local_attention(q, k, v, causal=True)
            return _dense(d, dt, "out")(out.reshape(b, t, -1))


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
    """The expert layer of one device: returns ``(y, rows, dropped)`` with
    ``rows`` the (held_count,) rows each held expert received."""

    num_experts: int
    experts_per_token: int
    width: int
    held_first: int
    held_count: int
    use_select_bias: bool
    renormalise: bool
    scale: float
    compute_dtype: jnp.dtype

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
            renormalise=self.renormalise, scale=self.scale,
        )
        self.sow("intermediates", "selected", route.selected)
        return y.reshape(x.shape), route.group_sizes[:h], dropped, route.buffer_rows


class HybridDecoderLM(nn.Module):
    """``tokens -> (logits, aux, dropped, expert_rows, buffer_rows)``:
    float32 logits, ``aux`` always 0 (no auxiliary loss), ``dropped`` the
    mean of the expert layers' (0 by construction), ``expert_rows`` (expert
    layers, held_count) float32 counts and ``buffer_rows`` (expert layers,)
    the rows of the row buffer each layer took for them — the tuple
    ``MoETrainer`` takes."""

    vocab: int
    d_model: int
    layer_types: tuple[str, ...]
    num_dense_layers: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
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

    @classmethod
    def from_config(cls, cfg: dict, **overrides) -> "HybridDecoderLM":
        """From the keys of an ``lfm2_moe`` ``config.json``. ``num_experts``
        there counts the experts HELD when ``router_num_experts`` states the
        model's own count beside it (a chip's share, ``held_experts`` its
        ids); otherwise all experts are held."""
        if cfg.get("conv_bias"):
            raise ValueError("conv_bias is not built")
        total = int(cfg.get("router_num_experts", cfg["num_experts"]))
        held = list(cfg.get("held_experts", range(int(cfg["num_experts"]))))
        if held != list(range(held[0], held[0] + len(held))):
            raise ValueError(f"held experts must be one range, got {held}")
        heads = int(cfg["num_attention_heads"])
        d = int(cfg["hidden_size"])
        kw = dict(
            vocab=int(cfg["vocab_size"]), d_model=d,
            layer_types=tuple(cfg["layer_types"]),
            num_dense_layers=int(cfg["num_dense_layers"]),
            n_heads=heads, n_kv_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg.get("head_dim") or d // heads),
            intermediate_size=int(cfg["intermediate_size"]),
            moe_intermediate_size=int(cfg["moe_intermediate_size"]),
            num_experts=total,
            experts_per_token=int(cfg["num_experts_per_tok"]),
            held_first=held[0], held_count=len(held),
            conv_taps=int(cfg["conv_L_cache"]),
            norm_eps=float(cfg["norm_eps"]),
            rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
            use_select_bias=bool(cfg["use_expert_bias"]),
            renormalise=bool(cfg["norm_topk_prob"]),
            routed_scale=float(cfg["routed_scaling_factor"]),
        )
        if len(kw["layer_types"]) != int(cfg["num_hidden_layers"]):
            raise ValueError("layer_types and num_hidden_layers disagree")
        kw.update(overrides)
        return cls(**kw)

    @nn.compact
    def __call__(self, tokens):
        dt = self.compute_dtype
        norm = lambda name: nn.RMSNorm(  # noqa: E731
            epsilon=self.norm_eps, dtype=dt, name=name
        )
        x = nn.Embed(self.vocab, self.d_model, dtype=dt, name="embed")(tokens)
        rows, dropped, buffers = [], [], []
        for i, kind in enumerate(self.layer_types):
            pre = f"layers_{i}_"
            h = norm(pre + "op_norm")(x)
            if kind == "conv":
                op = ShortConv(self.d_model, self.conv_taps, dt, name=pre + "conv")
            elif kind == "full_attention":
                op = GroupedQueryAttention(
                    self.n_heads, self.n_kv_heads, self.head_dim,
                    self.rope_theta, self.norm_eps, dt, name=pre + "attn",
                )
            else:
                raise ValueError(f"layer type {kind!r} is not built")
            x = x + op(h)
            h = norm(pre + "ffn_norm")(x)
            if i < self.num_dense_layers:
                y = GatedMLP(self.intermediate_size, dt, name=pre + "mlp")(h)
            else:
                y, r, dr, taken = HeldExperts(
                    self.num_experts, self.experts_per_token,
                    self.moe_intermediate_size, self.held_first, self.held_count,
                    self.use_select_bias, self.renormalise, self.routed_scale,
                    dt, name=pre + "moe",
                )(h)
                rows.append(r)
                dropped.append(dr)
                buffers.append(taken)
            x = x + y
        x = norm("final_norm")(x)
        head = self.param(
            "head", nn.initializers.normal(0.02), (self.d_model, self.vocab)
        )
        logits = lax.dot_general(
            x, head.astype(dt), (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        n = max(len(rows), 1)
        return (
            logits,
            jnp.float32(0.0),
            sum(dropped, jnp.float32(0.0)) / n,
            jnp.stack(rows).astype(jnp.float32) if rows
            else jnp.zeros((0, self.held_count), jnp.float32),
            jnp.stack(buffers).astype(jnp.float32) if buffers
            else jnp.zeros((0,), jnp.float32),
        )

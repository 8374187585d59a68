"""Plain reference: a ``qwen3_next`` decoder (Qwen3-Next-80B-A3B: Gated
DeltaNet layers three to one with gated full attention, an expert layer with
a gated shared expert in every layer), its loss, gradients and Adam, for the
experts one device holds.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``. Layer i is full attention where ``(i + 1) %
full_attention_interval == 0``, else linear attention; ``x <- x +
mixer(RMSNorm(x)); x <- x + moe(RMSNorm(x))``.

*Gated DeltaNet* (arXiv 2412.06464), ``x'`` (T, d), ``H_k`` key heads and
``H_v`` value heads:

- ``[q | k | v | z] = x' W_qkvz`` (H_k d_k, H_k d_k, H_v d_v, H_v d_v columns,
  in that plain order); ``[b | a] = x' W_ba`` (H_v each).
- ``[q | k | v] <- silu(conv([q | k | v]))``: causal, depthwise,
  ``linear_conv_kernel_dim`` taps, no bias, zeros to the left; tap j of the
  filter weighs position ``t - (L - 1) + j``.
- key head j serves the ``H_v / H_k`` consecutive value heads from ``j H_v /
  H_k``; ``q <- l2norm(q) d_k^-0.5``, ``k <- l2norm(k)``, ``l2norm(u) = u
  rsqrt(sum u^2 + 1e-6)``.
- per value head ``beta_t = sigmoid(b_t)``, ``g_t = -exp(A_log) softplus(a_t
  + dt_bias)``, ``alpha_t = exp(g_t)``.
- the state ``S`` (d_k, d_v) of a value head, ``S_0 = 0``, TOKEN BY TOKEN:
  ``S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T``,
  ``o_t = S_t^T q_t``. One ``lax.scan`` over the positions, no chunk algebra;
  each block of ``STATE_BLOCK`` positions is recomputed on the backward pass
  so that only the blocks' boundary states are kept, and the heads run in
  ``HEAD_GROUPS`` groups one after another (every head goes its own way
  between the two projections).
- ``y = RMSNorm(o) w (*) silu(z)`` over each head's d_v columns (the norm
  first, then the gate); ``out = y W_o``.

*Gated full attention*: ``[q | gate] = x' W_q`` (a head's ``head_dim`` query
columns, then its ``head_dim`` gate columns), ``k = x' W_k``, ``v = x' W_v``;
a learned RMSNorm over each head of q and of k; rotate-half RoPE on the first
``partial_rotary_factor x head_dim`` columns of each head, written out here
(column i with column i + r/2 for rotary width r, ``f_i = theta^(-2i/r)``),
the other columns unturned; ``a = softmax(q k^T / sqrt(head_dim), causal) v``,
each ``H / H_kv`` consecutive query heads on one K/V head, one head and
``ATTN_ROWS`` query rows at a time; ``out = (a (*) sigmoid(gate)) W_o``.

*Expert layer*: ``p = softmax(x'' W_r)`` over all experts in float32, the
``num_experts_per_tok`` largest by that many rounds of argmax (ties: the
lower index), weights ``p_sel / sum(p_sel)``; EVERY held expert ``W2 (silu(W1
x'') * W3 x'')`` applied to ALL tokens and masked by its routing weight (no
kernel, no sort of rows, no gather); plus ``sigmoid(x'' w_sg)`` times the
shared expert, once. What the absent experts would add is left out.
``intermediate_size`` is read by nothing.

A final RMSNorm, an untied head; the loss is the mean token cross-entropy. No
bias, no auxiliary loss, no prediction module.

It imports nothing of the program and takes nothing the program has made:
weights come from :func:`init_params`, the batches from the benchmark's
traffic generator. To fit on one 16 GB chip at the published widths each
layer, each block of positions of the recurrence, each block of attention
rows, each expert and each block of logits rows is recomputed on the backward
pass (``jax.checkpoint``) - that changes memory, not values.

``precision`` selects a control: ``router`` and ``store`` "float32" or
"bfloat16" (:data:`CONTROL`, the same mathematics one step down);
``state_carry`` false sets the state to zero at every ``RESET_EVERY``-th
position (:data:`NO_STATE_CARRY`: a chunked form that forgets to carry);
``delta`` false writes ``beta k v^T`` without the correction
(:data:`NO_DELTA`: gated linear attention); ``decay`` false holds ``alpha``
at 1 (:data:`NO_DECAY`); ``out_gate`` false leaves attention's gate out
(:data:`NO_OUT_GATE`); ``partial_rotary`` false turns all of a head's columns
(:data:`FULL_ROTARY`); ``shared_gate`` false adds the shared expert unweighted
(:data:`UNGATED_SHARED`). Each must fail the cell's check.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE = {"router": "float32", "store": "float32", "state_carry": True,
             "delta": True, "decay": True, "out_gate": True,
             "partial_rotary": True, "shared_gate": True}
#: one step below what the configuration states (f32 router, f32 state)
CONTROL = {**REFERENCE, "router": "bfloat16", "store": "bfloat16"}
#: the faults this architecture makes easy
NO_STATE_CARRY = {**REFERENCE, "state_carry": False}
NO_DELTA = {**REFERENCE, "delta": False}
NO_DECAY = {**REFERENCE, "decay": False}
NO_OUT_GATE = {**REFERENCE, "out_gate": False}
FULL_ROTARY = {**REFERENCE, "partial_rotary": False}
UNGATED_SHARED = {**REFERENCE, "shared_gate": False}

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 2048  # rows of logits alive at once
ATTN_ROWS = 1024  # query rows of one head's scores alive at once
STATE_BLOCK = 128  # positions of the recurrence between two kept states
RESET_EVERY = 64  # NO_STATE_CARRY: the chunk of a chunked form
HEAD_GROUPS = 4  # groups of heads a linear mixer runs one after another


def dims(cfg: dict) -> dict:
    held = list(cfg.get("held_experts", range(cfg["num_experts"])))
    return {
        "d": cfg["hidden_size"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "h": cfg["num_attention_heads"],
        "fe": cfg["moe_intermediate_size"],
        "fs": cfg["shared_expert_intermediate_size"],
        "hk": cfg["linear_num_key_heads"], "hv": cfg["linear_num_value_heads"],
        "dk": cfg["linear_key_head_dim"], "dv": cfg["linear_value_head_dim"],
        "taps": cfg["linear_conv_kernel_dim"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"], "held": held,
        "experts": cfg.get("router_num_experts", cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
    }


def layer_kinds(cfg: dict) -> list[str]:
    every = cfg["full_attention_interval"]
    return [
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(cfg["num_hidden_layers"])
    ]


def expert_layers(cfg: dict) -> list[int]:
    """Every layer: ``decoder_sparse_step`` 1 and no ``mlp_only_layers``."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer is an expert layer here")
    return list(range(cfg["num_hidden_layers"]))


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in a fixed order (the order seeds the leaves)."""
    s = dims(cfg)
    d, hd, n_held = s["d"], s["hd"], len(s["held"])
    keys, values = s["hk"] * s["dk"], s["hv"] * s["dv"]
    shapes: dict[str, tuple[int, ...]] = {"embed": (s["v"], d)}
    for i, kind in enumerate(layer_kinds(cfg)):
        p = f"layers.{i}."
        shapes[p + "op_norm.scale"] = (d,)
        if kind == "linear_attention":
            shapes.update({
                p + "gdn.qkvz.w": (d, 2 * keys + 2 * values),
                p + "gdn.ba.w": (d, 2 * s["hv"]),
                p + "gdn.conv": (2 * keys + values, s["taps"]),
                p + "gdn.A_log": (s["hv"],), p + "gdn.dt_bias": (s["hv"],),
                p + "gdn.norm.scale": (s["dv"],), p + "gdn.o.w": (values, d),
            })
        else:
            shapes.update({
                p + "q.w": (d, s["h"] * 2 * hd), p + "k.w": (d, s["kv"] * hd),
                p + "v.w": (d, s["kv"] * hd), p + "o.w": (s["h"] * hd, d),
                p + "q_norm.scale": (hd,), p + "k_norm.scale": (hd,),
            })
        shapes.update({
            p + "ffn_norm.scale": (d,),
            p + "router.w": (d, s["experts"]),
            p + "experts.w1": (n_held, d, s["fe"]),
            p + "experts.w3": (n_held, d, s["fe"]),
            p + "experts.w2": (n_held, s["fe"], d),
            p + "shared.w1": (d, s["fs"]), p + "shared.w3": (d, s["fs"]),
            p + "shared.w2": (s["fs"], d), p + "shared_gate.w": (d, 1),
        })
    shapes.update({"final_norm.scale": (d,), "head.w": (d, s["v"])})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds pass 2**31)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def leaf_stds(cfg: dict) -> tuple[float, float]:
    """``(std of every matrix, std of the embedding's rows)``: the second is
    ``embedding_initializer_range`` where the configuration gives one (as
    ``keye_moe_plain.leaf_stds`` says why)."""
    std = float(cfg["initializer_range"])
    return std, float(cfg.get("embedding_initializer_range", std))


def init_leaf(name: str, shape, index: int, key, stds) -> jax.Array:
    """As the Gated DeltaNet reference layer seeds its own leaves: ``A_log =
    log A``, ``A ~ U(0, 16)`` held away from 0; ``dt_bias`` the inverse
    softplus of a step log-uniform in [1e-3, 1e-1]; the filter uniform within
    ``taps^-0.5`` (a depthwise ``Conv1d``'s default). Every norm's scale 1 +
    noise, every matrix the noise."""
    key = jax.random.fold_in(key, index)
    if name.endswith(".A_log"):
        return jnp.log(jnp.maximum(jax.random.uniform(key, shape, jnp.float32, 0.0, 16.0), 1e-3))
    if name.endswith(".dt_bias"):
        step = jnp.exp(
            jax.random.uniform(key, shape, jnp.float32, math.log(1e-3), math.log(1e-1))
        )
        return step + jnp.log(-jnp.expm1(-step))
    if name.endswith(".conv"):
        bound = shape[-1] ** -0.5
        return jax.random.uniform(key, shape, jnp.float32, -bound, bound)
    noise = stds[name == "embed"] * jax.random.normal(key, shape, jnp.float32)
    return 1.0 + noise if name.endswith(".scale") else noise


def init_params(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """Every leaf random (the norms' weights too, so that none is a no-op),
    float32, made on the device in one jitted call."""
    shapes = param_shapes(cfg)
    stds = leaf_stds(cfg)

    def make(key):
        return {
            n: init_leaf(n, s, i, key, stds)
            for i, (n, s) in enumerate(shapes.items())
        }

    return jax.jit(make)(seed_key(seed))


def select_bias(cfg: dict, seed: int) -> jax.Array:
    """This router selects by its scores alone: an empty (expert layers, 0)
    array stands where ``moe_train``'s runner asks for a selection bias."""
    return jnp.zeros((len(expert_layers(cfg)), 0), jnp.float32)


# -- forward -------------------------------------------------------------------


def mm(a, b):
    """a (..., m, k) @ b (..., k, n), float32 at the highest precision."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def l2_norm(x):
    return x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def causal_conv(x, taps):
    """``x`` (T, C), ``taps`` (C, L): ``y_t = sum_j taps[:, j] x_{t-(L-1)+j}``,
    zeros before the sequence."""
    t, width = x.shape[0], taps.shape[1]
    padded = jnp.concatenate((jnp.zeros((width - 1, x.shape[1]), x.dtype), x), axis=0)
    return sum(taps[:, j] * padded[j: j + t] for j in range(width))


def delta_recurrence(q, k, v, g, beta, precision: dict):
    """One sequence, token by token: ``q``, ``k`` (T, H, d_k), ``v`` (T, H,
    d_v), ``g``, ``beta`` (T, H) -> ``(o (T, H, d_v), the final state (H, d_k,
    d_v))``. ``STATE_BLOCK`` positions are recomputed on the backward pass at
    a time."""
    t, h, dk = q.shape
    block = math.gcd(t, STATE_BLOCK)
    dot = functools.partial(jnp.einsum, precision=lax.Precision.HIGHEST)

    def one_position(state, x):
        q_t, k_t, v_t, g_t, beta_t, at = x
        if not precision["state_carry"]:
            state = jnp.where(at % RESET_EVERY == 0, 0.0, state)
        alpha = jnp.exp(g_t) if precision["decay"] else jnp.ones_like(g_t)
        state = alpha[:, None, None] * state
        write = v_t
        if precision["delta"]:
            write = v_t - dot("hkv,hk->hv", state, k_t)
        state = state + beta_t[:, None, None] * k_t[:, :, None] * write[:, None, :]
        return state, dot("hkv,hk->hv", state, q_t)

    @jax.checkpoint
    def one_block(state, xs):
        return lax.scan(one_position, state, xs)

    blocks = tuple(
        x.reshape(t // block, block, *x.shape[1:])
        for x in (q, k, v, g, beta, jnp.arange(t))
    )
    state, out = lax.scan(one_block, jnp.zeros((h, dk, v.shape[-1]), jnp.float32), blocks)
    return out.reshape(t, h, -1), state


def linear_attention(u, w, cfg: dict, precision: dict):
    """``u`` (B, T, d) the layer's normed input -> ``(out (B, T, d), the mean
    log decay, the final states (B, H_v, d_k, d_v))``. Between the two
    projections every head goes its own way (the filter is depthwise, the
    norms and the state a head's own), so that part runs ``HEAD_GROUPS``
    groups of consecutive heads one after another, each recomputed on the
    backward pass: a quarter of the float32 (T, 8,192) arrays alive at once."""
    s = dims(cfg)
    b, t, _ = u.shape
    hk, hv, dk, dv = s["hk"], s["hv"], s["dk"], s["dv"]
    keys, values = hk * dk, hv * dv
    groups = math.gcd(hk, HEAD_GROUPS)
    eps = cfg["rms_norm_eps"]

    def by_group(x, heads):  # (..., heads x width) -> (groups, ..., heads / groups x width)
        x = x.reshape(*x.shape[:-1], groups, (x.shape[-1] // heads) * (heads // groups))
        return jnp.moveaxis(x, -2, 0)

    taps = w("gdn.conv")  # (2 keys + values, L): a channel's filter is a row
    filters = tuple(
        by_group(f.T, heads).transpose(0, 2, 1)  # (groups, channels of the group, L)
        for f, heads in ((taps[:keys], hk), (taps[keys: 2 * keys], hk), (taps[2 * keys:], hv))
    )
    rates = (w("gdn.A_log").reshape(groups, -1), w("gdn.dt_bias").reshape(groups, -1))

    @jax.checkpoint
    def one_group(xs):
        q, k, v, z, b_, a_, fq, fk, fv, a_log, dt_bias = xs
        heads = lambda x, f, width: jax.nn.silu(causal_conv(x, f)).reshape(t, -1, width)  # noqa: E731
        q = l2_norm(heads(q, fq, dk)) * dk ** -0.5
        k = l2_norm(heads(k, fk, dk))
        v = heads(v, fv, dv)
        q, k = (jnp.repeat(x, hv // hk, axis=1) for x in (q, k))
        beta = jax.nn.sigmoid(b_)
        g = -jnp.exp(a_log) * jax.nn.softplus(a_ + dt_bias)
        o, state = delta_recurrence(q, k, v, g, beta, precision)
        y = rms_norm(o, w("gdn.norm.scale"), eps) * jax.nn.silu(z.reshape(t, -1, dv))
        return y.reshape(t, -1), jnp.sum(g), state

    outs, decays, states = [], [], []
    for n in range(b):  # a sequence at a time
        x = u[n]
        qkvz, ba = mm(x, w("gdn.qkvz.w")), mm(x, w("gdn.ba.w"))
        parts = (
            by_group(qkvz[:, :keys], hk), by_group(qkvz[:, keys: 2 * keys], hk),
            by_group(qkvz[:, 2 * keys: 2 * keys + values], hv),
            by_group(qkvz[:, 2 * keys + values:], hv),
            by_group(ba[:, :hv], hv), by_group(ba[:, hv:], hv),
        )
        y, g_sum, state = lax.map(one_group, (*parts, *filters, *rates))
        y = jnp.moveaxis(y, 0, 1).reshape(t, values)  # the groups' heads side by side again
        outs.append(mm(y, w("gdn.o.w")))
        decays.append(jnp.sum(g_sum) / (t * hv))
        states.append(state.reshape(hv, dk, dv))
    return jnp.stack(outs), sum(decays) / b, jnp.stack(states)


def partial_rope(x, theta: float, rotary: int):
    """Rotate-half rotary embedding of the first ``rotary`` columns of ``x``
    (T, H, D) at positions 0 .. T - 1; the other columns pass."""
    t = x.shape[0]
    inv = theta ** (-jnp.arange(0, rotary, 2, dtype=jnp.float32) / rotary)
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]  # (T, r / 2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., : rotary // 2], x[..., rotary // 2: rotary], x[..., rotary:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest), axis=-1)


def causal_attention(q, k, v):
    """One sequence: ``q`` (T, H, D); ``k``, ``v`` (T, H_kv, D), each K/V head
    serving its group of H / H_kv consecutive query heads. ``ATTN_ROWS`` query
    rows at a time, one head at a time inside."""
    t, h, d = q.shape
    group = h // k.shape[1]
    kh = jnp.repeat(k, group, axis=1).transpose(1, 0, 2)  # (H, T, D)
    vh = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)
    rows = math.gcd(t, ATTN_ROWS)

    @jax.checkpoint
    def one_block(qb, start):
        seen = jnp.arange(t)[None, :] <= (start + jnp.arange(rows))[:, None]

        @jax.checkpoint
        def one_head(_, qkv):
            qh, k1, v1 = qkv
            scores = mm(qh, k1.T) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            return None, mm(probs, v1)

        _, out = lax.scan(one_head, None, (qb.transpose(1, 0, 2), kh, vh))
        return out.transpose(1, 0, 2)

    out = lax.map(
        lambda blk: one_block(*blk),
        (q.reshape(t // rows, rows, h, d), jnp.arange(0, t, rows)),
    )
    return out.reshape(t, h, d)


def full_attention(u, w, cfg: dict, precision: dict):
    """``u`` (B, T, d) the layer's normed input -> (B, T, d)."""
    s = dims(cfg)
    b, t, _ = u.shape
    eps, theta, hd = cfg["rms_norm_eps"], float(cfg["rope_theta"]), s["hd"]
    rotary = int(hd * cfg["partial_rotary_factor"]) if precision["partial_rotary"] else hd
    outs = []
    for n in range(b):
        x = u[n]
        q_gate = mm(x, w("q.w")).reshape(t, s["h"], 2 * hd)
        q, gate = q_gate[..., :hd], q_gate[..., hd:]
        q = rms_norm(q, w("q_norm.scale"), eps)
        k = rms_norm(mm(x, w("k.w")).reshape(t, s["kv"], hd), w("k_norm.scale"), eps)
        v = mm(x, w("v.w")).reshape(t, s["kv"], hd)
        a = causal_attention(partial_rope(q, theta, rotary), partial_rope(k, theta, rotary), v)
        if precision["out_gate"]:
            a = a * jax.nn.sigmoid(gate)
        outs.append(mm(a.reshape(t, -1), w("o.w")))
    return jnp.stack(outs)


def routing_weights(x, router_w, cfg: dict, router_dtype):
    """``x`` (N, d) -> ``(weights, selected)``: (N, E) float32, zero off the
    selection, and the (N, k) selected ids, best first."""
    k, experts = cfg["num_experts_per_tok"], router_w.shape[1]
    if jnp.dtype(router_dtype) == jnp.float32:
        logits = mm(x, router_w)
    else:
        logits = jnp.matmul(
            x.astype(router_dtype), router_w.astype(router_dtype)
        ).astype(jnp.float32)
    p = jax.nn.softmax(logits, axis=-1)
    left, picked, chosen = p, jnp.zeros_like(p), []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)  # ties: the lower index
        hot = jax.nn.one_hot(best, experts, dtype=p.dtype)
        picked = picked + hot
        left = jnp.where(hot > 0, -jnp.inf, left)
        chosen.append(best)
    weights = picked * p
    if cfg["norm_topk_prob"]:
        weights = weights / weights.sum(axis=-1, keepdims=True)
    return lax.stop_gradient(picked) * weights, jnp.stack(chosen, axis=-1)


def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def expert_layer(u, w, cfg: dict, precision: dict, held=None, shared: bool = True):
    """The part of the expert layer's result that the ``held`` experts give,
    and with ``shared`` the gated shared expert's, which every share computes
    alike."""
    held = dims(cfg)["held"] if held is None else held
    x = u.reshape(-1, u.shape[-1])
    weights, chosen = routing_weights(x, w("router.w"), cfg, precision["router"])

    @jax.checkpoint
    def one_expert(ew):
        w1, w3, w2, column = ew
        return column[:, None] * gated_mlp(x, w1, w3, w2)

    # the sum's carry stays outside the recomputed part: a carry handed INTO
    # it would be kept once an expert for the backward pass (32 x 67 MB here)
    columns = weights[:, jnp.asarray(held)].T  # (held, N)
    y, _ = lax.scan(
        lambda y, ew: (y + one_expert(ew), None), jnp.zeros_like(x),
        (w("experts.w1"), w("experts.w3"), w("experts.w2"), columns),
    )
    if shared:
        every = gated_mlp(x, w("shared.w1"), w("shared.w3"), w("shared.w2"))
        if precision["shared_gate"]:
            every = every * jax.nn.sigmoid(mm(x, w("shared_gate.w")))
        y = y + every
    return y.reshape(u.shape), chosen


def layer(x, p, i: int, cfg: dict, precision: dict):
    """``(x, the selection, (mean log decay, final states) or None)``."""
    pre = f"layers.{i}."
    w = lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    u, state = rms_norm(x, w("op_norm.scale"), eps), None
    if layer_kinds(cfg)[i] == "linear_attention":
        a, decay, final = linear_attention(u, w, cfg, precision)
        state = (decay, final)
    else:
        a = full_attention(u, w, cfg, precision)
    x = x + a
    y, chosen = expert_layer(rms_norm(x, w("ffn_norm.scale"), eps), w, cfg, precision)
    return x + y, chosen, state


def hidden_states(p, tokens, cfg: dict, precision: dict):
    """The final RMSNorm's output, each layer's (N, k) expert selection, and
    of each linear layer (the mean log decay, the final states)."""
    x = p["embed"].astype(jnp.float32)[tokens]
    picks, states = [], []
    for i in expert_layers(cfg):
        x, chosen, state = jax.checkpoint(
            lambda x_, p_, i=i: layer(x_, p_, i, cfg, precision)
        )(x, p)
        picks.append(chosen)
        if state is not None:
            states.append(state)
    x = rms_norm(x, p["final_norm.scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    return x, picks, states


def _logits(rows, p, store):
    return mm(rows, p["head.w"].astype(jnp.float32)).astype(store).astype(jnp.float32)


def logits(p, tokens, cfg: dict, precision: dict = REFERENCE):
    """(B, T, vocab) float32 logits - for tests at sizes that hold them."""
    x, _, _ = hidden_states(p, tokens, cfg, precision)
    return _logits(x, p, jnp.dtype(precision["store"]))


def selections(p, tokens, cfg: dict, precision: dict = REFERENCE):
    """(layers, N, k) expert ids the forward pass selects."""
    return jnp.stack(hidden_states(p, tokens, cfg, precision)[1])


def state_stats(p, tokens, cfg: dict, precision: dict = REFERENCE):
    """``(the mean log decay over the linear layers, the largest
    root-mean-square of a linear layer's final states)``: what the program's
    two gauges read."""
    states = hidden_states(p, tokens, cfg, precision)[2]
    return (
        sum(decay for decay, _ in states) / len(states),
        jnp.max(jnp.stack([jnp.sqrt(jnp.mean(jnp.square(final))) for _, final in states])),
    )


def loss(p, tokens, labels, cfg: dict, precision: dict = REFERENCE):
    """Mean token cross-entropy, ``LOSS_ROWS`` rows of logits at a time."""
    store = jnp.dtype(precision["store"])
    x, _, _ = hidden_states(p, tokens, cfg, precision)
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    chunk = math.gcd(n, LOSS_ROWS)

    @jax.checkpoint
    def chunk_loss(xy):
        xs, ys = xy
        logp = jax.nn.log_softmax(_logits(xs, p, store), axis=-1)
        return -jnp.take_along_axis(logp, ys[:, None], axis=-1).sum()

    sums = lax.map(
        chunk_loss, (rows.reshape(n // chunk, chunk, -1),
                     labels.reshape(n // chunk, chunk)),
    )
    return sums.sum() / n


# -- three steps of Adam ---------------------------------------------------------


def _norms(tree: dict) -> dict:
    return {
        n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for n, a in tree.items()
    }


def make_step(cfg: dict, prog: dict, precision: dict = REFERENCE):
    """``(p, m, v, t, tokens, labels) -> (p, m, v, loss, grad_norms)``: one
    Adam step as ``optax.adam`` defines it, state donated. One jitted function
    per (configuration, program, precision): a second run of the same three is
    not compiled again."""
    return _make_step(*(json.dumps(a, sort_keys=True) for a in (cfg, prog, precision)))


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, prog_json: str, precision_json: str):
    cfg, prog, precision = (json.loads(a) for a in (cfg_json, prog_json, precision_json))
    lr, store = float(prog["learning_rate"]), jnp.dtype(precision["store"])

    def step(p, m, v, t, tokens, labels):
        value, g = jax.value_and_grad(loss)(p, tokens, labels, cfg, precision)
        g = {n: a.astype(jnp.float32) for n, a in g.items()}
        c1, c2 = 1.0 - ADAM_B1 ** t, 1.0 - ADAM_B2 ** t

        def leaf(n):
            m1 = ADAM_B1 * m[n].astype(jnp.float32) + (1 - ADAM_B1) * g[n]
            v1 = ADAM_B2 * v[n].astype(jnp.float32) + (1 - ADAM_B2) * g[n] ** 2
            upd = lr * (m1 / c1) / (jnp.sqrt(v1 / c2) + ADAM_EPS)
            p1 = p[n].astype(jnp.float32) - upd
            return p1.astype(store), m1.astype(store), v1.astype(store)

        new = {n: leaf(n) for n in p}
        return (
            {n: new[n][0] for n in p}, {n: new[n][1] for n in p},
            {n: new[n][2] for n in p}, value, _norms(g),
        )

    return jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _delta_fn(shapes: tuple, stds: tuple[float, float]):
    def norms(p, key):
        return {
            n: jnp.sqrt(jnp.sum(jnp.square(
                p[n].reshape(shape).astype(jnp.float32)
                - init_leaf(n, shape, i, key, stds)
            )))
            for i, (n, shape) in enumerate(shapes)
        }

    return jax.jit(norms)


def delta_norms(p: dict, cfg: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of ``p`` minus the seed's initial weights, which
    are made again inside the reductions (one program, no second copy of
    the model held)."""
    shapes = tuple(param_shapes(cfg).items())
    out = _delta_fn(shapes, leaf_stds(cfg))(p, seed_key(seed))
    return {n: float(v) for n, v in out.items()}


def follow(cfg: dict, prog: dict, seed: int, batches, precision: dict = REFERENCE):
    """Drive the seed's weights through ``batches`` (the first steps of the
    run). Returns each step's loss, the first gradient's norm per leaf and
    the norm of the parameters' change per leaf after the last step."""
    store = jnp.dtype(precision["store"])
    p = {n: a.astype(store) for n, a in init_params(cfg, seed).items()}
    m = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    v = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    step = make_step(cfg, prog, precision)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        p, m, v, value, norms = step(
            p, m, v, jnp.float32(t), jnp.asarray(tokens), jnp.asarray(labels)
        )
        losses.append(float(value))
        if grad_norms is None:
            grad_norms = {n: float(a) for n, a in norms.items()}
    del m, v
    return {
        "losses": losses, "grad_norms": grad_norms,
        "delta_norms": delta_norms(p, cfg, seed),
    }

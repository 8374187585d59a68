"""Plain reference: an ``lfm2_moe`` decoder (LFM2-24B-A2B's family), its loss,
gradients and Adam, for the experts one device holds.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``. Layer i: ``h = x + Op_i(RMSNorm(x))``,
``y = h + FF_i(RMSNorm(h))``; ``Op_i`` the gated short convolution or
grouped-query attention (per-head RMSNorm on q and k, rotate-half RoPE,
causal softmax) as ``layer_types`` says; ``FF_i`` the gated SiLU MLP for the
first ``num_dense_layers`` layers and the expert layer after: sigmoid
scores, the ``num_experts_per_tok`` largest of ``p + b`` selected (``b``
picks, ``p`` weighs), weights renormalised over the selected. No kernel, no
sort, no gather: the selection is ``k`` rounds of argmax, and EVERY held
expert is applied to ALL tokens and masked by its routing weight. The held
subset is a list of expert ids; what the absent experts would add is left
out. A final RMSNorm and an untied head; the loss is the mean token
cross-entropy. No bias anywhere, no auxiliary loss.

It imports nothing of the program and takes nothing the program has made:
weights and the selection bias come from :func:`init_params` and
:func:`select_bias` (seeded; the runner puts the same into the trainer), the
batches from the benchmark's traffic generator. To fit beside nothing else
on one 16 GB chip at the published widths it recomputes each layer, each
attention head, each expert and each block of logits rows on the backward
pass (``jax.checkpoint``) - that changes memory, not values.

``precision`` selects the control, the same mathematics one step down:
``router`` "float32" or "bfloat16" (the router's operands and logits),
``store`` "float32" or "bfloat16" (parameters, Adam moments and logits).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE = {"router": "float32", "store": "float32"}
#: one step below what the configuration states (f32 router, f32 state)
CONTROL = {"router": "bfloat16", "store": "bfloat16"}

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 2048  # rows of logits alive at once
SELECT_BIAS_STD = 0.01
RENORM_EPS = 1e-6


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    held = list(cfg.get("held_experts", range(cfg["num_experts"])))
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"],
        "hd": cfg.get("head_dim") or d // h,
        "f": cfg["intermediate_size"], "fe": cfg["moe_intermediate_size"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "taps": cfg["conv_L_cache"], "held": held,
        "experts": cfg.get("router_num_experts", cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
    }


def expert_layers(cfg: dict) -> list[int]:
    return list(range(cfg["num_dense_layers"], cfg["num_hidden_layers"]))


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in a fixed order (the order seeds the leaves)."""
    s = dims(cfg)
    d, hd, n_held = s["d"], s["hd"], len(s["held"])
    shapes: dict[str, tuple[int, ...]] = {"embed": (s["v"], d)}
    for i, kind in enumerate(cfg["layer_types"]):
        p = f"layers.{i}."
        shapes[p + "op_norm.scale"] = (d,)
        if kind == "conv":
            shapes.update({
                p + "conv.in.w": (d, 3 * d), p + "conv.filter": (d, s["taps"]),
                p + "conv.out.w": (d, d),
            })
        elif kind == "full_attention":
            shapes.update({
                p + "q.w": (d, s["h"] * hd), p + "k.w": (d, s["kv"] * hd),
                p + "v.w": (d, s["kv"] * hd), p + "o.w": (s["h"] * hd, d),
                p + "q_norm.scale": (hd,), p + "k_norm.scale": (hd,),
            })
        else:
            raise ValueError(f"layer type {kind!r}")
        shapes[p + "ffn_norm.scale"] = (d,)
        if i < cfg["num_dense_layers"]:
            shapes.update({
                p + "mlp.w1": (d, s["f"]), p + "mlp.w3": (d, s["f"]),
                p + "mlp.w2": (s["f"], d),
            })
        else:
            shapes.update({
                p + "router.w": (d, s["experts"]),
                p + "experts.w1": (n_held, d, s["fe"]),
                p + "experts.w3": (n_held, d, s["fe"]),
                p + "experts.w2": (n_held, s["fe"], d),
            })
    shapes.update({"final_norm.scale": (d,), "head.w": (d, s["v"])})
    return shapes


def seed_key(seed: int) -> jax.Array:
    """A key from any non-negative whole number (seeds pass 2**31)."""
    return jax.random.fold_in(
        jax.random.PRNGKey(seed & 0x7FFFFFFF), (seed >> 31) & 0x7FFFFFFF
    )


def init_leaf(name: str, shape, index: int, key, std: float) -> jax.Array:
    noise = std * jax.random.normal(
        jax.random.fold_in(key, index), shape, jnp.float32
    )
    return 1.0 + noise if name.endswith(".scale") else noise


def init_params(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """Every leaf random (the norms' weights too, so that none is a no-op),
    float32, made on the device in one jitted call."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])

    def make(key):
        return {
            n: init_leaf(n, s, i, key, std)
            for i, (n, s) in enumerate(shapes.items())
        }

    return jax.jit(make)(seed_key(seed))


def select_bias(cfg: dict, seed: int) -> jax.Array:
    """(expert layers, experts) float32: the router's selection bias, small,
    non-zero, fixed for the run (it is no trained leaf)."""
    shape = (len(expert_layers(cfg)), dims(cfg)["experts"])
    if not cfg["use_expert_bias"]:
        return jnp.zeros(shape, jnp.float32)
    key = jax.random.fold_in(seed_key(seed), 0x5E1EC7)
    return SELECT_BIAS_STD * jax.random.normal(key, shape, jnp.float32)


# -- forward -------------------------------------------------------------------


def mm(a, b):
    """a (..., m, k) @ b (..., k, n), float32 at the highest precision."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def rope(x, base: float):
    """Rotate-half rotary embedding; ``x`` is (B, T, H, D), positions 0..T-1."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1)


def short_conv(u, w_in, filt, w_out):
    """``[B, C, z] = split(u W_in)``; ``s = B z``; ``c_t = sum_j filt[:, j]
    s_{t-(L-1)+j}`` with zeros to the left; ``(C c) W_out``."""
    t, taps = u.shape[1], filt.shape[1]
    b, c, z = jnp.split(mm(u, w_in), 3, axis=-1)
    padded = jnp.pad(b * z, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(filt[:, j] * padded[:, j:j + t] for j in range(taps))
    return mm(c * conv, w_out)


def causal_attention(q, k, v):
    """q (B, T, H, D); k, v (B, T, H_kv, D), each KV head serving its group
    of H / H_kv consecutive query heads. One head at a time."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, d)  # noqa: E731
    visible = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = mm(qh, kh.T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    out = lax.map(one_head, (heads(q), heads(k), heads(v)))
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def attention(u, w, cfg: dict):
    s = dims(cfg)
    b, t, _ = u.shape
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    q = mm(u, w("q.w")).reshape(b, t, s["h"], s["hd"])
    k = mm(u, w("k.w")).reshape(b, t, s["kv"], s["hd"])
    v = mm(u, w("v.w")).reshape(b, t, s["kv"], s["hd"])
    q = rope(rms_norm(q, w("q_norm.scale"), eps), theta)
    k = rope(rms_norm(k, w("k_norm.scale"), eps), theta)
    return mm(causal_attention(q, k, v).reshape(b, t, -1), w("o.w"))


def routing_weights(x, router_w, bias, cfg: dict, router_dtype):
    """``x`` (N, d) -> ``(weights, selected)``: (N, E) float32, zero off the
    selection, and the (N, k) selected ids, best first."""
    k, experts = cfg["num_experts_per_tok"], router_w.shape[1]
    if jnp.dtype(router_dtype) == jnp.float32:
        logits = mm(x, router_w)
    else:
        logits = jnp.matmul(
            x.astype(router_dtype), router_w.astype(router_dtype)
        ).astype(jnp.float32)
    p = jax.nn.sigmoid(logits)
    left = p + bias  # the bias picks; p alone weighs
    picked = jnp.zeros_like(p)
    selected = []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)  # ties: the lower index
        hot = jax.nn.one_hot(best, experts, dtype=p.dtype)
        picked = picked + hot
        left = jnp.where(hot > 0, -jnp.inf, left)
        selected.append(best)
    weights = picked * p
    if cfg["norm_topk_prob"]:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + RENORM_EPS)
    weights = weights * cfg["routed_scaling_factor"]
    return lax.stop_gradient(picked) * weights, jnp.stack(selected, axis=-1)


def expert_layer(u, w, bias, cfg: dict, router_dtype, held=None):
    """The part of the expert layer's result that the ``held`` experts give."""
    held = dims(cfg)["held"] if held is None else held
    x = u.reshape(-1, u.shape[-1])
    weights, selected = routing_weights(x, w("router.w"), bias, cfg, router_dtype)

    @jax.checkpoint
    def one_expert(y, ew):
        w1, w3, w2, column = ew
        out = mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)
        return y + column[:, None] * out, None

    columns = weights[:, jnp.asarray(held)].T  # (held, N)
    y, _ = lax.scan(
        one_expert, jnp.zeros_like(x),
        (w("experts.w1"), w("experts.w3"), w("experts.w2"), columns),
    )
    return y.reshape(u.shape), selected


def layer(x, p, bias, i: int, cfg: dict, precision: dict):
    pre = f"layers.{i}."
    w = lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    eps = cfg["norm_eps"]
    u = rms_norm(x, w("op_norm.scale"), eps)
    if cfg["layer_types"][i] == "conv":
        x = x + short_conv(u, w("conv.in.w"), w("conv.filter"), w("conv.out.w"))
    else:
        x = x + attention(u, w, cfg)
    u = rms_norm(x, w("ffn_norm.scale"), eps)
    if i < cfg["num_dense_layers"]:
        y = mm(jax.nn.silu(mm(u, w("mlp.w1"))) * mm(u, w("mlp.w3")), w("mlp.w2"))
        return x + y, jnp.zeros((0,), jnp.int32)
    y, selected = expert_layer(u, w, bias, cfg, precision["router"])
    return x + y, selected


def hidden_states(p, fixed, tokens, cfg: dict, precision: dict):
    """The final RMSNorm's output and each expert layer's (N, k) selection."""
    x = p["embed"].astype(jnp.float32)[tokens]
    first, picks = cfg["num_dense_layers"], []
    for i in range(cfg["num_hidden_layers"]):
        bias = fixed[i - first] if i >= first else None
        x, selected = jax.checkpoint(
            lambda x_, p_, b_, i=i: layer(x_, p_, b_, i, cfg, precision)
        )(x, p, bias)
        if i >= first:
            picks.append(selected)
    x = rms_norm(x, p["final_norm.scale"].astype(jnp.float32), cfg["norm_eps"])
    return x, picks


def _logits(rows, p, store):
    return mm(rows, p["head.w"].astype(jnp.float32)).astype(store).astype(jnp.float32)


def logits(p, fixed, tokens, cfg: dict, precision: dict = REFERENCE):
    """(B, T, vocab) float32 logits - for tests at sizes that hold them."""
    x, _ = hidden_states(p, fixed, tokens, cfg, precision)
    return _logits(x, p, jnp.dtype(precision["store"]))


def selections(p, fixed, tokens, cfg: dict, precision: dict = REFERENCE):
    """(expert layers, N, k) expert ids the forward pass selects."""
    return jnp.stack(hidden_states(p, fixed, tokens, cfg, precision)[1])


def mean_loss(p, fixed, tokens, labels, cfg: dict, precision: dict = REFERENCE):
    """Mean token cross-entropy over the batch, ``LOSS_ROWS`` rows at a time."""
    store = jnp.dtype(precision["store"])
    x, _ = hidden_states(p, fixed, tokens, cfg, precision)
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
    """``(p, m, v, fixed, t, tokens, labels) -> (p, m, v, loss, grad_norms)``:
    one Adam step as ``optax.adam`` defines it, state donated."""
    lr, store = float(prog["learning_rate"]), jnp.dtype(precision["store"])

    def step(p, m, v, fixed, t, tokens, labels):
        loss, g = jax.value_and_grad(mean_loss)(
            p, fixed, tokens, labels, cfg, precision
        )
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
            {n: new[n][2] for n in p}, loss, _norms(g),
        )

    return jax.jit(step, donate_argnums=(0, 1, 2))


@functools.lru_cache(maxsize=None)
def _delta_fn(shapes: tuple, std: float):
    def norms(p, key):
        return {
            n: jnp.sqrt(jnp.sum(jnp.square(
                p[n].reshape(shape).astype(jnp.float32)
                - init_leaf(n, shape, i, key, std)
            )))
            for i, (n, shape) in enumerate(shapes)
        }

    return jax.jit(norms)


def delta_norms(p: dict, cfg: dict, seed: int) -> dict[str, float]:
    """Per leaf, the norm of ``p`` minus the seed's initial weights, which
    are made again inside the reductions (one program, no second copy of
    the model held)."""
    shapes = tuple(param_shapes(cfg).items())
    out = _delta_fn(shapes, float(cfg["initializer_range"]))(p, seed_key(seed))
    return {n: float(v) for n, v in out.items()}


def follow(cfg: dict, prog: dict, seed: int, batches, precision: dict = REFERENCE):
    """Drive the seed's weights through ``batches`` (the first steps of the
    run). Returns each step's loss, the first gradient's norm per leaf and the
    norm of the parameters' change per leaf after the last step."""
    store = jnp.dtype(precision["store"])
    p = {n: a.astype(store) for n, a in init_params(cfg, seed).items()}
    m = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    v = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    fixed = select_bias(cfg, seed)
    step = make_step(cfg, prog, precision)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        p, m, v, loss, norms = step(
            p, m, v, fixed, jnp.float32(t), jnp.asarray(tokens), jnp.asarray(labels)
        )
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {n: float(a) for n, a in norms.items()}
    del m, v
    return {
        "losses": losses, "grad_norms": grad_norms,
        "delta_norms": delta_norms(p, cfg, seed),
    }

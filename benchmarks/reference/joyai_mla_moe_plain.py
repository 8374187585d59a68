"""Plain reference: a ``joyai_llm_flash`` decoder (JoyAI-LLM-Flash; the
DeepSeek-V3 dialect), both of its losses, gradients and Adam, for the experts
one device holds.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``. Layer i: ``h = x + MLA(RMSNorm(x))``,
``y = h + FF_i(RMSNorm(h))``.

*Latent attention.* ``c_q = RMSNorm(x W_qa)``; ``[q_nope | q_r] = c_q W_qb``
per head. ``[c_kv | k_r] = x W_kva``; ``c_kv = RMSNorm(c_kv)``;
``[k_nope | v] = c_kv W_kvb`` per head. ``q = [q_nope | RoPE(q_r)]``,
``k = [k_nope | RoPE(k_r)]`` with the one ``k_r`` broadcast to every head
(rotate-half RoPE: the checkpoint's interleaved layout is the same function
up to a fixed permutation of the rotary columns, applied to q and k alike);
scores scaled by ``(nope + rope) ** -0.5``; causal softmax, one head at a
time so that 8,192 positions fit; ``[T, H x v] W_o``.

*Feed-forward.* The gated SiLU MLP for the first ``first_k_dense_replace``
layers; the expert layer after: sigmoid scores, the ``num_experts_per_tok``
largest of ``p + b`` selected (``b`` picks, ``p`` weighs; one group),
weights renormalised over the selected and scaled by
``routed_scaling_factor``. No kernel, no sort, no gather: the selection is
``k`` rounds of argmax, and EVERY held expert is applied to ALL tokens and
masked by its routing weight. The held subset is a list of expert ids; what
the absent experts would add is left out. The shared expert, a gated SiLU
MLP of width ``n_shared_experts x moe_intermediate_size``, is applied once
to every token and added unweighted.

*Multi-token prediction* (depth 1; DeepSeek-V3's report, section 2.2):
``h' = [RMSNorm_e(Emb(t_{i+1})) | RMSNorm_h(h_i)] W_eh`` with ``h_i`` the last
layer's output BEFORE the final norm; one more latent-attention + expert
layer over ``h'``; its own final RMSNorm; the shared embedding and head;
cross-entropy against ``t_{i+2}``. The last position has no target and
weighs 0; both losses divide by all T positions. The step minimises
``main + mtp_loss_weight x MTP``.

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
import json
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
MTP = "mtp.layer."  # the prediction module's decoder layer, named as a layer


def dims(cfg: dict) -> dict:
    held = list(cfg.get("held_experts", range(cfg["n_routed_experts"])))
    return {
        "d": cfg["hidden_size"], "h": cfg["num_attention_heads"],
        "q_rank": cfg["q_lora_rank"], "kv_rank": cfg["kv_lora_rank"],
        "nope": cfg["qk_nope_head_dim"], "rope": cfg["qk_rope_head_dim"],
        "vd": cfg["v_head_dim"], "f": cfg["intermediate_size"],
        "fe": cfg["moe_intermediate_size"],
        "fs": cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"],
        "dense": cfg["first_k_dense_replace"], "held": held,
        "experts": cfg.get("router_num_experts", cfg["n_routed_experts"]),
        "k": cfg["num_experts_per_tok"],
        "mtp": cfg["num_nextn_predict_layers"],
    }


def layer_prefixes(cfg: dict) -> list[str]:
    """Every decoder layer's prefix, the prediction module's last."""
    s = dims(cfg)
    return [f"layers.{i}." for i in range(s["layers"])] + [MTP] * s["mtp"]


def expert_layers(cfg: dict) -> list[str]:
    """Prefixes of the layers with experts, in the order of the selection
    bias's rows and of the program's row counters."""
    return layer_prefixes(cfg)[dims(cfg)["dense"]:]


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in a fixed order (the order seeds the leaves)."""
    s = dims(cfg)
    d, h, n_held = s["d"], s["h"], len(s["held"])
    shapes: dict[str, tuple[int, ...]] = {"embed": (s["v"], d)}

    def layer(p: str, dense: bool) -> None:
        shapes.update({
            p + "op_norm.scale": (d,),
            p + "q_a.w": (d, s["q_rank"]), p + "q_a_norm.scale": (s["q_rank"],),
            p + "q_b.w": (s["q_rank"], h * (s["nope"] + s["rope"])),
            p + "kv_a.w": (d, s["kv_rank"] + s["rope"]),
            p + "kv_a_norm.scale": (s["kv_rank"],),
            p + "kv_b.w": (s["kv_rank"], h * (s["nope"] + s["vd"])),
            p + "o.w": (h * s["vd"], d),
            p + "ffn_norm.scale": (d,),
        })
        if dense:
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
                p + "shared.w1": (d, s["fs"]), p + "shared.w3": (d, s["fs"]),
                p + "shared.w2": (s["fs"], d),
            })

    for i in range(s["layers"]):
        layer(f"layers.{i}.", i < s["dense"])
    if s["mtp"]:
        shapes.update({
            "mtp.enorm.scale": (d,), "mtp.hnorm.scale": (d,),
            "mtp.eh_proj.w": (2 * d, d),  # rows: the embedding's half, then h's
        })
        layer(MTP, False)
        shapes["mtp.final_norm.scale"] = (d,)
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
    """(expert layers, experts) float32: the router's selection bias
    (``noaux_tc``'s correction), small, non-zero, fixed for the run (it is
    no trained leaf). The prediction module's layer has the last row."""
    shape = (len(expert_layers(cfg)), dims(cfg)["experts"])
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


def causal_attention(q, k, v, scale: float):
    """q, k (B, T, H, D); v (B, T, H, Dv). One head at a time."""
    b, t, h, _ = q.shape
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, -1)  # noqa: E731
    visible = jnp.tril(jnp.ones((t, t), bool))

    @jax.checkpoint
    def one_head(qkv):
        qh, kh, vh = qkv
        scores = mm(qh, kh.T) * scale
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    out = lax.map(one_head, (heads(q), heads(k), heads(v)))
    return out.reshape(b, h, t, -1).transpose(0, 2, 1, 3)


def latent_attention(u, w, cfg: dict):
    s = dims(cfg)
    b, t, _ = u.shape
    h, nope, eps = s["h"], s["nope"], cfg["rms_norm_eps"]
    theta = float(cfg["rope_theta"])
    c_q = rms_norm(mm(u, w("q_a.w")), w("q_a_norm.scale"), eps)
    q = mm(c_q, w("q_b.w")).reshape(b, t, h, nope + s["rope"])
    latent = mm(u, w("kv_a.w"))
    c_kv = rms_norm(latent[..., : s["kv_rank"]], w("kv_a_norm.scale"), eps)
    kv = mm(c_kv, w("kv_b.w")).reshape(b, t, h, nope + s["vd"])
    k_r = rope(latent[..., s["kv_rank"]:][:, :, None, :], theta)
    q = jnp.concatenate((q[..., :nope], rope(q[..., nope:], theta)), axis=-1)
    k = jnp.concatenate(
        (kv[..., :nope], jnp.broadcast_to(k_r, (b, t, h, s["rope"]))), axis=-1
    )
    out = causal_attention(q, k, kv[..., nope:], (nope + s["rope"]) ** -0.5)
    return mm(out.reshape(b, t, -1), w("o.w"))


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


def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def routed_experts(u, w, bias, cfg: dict, router_dtype, held=None):
    """The part of the routed experts' result that the ``held`` experts give."""
    held = dims(cfg)["held"] if held is None else held
    x = u.reshape(-1, u.shape[-1])
    weights, selected = routing_weights(x, w("router.w"), bias, cfg, router_dtype)

    @jax.checkpoint
    def one_expert(y, ew):
        w1, w3, w2, column = ew
        return y + column[:, None] * gated_mlp(x, w1, w3, w2), None

    columns = weights[:, jnp.asarray(held)].T  # (held, N)
    y, _ = lax.scan(
        one_expert, jnp.zeros_like(x),
        (w("experts.w1"), w("experts.w3"), w("experts.w2"), columns),
    )
    return y.reshape(u.shape), selected


def expert_layer(u, w, bias, cfg: dict, router_dtype, held=None, shared=True):
    """Held routed experts plus (``shared``) the shared expert, unweighted:
    every device computes it alike, so of a layer's shares one counts it."""
    y, selected = routed_experts(u, w, bias, cfg, router_dtype, held)
    if shared:
        y = y + gated_mlp(u, w("shared.w1"), w("shared.w3"), w("shared.w2"))
    return y, selected


def layer(x, p, bias, pre: str, dense: bool, cfg: dict, precision: dict):
    w = lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    x = x + latent_attention(rms_norm(x, w("op_norm.scale"), eps), w, cfg)
    u = rms_norm(x, w("ffn_norm.scale"), eps)
    if dense:
        return x + gated_mlp(u, w("mlp.w1"), w("mlp.w3"), w("mlp.w2")), jnp.zeros((0,), jnp.int32)
    y, selected = expert_layer(u, w, bias, cfg, precision["router"])
    return x + y, selected


def hidden_states(p, fixed, tokens, next_tokens, cfg: dict, precision: dict):
    """``(x, x_mtp, picks)``: the final RMSNorm's output, the prediction
    module's (None without one) and each expert layer's (N, k) selection."""
    s, eps = dims(cfg), cfg["rms_norm_eps"]
    f32 = lambda n: p[n].astype(jnp.float32)  # noqa: E731
    picks = []

    def run(x, pre, dense):
        bias = None if dense else fixed[len(picks)]
        x, selected = jax.checkpoint(
            lambda x_, p_, b_: layer(x_, p_, b_, pre, dense, cfg, precision)
        )(x, p, bias)
        if not dense:
            picks.append(selected)
        return x

    x = f32("embed")[tokens]
    for i in range(s["layers"]):
        x = run(x, f"layers.{i}.", i < s["dense"])
    out = rms_norm(x, f32("final_norm.scale"), eps)
    if not s["mtp"]:
        return out, None, picks
    merged = jnp.concatenate((
        rms_norm(f32("embed")[next_tokens], f32("mtp.enorm.scale"), eps),
        rms_norm(x, f32("mtp.hnorm.scale"), eps),
    ), axis=-1)
    x = run(mm(merged, f32("mtp.eh_proj.w")), MTP, False)
    return out, rms_norm(x, f32("mtp.final_norm.scale"), eps), picks


def _logits(rows, p, store):
    return mm(rows, p["head.w"].astype(jnp.float32)).astype(store).astype(jnp.float32)


def logits(p, fixed, tokens, next_tokens, cfg: dict, precision: dict = REFERENCE):
    """``(main, mtp)`` (B, T, vocab) float32 logits - for tests at sizes that
    hold them; ``mtp`` None without a prediction module."""
    x, x_mtp, _ = hidden_states(p, fixed, tokens, next_tokens, cfg, precision)
    store = jnp.dtype(precision["store"])
    return _logits(x, p, store), None if x_mtp is None else _logits(x_mtp, p, store)


def selections(p, fixed, tokens, next_tokens, cfg: dict, precision: dict = REFERENCE):
    """(expert layers, N, k) expert ids the forward pass selects."""
    return jnp.stack(hidden_states(p, fixed, tokens, next_tokens, cfg, precision)[2])


def _token_loss(x, labels, weights, p, store):
    """Sum of ``weights`` x token cross-entropy over all positions divided by
    their number, ``LOSS_ROWS`` rows at a time."""
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    chunk = math.gcd(n, LOSS_ROWS)

    @jax.checkpoint
    def chunk_loss(xyw):
        xs, ys, ws = xyw
        logp = jax.nn.log_softmax(_logits(xs, p, store), axis=-1)
        return -(jnp.take_along_axis(logp, ys[:, None], axis=-1)[:, 0] * ws).sum()

    split = lambda a: a.reshape(n // chunk, chunk, *a.shape[1:])  # noqa: E731
    sums = lax.map(chunk_loss, (split(rows), split(labels.reshape(-1)),
                                split(weights.reshape(-1))))
    return sums.sum() / n


def both_losses(p, fixed, tokens, labels, cfg: dict, precision: dict = REFERENCE):
    """``(main, mtp)``: the mean token cross-entropy of the next token, and
    of the token after it from the prediction module (0 without one)."""
    store = jnp.dtype(precision["store"])
    x, x_mtp, _ = hidden_states(p, fixed, tokens, labels, cfg, precision)
    main = _token_loss(x, labels, jnp.ones(labels.shape, jnp.float32), p, store)
    if x_mtp is None:
        return main, jnp.float32(0.0)
    has_target = jnp.broadcast_to(
        jnp.arange(labels.shape[1]) < labels.shape[1] - 1, labels.shape
    ).astype(jnp.float32)
    further = jnp.roll(labels, -1, axis=1)  # position i: the token after labels[i]
    return main, _token_loss(x_mtp, further, has_target, p, store)


def mean_loss(p, fixed, tokens, labels, cfg: dict, precision: dict = REFERENCE):
    """What the step minimises, and both of its terms beside it."""
    main, mtp = both_losses(p, fixed, tokens, labels, cfg, precision)
    weight = float(cfg["program"].get("mtp_loss_weight", 0.3))
    return main + weight * mtp, (main, mtp)


# -- three steps of Adam ---------------------------------------------------------


def _norms(tree: dict) -> dict:
    return {
        n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for n, a in tree.items()
    }


def make_step(cfg: dict, prog: dict, precision: dict = REFERENCE):
    """``(p, m, v, fixed, t, tokens, labels) -> (p, m, v, (main, mtp),
    grad_norms)``: one Adam step as ``optax.adam`` defines it, state donated.
    One jitted step per (configuration, precision), so that following
    several seeds compiles once."""
    return _make_step(
        json.dumps(cfg, sort_keys=True), float(prog["learning_rate"]),
        tuple(sorted(precision.items())),
    )


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, lr: float, precision_items: tuple):
    cfg, precision = json.loads(cfg_json), dict(precision_items)
    store = jnp.dtype(precision["store"])

    def step(p, m, v, fixed, t, tokens, labels):
        (_, losses), g = jax.value_and_grad(mean_loss, has_aux=True)(
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
            {n: new[n][2] for n in p}, losses, _norms(g),
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
    run). Returns ``losses`` (each step's main loss, then each step's
    prediction-module loss: one list, so that one gap holds both), the first
    gradient's norm per leaf and the norm of the parameters' change per leaf
    after the last step."""
    store = jnp.dtype(precision["store"])
    p = {n: a.astype(store) for n, a in init_params(cfg, seed).items()}
    m = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    v = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    fixed = select_bias(cfg, seed)
    step = make_step(cfg, prog, precision)
    main, further, grad_norms = [], [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        p, m, v, losses, norms = step(
            p, m, v, fixed, jnp.float32(t), jnp.asarray(tokens), jnp.asarray(labels)
        )
        main.append(float(losses[0]))
        further.append(float(losses[1]))
        if grad_norms is None:
            grad_norms = {n: float(a) for n, a in norms.items()}
    del m, v
    return {
        "losses": main + further if dims(cfg)["mtp"] else main,
        "grad_norms": grad_norms,
        "delta_norms": delta_norms(p, cfg, seed),
    }

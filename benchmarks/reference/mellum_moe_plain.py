"""Plain reference: a ``mellum`` decoder (Mellum2-12B-A2.5B's family), its
loss, gradients and Adam, for the experts one device holds.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``. Layer l of kind ``layer_types[l]``, on the residual
stream ``x`` (T, d):

- ``x' = RMSNorm(x)``; ``q = x' W_q`` (T, H, head), ``k = x' W_k``, ``v = x'
  W_v`` (T, H_kv, head): no bias, no per-head norm, no gate. ``q, k`` rotated
  over ALL columns of the head, rotate-half pairing, by the rule of the
  layer's kind (``rope_parameters[layer_types[l]]``): ``default``, angles
  ``p * theta^(-2i/head)``; ``yarn``, :func:`yarn_table`'s frequencies,
  written out here from the formulas and imported from nowhere, cos and sin
  both times ``attention_factor``. ``a = softmax(q k^T / sqrt(head) + mask)
  v``, each ``H / H_kv`` query heads on one K/V head; ``x <- x + a W_o``.
  ``full_attention``: key j visible to query i iff ``0 <= i - j``.
  ``sliding_attention``: iff ``0 <= i - j < sliding_window``, an explicit
  mask. One head at a time, ``ATTN_ROWS`` query rows at a time.
- ``x'' = RMSNorm(x)``; ``p = softmax(x'' W_r)`` over all experts in float32,
  the ``num_experts_per_tok`` largest selected by that many rounds of argmax
  (ties: the lower index), weights ``p_sel / sum(p_sel)`` (no epsilon, no
  scale); EVERY held expert ``W2_e (silu(W1_e x'') * W3_e x'')`` applied to
  ALL tokens and masked by its routing weight (no kernel, no sort, no
  gather); ``x <- x + y``. No shared expert, no dense feed-forward in any
  layer: ``intermediate_size`` is read by nothing. What the absent experts
  would add is left out.

A final RMSNorm, an untied head; the loss is the mean token cross-entropy.
No bias anywhere, no auxiliary loss, no selection bias, no prediction module.

Departures from the description, each noted where it is made: rotate-half
pairing whatever layout a checkpoint has (seeded weights have none); to fit
on one 16 GB chip at the published widths each layer, each block of
attention rows, each expert and each block of logits rows is recomputed on
the backward pass (``jax.checkpoint``) - that changes memory, not values.

It imports nothing of the program and takes nothing the program has made:
weights come from :func:`init_params` (seeded, the embedding's rows at a
spread of their own where the configuration gives one: :func:`leaf_stds`;
the runner puts the same into the trainer), the batches from the benchmark's
traffic generator.

``precision`` selects a control: ``router`` and ``store`` "float32" or
"bfloat16" (:data:`CONTROL`, the same mathematics one step down); ``window``
None leaves the window out of the sliding layers (:data:`NO_WINDOW`), 0.5
halves it (:data:`HALF_WINDOW`: 512 at the published 1,024, the band of the
configuration this dialect was first read for); ``attention_factor`` false
leaves YaRN's factor at 1 (:data:`NO_ATTENTION_FACTOR`). Each must fail the
cell's check.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE = {"router": "float32", "store": "float32", "window": 1,
             "attention_factor": True}
#: one step below what the configuration states (f32 router, f32 state)
CONTROL = {**REFERENCE, "router": "bfloat16", "store": "bfloat16"}
#: the three faults this architecture makes easy: full causal attention in
#: the sliding layers, another configuration's narrower band, and YaRN's
#: factor forgotten on cos and sin
NO_WINDOW = {**REFERENCE, "window": None}
HALF_WINDOW = {**REFERENCE, "window": 0.5}
NO_ATTENTION_FACTOR = {**REFERENCE, "attention_factor": False}

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 2048  # rows of logits alive at once
ATTN_ROWS = 2048  # query rows of one head's scores alive at once


def dims(cfg: dict) -> dict:
    held = list(cfg.get("held_experts", range(cfg["num_experts"])))
    return {
        "d": cfg["hidden_size"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "h": cfg["num_attention_heads"],
        "fe": cfg["moe_intermediate_size"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"], "held": held,
        "experts": cfg.get("router_num_experts", cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
    }


def expert_layers(cfg: dict) -> list[int]:
    """Every layer: ``mlp_layer_types`` is ``sparse`` throughout."""
    if set(cfg["mlp_layer_types"]) != {"sparse"}:
        raise ValueError(f"mlp_layer_types {cfg['mlp_layer_types']}: all sparse here")
    return list(range(cfg["num_hidden_layers"]))


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in a fixed order (the order seeds the leaves)."""
    s = dims(cfg)
    d, hd, n_held = s["d"], s["hd"], len(s["held"])
    shapes: dict[str, tuple[int, ...]] = {"embed": (s["v"], d)}
    for i in range(s["layers"]):
        p, h = f"layers.{i}.", s["h"]
        shapes.update({
            p + "op_norm.scale": (d,),
            p + "q.w": (d, h * hd), p + "k.w": (d, s["kv"] * hd),
            p + "v.w": (d, s["kv"] * hd), p + "o.w": (h * hd, d),
            p + "ffn_norm.scale": (d,),
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


def leaf_stds(cfg: dict) -> tuple[float, float]:
    """``(std of every matrix, std of the embedding's rows)``. The second is
    ``embedding_initializer_range`` where the configuration gives one: with
    every matrix at 0.02 what attention adds to the residual stream (a mean
    of up to 1,024 random values through two such matrices, entries of 0.04
    and more) outweighs the embedding (0.02), every token's router reads
    nearly the same vector, and the load on the held experts follows the seed
    from the first step on (PERF.md section 6, PR 39); a trained model's
    stream is the token's own."""
    std = float(cfg["initializer_range"])
    return std, float(cfg.get("embedding_initializer_range", std))


def init_leaf(name: str, shape, index: int, key, stds) -> jax.Array:
    noise = stds[name == "embed"] * jax.random.normal(
        jax.random.fold_in(key, index), shape, jnp.float32
    )
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


def yarn_table(rule: dict, r: int) -> tuple[list[float], int, int]:
    """``(inv_i for i < r/2, low, high)`` of YaRN over a rotary width ``r``:
    ``f_i = b^(-2i/r)``; ``c(n) = r ln(L / (2 pi n)) / (2 ln b)``;
    ``low = floor(c(beta_fast))``, ``high = ceil(c(beta_slow))`` clipped to
    [0, r - 1]; ``ramp_i = clip((i - low) / (high - low), 0, 1)``;
    ``inv_i = (f_i / factor) ramp_i + f_i (1 - ramp_i)``. Host arithmetic."""
    b, factor = float(rule["rope_theta"]), float(rule["factor"])
    big_l = rule["original_max_position_embeddings"]

    def c(n):
        return r * math.log(big_l / (2 * math.pi * n)) / (2 * math.log(b))

    low = max(math.floor(c(rule["beta_fast"])), 0)
    high = min(math.ceil(c(rule["beta_slow"])), r - 1)
    inv = []
    for i in range(r // 2):
        f = b ** (-2 * i / r)
        ramp = min(max((i - low) / (high - low), 0.0), 1.0)
        inv.append((f / factor) * ramp + f * (1 - ramp))
    return inv, low, high


def rope(x, rule: dict, cfg: dict, precision: dict):
    """Rotate-half rotary embedding of ``x`` (B, T, H, D), positions 0..T-1,
    by ``rule`` (``rope_parameters[kind]``): over all D columns, which is what
    this family's rules say (a ``partial_rotary_factor``, if a rule had one,
    would turn the first columns only and pass the rest)."""
    d = x.shape[-1]
    r = int(d * rule.get("partial_rotary_factor", cfg.get("partial_rotary_factor", 1)))
    if rule["rope_type"] == "yarn":
        inv = jnp.asarray(yarn_table(rule, r)[0], jnp.float32)
        factor = float(rule["attention_factor"]) if precision["attention_factor"] else 1.0
    else:
        inv = float(rule["rope_theta"]) ** (-jnp.arange(0, r, 2, dtype=jnp.float32) / r)
        factor = 1.0
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv[None, :]
    cos = (factor * jnp.cos(ang))[None, :, None, :]
    sin = (factor * jnp.sin(ang))[None, :, None, :]
    x1, x2, rest = x[..., : r // 2], x[..., r // 2: r], x[..., r:]
    return jnp.concatenate(
        (x1 * cos - x2 * sin, x1 * sin + x2 * cos, rest), axis=-1
    )


def masked_attention(q, k, v, window: int | None):
    """q (B, T, H, D); k, v (B, T, H_kv, D), each KV head serving its group
    of H / H_kv consecutive query heads. Key j is visible to query i iff
    ``i - j >= 0``, and ``i - j < window`` where there is one. One head and
    ``ATTN_ROWS`` query rows at a time."""
    b, t, h, d = q.shape
    group = h // k.shape[2]
    k, v = jnp.repeat(k, group, axis=2), jnp.repeat(v, group, axis=2)
    heads = lambda a: a.transpose(0, 2, 1, 3).reshape(b * h, t, d)  # noqa: E731
    rows = math.gcd(t, ATTN_ROWS)
    j = jnp.arange(t)[None, :]

    @jax.checkpoint
    def one_block(qb, start, kh, vh):
        i = start + jnp.arange(rows)[:, None]
        visible = i - j >= 0
        if window is not None:
            visible = visible & (i - j < window)
        scores = mm(qb, kh.T) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(visible, scores, -jnp.inf), axis=-1)
        return mm(probs, vh)

    def one_head(qkv):
        qh, kh, vh = qkv
        blocks = lax.map(
            lambda qs: one_block(qs[0], qs[1], kh, vh),
            (qh.reshape(t // rows, rows, d), jnp.arange(0, t, rows)),
        )
        return blocks.reshape(t, d)

    out = lax.map(one_head, (heads(q), heads(k), heads(v)))
    return out.reshape(b, h, t, d).transpose(0, 2, 1, 3)


def attention(u, w, i: int, cfg: dict, precision: dict):
    s = dims(cfg)
    b, t, _ = u.shape
    h, kind = s["h"], cfg["layer_types"][i]
    rule = cfg["rope_parameters"][kind]
    q = mm(u, w("q.w")).reshape(b, t, h, s["hd"])
    k = mm(u, w("k.w")).reshape(b, t, s["kv"], s["hd"])
    v = mm(u, w("v.w")).reshape(b, t, s["kv"], s["hd"])
    q, k = rope(q, rule, cfg, precision), rope(k, rule, cfg, precision)
    window = None
    if kind == "sliding_attention" and precision["window"]:
        window = int(cfg["sliding_window"] * precision["window"])
    elif kind not in ("full_attention", "sliding_attention"):
        raise ValueError(f"layer type {kind!r}")
    out = masked_attention(q, k, v, window)
    return mm(out.reshape(b, t, -1), w("o.w"))


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
    left, picked, selected = p, jnp.zeros_like(p), []
    for _ in range(k):
        best = jnp.argmax(left, axis=-1)  # ties: the lower index
        hot = jax.nn.one_hot(best, experts, dtype=p.dtype)
        picked = picked + hot
        left = jnp.where(hot > 0, -jnp.inf, left)
        selected.append(best)
    weights = picked * p
    weights = weights / weights.sum(axis=-1, keepdims=True)
    return lax.stop_gradient(picked) * weights, jnp.stack(selected, axis=-1)


def gated_mlp(x, w1, w3, w2):
    return mm(jax.nn.silu(mm(x, w1)) * mm(x, w3), w2)


def expert_layer(u, w, cfg: dict, router_dtype, held=None):
    """The part of the expert layer's result that the ``held`` experts give
    (nothing is computed by every share alike: there is no shared expert)."""
    held = dims(cfg)["held"] if held is None else held
    x = u.reshape(-1, u.shape[-1])
    weights, selected = routing_weights(x, w("router.w"), cfg, router_dtype)

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


def layer(x, p, i: int, cfg: dict, precision: dict):
    pre = f"layers.{i}."
    w = lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, w("op_norm.scale"), eps), w, i, cfg, precision)
    u = rms_norm(x, w("ffn_norm.scale"), eps)
    y, selected = expert_layer(u, w, cfg, precision["router"])
    return x + y, selected


def hidden_states(p, tokens, cfg: dict, precision: dict):
    """The final RMSNorm's output and each expert layer's (N, k) selection."""
    x = p["embed"].astype(jnp.float32)[tokens]
    picks = []
    for i in expert_layers(cfg):
        x, selected = jax.checkpoint(
            lambda x_, p_, i=i: layer(x_, p_, i, cfg, precision)
        )(x, p)
        picks.append(selected)
    x = rms_norm(x, p["final_norm.scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    return x, picks


def _logits(rows, p, store):
    return mm(rows, p["head.w"].astype(jnp.float32)).astype(store).astype(jnp.float32)


def logits(p, tokens, cfg: dict, precision: dict = REFERENCE):
    """(B, T, vocab) float32 logits - for tests at sizes that hold them."""
    x, _ = hidden_states(p, tokens, cfg, precision)
    return _logits(x, p, jnp.dtype(precision["store"]))


def selections(p, tokens, cfg: dict, precision: dict = REFERENCE):
    """(expert layers, N, k) expert ids the forward pass selects."""
    return jnp.stack(hidden_states(p, tokens, cfg, precision)[1])


def mean_loss(p, tokens, labels, cfg: dict, precision: dict = REFERENCE):
    """Mean token cross-entropy over the batch, ``LOSS_ROWS`` rows at a time."""
    store = jnp.dtype(precision["store"])
    x, _ = hidden_states(p, tokens, cfg, precision)
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
    Adam step as ``optax.adam`` defines it, state donated."""
    lr, store = float(prog["learning_rate"]), jnp.dtype(precision["store"])

    def step(p, m, v, t, tokens, labels):
        loss, g = jax.value_and_grad(mean_loss)(p, tokens, labels, cfg, precision)
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
    run). Returns each step's loss, the first gradient's norm per leaf and the
    norm of the parameters' change per leaf after the last step."""
    store = jnp.dtype(precision["store"])
    p = {n: a.astype(store) for n, a in init_params(cfg, seed).items()}
    m = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    v = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    step = make_step(cfg, prog, precision)
    losses, grad_norms = [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        p, m, v, loss, norms = step(
            p, m, v, jnp.float32(t), jnp.asarray(tokens), jnp.asarray(labels)
        )
        losses.append(float(loss))
        if grad_norms is None:
            grad_norms = {n: float(a) for n, a in norms.items()}
    del m, v
    return {
        "losses": losses, "grad_norms": grad_norms,
        "delta_norms": delta_norms(p, cfg, seed),
    }

"""Plain reference: the language model of ``KeyeVL2`` (Keye-VL-2.0-30B-A3B:
the Qwen3-MoE key set with ``sa_config``), its loss, the indexer's own loss,
gradients and Adam, for the experts one device holds.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``. Layer l, on the residual stream ``x`` (T, d), with
position ids ``pos`` (3, T) (temporal, height, width; equal rows for text):

- ``x' = RMSNorm(x)``; ``q = x' W_q`` (T, H, head), ``k = x' W_k``, ``v = x'
  W_v`` (T, H_kv, head), no bias; RMSNorm with a learned weight over the
  columns of each head of q and of k. M-RoPE on all columns of q and k,
  rotate-half pairing (column i with i + head/2), ``f_i = theta^(-2i/head)``,
  angle ``pos[s(i), t] f_i`` with ``s(i)`` the section of ``mrope_section``
  that holds i, the sections one after another.
- The indexer, on ``sg(x')``: ``q_I = sg(x') W_qI`` (T, J, D_I); ``k_I =
  LayerNorm(sg(x') W_kI; weight, bias)`` (T, D_I), one key head; ``w = sg(x')
  W_w J^-0.5 D_I^-0.5``; rotate-half RoPE over all D_I columns of q_I and k_I
  at ``rope_theta`` by the temporal row; ``I[t, s] = sum_j w[t, j] relu(q_I[t,
  j] . k_I[s])``.
- ``S_t``: the ``min(t + 1, topk)`` keys ``s <= t`` of largest ``I[t, s]``
  (ties: the lower s), by ``lax.top_k`` over the row with the keys after t
  at minus infinity.
- ``a = softmax over S_t of (q k^T / sqrt(head)) v``, each ``H / H_kv`` query
  heads on one K/V head; ``x <- x + a W_o``. One head and ``ATTN_ROWS`` query
  rows at a time.
- The indexer's loss of the layer: ``pbar_t[s] = sg(mean_h A_h[t, s])`` on
  ``S_t``; ``L_I = mean_t KL(pbar_t || softmax over S_t of I[t, .])``.
- ``x'' = RMSNorm(x)``; ``p = softmax(x'' W_r)`` over all experts in float32,
  the ``num_experts_per_tok`` largest selected by that many rounds of argmax
  (ties: the lower index), weights ``p_sel / sum(p_sel)`` (no epsilon, no
  scale); EVERY held expert ``W2_e (silu(W1_e x'') * W3_e x'')`` applied to
  ALL tokens and masked by its routing weight (no kernel, no sort of rows, no
  gather); ``x <- x + y``. No shared expert, no dense feed-forward:
  ``intermediate_size`` is read by nothing. What the absent experts would add
  is left out.

A final RMSNorm, an untied head; the total is the mean token cross-entropy
plus the sum of ``L_I`` over the layers (weight 1). No
bias but the index key's LayerNorm, no auxiliary routing loss.

Left out, said here: the vision tower and its projector (the catalog's row
gives no width of them), so a sequence is token ids and its position rows;
``q_chunk_size`` / ``kv_chunk_size`` are tiles in which a kernel would make
scores and change no result, so nothing reads them.

Departures from the description, each noted where it is made: rotate-half
pairing whatever layout a checkpoint has (seeded weights have none); to fit
on one 16 GB chip at the published widths each layer, each block of
attention rows, each expert and each block of logits rows is recomputed on
the backward pass (``jax.checkpoint``) - that changes memory, not values.

It imports nothing of the program and takes nothing the program has made:
weights come from :func:`init_params` (seeded, the embedding's rows at a
spread of their own where the configuration gives one), the batches from the
benchmark's traffic generator.

``precision`` selects a control: ``router`` and ``store`` "float32" or
"bfloat16" (:data:`CONTROL`, the same mathematics one step down);
``selection`` false leaves every causal key visible (:data:`NO_SELECTION`);
``topk`` 0.5 halves the keys kept (:data:`HALF_TOPK`); ``indexer_loss`` false
gives the indexer's loss the weight 0 (:data:`NO_INDEXER_LOSS`: its leaves
get no gradient); ``equal_rows`` true reads the temporal row in place of all
three (:data:`EQUAL_ROWS`). Each must fail the cell's check (the last where
the rows differ: the CPU test).
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE = {"router": "float32", "store": "float32", "selection": True,
             "topk": 1, "indexer_loss": True, "equal_rows": False}
#: one step below what the configuration states (f32 router, f32 state)
CONTROL = {**REFERENCE, "router": "bfloat16", "store": "bfloat16"}
#: the faults this architecture makes easy
NO_SELECTION = {**REFERENCE, "selection": False}
HALF_TOPK = {**REFERENCE, "topk": 0.5}
NO_INDEXER_LOSS = {**REFERENCE, "indexer_loss": False}
EQUAL_ROWS = {**REFERENCE, "equal_rows": True}

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 2048  # rows of logits alive at once
ATTN_ROWS = 1024  # query rows of one head's scores alive at once


def dims(cfg: dict) -> dict:
    held = list(cfg.get("held_experts", range(cfg["num_experts"])))
    sa = cfg.get("sa_config")
    return {
        "d": cfg["hidden_size"], "kv": cfg["num_key_value_heads"],
        "hd": cfg["head_dim"], "h": cfg["num_attention_heads"],
        "fe": cfg["moe_intermediate_size"],
        "v": cfg["vocab_size"], "layers": cfg["num_hidden_layers"], "held": held,
        "experts": cfg.get("router_num_experts", cfg["num_experts"]),
        "k": cfg["num_experts_per_tok"],
        "ij": sa["indexer_num_heads"] if sa else 0,
        "id": sa["indexer_head_dim"] if sa else 0,
        "topk": sa["topk"] if sa else 0,
    }


def expert_layers(cfg: dict) -> list[int]:
    """Every layer: ``decoder_sparse_step`` 1 and no ``mlp_only_layers``."""
    if cfg["decoder_sparse_step"] != 1 or cfg["mlp_only_layers"]:
        raise ValueError("every layer is an expert layer here")
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
            p + "q_norm.scale": (hd,), p + "k_norm.scale": (hd,),
        })
        if s["ij"]:
            shapes.update({
                p + "index_q.w": (d, s["ij"] * s["id"]), p + "index_k.w": (d, s["id"]),
                p + "index_k_norm.scale": (s["id"],), p + "index_k_norm.bias": (s["id"],),
                p + "index_w.w": (d, s["ij"]),
            })
        shapes.update({
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
    every matrix at 0.02 what attention adds to the residual stream outweighs
    an embedding of 0.02, every token's router reads nearly the same vector
    and the load on the held experts follows the seed (PERF.md section 6,
    PRs 39 and 41); a trained model's stream is the token's own."""
    std = float(cfg["initializer_range"])
    return std, float(cfg.get("embedding_initializer_range", std))


def init_leaf(name: str, shape, index: int, key, stds) -> jax.Array:
    noise = stds[name == "embed"] * jax.random.normal(
        jax.random.fold_in(key, index), shape, jnp.float32
    )
    return 1.0 + noise if name.endswith(".scale") else noise


def init_params(cfg: dict, seed: int) -> dict[str, jax.Array]:
    """Every leaf random (the norms' weights and the one bias too, so that
    none is a no-op), float32, made on the device in one jitted call."""
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


def text_positions(seq_len: int) -> jax.Array:
    """The three position rows of a text sequence: equal, 0 .. T - 1."""
    return jnp.broadcast_to(jnp.arange(seq_len, dtype=jnp.int32), (3, seq_len))


# -- forward -------------------------------------------------------------------


def mm(a, b):
    """a (..., m, k) @ b (..., k, n), float32 at the highest precision."""
    return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)


def rms_norm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * scale


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def mrope(x, pos, theta: float, sections):
    """Rotate-half rotary embedding of ``x`` (T, H, D) over all D columns:
    frequency i of the D / 2 turns by the row of ``pos`` (S, T) whose section
    holds it, the ``sections`` (S counts that sum to D / 2) one after another."""
    d = x.shape[-1]
    if sum(sections) != d // 2 or len(sections) != pos.shape[0]:
        raise ValueError(f"sections {sections} for a head of {d} and {pos.shape[0]} rows")
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    row_of = jnp.asarray([s for s, n in enumerate(sections) for _ in range(n)])
    ang = pos.astype(jnp.float32)[row_of].T * inv[None, :]  # (T, D / 2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1)


def index_scores(q_i, k_i, w):
    """``q_i`` (R, J, D), ``k_i`` (T, D), ``w`` (R, J) -> (R, T):
    ``sum_j w[r, j] relu(q_i[r, j] . k_i[s])``."""
    pre = jnp.einsum("rjd,sd->rjs", q_i, k_i, precision=lax.Precision.HIGHEST)
    return jnp.sum(w[:, :, None] * jax.nn.relu(pre), axis=1)


def selected(scores, start, topk: int):
    """bool (R, T): for the query at ``start + r`` the ``min(t + 1, topk)``
    keys ``s <= t`` of largest score; ``lax.top_k`` puts the lower index
    first among equals. ``topk`` None: every causal key."""
    r, t = scores.shape
    causal = jnp.arange(t)[None, :] <= (start + jnp.arange(r))[:, None]
    if topk is None or topk >= t:
        return causal
    scores = jnp.where(scores == 0, 0.0, lax.stop_gradient(scores))  # -0.0 ties with 0.0
    _, best = lax.top_k(jnp.where(causal, scores, -jnp.inf), topk)
    picked = jnp.zeros((r, t), bool).at[jnp.arange(r)[:, None], best].set(True)
    return picked & causal


def sparse_attention(q, k, v, q_i, k_i, w, topk):
    """One sequence: ``q`` (T, H, D); ``k``, ``v`` (T, H_kv, D), each K/V head
    serving its group of H / H_kv consecutive query heads; the indexer's
    ``q_i`` (T, J, D_I), ``k_i`` (T, D_I), ``w`` (T, J), or None: no indexer,
    every causal key visible and no loss. Returns ``(a (T, H, D), sum_t KL_t,
    the mask int8 (T, T))``. ``ATTN_ROWS`` query rows at a time, one head at
    a time inside."""
    t, h, d = q.shape
    group = h // k.shape[1]
    kh = jnp.repeat(k, group, axis=1).transpose(1, 0, 2)  # (H, T, D)
    vh = jnp.repeat(v, group, axis=1).transpose(1, 0, 2)
    rows = math.gcd(t, ATTN_ROWS)

    @jax.checkpoint
    def one_block(qb, start, ib):
        if ib is None:
            seen, scores = selected(jnp.zeros((rows, t)), start, None), None
        else:
            scores = index_scores(ib[0], k_i, ib[1])
            seen = selected(scores, start, topk)

        @jax.checkpoint
        def one_head(mean, qkv):  # the heads' mean probabilities, sg
            qh, k1, v1 = qkv
            s = mm(qh, k1.T) / math.sqrt(d)
            probs = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return mean + lax.stop_gradient(probs) / h, mm(probs, v1)

        target, out = lax.scan(
            one_head, jnp.zeros((rows, t)), (qb.transpose(1, 0, 2), kh, vh)
        )
        kl = jnp.float32(0.0)
        if scores is not None:  # the target sums to 1 over S_t
            log_q = jax.nn.log_softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
            held = seen & (target > 0)
            log_p = jnp.log(jnp.where(held, target, 1.0))
            kl = jnp.sum(jnp.where(held, target * (log_p - log_q), 0.0))
        return out.transpose(1, 0, 2), kl, seen.astype(jnp.int8)

    blocks = (q.reshape(t // rows, rows, h, d), jnp.arange(0, t, rows))
    if q_i is not None:
        blocks += ((q_i.reshape(t // rows, rows, *q_i.shape[1:]),
                    w.reshape(t // rows, rows, -1)),)
        out, kl, seen = lax.map(lambda b: one_block(*b), blocks)
    else:
        out, kl, seen = lax.map(lambda b: one_block(*b, None), blocks)
    return out.reshape(t, h, d), kl.sum(), seen.reshape(t, t)


def attention(u, w, pos, cfg: dict, precision: dict):
    """``u`` (B, T, d) the layer's normed input -> ``(a W_o (B, T, d), the
    indexer's loss of the layer (mean over the tokens), masks (B, T, T))``."""
    s = dims(cfg)
    b, t, _ = u.shape
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    sections = tuple((cfg.get("rope_scaling") or {}).get("mrope_section", ()))
    if not sections:  # one row of positions over the whole head
        sections, pos = (s["hd"] // 2,), pos[:1]
    if precision["equal_rows"]:
        pos = jnp.broadcast_to(pos[:1], pos.shape)
    topk = None
    if s["ij"] and precision["selection"]:
        topk = int(s["topk"] * precision["topk"])
    outs, kls, masks = [], [], []
    for n in range(b):  # a sequence at a time
        x = u[n]
        q = rms_norm(mm(x, w("q.w")).reshape(t, s["h"], s["hd"]), w("q_norm.scale"), eps)
        k = rms_norm(mm(x, w("k.w")).reshape(t, s["kv"], s["hd"]), w("k_norm.scale"), eps)
        v = mm(x, w("v.w")).reshape(t, s["kv"], s["hd"])
        q, k = mrope(q, pos, theta, sections), mrope(k, pos, theta, sections)
        index = (None, None, None)
        if s["ij"]:
            xs = lax.stop_gradient(x)
            q_i = mm(xs, w("index_q.w")).reshape(t, s["ij"], s["id"])
            k_i = layer_norm(
                mm(xs, w("index_k.w")), w("index_k_norm.scale"),
                w("index_k_norm.bias"), eps,
            )
            weights = mm(xs, w("index_w.w")) * (s["ij"] ** -0.5 * s["id"] ** -0.5)
            half = (s["id"] // 2,)
            q_i = mrope(q_i, pos[:1], theta, half)
            k_i = mrope(k_i[:, None, :], pos[:1], theta, half)[:, 0, :]
            index = (q_i, k_i, weights)
        a, kl, seen = sparse_attention(q, k, v, *index, topk)
        outs.append(mm(a.reshape(t, -1), w("o.w")))
        kls.append(kl)
        masks.append(seen)
    return jnp.stack(outs), sum(kls) / (b * t), jnp.stack(masks)


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


def expert_layer(u, w, cfg: dict, router_dtype, held=None):
    """The part of the expert layer's result that the ``held`` experts give
    (nothing is computed by every share alike: there is no shared expert)."""
    held = dims(cfg)["held"] if held is None else held
    x = u.reshape(-1, u.shape[-1])
    weights, chosen = routing_weights(x, w("router.w"), cfg, router_dtype)

    @jax.checkpoint
    def one_expert(y, ew):
        w1, w3, w2, column = ew
        return y + column[:, None] * gated_mlp(x, w1, w3, w2), None

    columns = weights[:, jnp.asarray(held)].T  # (held, N)
    y, _ = lax.scan(
        one_expert, jnp.zeros_like(x),
        (w("experts.w1"), w("experts.w3"), w("experts.w2"), columns),
    )
    return y.reshape(u.shape), chosen


def layer(x, p, pos, i: int, cfg: dict, precision: dict):
    pre = f"layers.{i}."
    w = lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    eps = cfg["rms_norm_eps"]
    a, kl, seen = attention(rms_norm(x, w("op_norm.scale"), eps), w, pos, cfg, precision)
    x = x + a
    u = rms_norm(x, w("ffn_norm.scale"), eps)
    y, chosen = expert_layer(u, w, cfg, precision["router"])
    return x + y, kl, (chosen, seen)


def hidden_states(p, tokens, cfg: dict, precision: dict, positions=None):
    """The final RMSNorm's output, the indexer's loss summed over the layers,
    and each layer's ((N, k) expert selection, (B, T, T) attention mask)."""
    pos = text_positions(tokens.shape[1]) if positions is None else positions
    x = p["embed"].astype(jnp.float32)[tokens]
    picks, index_loss = [], jnp.float32(0.0)
    for i in expert_layers(cfg):
        x, kl, picked = jax.checkpoint(
            lambda x_, p_, i=i: layer(x_, p_, pos, i, cfg, precision)
        )(x, p)
        index_loss += kl
        picks.append(picked)
    x = rms_norm(x, p["final_norm.scale"].astype(jnp.float32), cfg["rms_norm_eps"])
    return x, index_loss, picks


def _logits(rows, p, store):
    return mm(rows, p["head.w"].astype(jnp.float32)).astype(store).astype(jnp.float32)


def logits(p, tokens, cfg: dict, precision: dict = REFERENCE, positions=None):
    """(B, T, vocab) float32 logits - for tests at sizes that hold them."""
    x, _, _ = hidden_states(p, tokens, cfg, precision, positions)
    return _logits(x, p, jnp.dtype(precision["store"]))


def selections(p, tokens, cfg: dict, precision: dict = REFERENCE, positions=None):
    """(layers, N, k) expert ids the forward pass selects."""
    picks = hidden_states(p, tokens, cfg, precision, positions)[2]
    return jnp.stack([chosen for chosen, _ in picks])


def masks(p, tokens, cfg: dict, precision: dict = REFERENCE, positions=None):
    """(layers, B, T, T) int8: the keys each query of each layer attends to."""
    picks = hidden_states(p, tokens, cfg, precision, positions)[2]
    return jnp.stack([seen for _, seen in picks])


def losses(p, tokens, labels, cfg: dict, precision: dict = REFERENCE, positions=None):
    """``(mean token cross-entropy, the indexer's loss summed over the
    layers)``, the first ``LOSS_ROWS`` rows of logits at a time."""
    store = jnp.dtype(precision["store"])
    x, index_loss, _ = hidden_states(p, tokens, cfg, precision, positions)
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
    return sums.sum() / n, index_loss


def total_loss(p, tokens, labels, cfg: dict, precision: dict = REFERENCE, positions=None):
    """What the gradient is taken of, and the two losses it is made of."""
    ce, index_loss = losses(p, tokens, labels, cfg, precision, positions)
    if not precision["indexer_loss"]:  # the control: the cross-entropy alone
        return ce, (ce, index_loss)
    return ce + index_loss, (ce, index_loss)


# -- three steps of Adam ---------------------------------------------------------


def _norms(tree: dict) -> dict:
    return {
        n: jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))
        for n, a in tree.items()
    }


def make_step(cfg: dict, prog: dict, precision: dict = REFERENCE):
    """``(p, m, v, t, tokens, labels[, positions]) -> (p, m, v, (loss, the
    indexer's loss), grad_norms)``: one Adam step as ``optax.adam`` defines
    it, state donated. One jitted function per (configuration, program,
    precision): a second run of the same three is not compiled again."""
    return _make_step(*(json.dumps(a, sort_keys=True) for a in (cfg, prog, precision)))


@functools.lru_cache(maxsize=None)
def _make_step(cfg_json: str, prog_json: str, precision_json: str):
    cfg, prog, precision = (json.loads(a) for a in (cfg_json, prog_json, precision_json))
    lr, store = float(prog["learning_rate"]), jnp.dtype(precision["store"])

    def step(p, m, v, t, tokens, labels, positions=None):
        (_, both), g = jax.value_and_grad(total_loss, has_aux=True)(
            p, tokens, labels, cfg, precision, positions
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
            {n: new[n][2] for n in p}, both, _norms(g),
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


def follow(cfg: dict, prog: dict, seed: int, batches, precision: dict = REFERENCE,
           positions=None):
    """Drive the seed's weights through ``batches`` (the first steps of the
    run). Returns the losses (each step's cross-entropy, then each step's
    indexer loss), the first gradient's norm per leaf and the norm of the
    parameters' change per leaf after the last step."""
    store = jnp.dtype(precision["store"])
    p = {n: a.astype(store) for n, a in init_params(cfg, seed).items()}
    m = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    v = {n: jnp.zeros(a.shape, store) for n, a in p.items()}
    step = make_step(cfg, prog, precision)
    main, index, grad_norms = [], [], None
    for t, (tokens, labels) in enumerate(batches, start=1):
        p, m, v, (ce, kl), norms = step(
            p, m, v, jnp.float32(t), jnp.asarray(tokens), jnp.asarray(labels), positions
        )
        main.append(float(ce))
        index.append(float(kl))
        if grad_norms is None:
            grad_norms = {n: float(a) for n, a in norms.items()}
    del m, v
    return {
        "losses": main + index, "grad_norms": grad_norms,
        "delta_norms": delta_norms(p, cfg, seed),
    }

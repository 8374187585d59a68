"""Plain reference: a StarCoder2-style decoder LM, its loss, gradients and Adam.

Straightforward ``jax.numpy`` in float32 with every matmul at
``Precision.HIGHEST``: pre-LayerNorm blocks with biased q/k/v/o projections,
grouped-query attention with rotate-half RoPE and a causal softmax, a
tanh-GELU MLP, a final LayerNorm and a biased output head; the loss is the
mean token cross-entropy over the batch. No kernel, no cache, no sharding.

It imports nothing of the program and takes nothing the program has made:
the weights come from :func:`init_params` (seeded; the runner puts the same
weights into the trainer), the batches from the benchmark's traffic
generator. To fit beside nothing else on one 16 GB chip at the published
widths it recomputes each block, each attention head and each block of
logits rows on the backward pass (``jax.checkpoint``) - that changes memory,
not values.

``precision`` selects the control: the same mathematics one step down.
``matmul``: "highest" (the reference) or "int8" (operands fake-quantised per
contraction vector, W8A8).
``store``: "float32" or "bfloat16" for parameters, Adam moments and logits.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

REFERENCE = {"matmul": "highest", "store": "float32"}
#: one step below what the configuration states (bf16 matmuls, f32 state)
CONTROL = {"matmul": "int8", "store": "bfloat16"}

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
LOSS_ROWS = 2048  # rows of logits alive at once


def dims(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return {
        "d": d, "h": h, "kv": cfg["num_key_value_heads"], "hd": d // h,
        "f": cfg["intermediate_size"], "v": cfg["vocab_size"],
        "layers": cfg["num_hidden_layers"],
    }


def param_shapes(cfg: dict) -> dict[str, tuple[int, ...]]:
    """Leaf name -> shape, in a fixed order (the order seeds the leaves)."""
    s = dims(cfg)
    d, f, v = s["d"], s["f"], s["v"]
    shapes: dict[str, tuple[int, ...]] = {"embed": (v, d)}
    for i in range(s["layers"]):
        p = f"layers.{i}."
        shapes.update({
            p + "ln1.scale": (d,), p + "ln1.bias": (d,),
            p + "q.w": (d, s["h"] * s["hd"]), p + "q.b": (s["h"] * s["hd"],),
            p + "k.w": (d, s["kv"] * s["hd"]), p + "k.b": (s["kv"] * s["hd"],),
            p + "v.w": (d, s["kv"] * s["hd"]), p + "v.b": (s["kv"] * s["hd"],),
            p + "o.w": (s["h"] * s["hd"], d), p + "o.b": (d,),
            p + "ln2.scale": (d,), p + "ln2.bias": (d,),
            p + "fc.w": (d, f), p + "fc.b": (f,),
            p + "proj.w": (f, d), p + "proj.b": (d,),
        })
    shapes.update({
        "ln_f.scale": (d,), "ln_f.bias": (d,), "head.w": (d, v), "head.b": (v,),
    })
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
    """Every leaf random (biases and LayerNorm too, so that none is a no-op),
    float32, made on the device in one jitted call."""
    shapes = param_shapes(cfg)
    std = float(cfg["initializer_range"])

    def make(key):
        return {
            n: init_leaf(n, s, i, key, std)
            for i, (n, s) in enumerate(shapes.items())
        }

    return jax.jit(make)(seed_key(seed))


# -- forward -------------------------------------------------------------------


def _fake_int8(a, axis):
    scale = lax.stop_gradient(
        jnp.maximum(jnp.max(jnp.abs(a), axis=axis, keepdims=True), 1e-30) / 127.0
    )
    q = jnp.clip(jnp.round(a / scale), -127, 127) * scale
    return a + lax.stop_gradient(q - a)


def _matmul(kind: str):
    def mm(a, b):
        """a (..., m, k) @ b (..., k, n) in float32 out."""
        if kind == "int8":
            a, b = _fake_int8(a, -1), _fake_int8(b, -2)
        elif kind != "highest":
            raise ValueError(f"unknown matmul precision {kind!r}")
        return jnp.matmul(a, b, precision=lax.Precision.HIGHEST)

    return mm


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def rope(x, base: float):
    """Rotate-half rotary embedding; ``x`` is (B, T, H, D), positions 0..T-1."""
    d = x.shape[-1]
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate((x1 * cos - x2 * sin, x1 * sin + x2 * cos), axis=-1)


def gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)
    ))


def causal_attention(q, k, v, mm):
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


def block(x, p, pre: str, cfg: dict, prog: dict, mm):
    s = dims(cfg)
    b, t, _ = x.shape
    eps, w = prog["layer_norm_epsilon"], lambda n: p[pre + n].astype(jnp.float32)  # noqa: E731
    hid = layer_norm(x, w("ln1.scale"), w("ln1.bias"), eps)
    q = (mm(hid, w("q.w")) + w("q.b")).reshape(b, t, s["h"], s["hd"])
    k = (mm(hid, w("k.w")) + w("k.b")).reshape(b, t, s["kv"], s["hd"])
    v = (mm(hid, w("v.w")) + w("v.b")).reshape(b, t, s["kv"], s["hd"])
    att = causal_attention(
        rope(q, prog["rope_base"]), rope(k, prog["rope_base"]), v, mm
    ).reshape(b, t, s["h"] * s["hd"])
    x = x + mm(att, w("o.w")) + w("o.b")
    hid = layer_norm(x, w("ln2.scale"), w("ln2.bias"), eps)
    hid = gelu_tanh(mm(hid, w("fc.w")) + w("fc.b"))
    return x + mm(hid, w("proj.w")) + w("proj.b")


def hidden_states(p, tokens, cfg: dict, prog: dict, mm):
    x = p["embed"].astype(jnp.float32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        x = jax.checkpoint(
            lambda x_, p_, pre=f"layers.{i}.": block(x_, p_, pre, cfg, prog, mm)
        )(x, p)
    return layer_norm(
        x, p["ln_f.scale"].astype(jnp.float32),
        p["ln_f.bias"].astype(jnp.float32), prog["layer_norm_epsilon"],
    )


def _logits(rows, p, mm, store):
    out = mm(rows, p["head.w"].astype(jnp.float32)) + p["head.b"].astype(jnp.float32)
    return out.astype(store).astype(jnp.float32)


def logits(p, tokens, cfg: dict, prog: dict, precision: dict = REFERENCE):
    """(B, T, vocab) float32 logits - for tests at sizes that hold them."""
    mm = _matmul(precision["matmul"])
    x = hidden_states(p, tokens, cfg, prog, mm)
    return _logits(x, p, mm, jnp.dtype(precision["store"]))


def mean_loss(p, tokens, labels, cfg: dict, prog: dict, precision: dict = REFERENCE):
    """Mean token cross-entropy over the batch, ``LOSS_ROWS`` rows at a time."""
    mm, store = _matmul(precision["matmul"]), jnp.dtype(precision["store"])
    x = hidden_states(p, tokens, cfg, prog, mm)
    rows = x.reshape(-1, x.shape[-1])
    n = rows.shape[0]
    chunk = math.gcd(n, LOSS_ROWS)

    @jax.checkpoint
    def chunk_loss(xy):
        xs, ys = xy
        logp = jax.nn.log_softmax(_logits(xs, p, mm, store), axis=-1)
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
        loss, g = jax.value_and_grad(mean_loss)(
            p, tokens, labels, cfg, prog, precision
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

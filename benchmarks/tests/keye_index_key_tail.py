"""How the norm of the index key's first gradient spreads over seeds when the
indexer's operands are bf16 and the comparison is float32: the indexer's own
loss alone (``ops/sparse_attention.py`` ``indexer_kl``), at the statistics of
the cell's seeded weights (index queries at std 0.9, LayerNormed index keys,
per-query head weights at std 0.9 / 32, main scores at std 0.8), on the CPU.
Run by hand; it backs ``correct_limits.grad_norm_gap`` of
``configs/keye_vl2_30b_a3b_ep8.json`` (PERF.md section 6, PR 41):

    JAX_PLATFORMS=cpu python3 benchmarks/tests/keye_index_key_tail.py 0 500

One line a seed: the seed, ``| |g_bf16| - |g_f32| | / |g_f32|`` and
``|g_bf16 - g_f32| / |g_f32|`` of the gradient to the key before its
LayerNorm (the leaf ``index_k.w`` is that gradient against token rows that
are all but orthogonal, so its norm follows this one). The loss is a mean over
queries of a KL each, so the first few queries, with one to a few keys each at
probabilities of order one, hold a quarter of this gradient's square and are
not averaged with anything: the spread has a power-law tail.
"""

from __future__ import annotations

import sys

import common  # noqa: F401
import jax
import jax.numpy as jnp

from akka_allreduce_tpu.ops import sparse_attention as sa

T, TOPK, D, J, H, H_KV, HEAD = 512, 128, 64, 16, 8, 2, 32


def layer_norm(x, dtype):
    x = x.astype(jnp.float32)
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return ((x - mean) * jax.lax.rsqrt(var + 1e-6)).astype(dtype)


@jax.jit
def gaps(key):
    ks = jax.random.split(key, 5)
    q_i = 0.9 * jax.random.normal(ks[0], (J, T, D))
    k_pre = 0.9 * jax.random.normal(ks[1], (T, D))
    w = 0.9 * jax.random.normal(ks[2], (T, J)) / 32
    q = 0.9 * HEAD ** -0.5 * jax.random.normal(ks[3], (H, T, HEAD))
    k = 0.9 * jax.random.normal(ks[4], (H_KV, T, HEAD))
    mask = sa.indexer_mask(q_i, layer_norm(k_pre, jnp.float32), w, TOPK)
    s = jnp.einsum("kgrd,kcd->kgrc", q.reshape(H_KV, H // H_KV, T, HEAD), k)
    lse = jax.nn.logsumexp(jnp.where(mask[None, None] != 0, s, -jnp.inf), axis=-1)

    def loss(k_pre, dt):
        return sa.indexer_kl(q_i.astype(dt), layer_norm(k_pre.astype(dt), dt), w, mask,
                             q.astype(dt), k.astype(dt), lse) / T

    exact = jax.grad(loss)(k_pre, jnp.float32)
    lower = jax.grad(loss)(k_pre, jnp.bfloat16)
    norm = jnp.linalg.norm(exact)
    return jnp.abs(jnp.linalg.norm(lower) - norm) / norm, jnp.linalg.norm(lower - exact) / norm


if __name__ == "__main__":
    for seed in range(int(sys.argv[1]), int(sys.argv[2])):
        print(seed, *(float(g) for g in gaps(jax.random.PRNGKey(seed))), flush=True)

"""Operations a train step needs, from the configuration's shapes.

Kept with the benchmark so that no later PR can change the count. Per token:

    6 * (N - N_embed)  +  6 * T * d_model * n_layers

``N`` counts every parameter that sits in a matmul: the projections, the MLP
and the (untied) output head with their biases and LayerNorms; the embedding
table is a lookup and is left out. Attention is two score/value matmuls
forward and four backward over a causal (halved) T x T: 12 * T * d per layer
and token before halving. Recomputed operations (remat, the flash kernel's
backward) are not counted, as model-FLOP utilisation wants.

The program's own ``utils/benchmarking.transformer_train_flops`` takes
6 * N with the embedding table in N; at this vocabulary that reads 22% high.
"""

from __future__ import annotations


def lm_param_counts(cfg: dict) -> dict:
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, v, layers = d // h, cfg["vocab_size"], cfg["num_hidden_layers"]
    per_layer = (
        d * h * hd + h * hd  # q
        + 2 * (d * kv * hd + kv * hd)  # k, v
        + h * hd * d + d  # o
        + d * f + f + f * d + d  # mlp
        + 4 * d  # two LayerNorms
    )
    embed, head = v * d, d * v + v
    total = embed + layers * per_layer + 2 * d + head
    return {"total": total, "embedding": embed, "per_layer": per_layer, "head": head}


def lm_train_flops_per_token(cfg: dict, seq_len: int) -> dict:
    n = lm_param_counts(cfg)
    dense = 6 * (n["total"] - n["embedding"])
    attention = 6 * seq_len * cfg["hidden_size"] * cfg["num_hidden_layers"]
    return {"dense": dense, "attention": attention, "total": dense + attention}


def attention_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward + backward score/value matmuls of one step, all layers."""
    return batch * seq_len * lm_train_flops_per_token(cfg, seq_len)["attention"]


def allreduce_bus_bytes(floats: int, itemsize: int, n: int) -> float:
    """Bytes a ring-optimal allreduce moves per device: 2 (n-1)/n x payload."""
    return 2.0 * (n - 1) / n * floats * itemsize

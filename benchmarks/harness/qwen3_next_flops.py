"""Operations and bytes of a train step of a decoder of Gated DeltaNet layers
among gated full-attention layers, with held experts and a gated shared
expert in every layer (``qwen3_next``), from the configuration's shapes and
the rows the program's counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per step,
by the work the mathematics needs, whatever runs it:

    6 * tokens * (matmul parameters every token meets)
    + 6 * one expert * rows routed
    + per full layer:   12 * causal pairs * H * head
    + per linear layer: the gated delta rule's count below

Every token meets a linear layer's ``W_qkvz``, ``W_ba`` and ``W_o``, a full
layer's ``W_q`` (doubled: queries and gates), ``W_k``, ``W_v`` and ``W_o``,
each layer's shared expert, its gate and its router (all of its outputs), and
the head. The embedding table is a lookup; the convolution's taps, the norms,
RoPE and the gates are elementwise; all are left out. Attention is a score
and a value product forward and two of each backward over the causal pairs,
``T (T + 1) / 2``, counted exactly.

**The gated delta rule is counted as the OPERATION, at chunks of 64 positions
whatever the program does**, so that a later kernel (another chunk, a fused
solve, fewer passes) is read against the same work. Per chunk of ``C``
positions and value head, multiply-adds forward:

    C^2 (3 d_k + 2 d_v)  +  3 C d_k d_v

``C^2 d_k`` each for the keys' Gram matrix ``K K^T``, for ``Q K^T`` and for
``w = T (beta exp(gamma) K)``; ``C^2 d_v`` each for ``u = T (beta V)`` and
for the chunk's own part of the output ``(Q K^T * decay) U``; ``C d_k d_v``
each for ``w S``, for ``Q S`` and for the state's update ``K^T U``. The
triangular solve that makes ``T`` (``C^3 / 3`` multiply-adds however it is
done) is NOT counted: a form that spends more on it reads lower. A train step
is three times the forward (each product has two transposes backward). At C
64 and d 128: 5.77M multiply-adds a chunk and head, 141.8 GFLOP a layer at
8,192 positions and 32 value heads. The least bytes: q, k, v, o and their
four gradients, each read or written once in bf16.
"""

from __future__ import annotations

from harness.laguna_flops import pairs  # (query, key) pairs a mask leaves, exactly

DELTA_CHUNK = 64  # of the count, not of the program


def layer_kinds(cfg: dict) -> list[str]:
    every = cfg["full_attention_interval"]
    return [
        "full_attention" if (i + 1) % every == 0 else "linear_attention"
        for i in range(cfg["num_hidden_layers"])
    ]


def matmul_params(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    keys = cfg["linear_num_key_heads"] * cfg["linear_key_head_dim"]
    values = cfg["linear_num_value_heads"] * cfg["linear_value_head_dim"]
    kinds = layer_kinds(cfg)
    n_linear, n_full = kinds.count("linear_attention"), kinds.count("full_attention")
    return {
        "linear_mixers": n_linear * (
            d * (2 * keys + 2 * values) + d * 2 * cfg["linear_num_value_heads"] + values * d
        ),
        "attention": n_full * (d * hd * (2 * h + 2 * kv) + h * hd * d),
        "shared_experts": len(kinds) * (3 * d * cfg["shared_expert_intermediate_size"] + d),
        "router": len(kinds) * d * cfg.get("router_num_experts", cfg["num_experts"]),
        "head": d * cfg["vocab_size"],
        "one_expert": 3 * d * cfg["moe_intermediate_size"],
    }


def attention_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward + backward score/value products of one step over the causal
    pairs, every full-attention layer."""
    per_pair = 12 * cfg["num_attention_heads"] * cfg["head_dim"]
    full = layer_kinds(cfg).count("full_attention")
    return batch * full * per_pair * pairs(seq_len, None)


def delta_rule_layer(cfg: dict, batch: int, seq_len: int) -> dict:
    """ONE linear layer's gated delta rule in a train step: ``flops`` (forward
    and backward, three times the forward's multiply-adds, two operations
    each) and the least ``bytes`` it moves."""
    c = DELTA_CHUNK
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    chunks = -(-seq_len // c)
    macs = c * c * (3 * dk + 2 * dv) + 3 * c * dk * dv
    return {
        "flops": 2 * 3 * macs * chunks * hv * batch,
        "bytes": 2 * 2 * batch * seq_len * (2 * hk * dk + 2 * hv * dv),
    }


def delta_rule_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    linear = layer_kinds(cfg).count("linear_attention")
    return linear * delta_rule_layer(cfg, batch, seq_len)["flops"]


def train_flops_per_step(
    cfg: dict, batch: int, seq_len: int, routed_rows: float
) -> dict:
    """``routed_rows``: (token, choice) pairs routed to held experts in a
    step, summed over the expert layers."""
    n = matmul_params(cfg)
    always = 6 * batch * seq_len * sum(v for k, v in n.items() if k != "one_expert")
    experts = 6 * n["one_expert"] * routed_rows
    attention = attention_train_flops(cfg, batch, seq_len)
    delta = delta_rule_train_flops(cfg, batch, seq_len)
    return {"always": always, "experts": experts, "attention": attention,
            "delta_rule": delta, "total": always + experts + attention + delta}

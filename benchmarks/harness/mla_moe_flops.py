"""Operations and bytes of a train step of a latent-attention decoder with
held experts, a shared expert and a multi-token-prediction module
(``joyai_llm_flash``, the DeepSeek-V3 dialect), from the configuration's
shapes and the rows the program's counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per token:

    6 * (matmul parameters every token meets)  +  6 * one expert * rows routed
    +  3 * T * heads * (qk head + v head) per attention layer

Every token meets latent attention's five projections (``W_qa``, ``W_qb``,
``W_kva``, ``W_kvb``, ``W_o``) in every hidden layer and in the prediction
module's layer, the dense layers' gated MLP, each expert layer's shared
expert and router (all of its outputs), the module's ``eh_proj``, and the
head TWICE (the main logits and the module's). The embedding table is a
lookup; the norms, RoPE and the gates are elementwise; both are left out. An
expert's three matrices are met once per (token, choice) routed to an expert
held HERE: the counter's rows, not tokens times k. Attention is a score and a
value matmul forward and two of each backward over a causal (halved) T x T,
the scores over the 192-wide query/key head and the values over the 128-wide
one: 3 T H (qk + v) a token and layer. Recomputed operations (the kernel's
backward recomputes the scores) are not counted.
"""

from __future__ import annotations


def shapes(cfg: dict) -> dict:
    mtp = cfg["num_nextn_predict_layers"]
    layers = cfg["num_hidden_layers"] + mtp
    return {
        "d": cfg["hidden_size"], "heads": cfg["num_attention_heads"],
        "qk": cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
        "v": cfg["v_head_dim"], "layers": layers, "mtp": mtp,
        "moe_layers": layers - cfg["first_k_dense_replace"],
        "held": cfg["n_routed_experts"], "expert_width": cfg["moe_intermediate_size"],
    }


def matmul_params(cfg: dict) -> dict:
    s = shapes(cfg)
    d, h = s["d"], s["heads"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rot = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    fe = cfg["moe_intermediate_size"]
    mla = (
        d * q_rank + q_rank * h * (nope + rot) + d * (kv_rank + rot)
        + kv_rank * h * (nope + s["v"]) + h * s["v"] * d
    )
    return {
        "latent_attention": s["layers"] * mla,
        "dense_mlp": cfg["first_k_dense_replace"] * 3 * d * cfg["intermediate_size"],
        "shared_experts": s["moe_layers"] * 3 * d * cfg["n_shared_experts"] * fe,
        "router": s["moe_layers"] * d * cfg.get("router_num_experts", s["held"]),
        "eh_proj": s["mtp"] * 2 * d * d,
        "heads": (1 + s["mtp"]) * d * cfg["vocab_size"],
        "one_expert": 3 * d * fe,
    }


def attention_flops_per_token(cfg: dict, seq_len: int) -> int:
    s = shapes(cfg)
    return 3 * seq_len * s["heads"] * (s["qk"] + s["v"]) * s["layers"]


def train_flops_per_token(cfg: dict, seq_len: int, routed_rows_per_token: float) -> dict:
    """``routed_rows_per_token``: (token, choice) pairs routed to held
    experts per token, summed over the expert layers (the module's too)."""
    n = matmul_params(cfg)
    always = 6 * sum(v for k, v in n.items() if k != "one_expert")
    experts = 6 * n["one_expert"] * routed_rows_per_token
    attention = attention_flops_per_token(cfg, seq_len)
    return {"always": always, "experts": experts, "attention": attention,
            "total": always + experts + attention}


def attention_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward + backward score/value matmuls of one step, all layers."""
    return batch * seq_len * attention_flops_per_token(cfg, seq_len)


def grouped_products(cfg: dict, rows_per_layer: float, active_experts: float | None = None) -> dict:
    """FLOPs and least bytes of one expert layer's grouped products in a
    train step with ``rows_per_layer`` rows routed to the held experts, as
    ``harness/moe_flops.grouped_products`` counts them: three matrices
    (d x fe, d x fe, fe x d), each in three products - forward, the rows'
    gradient, the weights' gradient; rows in and out once in bf16, the
    weights read in bf16 twice and their gradient written in f32 - the
    weights of the ``active_experts`` that received a row (all held ones
    where left out): the kernels' grid follows the rows, and an expert
    without one has no tile to read or write."""
    s = shapes(cfg)
    d, fe = s["d"], s["expert_width"]
    weights = (s["held"] if active_experts is None else active_experts) * d * fe
    row_bytes = 2 * rows_per_layer * (d + fe)
    per_matrix = 2 * (row_bytes + 2 * weights) + row_bytes + 4 * weights
    return {"flops": 3 * 3 * 2 * rows_per_layer * d * fe, "bytes": 3 * per_matrix}

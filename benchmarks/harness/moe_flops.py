"""Operations and bytes of a train step of a configuration-built decoder
whose expert layers hold a subset of the experts (``lfm2_moe``), from the
configuration's shapes and the rows the program's counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per token:

    6 * (matmul parameters every token meets)  +  6 * one expert * rows routed
    +  6 * T * (heads * head size) per attention layer

Every token meets the operators' projections (a convolution's 3d*d in and d*d
out; attention's q, k, v, o), the dense layers' gated MLP, each expert
layer's router (all of its outputs) and the head. The embedding table is a
lookup; the convolution's taps, the norms and the gates are elementwise; both
are left out. An expert's three matrices are met once per (token, choice)
routed to an expert held HERE: the counter's rows, not tokens times k.
Attention is two score/value matmuls forward and four backward over a causal
(halved) T x T, as ``harness/flops.py`` counts it. Recomputed operations are
not counted.
"""

from __future__ import annotations


def matmul_params(cfg: dict) -> dict:
    d, f, fe = cfg["hidden_size"], cfg["intermediate_size"], cfg["moe_intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // h
    kinds = cfg["layer_types"]
    n_conv, n_attn = kinds.count("conv"), kinds.count("full_attention")
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    routed = cfg.get("router_num_experts", cfg["num_experts"])
    return {
        "operators": n_conv * 4 * d * d + n_attn * 2 * d * hd * (h + kv),
        "dense_mlp": n_dense * 3 * d * f,
        "router": n_moe * d * routed,
        "head": d * cfg["vocab_size"],
        "one_expert": 3 * d * fe,
        "attention_width": n_attn * h * hd,
        "expert_layers": n_moe,
    }


def train_flops_per_token(cfg: dict, seq_len: int, routed_rows_per_token: float) -> dict:
    """``routed_rows_per_token``: (token, choice) pairs routed to held
    experts per token, summed over the expert layers."""
    n = matmul_params(cfg)
    always = 6 * (n["operators"] + n["dense_mlp"] + n["router"] + n["head"])
    experts = 6 * n["one_expert"] * routed_rows_per_token
    attention = 6 * seq_len * n["attention_width"]
    return {"always": always, "experts": experts, "attention": attention,
            "total": always + experts + attention}


def attention_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward + backward score/value matmuls of one step, all layers."""
    return batch * seq_len * 6 * seq_len * matmul_params(cfg)["attention_width"]


def grouped_products(cfg: dict, rows_per_layer: float) -> dict:
    """FLOPs and least bytes of one expert layer's grouped products in a
    train step with ``rows_per_layer`` rows routed to the held experts: three
    matrices (d x fe, d x fe, fe x d), each in three products - forward, the
    rows' gradient, the weights' gradient. Bytes: every product reads its
    rows and writes its result once in bf16; the forward and rows'-gradient
    products read the held experts' weights in bf16, the weights'-gradient
    product writes them in f32."""
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    flops = 3 * 3 * 2 * rows_per_layer * d * fe
    weights = held * d * fe
    row_bytes = 2 * rows_per_layer * (d + fe)  # one side in, the other out
    per_matrix = (
        2 * (row_bytes + 2 * weights)  # forward, rows' gradient
        + row_bytes + 4 * weights  # weights' gradient
    )
    return {"flops": flops, "bytes": 3 * per_matrix}

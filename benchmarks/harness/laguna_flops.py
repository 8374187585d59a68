"""Operations of a train step of a decoder that mixes windowed and full
attention at different head counts, with held experts and a shared expert
(``laguna``), from the configuration's shapes and the rows the program's
counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per step:

    6 * tokens * (matmul parameters every token meets)
    +  6 * one expert * rows routed  +  12 * pairs * H_l * head per layer

Every token meets attention's five projections (``W_q``, ``W_k``, ``W_v``,
the gate ``W_g``, ``W_o``) of each layer AT THAT LAYER'S head count, the dense
layers' gated MLP, each expert layer's shared expert and router (all of its
outputs) and the head. The embedding table is a lookup; the norms, RoPE and
the gates' products are elementwise; both are left out. An expert's three
matrices are met once per (token, choice) routed to an expert held HERE: the
counter's rows, not tokens times k. Attention is a score and a value product
forward and two of each backward over the (query, key) pairs the layer's mask
leaves, counted exactly: ``T (T + 1) / 2`` where it is causal,
``sum_i min(i + 1, window)`` in a band. Recomputed operations (the kernel's
backward recomputes the scores) and what a tile computes of masked-out pairs
are not counted.
"""

from __future__ import annotations


def pairs(seq_len: int, window: int | None = None) -> int:
    """(query, key) pairs a sequence's mask leaves: key j to query i iff
    ``0 <= i - j`` and, with a window, ``i - j < window``."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def attention_layers(cfg: dict) -> list[tuple[int, int | None]]:
    """``(query heads, window or None)`` of each layer."""
    return [
        (h, cfg["sliding_window"] if kind == "sliding_attention" else None)
        for h, kind in zip(cfg["num_attention_heads_per_layer"], cfg["layer_types"])
    ]


def matmul_params(cfg: dict) -> dict:
    d, hd, kv = cfg["hidden_size"], cfg["head_dim"], cfg["num_key_value_heads"]
    n_dense = cfg["mlp_layer_types"].count("dense")
    n_moe = cfg["num_hidden_layers"] - n_dense
    return {
        "attention": sum(
            d * hd * (h + 2 * kv) + d * h + h * hd * d
            for h, _ in attention_layers(cfg)
        ),
        "dense_mlp": n_dense * 3 * d * cfg["intermediate_size"],
        "shared_experts": n_moe * 3 * d * cfg["shared_expert_intermediate_size"],
        "router": n_moe * d * cfg.get("router_num_experts", cfg["num_experts"]),
        "head": d * cfg["vocab_size"],
        "one_expert": 3 * d * cfg["moe_intermediate_size"],
    }


def attention_train_flops(
    cfg: dict, batch: int, seq_len: int, *, windowed: bool | None = None
) -> int:
    """Forward + backward score/value products of one step: of every layer,
    or of the windowed (``True``) or the full (``False``) layers alone."""
    return batch * sum(
        12 * pairs(seq_len, w) * h * cfg["head_dim"]
        for h, w in attention_layers(cfg)
        if windowed is None or windowed == (w is not None)
    )


def train_flops_per_step(
    cfg: dict, batch: int, seq_len: int, routed_rows: float
) -> dict:
    """``routed_rows``: (token, choice) pairs routed to held experts in a
    step, summed over the expert layers."""
    n = matmul_params(cfg)
    always = 6 * batch * seq_len * sum(v for k, v in n.items() if k != "one_expert")
    experts = 6 * n["one_expert"] * routed_rows
    attention = attention_train_flops(cfg, batch, seq_len)
    return {"always": always, "experts": experts, "attention": attention,
            "total": always + experts + attention}

"""Operations and bytes of a train step of a decoder with windowed and full
attention by the layer's kind and held experts in every layer, nothing beside
them (``mellum``), from the configuration's shapes and the rows the program's
counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per step:

    6 * tokens * (matmul parameters every token meets)
    +  6 * one expert * rows routed  +  12 * pairs * H * head per layer

Every token meets attention's four projections (``W_q``, ``W_k``, ``W_v``,
``W_o``) and the router (all of its outputs) of each layer, and the head. The
embedding table is a lookup; the norms and RoPE are elementwise; both are
left out. An expert's three matrices are met once per (token, choice) routed
to an expert held HERE: the counter's rows, not tokens times k. Attention is
a score and a value product forward and two of each backward over the (query,
key) pairs the layer's mask leaves, counted exactly: ``T (T + 1) / 2`` where
it is causal, ``sum_i min(i + 1, window)`` in a band. Recomputed operations
(the kernel's backward recomputes the scores) and what a tile computes of
masked-out pairs are not counted.
"""

from __future__ import annotations

from harness.laguna_flops import pairs  # (query, key) pairs a mask leaves, exactly


def windows(cfg: dict) -> list[int | None]:
    """The window of each layer, None where it is full attention."""
    return [
        cfg["sliding_window"] if kind == "sliding_attention" else None
        for kind in cfg["layer_types"]
    ]


def matmul_params(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, layers = cfg["num_attention_heads"], cfg["num_key_value_heads"], len(cfg["layer_types"])
    return {
        "attention": layers * (d * hd * (h + 2 * kv) + h * hd * d),
        "router": layers * d * cfg.get("router_num_experts", cfg["num_experts"]),
        "head": d * cfg["vocab_size"],
        "one_expert": 3 * d * cfg["moe_intermediate_size"],
    }


def attention_train_flops(
    cfg: dict, batch: int, seq_len: int, *, windowed: bool | None = None
) -> int:
    """Forward + backward score/value products of one step: of every layer,
    or of the windowed (``True``) or the full (``False``) layers alone."""
    per_pair = 12 * cfg["num_attention_heads"] * cfg["head_dim"]
    return batch * per_pair * sum(
        pairs(seq_len, w) for w in windows(cfg)
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


def grouped_products(cfg: dict, rows: float, experts_with_a_row: int) -> dict:
    """One expert layer's grouped products in a train step, forward and
    backward: nine products over the rows routed (gate, up, down; the rows'
    gradient of each; the weights' gradient of each), ``18 x rows x d x f``
    operations; and the least bytes they move: each product reads or writes
    its two row operands once (bf16: three products of (d, f) rows forward,
    three backward, three weight gradients) and the weights of the experts
    WITH a row once per product in bf16 forward and backward, their gradient
    once in f32 - an expert no row reached is never read."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    row_bytes = 2 * rows * (d + f)  # one product's two row operands, bf16
    weight_bytes = experts_with_a_row * d * f
    return {
        "flops": 18 * rows * d * f,
        "bytes": 9 * row_bytes + 3 * (2 + 2 + 4) * weight_bytes,
    }

"""Operations of a train step of a decoder whose attention runs under a mask
a learned indexer makes, with held experts in every layer (``KeyeVL2``'s
language model: the Qwen3-MoE keys with ``sa_config``), from the
configuration's shapes and the rows the program's counter says were routed.

Kept with the benchmark so that no later PR can change the count. Per step,
by the work the mathematics needs, whatever runs it:

    6 * tokens * (matmul parameters every token meets, indexer apart)
    + 4 * tokens * (the indexer's three projections)
    + 6 * one expert * rows routed
    + per layer: 12 * selected pairs * H * head      (attention)
               +  6 * causal pairs * J * D_I         (index scores)
               +  2 * selected pairs * H * head      (the loss's target)

Every token meets attention's four projections and the router (all of its
outputs) of each layer, and the head. The indexer's projections read the
layer's input with its gradient stopped: a forward product and a weight
gradient, no input gradient. The embedding table is a lookup; norms, RoPE,
the selection itself (comparisons) and the loss's elementwise part are left
out. Attention is a score and a value product forward and two of each
backward over the (query, key) pairs the mask KEEPS, ``sum_t min(t + 1,
topk)``: a kernel that runs every causal pair under the mask is given no
credit for the pairs it masks out. Index scores are needed on every causal
pair (the selection ranks them all): ``J`` heads of ``D_I`` forward and two
products backward. The target, the head mean of attention's probabilities on
the kept pairs, is one more score product forward, nothing backward.
Recomputed operations are not counted.
"""

from __future__ import annotations

from harness.laguna_flops import pairs  # (query, key) pairs a mask leaves, exactly


def selected_pairs(cfg: dict, seq_len: int) -> int:
    """Pairs one sequence's mask keeps in a layer: ``sum_t min(t + 1, topk)``."""
    return pairs(seq_len, cfg["sa_config"]["topk"])


def matmul_params(cfg: dict) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, kv, layers = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["num_hidden_layers"]
    sa = cfg["sa_config"]
    return {
        "attention": layers * (d * hd * (h + 2 * kv) + h * hd * d),
        "indexer": layers * d * (sa["indexer_num_heads"] * sa["indexer_head_dim"]
                                 + sa["indexer_head_dim"] + sa["indexer_num_heads"]),
        "router": layers * d * cfg.get("router_num_experts", cfg["num_experts"]),
        "head": d * cfg["vocab_size"],
        "one_expert": 3 * d * cfg["moe_intermediate_size"],
    }


def attention_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """Forward + backward score/value products of one step over the pairs the
    masks keep, every layer."""
    per_pair = 12 * cfg["num_attention_heads"] * cfg["head_dim"]
    return batch * cfg["num_hidden_layers"] * per_pair * selected_pairs(cfg, seq_len)


def index_scores_train_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """The indexer's scores on every causal pair, forward and two products
    backward, every layer."""
    sa = cfg["sa_config"]
    per_pair = 6 * sa["indexer_num_heads"] * sa["indexer_head_dim"]
    return batch * cfg["num_hidden_layers"] * per_pair * pairs(seq_len, None)


def target_flops(cfg: dict, batch: int, seq_len: int) -> int:
    """One more score product over the kept pairs, every layer."""
    per_pair = 2 * cfg["num_attention_heads"] * cfg["head_dim"]
    return batch * cfg["num_hidden_layers"] * per_pair * selected_pairs(cfg, seq_len)


def train_flops_per_step(
    cfg: dict, batch: int, seq_len: int, routed_rows: float
) -> dict:
    """``routed_rows``: (token, choice) pairs routed to held experts in a
    step, summed over the expert layers."""
    n = matmul_params(cfg)
    tokens = batch * seq_len
    always = 6 * tokens * (n["attention"] + n["router"] + n["head"]) + 4 * tokens * n["indexer"]
    experts = 6 * n["one_expert"] * routed_rows
    attention = attention_train_flops(cfg, batch, seq_len)
    index = index_scores_train_flops(cfg, batch, seq_len)
    target = target_flops(cfg, batch, seq_len)
    return {"always": always, "experts": experts, "attention": attention,
            "index_scores": index, "target": target,
            "total": always + experts + attention + index + target}


def grouped_products(cfg: dict, rows: float, experts_with_a_row: int) -> dict:
    """One expert layer's grouped products in a train step, forward and
    backward, as ``harness/mellum_flops.grouped_products`` counts them: nine
    products over the rows routed (gate, up, down; the rows' gradient of
    each; the weights' gradient of each), ``18 x rows x d x f`` operations;
    and the least bytes they move: each product reads or writes its two row
    operands once in bf16, and the weights of the experts WITH a row once per
    product in bf16 forward and backward, their gradient once in f32."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    row_bytes = 2 * rows * (d + f)  # one product's two row operands, bf16
    weight_bytes = experts_with_a_row * d * f
    return {
        "flops": 18 * rows * d * f,
        "bytes": 9 * row_bytes + 3 * (2 + 2 + 4) * weight_bytes,
    }

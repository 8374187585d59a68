"""The configuration-built decoder whose attention runs under a mask a learned
indexer makes (``models/hybrid_decoder.py`` from the Qwen3-MoE keys with
``sa_config``: ``KeyeVL2``'s language model) and its indexer's own loss in
``MoETrainer``, against the benchmark's plain reference
``benchmarks/reference/keye_moe_plain.py`` at tiny widths on the CPU, on
seeded weights, text and unequal position rows alike; the reader's refusals,
the shares of one layer, the configuration file's count. The ops under it:
``tests/test_with_learned_mask_attention.py``."""

from __future__ import annotations

import json
import math
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness import spec, traffic  # noqa: E402

ref = spec.load_module("reference", "keye_moe_plain")
runner = spec.load_module("runners", "keye_moe_train")

TRAFFIC = {"batch": 2, "seq_len": 32, "tokens": "copy_half"}
TINY = os.path.join(BENCH, "tests", "tiny_keye_moe.json")
REAL = os.path.join(BENCH, "configs", "keye_vl2_30b_a3b_ep8.json")
CELL = "keye_vl2_ep8_train_b1_t8192"
ACCEPTED = ("lfm2_24b_a2b_ep8_d5", "joyai_llm_flash_ep32_d5_mtp1", "laguna_xs2_d5",
            "mellum2_12b_d4")
CONTROLS = ("CONTROL", "NO_SELECTION", "HALF_TOPK", "NO_INDEXER_LOSS")
LAYERS = 2  # of the tiny file


def _json(path):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def cfg():
    """The tiny configuration; ``use_expert_bias`` is the runners' key for
    "no bias"."""
    return dict(_json(TINY), use_expert_bias=False)


def _close(got, want, tol=2e-5):
    scale = float(jnp.max(jnp.abs(want))) + 1e-30
    assert float(jnp.max(jnp.abs(got - want))) <= tol * scale


def _batches(cfg, seed, n=3):
    return [traffic.token_batch(TRAFFIC, cfg["vocab_size"], seed, i) for i in range(n)]


def _variables(cfg, seed):
    return runner.to_program_tree(ref.init_params(cfg, seed), None, cfg)


def _unequal_rows(t):
    """Three rows of positions that differ, as an image's patches give them."""
    i = jnp.arange(t)
    return jnp.stack([i, i // 3, (2 * i) % 7]).astype(jnp.int32)


def _reference_forward(leaves, x, cfg, precision, pos):
    """One jitted pass of the reference: its logits, the indexer's loss, the
    experts each token picks and the keys each query keeps, layer by layer."""
    def forward(leaves, x):
        hidden, index_loss, picks = ref.hidden_states(leaves, x, cfg, precision, pos)
        return (ref._logits(hidden, leaves, jnp.float32), index_loss,
                jnp.stack([chosen for chosen, _ in picks]),
                jnp.stack([seen for _, seen in picks]))

    return jax.jit(forward)(leaves, jnp.asarray(x))


_FOLLOWED: dict = {}


def _followed(cfg, seed, control="REFERENCE", rows="text"):
    """``ref.follow`` of the seed's three batches, once a (seed, control, rows)."""
    key = (seed, control, rows)
    if key not in _FOLLOWED:
        pos = None if rows == "text" else _unequal_rows(TRAFFIC["seq_len"])
        _FOLLOWED[key] = ref.follow(
            cfg, cfg["program"], seed, _batches(cfg, seed), getattr(ref, control), pos)
    return _FOLLOWED[key]


_TRAINER: list = []


def _trainer(cfg, seed):
    """ONE trainer (one compile of its step) given the seed's weights anew."""
    variables = _variables(cfg, seed)
    if not _TRAINER:
        _TRAINER.append(
            runner.build_trainer(cfg, TRAFFIC["seq_len"], variables, jax.devices())
        )
    else:
        t = _TRAINER[0]
        t.params, t.opt_state = variables, t.tx.init(variables)
    return _TRAINER[0]


# -- the decoder against the reference -------------------------------------------------


@pytest.mark.parametrize("rows", ["text", "unequal"])
def test_logits_masks_and_selections_match_the_reference(cfg, rows):
    leaves = ref.init_params(cfg, 3)
    x, _ = _batches(cfg, 3, 1)[0]
    pos = None if rows == "text" else _unequal_rows(x.shape[1])
    model = runner.build_model(cfg)
    out, state = jax.jit(lambda v: model.apply(v, x, positions=pos, mutable=["intermediates"]))(
        runner.to_program_tree(leaves, None, cfg))
    logits, aux, dropped, expert_rows, buffers, kl, pairs = out
    want, index_loss, picks, masks = _reference_forward(leaves, x, cfg, ref.REFERENCE, pos)
    _close(logits, want)
    assert float(aux) == 0.0 and float(dropped) == 0.0 and logits.dtype == jnp.float32
    assert expert_rows.shape == (LAYERS, 4) and buffers.tolist() == [256.0] * LAYERS
    # topk 8 of 32 positions: the selection is live in three quarters of the rows
    per_layer = 2 * (8 * 9 // 2 + 24 * 8)
    assert pairs.tolist() == [per_layer] * LAYERS
    assert masks.shape == (LAYERS, 2, 32, 32)
    assert masks.sum(axis=(1, 2, 3)).tolist() == [per_layer] * LAYERS
    assert abs(float(kl) - float(index_loss)) < 1e-5 * float(index_loss) and float(kl) > 0
    got = jnp.stack([
        state["intermediates"][f"layers_{i}_moe"]["selected"][0] for i in range(LAYERS)])
    np.testing.assert_array_equal(np.asarray(got), np.asarray(picks))
    # each control is another function of the same leaves
    for control in ("NO_SELECTION", "HALF_TOPK") if pos is None else ("HALF_TOPK", "EQUAL_ROWS"):
        other = _reference_forward(leaves, x, cfg, getattr(ref, control), pos)[0]
        assert float(jnp.abs(other - logits).max()) > 1e-3, control


def test_losses_and_every_first_gradient_match_on_unequal_position_rows(cfg):
    """Straight through ``model.apply`` with three unequal rows (the trainer
    feeds text): the cross-entropy plus the indexer's loss, and its gradient
    on EVERY leaf; the cross-entropy alone reaches no leaf of the indexer and
    the indexer's loss reaches nothing else."""
    import optax

    leaves, (x, y) = ref.init_params(cfg, 6), _batches(cfg, 6, 1)[0]
    pos, model, names = _unequal_rows(x.shape[1]), runner.build_model(cfg), list(ref.param_shapes(cfg))
    ce = optax.softmax_cross_entropy_with_integer_labels

    def program(variables, index_weight=1.0, ce_weight=1.0):
        out = model.apply(variables, x, positions=pos)
        return ce_weight * ce(out[0], jnp.asarray(y)).mean() + index_weight * out[-2]

    variables = runner.to_program_tree(leaves, None, cfg)
    both = jax.jit(jax.value_and_grad(program), static_argnums=(1, 2))
    total, grads = both(variables, 1.0, 1.0)
    (want_total, _), want = jax.jit(jax.value_and_grad(
        lambda p: ref.total_loss(p, jnp.asarray(x), jnp.asarray(y), cfg, positions=pos),
        has_aux=True))(leaves)
    assert abs(float(total) - float(want_total)) < 1e-5 * float(want_total)
    grads = runner.by_reference_name(grads, names)
    for n in names:
        _close(grads[n].reshape(want[n].shape), want[n], 1e-4)
        assert float(jnp.abs(want[n]).max()) > 0, n  # no leaf is a no-op
    only_ce = runner.by_reference_name(both(variables, 0.0, 1.0)[1], names)
    only_index = runner.by_reference_name(both(variables, 1.0, 0.0)[1], names)
    for n in names:
        of_the_indexer = ".index_" in n
        assert (float(jnp.abs(only_ce[n]).max()) == 0.0) == of_the_indexer, n
        assert (float(jnp.abs(only_index[n]).max()) == 0.0) != of_the_indexer, n
    # rows read as equal are another function
    wrong = jax.jit(lambda p: ref.total_loss(
        p, jnp.asarray(x), jnp.asarray(y), cfg, ref.EQUAL_ROWS, positions=pos)[0])(leaves)
    assert abs(float(wrong) - float(want_total)) > 1e-4


def test_three_steps_through_moe_trainer_match_the_reference(cfg):
    """The two losses, the first gradient of EVERY leaf (as Adam's first
    moment holds it) and the parameters' change after three steps; the
    indexer's counters move by the steps' own metrics."""
    from akka_allreduce_tpu.obs import metrics

    names_ = ("trainer.indexer.selected_pairs",)
    seed, names = 11, list(ref.param_shapes(cfg))
    trainer, batches = _trainer(cfg, seed), _batches(cfg, seed)
    before = [metrics.REGISTRY.snapshot().get(n, 0) for n in names_]
    m = trainer.train_step(*batches[0])
    mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
    grads = {n: a / (1.0 - cfg["program"]["adam_b1"])
             for n, a in runner.by_reference_name(mu, names).items()}
    leaves = ref.init_params(cfg, seed)
    x, y = (jnp.asarray(a) for a in batches[0])
    (_, (loss, index_loss)), want = jax.jit(jax.value_and_grad(
        lambda p: ref.total_loss(p, x, y, cfg), has_aux=True))(leaves)
    assert abs(m.loss - float(loss)) < 1e-5 * float(loss)
    assert abs(m.indexer_loss - float(index_loss)) < 1e-5 * float(index_loss)
    for n in names:
        _close(grads[n].reshape(want[n].shape), want[n], 1e-4)
    assert m.dropped == 0.0 and m.aux_loss == 0.0 and m.contributors == 1.0
    assert m.mtp_loss is None and m.expert_rows.shape == (LAYERS, 4)
    assert m.selected_pairs.tolist() == [456.0] * LAYERS
    steps = [m] + [trainer.train_step(*b) for b in batches[1:]]
    now = metrics.REGISTRY.snapshot()
    assert now[names_[0]] - before[0] == 3 * LAYERS * 456.0
    assert now["trainer.indexer.loss"] == steps[-1].indexer_loss
    got = ref.delta_norms(runner.by_reference_name(trainer.params, names), cfg, seed)
    followed = _followed(cfg, seed)
    for n in names:
        assert abs(got[n] - followed["delta_norms"][n]) <= 1e-3 * followed["delta_norms"][n], n
    assert len(followed["losses"]) == 6  # three main losses, then the indexer's three


@pytest.mark.parametrize("control", CONTROLS)
def test_runner_check_passes_sound_and_fails_each_control(cfg, control):
    compare = spec.load_module("runners", "lm_train").compare
    seed, names = 11, list(ref.param_shapes(cfg))
    if "observed" not in _FOLLOWED:  # the program's three steps, once
        _FOLLOWED["observed"] = runner.first_steps(
            _trainer(cfg, seed), ref, cfg, seed, _batches(cfg, seed), names)
    followed = _followed(cfg, seed)
    assert all(c["ok"] for c in compare(_FOLLOWED["observed"], followed, cfg["correct_limits"]))
    wrongly = _followed(cfg, seed, control)
    failed = {c["name"]: c["value"] for c in compare(wrongly, followed, cfg["correct_limits"])
              if not c["ok"]}
    assert failed, control
    if control == "NO_INDEXER_LOSS":  # the indexer's leaves get no gradient at all
        assert failed["grad_norm_gap"] == pytest.approx(1.0)


def test_unequal_rows_read_as_equal_fail_the_check(cfg):
    compare = spec.load_module("runners", "lm_train").compare
    followed = _followed(cfg, 11, rows="unequal")
    wrongly = _followed(cfg, 11, "EQUAL_ROWS", "unequal")
    assert [c["name"] for c in compare(wrongly, followed, cfg["correct_limits"]) if not c["ok"]]
    assert all(c["ok"] for c in compare(followed, followed, cfg["correct_limits"]))


def test_a_whole_tiny_run_of_the_cell_is_correct():
    """The cell's own entry in BENCHMARK.json through the harness, tiny, on
    the CPU: units carry the indexer's loss and the pairs kept."""
    from harness.cell_run import run_cell

    traffic_cfg = dict(TRAFFIC, loop="closed", unit="train_step", warmup_units=3,
                       trace_seconds=0.5)
    result = run_cell(
        CELL, 2**31 + 9, 0.4, False, devices=jax.devices(),
        peak={"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11},
        t_process=time.perf_counter(),
        overrides={"config": _json(TINY), "traffic": traffic_cfg},
    )
    assert result["correct"] and result["failed"] == 0 and result["attempted"] >= 1
    assert set(result["metrics"]) == {"train_tokens_per_s", "setup_s"}


# -- the shares of one layer ----------------------------------------------------------


def test_the_eight_shares_of_one_layer_add_up_to_the_uncut_layer(cfg):
    """128 experts over 8 chips at tiny widths: every share computes attention
    (indexer, selection, mask and all) alike, so it is counted once; each
    routes over all 128 experts and computes its own 16 experts' part of the
    expert layer. The program's parts summed are the uncut reference's layer."""
    from akka_allreduce_tpu.models.hybrid_decoder import (
        GroupedQueryAttention,
        HeldExperts,
        HybridDecoderLM,
        IndexerRule,
    )

    whole = dict(cfg, num_experts=128, router_num_experts=128,
                 held_experts=list(range(128)), num_experts_per_tok=8, num_hidden_layers=1)
    leaves = ref.init_params(whole, 21)
    x = jax.random.normal(jax.random.PRNGKey(8), (2, 32, cfg["hidden_size"])) * 0.5
    w = lambda n: leaves["layers.0." + n]  # noqa: E731
    eps = whole["rms_norm_eps"]
    attended, _, _ = ref.attention(
        ref.rms_norm(x, w("op_norm.scale"), eps), w, ref.text_positions(32), whole, ref.REFERENCE)
    u = ref.rms_norm(x + attended, w("ffn_norm.scale"), eps)
    uncut, _ = ref.expert_layer(u, w, whole, "float32")
    want = x + attended + uncut

    tree = runner.to_program_tree(leaves, None, whole)["params"]
    attn = GroupedQueryAttention(
        8, 2, 16, 1e7, eps, jnp.float32, scope_name="sparse_attention",
        mrope_sections=(2, 3, 3), indexer=IndexerRule(4, 8, 8))
    a, kl, pairs = attn.apply(
        {"params": tree["layers_0_attn"]}, ref.rms_norm(x, w("op_norm.scale"), eps), None)
    _close(a, attended, 1e-4)
    assert float(pairs) == 456.0 and float(kl) > 0
    parts = []
    for share in range(8):
        held = list(range(16 * share, 16 * share + 16))
        model = HybridDecoderLM.from_config(dict(whole, num_experts=16, held_experts=held))
        assert (model.held_first, model.held_count, model.num_experts) == (16 * share, 16, 128)
        module = HeldExperts(
            model.num_experts, model.experts_per_token, model.moe_intermediate_size,
            model.held_first, model.held_count, False, True, 1.0, jnp.float32, 0, "softmax")
        hold = slice(held[0], held[-1] + 1)
        y, rows, dropped, _ = module.apply({"params": {
            "router": w("router.w"), "w1": w("experts.w1")[hold],
            "w3": w("experts.w3")[hold], "w2": w("experts.w2")[hold]}}, u)
        assert float(dropped) == 0.0 and float(rows.sum()) > 0
        parts.append(y)
        _close(y, ref.expert_layer(u, w_of(leaves, share), whole, "float32", held)[0], 1e-4)
    _close(x + a + sum(parts), want, 1e-4)
    assert float(jnp.abs(parts[0] - parts[1]).max()) > 0


def w_of(leaves, share):
    """The reference's leaf getter over one share's experts."""
    def w(n):
        a = leaves["layers.0." + n]
        return a[16 * share: 16 * share + 16] if n.startswith("experts.") else a
    return w


def test_a_share_of_the_program_is_the_reference_given_the_same_share(cfg):
    """The program's expert layer told it holds experts 4-7 of 16 gives what
    the reference gives for those four (the tiny file's own share)."""
    leaves = ref.init_params(cfg, 22)
    x, _ = _batches(cfg, 22, 1)[0]
    both = [
        _reference_forward(leaves, x, dict(cfg, held_experts=held), ref.REFERENCE, None)[0]
        for held in ([4, 5, 6, 7], [0, 1, 2, 3])
    ]
    assert float(jnp.abs(both[0] - both[1]).max()) > 1e-4
    model = runner.build_model(cfg)
    logits = jax.jit(lambda v: model.apply(v, x))(runner.to_program_tree(leaves, None, cfg))[0]
    _close(logits, both[0])


# -- the configuration file and the reader ----------------------------------------------


def test_from_config_reads_the_key_set(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM, IndexerRule

    m = HybridDecoderLM.from_config(cfg)
    assert (m.num_experts, m.held_first, m.held_count) == (16, 4, 4)
    assert (m.router_score, m.use_select_bias, m.renormalise, m.routed_scale) == (
        "softmax", False, True, 1.0)
    assert (m.n_heads, m.n_kv_heads, m.head_dim) == (8, 2, 16)
    assert m.layer_types == ("full_attention",) * LAYERS and m.num_dense_layers == 0
    assert m.indexer == IndexerRule(heads=4, head_dim=8, topk=8)
    assert m.mrope_sections == (2, 3, 3) and m.rope_theta == 1e7 and m.norm_eps == 1e-6
    assert (m.shared_width, m.mtp_depth) == (0, 0)
    tree = jax.eval_shape(m.init, jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32))
    attn = tree["params"]["layers_1_attn"]
    assert sorted(attn) == ["index_k", "index_k_norm", "index_q", "index_w", "k", "k_norm",
                            "out", "q", "q_norm", "v"]
    assert sorted(tree["params"]["layers_1_moe"]) == ["router", "w1", "w2", "w3"]
    assert "fixed" not in tree and "layers_0_mlp" not in tree["params"]
    real = HybridDecoderLM.from_config(_json(REAL))
    assert real.indexer == IndexerRule(16, 64, 2048) and real.mrope_sections == (16, 24, 24)
    assert (real.num_experts, real.held_count, real.experts_per_token) == (128, 16, 8)


def test_the_same_reader_builds_plain_causal_attention_without_sa_config(cfg):
    """The Qwen3-MoE key set without ``sa_config``: no indexer leaf, the
    tuple of five, LFM2's scope, and the reference without an indexer."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    plain = {k: v for k, v in cfg.items() if k != "sa_config"}
    m = HybridDecoderLM.from_config(plain)
    assert m.indexer is None and m.mrope_sections == (2, 3, 3)
    leaves = ref.init_params(plain, 5)
    assert not [n for n in leaves if ".index_" in n]
    x, _ = _batches(plain, 5, 1)[0]
    for pos in (None, _unequal_rows(32)):
        out = jax.jit(lambda v, pos=pos: m.apply(v, x, positions=pos))(
            runner.to_program_tree(leaves, None, plain))
        assert len(out) == 5
        _close(out[0], _reference_forward(leaves, x, plain, ref.REFERENCE, pos)[0])
    text = jax.jit(lambda v: m.apply(v, x)).lower(
        runner.to_program_tree(leaves, None, plain)).as_text(debug_info=True)
    assert "/attention/" in text and "sparse_attention" not in text and "attn_indexer" not in text
    no_sections = dict(plain, rope_scaling={"rope_type": "default"})
    assert HybridDecoderLM.from_config(no_sections).mrope_sections is None
    bare = HybridDecoderLM.from_config(no_sections)
    out = jax.jit(lambda v: bare.apply(v, x))(runner.to_program_tree(leaves, None, no_sections))
    _close(out[0], _reference_forward(leaves, x, no_sections, ref.REFERENCE, None)[0])


@pytest.mark.parametrize("key,bad,named", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "rope_type": "yarn"}, "rope_scaling.rope_type"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "type": "linear"}, "rope_scaling.type"),
    ("rope_scaling", {"mrope_section": [2, 3, 3], "mrope_interleaved": True}, "mrope_interleaved"),
    ("rope_scaling", {"mrope_section": [2, 3, 4]}, "mrope_section"),
    ("sa_config", {"indexer_head_dim": 8, "indexer_num_heads": 4, "indexer_num_kv_heads": 2,
                   "topk": 8}, "indexer_num_kv_heads"),
    ("program", {"remat": True}, "program.remat"),
])
def test_from_config_refuses_by_name_what_it_does_not_build(cfg, key, bad, named):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    with pytest.raises(ValueError, match=named.replace(".", r"\.")):
        HybridDecoderLM.from_config(dict(cfg, **{key: bad}))


@pytest.mark.parametrize("name", ACCEPTED + ("tiny_lfm2_moe", "tiny_joyai_mla_moe",
                                             "tiny_laguna_moe", "tiny_mellum_moe"))
def test_the_accepted_files_do_not_enter_the_new_reader(name, monkeypatch):
    from akka_allreduce_tpu.models import hybrid_decoder

    def never(cfg):
        raise AssertionError("an accepted file entered the Qwen3-MoE reader")

    monkeypatch.setattr(hybrid_decoder, "_from_qwen3_moe_keys", never)
    folder = "tests" if name.startswith("tiny_") else "configs"
    m = hybrid_decoder.HybridDecoderLM.from_config(_json(os.path.join(BENCH, folder, name + ".json")))
    assert m.indexer is None and m.mrope_sections is None


def test_train_moe_cli_trains_from_the_configuration_file(capsys):
    from akka_allreduce_tpu.__main__ import main

    rc = main(["train-moe", "--config", TINY, "--seq-len", "32", "--batch", "8", "--steps", "20"])
    out = capsys.readouterr().out
    assert rc in (0, None)
    assert "dropped 0.0%" in out and "indexer loss" in out


def test_the_cells_configuration_counts_as_the_issue_says():
    """562,290,560 parameters, from the file's own keys; every number of the
    catalog's row under the same key but the three it lists as reduced."""
    real = _json(REAL)
    shapes = ref.param_shapes(real)
    count = lambda pick: sum(math.prod(s) for n, s in shapes.items() if pick(n))  # noqa: E731
    layer0 = lambda part: count(lambda n: n.startswith("layers.0.") and part(n))  # noqa: E731
    assert layer0(lambda n: n.split(".")[2] in "qkvo" and n.endswith(".w")) == 18_874_368
    assert layer0(lambda n: "_norm.scale" in n and ".index_" not in n and "op_" not in n
                  and "ffn_" not in n) == 256
    assert layer0(lambda n: ".index_" in n) == 2_261_120
    assert layer0(lambda n: "router" in n) == 262_144
    assert layer0(lambda n: n.endswith(("op_norm.scale", "ffn_norm.scale"))) == 4_096
    assert layer0(lambda n: ".experts." in n) == 16 * 4_718_592
    assert count(lambda n: n in ("embed", "head.w")) == 2 * 38_895_616
    assert count(lambda n: True) == 562_290_560
    assert count(lambda n: ".experts." in n) / 562_290_560 == pytest.approx(0.671, abs=1e-3)
    assert real["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = {
        "hidden_size": 2048, "num_attention_heads": 32, "num_key_value_heads": 4,
        "head_dim": 128, "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "intermediate_size": 6144, "rope_theta": 10000000, "num_local_experts": 128,
        "max_position_embeddings": 262144, "max_window_layers": 48, "rms_norm_eps": 1e-6,
        "decoder_sparse_step": 1, "mlp_only_layers": [], "norm_topk_prob": True,
        "sliding_window": None, "use_sliding_window": False, "model_type": "KeyeVL2",
        "rope_scaling": {"mrope_section": [16, 24, 24], "rope_type": "default", "type": "default"},
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16, "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512, "topk": 2048},
    }
    assert {k: real[k] for k in published} == published
    assert (real["num_hidden_layers"], real["num_experts"], real["vocab_size"]) == (5, 16, 18992)
    assert real["router_num_experts"] == 128 and real["held_experts"] == list(range(16))
    assert real["vocab_size"] * 8 == 151936 and not real["program"]["remat"]
    for key in ("reduced_from", "stands_for", "assumed", "departures", "memory_plan",
                "correct_limits", "correct_limits_why"):
        assert real[key], key
    assert real["memory_plan"]["batch1_t8192_gb"]["sum"] <= 14.2


def test_the_seeded_weights_spread_the_embedding_on_its_own():
    real = _json(REAL)
    assert ref.leaf_stds(real) == (0.02, real["embedding_initializer_range"])
    assert real["embedding_initializer_range"] in (1.0, 2.0, 4.0, 8.0)
    tiny = dict(_json(TINY), embedding_initializer_range=4.0)
    leaves = ref.init_params(tiny, 1)
    assert float(leaves["embed"].std()) == pytest.approx(4.0, rel=0.05)
    assert float(leaves["layers.0.q.w"].std()) == pytest.approx(0.05, rel=0.05)
    assert float(leaves["layers.0.index_k_norm.bias"].std()) == pytest.approx(0.05, rel=0.3)
    assert float(leaves["layers.0.index_k_norm.scale"].mean()) == pytest.approx(1.0, abs=0.05)




KEYE_NEW = ("indexer_ms", "indexer_select_ms", "indexer_target_ms",
            "attn_kernel_roofline_pct.keye", "sparse_tile_useful_pct", "mfu_pct.keye",
            "moe_gmm_roofline_pct.keye", "indexer_scores_ms")  # the last since PR 42


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    names = [c["name"] for c in bench["configs"]]
    config = bench["configs"][names.index("keye_vl2_30b_a3b_ep8")]
    assert names.index("keye_vl2_30b_a3b_ep8") == names.index("mellum2_12b_d4") + 1
    assert config["file"] == "benchmarks/configs/keye_vl2_30b_a3b_ep8.json"
    assert config["reduced"] == _json(REAL)["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert config["source"] == _json(REAL)["source"] == (
        "https://huggingface.co/Kwai-Keye/Keye-VL-2.0-30B-A3B/blob/main/config.json")
    at = [w["name"] for w in bench["workloads"]].index(CELL)
    cell = bench["workloads"][at]
    assert at == 7 and (cell["config"], cell["traffic"], cell["chips"]) == (
        "keye_vl2_30b_a3b_ep8", "closed_b1_t8192", 1)
    assert all(len(x["why"]) <= 200 for x in (config, cell))
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == set(KEYE_NEW) | {
        "step_ms_p50", "step_ms_p90", "step_span_ms_p50", "step_span_ms_p90",
        "host_gap_ms.train", "host_gap_ms.around_run", "host_gap_ms.caller",
        "host_gap_ms.place", "slow_steps", "slow_step_excess_ms.fetch",
        "slow_step_excess_ms.host", "device_idle_pct.train", "optimizer_own_pass_ms",
        "attn_kernel_ms", "gqa_proj_ms", "gqa_around_kernel_ms", "moe_gmm_ms",
        "moe_load_max_over_mean", "moe_row_buffer_fill_pct", "moe_path_ms",
        "moe_past_first_rung_pct"}
    # no windowed kernel here, no other configuration's share, none silent since PR 31
    assert not listed & {"swa_kernel_ms", "swa_tile_useful_pct", "mfu_pct.mellum",
                         "moe_gmm_roofline_pct.mellum", "flash_attn_ms", "mtp_share_pct"}
    metrics = [m["name"] for m in bench["per_layer"]]
    first = metrics.index(KEYE_NEW[0])  # new metrics: this cell's first, appended in one run
    assert metrics[first: first + len(KEYE_NEW)] == list(KEYE_NEW)  # later cells' follow
    for m in bench["per_layer"][first: first + len(KEYE_NEW)]:
        assert m["workloads"][0] == CELL and m["moves"] == "train_tokens_per_s"
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert CELL in e2e["train_tokens_per_s"]["workloads"] and e2e["train_tokens_per_s"]["bound"] == 0.022
    loaded = spec.load_cell(CELL)
    assert loaded.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert set(loaded.per_layer) == listed | {"compile_or_load_s"}
    for name in loaded.per_layer:  # every reader is a file beside the others
        assert hasattr(spec.load_module("layer_metrics", name), "compute")
    # no process of an older cell loads a file this PR adds
    for w in bench["workloads"][:at]:
        older = spec.load_cell(w["name"])
        assert older.config["runner"] != "keye_moe_train"
        assert not set(KEYE_NEW) & set(older.per_layer)

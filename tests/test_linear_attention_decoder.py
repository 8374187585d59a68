"""The configuration-built decoder of linear-attention layers (the gated delta
rule) among gated full-attention layers (``models/hybrid_decoder.py`` from
``qwen3_next``'s keys) through ``MoETrainer``, against the benchmark's plain
reference ``benchmarks/reference/qwen3_next_moe_plain.py`` (the token-by-token
recurrence) at tiny widths on the CPU, on seeded weights; the reader's
refusals, the sixteen shares of one layer, the configuration file's count, the
benchmark's entries. The op under it: ``tests/test_delta_rule.py``."""

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

ref = spec.load_module("reference", "qwen3_next_moe_plain")
runner = spec.load_module("runners", "qwen3_next_moe_train")

# 128 positions: two of the rule's chunks of 64, so the state is carried once
TRAFFIC = {"batch": 2, "seq_len": 128, "tokens": "copy_half"}
TINY = os.path.join(BENCH, "tests", "tiny_qwen3_next_moe.json")
REAL = os.path.join(BENCH, "configs", "qwen3_next_80b_a3b_ep16_d4.json")
CELL = "qwen3_next_ep16_train_b1_t8192"
ACCEPTED = ("lfm2_24b_a2b_ep8_d5", "joyai_llm_flash_ep32_d5_mtp1", "laguna_xs2_d5",
            "mellum2_12b_d4", "keye_vl2_30b_a3b_ep8", "starcoder2_3b_d4")
TINY_ONES = ("tiny_lfm2_moe", "tiny_joyai_mla_moe", "tiny_laguna_moe", "tiny_mellum_moe",
             "tiny_keye_moe")
CONTROLS = ("CONTROL", "NO_STATE_CARRY", "NO_DELTA", "NO_DECAY", "NO_OUT_GATE",
            "FULL_ROTARY", "UNGATED_SHARED")
KINDS = ("linear_attention",) * 3 + ("full_attention",)  # of the cell's file
TINY_KINDS = ("linear_attention", "full_attention")  # the tiny file: a period of two
NEW = ("gdn_ms", "gdn_proj_ms", "gdn_conv_ms", "gdn_core_ms", "gdn_core_roofline_pct",
       "mfu_pct.qwen3next", "attn_kernel_roofline_pct.qwen3next")


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


_FOLLOWED: dict = {}


def _followed(cfg, seed, control="REFERENCE"):
    """``ref.follow`` of the seed's three batches, once a (seed, control)."""
    key = (seed, control)
    if key not in _FOLLOWED:
        _FOLLOWED[key] = ref.follow(
            cfg, cfg["program"], seed, _batches(cfg, seed), getattr(ref, control))
    return _FOLLOWED[key]


_TRAINER: list = []


def _trainer(cfg, seed):
    """ONE trainer (one compile of its step) given the seed's weights anew."""
    variables = runner.to_program_tree(ref.init_params(cfg, seed), None, cfg)
    if not _TRAINER:
        _TRAINER.append(
            runner.build_trainer(cfg, TRAFFIC["seq_len"], variables, jax.devices())
        )
    else:
        t = _TRAINER[0]
        t.params, t.opt_state = variables, t.tx.init(variables)
    return _TRAINER[0]


# -- the decoder against the reference -------------------------------------------------


def test_logits_selections_and_the_states_readings_match_the_reference(cfg):
    leaves = ref.init_params(cfg, 3)
    x, _ = _batches(cfg, 3, 1)[0]
    model = runner.build_model(cfg)
    out, state = jax.jit(lambda v: model.apply(v, x, mutable=["intermediates"]))(
        runner.to_program_tree(leaves, None, cfg))
    logits, aux, dropped, expert_rows, buffers, log_decay, state_rms = out

    def forward(leaves, precision):
        hidden, picks, _ = ref.hidden_states(leaves, jnp.asarray(x), cfg, precision)
        return ref._logits(hidden, leaves, jnp.float32), jnp.stack(picks)

    want, picks = jax.jit(lambda p: forward(p, ref.REFERENCE))(leaves)
    _close(logits, want)
    assert float(aux) == 0.0 and float(dropped) == 0.0 and logits.dtype == jnp.float32
    assert expert_rows.shape == (2, 4) and buffers.shape == (2,)
    got = jnp.stack([
        state["intermediates"][f"layers_{i}_moe"]["selected"][0] for i in range(2)])
    # the same experts, but for a near-tie at the last pick of a few tokens
    differ = np.sort(np.asarray(got), axis=-1) != np.sort(np.asarray(picks), axis=-1)
    assert differ.any(axis=-1).mean() < 0.01
    decay, rms = jax.jit(lambda p: ref.state_stats(p, jnp.asarray(x), cfg))(leaves)
    assert float(log_decay) == pytest.approx(float(decay), rel=1e-5) and float(decay) < 0
    assert float(state_rms) == pytest.approx(float(rms), rel=1e-4) and float(rms) > 0
    # a structural control is another function of the same leaves (each of
    # them against the check: ``test_runner_check_...`` below)
    other = jax.jit(lambda p: forward(p, ref.NO_DELTA))(leaves)[0]
    assert float(jnp.abs(other - logits).max()) > 1e-4


def test_loss_and_every_first_gradient_match(cfg):
    import optax

    leaves, (x, y) = ref.init_params(cfg, 6), _batches(cfg, 6, 1)[0]
    model, names = runner.build_model(cfg), list(ref.param_shapes(cfg))
    ce = optax.softmax_cross_entropy_with_integer_labels
    variables = runner.to_program_tree(leaves, None, cfg)
    total, grads = jax.jit(jax.value_and_grad(
        lambda v: ce(model.apply(v, x)[0], jnp.asarray(y)).mean()))(variables)
    want_total, want = jax.jit(jax.value_and_grad(
        lambda p: ref.loss(p, jnp.asarray(x), jnp.asarray(y), cfg)))(leaves)
    assert abs(float(total) - float(want_total)) < 1e-5 * float(want_total)
    grads = runner.by_reference_name(grads, names)
    for n in names:
        _close(grads[n].reshape(want[n].shape), want[n], 2e-4)
        assert float(jnp.abs(want[n]).max()) > 0, n  # no leaf is a no-op


def test_three_steps_through_moe_trainer_match_the_reference(cfg):
    """The loss, the first gradient of EVERY leaf (as Adam's first moment
    holds it) and the parameters' change after three steps; the two gauges
    are the last step's readings."""
    from akka_allreduce_tpu.obs import metrics

    seed, names = 11, list(ref.param_shapes(cfg))
    trainer, batches = _trainer(cfg, seed), _batches(cfg, seed)
    followed = _followed(cfg, seed)
    m = trainer.train_step(*batches[0])
    mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
    norms = runner.base.leaf_norms(mu, names)
    for n in names:  # each leaf's gradient itself: the test above
        assert norms[n] / (1.0 - cfg["program"]["adam_b1"]) == pytest.approx(
            followed["grad_norms"][n], rel=2e-4), n
    leaves = ref.init_params(cfg, seed)
    x = jnp.asarray(batches[0][0])
    assert m.dropped == 0.0 and m.aux_loss == 0.0 and m.contributors == 1.0
    assert m.mtp_loss is None and m.indexer_loss is None and m.expert_rows.shape == (2, 4)
    decay, rms = jax.jit(lambda p: ref.state_stats(p, x, cfg))(leaves)
    assert m.log_decay_mean == pytest.approx(float(decay), rel=1e-5)
    assert m.state_rms == pytest.approx(float(rms), rel=1e-4)
    steps = [m] + [trainer.train_step(*b) for b in batches[1:]]
    now = metrics.REGISTRY.snapshot()
    assert now["trainer.linear_attention.log_decay_mean"] == steps[-1].log_decay_mean
    assert now["trainer.linear_attention.state_rms"] == steps[-1].state_rms
    got = ref.delta_norms(runner.by_reference_name(trainer.params, names), cfg, seed)
    for n in names:
        assert abs(got[n] - followed["delta_norms"][n]) <= 2e-3 * followed["delta_norms"][n], n
    assert [s.loss for s in steps] == pytest.approx(followed["losses"], rel=1e-5)


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
    assert [c["name"] for c in compare(wrongly, followed, cfg["correct_limits"])
            if not c["ok"]], control


def test_a_model_without_linear_layers_moves_neither_gauge():
    """Mellum2's tiny file: no linear rule, no gate on a shared expert, and
    the model's tuple does not grow (so the trainer's two fields stay None)."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    other = _json(os.path.join(BENCH, "tests", "tiny_mellum_moe.json"))
    model = HybridDecoderLM.from_config(other)
    assert model.linear_attention is None and model.shared_gate is False
    out = jax.eval_shape(
        lambda: model.apply(model.init(jax.random.PRNGKey(0), jnp.zeros((1, 32), jnp.int32)),
                            jnp.zeros((1, 32), jnp.int32)))
    assert len(out) == 5


def test_a_whole_tiny_run_of_the_cell_is_correct():
    """The cell's own entry in BENCHMARK.json through the harness, tiny, on
    the CPU: units carry the two readings of the state."""
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


def _w_of(leaves, first=None, count=None):
    """The reference's leaf getter, over one share's experts where given."""
    def w(n):
        a = leaves["layers.0." + n].astype(jnp.float32)
        return a[first: first + count] if first is not None and n.startswith("experts.") else a
    return w


def test_the_sixteen_shares_of_one_layer_add_up_to_the_uncut_layer(cfg):
    """512 experts over 16 chips at tiny widths: every share computes the
    mixer and the gated shared expert alike, so each is counted once; each
    routes over all 512 experts and computes its own 32 experts' part. The
    program's parts summed are the uncut reference's layer."""
    from akka_allreduce_tpu.models.hybrid_decoder import GatedDeltaNet, HeldExperts, HybridDecoderLM

    whole = dict(cfg, num_experts=512, router_num_experts=512,
                 held_experts=list(range(512)), num_experts_per_tok=10, num_hidden_layers=1,
                 moe_intermediate_size=8)
    leaves = ref.init_params(whole, 21)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 128, cfg["hidden_size"])) * 0.5
    w, eps = _w_of(leaves), whole["rms_norm_eps"]
    mixed, _, _ = ref.linear_attention(
        ref.rms_norm(x, w("op_norm.scale"), eps), w, whole, ref.REFERENCE)
    u = ref.rms_norm(x + mixed, w("ffn_norm.scale"), eps)
    uncut, _ = ref.expert_layer(u, w, whole, ref.REFERENCE)
    want = x + mixed + uncut

    tree = runner.to_program_tree(leaves, None, whole)["params"]
    model = HybridDecoderLM.from_config(whole)
    a, decay, rms = GatedDeltaNet(model.linear_attention, eps, jnp.float32).apply(
        {"params": tree["layers_0_linear"]}, ref.rms_norm(x, w("op_norm.scale"), eps))
    _close(a, mixed, 1e-4)
    assert float(decay) < 0 and float(rms) > 0
    moe, parts, every = tree["layers_0_moe"], [], None
    for share in range(16):
        held = list(range(32 * share, 32 * share + 32))
        m = HybridDecoderLM.from_config(dict(whole, num_experts=32, held_experts=held))
        assert (m.held_first, m.held_count, m.num_experts) == (32 * share, 32, 512)
        hold = slice(held[0], held[-1] + 1)
        mine = {"router": moe["router"], "w1": moe["w1"][hold], "w3": moe["w3"][hold],
                "w2": moe["w2"][hold]}

        def part(shared_width, params, m=m):
            module = HeldExperts(
                m.num_experts, m.experts_per_token, m.moe_intermediate_size, m.held_first,
                m.held_count, False, True, 1.0, jnp.float32, shared_width, "softmax",
                bool(shared_width))
            y, rows, dropped, _ = module.apply({"params": params}, u)
            assert float(dropped) == 0.0
            return y

        routed = part(0, mine)
        parts.append(routed)
        _close(routed, ref.expert_layer(
            u, _w_of(leaves, held[0], 32), whole, ref.REFERENCE, held, shared=False)[0], 1e-4)
        if share in (0, 15):  # what every chip computes alike: counted once
            with_shared = part(m.shared_width, dict(
                mine, shared=moe["shared"], shared_gate=moe["shared_gate"]))
            if every is None:
                every = with_shared - routed
            else:
                _close(with_shared - routed, every, 1e-4)
    _close(x + a + sum(parts) + every, want, 1e-4)
    ungated = ref.expert_layer(u, w, whole, ref.UNGATED_SHARED)[0]
    assert float(jnp.abs(ungated - uncut).max()) > 1e-4


def test_a_share_of_the_program_is_the_reference_given_the_same_share(cfg):
    """The program told it holds experts 4-7 of 16 gives what the reference
    gives for those four (the tiny file's own share)."""
    leaves = ref.init_params(cfg, 22)
    x, _ = _batches(cfg, 22, 1)[0]
    both = [
        jax.jit(lambda p, c=dict(cfg, held_experts=held): ref.logits(p, jnp.asarray(x), c))(leaves)
        for held in ([4, 5, 6, 7], [0, 1, 2, 3])
    ]
    assert float(jnp.abs(both[0] - both[1]).max()) > 1e-4
    model = runner.build_model(cfg)
    logits = jax.jit(lambda v: model.apply(v, x))(runner.to_program_tree(leaves, None, cfg))[0]
    _close(logits, both[0])


# -- the configuration file and the reader ----------------------------------------------


def test_from_config_reads_the_key_set(cfg):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM, LinearAttentionRule

    m = HybridDecoderLM.from_config(cfg)
    assert m.layer_types == TINY_KINDS and m.num_dense_layers == 0
    assert (m.num_experts, m.held_first, m.held_count) == (16, 4, 4)
    assert (m.router_score, m.use_select_bias, m.renormalise, m.routed_scale) == (
        "softmax", False, True, 1.0)
    assert (m.n_heads, m.n_kv_heads, m.head_dim, m.rotary_dim) == (4, 2, 16, 4)
    assert m.linear_attention == LinearAttentionRule(2, 4, 8, 8, 4)
    assert (m.attn_gate, m.shared_width, m.shared_gate) == ("element", 32, True)
    assert m.indexer is None and m.mrope_sections is None and m.mtp_depth == 0
    tree = jax.eval_shape(m.init, jax.random.PRNGKey(0), jnp.zeros((1, 64), jnp.int32))["params"]
    assert sorted(tree["layers_0_linear"]) == [
        "A_log", "ba", "conv", "dt_bias", "norm", "out", "qkvz"]
    assert sorted(tree["layers_1_attn"]) == ["k", "k_norm", "out", "q", "q_norm", "v"]
    assert tree["layers_1_attn"]["q"]["kernel"].shape == (64, 4 * 2 * 16)  # queries and gates
    assert sorted(tree["layers_1_moe"]) == ["router", "shared", "shared_gate", "w1", "w2", "w3"]
    assert "layers_1_linear" not in tree and "layers_0_attn" not in tree
    listed = HybridDecoderLM.from_config(dict(cfg, layer_types=list(TINY_KINDS[::-1])))
    assert listed.layer_types == TINY_KINDS[::-1]  # a file that has the list is read by it
    four = HybridDecoderLM.from_config(dict(cfg, num_hidden_layers=8, full_attention_interval=4))
    assert four.layer_types == KINDS * 2
    real = HybridDecoderLM.from_config(_json(REAL))
    assert real.layer_types == KINDS and real.rotary_dim == 64 and real.head_dim == 256
    assert real.linear_attention == LinearAttentionRule(16, 32, 128, 128, 4)
    assert (real.num_experts, real.held_count, real.experts_per_token) == (512, 32, 10)
    assert real.first_rung(8192) == 6656


@pytest.mark.parametrize("key,bad,named", [
    ("use_sliding_window", True, "use_sliding_window"),
    ("decoder_sparse_step", 2, "decoder_sparse_step"),
    ("mlp_only_layers", [0], "mlp_only_layers"),
    ("attention_bias", True, "attention_bias"),
    ("tie_word_embeddings", True, "tie_word_embeddings"),
    ("hidden_act", "gelu", "hidden_act"),
    ("rope_scaling", {"rope_type": "yarn", "factor": 4.0}, "rope_scaling"),
    ("linear_num_value_heads", 3, "linear_num_value_heads"),
    ("program", {"remat": True}, "program.remat"),
    ("layer_types", ["linear_attention", "sliding_attention"], "sliding_attention"),
])
def test_from_config_refuses_by_name_what_it_does_not_build(cfg, key, bad, named):
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    with pytest.raises(ValueError, match=named.replace(".", r"\.")):
        HybridDecoderLM.from_config(dict(cfg, **{key: bad}))


@pytest.mark.parametrize("key,value", [
    ("shared_expert_intermediate_size", 512), ("partial_rotary_factor", 0.25),
    ("linear_conv_kernel_dim", 4), ("full_attention_interval", 4),
])
def test_the_qwen3_moe_reader_refuses_what_only_the_new_reader_builds(key, value):
    """A file that the new reader's test does not catch (no
    ``linear_num_value_heads``) can not be built as plain attention without
    what these keys ask for."""
    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    keye = _json(os.path.join(BENCH, "tests", "tiny_keye_moe.json"))
    assert HybridDecoderLM.from_config(keye).indexer is not None
    with pytest.raises(ValueError, match=key):
        HybridDecoderLM.from_config(dict(keye, **{key: value}))


@pytest.mark.parametrize("name", ACCEPTED + TINY_ONES)
def test_the_accepted_files_do_not_enter_the_new_reader(name, monkeypatch):
    from akka_allreduce_tpu.models import hybrid_decoder

    def never(cfg):
        raise AssertionError("an accepted file entered the qwen3_next reader")

    entered = []
    real_reader = hybrid_decoder._from_qwen3_moe_keys
    monkeypatch.setattr(hybrid_decoder, "_from_qwen3_next", never)
    monkeypatch.setattr(hybrid_decoder, "_from_qwen3_moe_keys",
                        lambda cfg: entered.append(1) or real_reader(cfg))
    folder = "tests" if name.startswith("tiny_") else "configs"
    file = _json(os.path.join(BENCH, folder, name + ".json"))
    if name == "starcoder2_3b_d4":  # no configuration-built decoder: another model's keys
        assert "linear_num_value_heads" not in file and "num_experts" not in file
        return
    m = hybrid_decoder.HybridDecoderLM.from_config(file)
    assert m.linear_attention is None and not m.shared_gate and m.attn_gate in (False, True)
    assert bool(entered) == ("keye" in name)  # Keye's files still enter the Qwen3-MoE reader


def test_train_moe_cli_trains_from_the_configuration_file(capsys):
    from akka_allreduce_tpu.__main__ import main

    rc = main(["train-moe", "--config", TINY, "--seq-len", "128", "--batch", "8", "--steps", "12"])
    out = capsys.readouterr().out
    assert rc in (0, None)
    assert "dropped 0.0%" in out and "log decay -" in out and "state rms" in out
    assert "layers line/full" in out


def test_the_cells_configuration_counts_as_the_issue_says():
    """625,667,136 parameters, from the file's own keys; every number of the
    catalog's row under the same key but the three it lists as reduced."""
    real = _json(REAL)
    shapes = ref.param_shapes(real)
    count = lambda pick: sum(math.prod(s) for n, s in shapes.items() if pick(n))  # noqa: E731
    layer = lambda i, part: count(lambda n: n.startswith(f"layers.{i}.") and part(n))  # noqa: E731
    assert layer(0, lambda n: ".gdn." in n) == 33_718_464
    assert layer(0, lambda n: n.endswith("gdn.qkvz.w")) == 25_165_824
    assert layer(0, lambda n: n.endswith("gdn.o.w")) == 8_388_608
    assert layer(3, lambda n: n.split(".")[2] in ("q", "k", "v", "o", "q_norm", "k_norm")) == 27_263_488
    moe = lambda n: n.split(".")[2] in ("router", "experts", "shared", "shared_gate")  # noqa: E731
    assert layer(0, moe) == layer(3, moe) == 104_859_648
    assert layer(0, lambda n: ".experts." in n) == 32 * 3_145_728
    assert layer(0, lambda n: n.endswith(("op_norm.scale", "ffn_norm.scale"))) == 4_096
    assert count(lambda n: n in ("embed", "head.w")) == 2 * 18_992 * 2048
    assert count(lambda n: True) == 625_667_136
    assert count(lambda n: ".experts." in n) / 625_667_136 == pytest.approx(0.64, abs=5e-3)
    assert ref.layer_kinds(real) == list(KINDS)
    assert real["reduced"] == ["num_hidden_layers", "num_experts", "vocab_size"]
    published = {
        "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
        "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
        "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128, "linear_num_key_heads": 16,
        "linear_num_value_heads": 32, "linear_value_head_dim": 128,
        "max_position_embeddings": 262144, "mlp_only_layers": [], "model_type": "qwen3_next",
        "moe_intermediate_size": 512, "norm_topk_prob": True, "num_attention_heads": 16,
        "num_experts_per_tok": 10, "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 10000000,
        "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
        "use_sliding_window": False,
    }
    assert {k: real[k] for k in published} == published
    assert (real["num_hidden_layers"], real["num_experts"], real["vocab_size"]) == (4, 32, 18992)
    assert real["router_num_experts"] == 512 and real["held_experts"] == list(range(32))
    assert real["vocab_size"] * 8 == 151936 and not real["program"]["remat"]
    for key in ("reduced_from", "stands_for", "assumed", "departures", "memory_plan",
                "correct_limits", "correct_limits_why", "embedding_spread"):
        assert real[key], key
    assert real["memory_plan"]["batch1_t8192_gb"]["sum"] <= 14.5
    assert real["embedding_initializer_range"] in (1.0, 2.0, 4.0, 8.0)


def test_the_seeded_weights_follow_the_reference_layers_own_initialisers():
    tiny = dict(_json(TINY), embedding_initializer_range=4.0, linear_num_value_heads=512,
                linear_num_key_heads=256)
    leaves = ref.init_params(tiny, 1)
    assert float(leaves["embed"].std()) == pytest.approx(4.0, rel=0.05)
    assert float(leaves["layers.0.gdn.qkvz.w"].std()) == pytest.approx(0.05, rel=0.05)
    rate, bias = jnp.exp(leaves["layers.0.gdn.A_log"]), leaves["layers.0.gdn.dt_bias"]
    assert 1e-3 <= float(rate.min()) and float(rate.max()) <= 16 and float(rate.mean()) > 6
    step = jax.nn.softplus(bias)  # log-uniform in [1e-3, 1e-1]
    assert 1e-3 * 0.99 <= float(step.min()) and float(step.max()) <= 0.1 * 1.01
    assert float(jnp.abs(leaves["layers.0.gdn.conv"]).max()) <= 0.5
    assert float(leaves["layers.0.gdn.norm.scale"].mean()) == pytest.approx(1.0, abs=0.05)
    # so a token's log decay starts in about (-1.6, 0)
    assert -1.7 < float((-rate * step).min()) and float((-rate * step).max()) < 0


def test_the_count_of_the_delta_rule_is_of_the_operation():
    from harness import qwen3_next_flops as flops

    real = _json(REAL)
    one = flops.delta_rule_layer(real, 1, 8192)
    assert one["flops"] == 2 * 3 * 5_767_168 * 128 * 32 and one["flops"] / 1e9 == pytest.approx(141.7, abs=0.1)
    assert one["bytes"] == 4 * 8192 * 12288
    total = flops.train_flops_per_step(real, 1, 8192, 4 * 5120.0)
    assert total["always"] / 1e12 == pytest.approx(9.05, abs=0.02)
    assert total["attention"] / 1e12 == pytest.approx(1.65, abs=0.01)
    assert total["delta_rule"] == 3 * one["flops"]
    assert total["experts"] == 6 * 3_145_728 * 4 * 5120.0
    assert flops.layer_kinds(real) == list(KINDS)


def test_the_new_readers_say_nothing_where_there_is_nothing_to_read():
    """A record without a scope map (a program without the scopes, a runner
    without the map): every new reader returns None and does not raise."""
    import types

    class NoTrace:
        ops: dict = {}

        def main_module(self):
            return [(0.0, 0.1)]

        def matching(self, pattern):
            return [], 0.0

    record = {
        "cell": types.SimpleNamespace(
            config=_json(REAL), traffic={"batch": 1, "seq_len": 8192}),
        "chips": 1, "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": [{"t0": 0.0, "t1": 0.2, "work": 8192, "ok": True}],
                   "start": 0.0, "paused": 0.0},
    }
    for name in NEW:
        assert spec.load_module("layer_metrics", name).compute(record, NoTrace()) is None, name


def test_the_benchmark_lists_the_cell_where_the_issue_says():
    bench = _json(os.path.join(ROOT, "BENCHMARK.json"))
    config, cell = bench["configs"][-1], bench["workloads"][-1]
    assert len(bench["configs"]) == 8 and len(bench["workloads"]) == 9
    assert config["name"] == "qwen3_next_80b_a3b_ep16_d4"
    assert config["file"] == "benchmarks/configs/qwen3_next_80b_a3b_ep16_d4.json"
    assert config["reduced"] == _json(REAL)["reduced"]
    assert config["source"] == _json(REAL)["source"] == (
        "https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json")
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        CELL, config["name"], "closed_b1_t8192", 1)
    assert all(len(x["why"]) <= 200 for x in (config, cell))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    listed = {m["name"] for m in bench["per_layer"] if CELL in m.get("workloads", ())}
    assert listed == set(NEW) | {
        "step_ms_p50", "step_ms_p90", "step_span_ms_p50", "step_span_ms_p90",
        "host_gap_ms.train", "host_gap_ms.around_run", "host_gap_ms.caller",
        "host_gap_ms.place", "slow_steps", "slow_step_excess_ms.fetch",
        "slow_step_excess_ms.host", "device_idle_pct.train", "optimizer_own_pass_ms",
        "attn_kernel_ms", "gqa_proj_ms", "gqa_around_kernel_ms", "moe_gmm_ms",
        "moe_load_max_over_mean", "moe_row_buffer_fill_pct", "moe_path_ms",
        "moe_past_first_rung_pct"}
    metrics = [m["name"] for m in bench["per_layer"]]
    assert metrics[-len(NEW):] == list(NEW)  # appended, in one run
    for m in bench["per_layer"]:
        if CELL in m.get("workloads", ()):
            assert m["workloads"][-1] == CELL and m["moves"] == "train_tokens_per_s"
    for m in bench["per_layer"][-len(NEW):]:
        assert m["workloads"] == [CELL]
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert e2e["train_tokens_per_s"]["workloads"][-1] == CELL
    assert e2e["train_tokens_per_s"]["bound"] == 0.022
    loaded = spec.load_cell(CELL)
    assert loaded.end_to_end == ["train_tokens_per_s", "setup_s"]
    assert set(loaded.per_layer) == listed | {"compile_or_load_s"}
    for name in loaded.per_layer:  # every reader is a file beside the others
        assert hasattr(spec.load_module("layer_metrics", name), "compute")
    for w in bench["workloads"][:-1]:  # no older cell loads a file this PR adds
        older = spec.load_cell(w["name"])
        assert older.config["runner"] != "qwen3_next_moe_train"
        assert not set(NEW) & set(older.per_layer)

"""The ``lfm2_24b_a2b_ep8_d5`` configuration's files: the plain reference
against the program at tiny widths on the CPU, the control that has to fail,
a whole tiny run, the readers of its per-layer metrics on a made-up record,
and the cell's step and the reference's step compiled at real size for a
DESCRIBED ``v5e:2x2`` (no chip attached, nothing runs).

Run by hand (tier-1 does not collect ``benchmarks/tests``), in one process:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_lfm2_moe.py -q
"""

from __future__ import annotations

import copy
import json
import os
import time
import types

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common
import jax
import jax.numpy as jnp
import pytest
from harness import spec, traffic
from jax.sharding import SingleDeviceSharding
from test_cells_compile import as_tpu, topo  # noqa: F401  (fixtures)

CELL = "lfm2_ep8_train_b1_t8192"
TINY_TRAFFIC = {"loop": "closed", "unit": "train_step", "batch": 2, "seq_len": 32,
                "tokens": "copy_half", "warmup_units": 3, "trace_seconds": 0.5}
ref = spec.load_module("reference", "lfm2_moe_plain")
run = spec.load_module("runners", "moe_train")
compare = spec.load_module("runners", "lm_train").compare


def _json(*parts):
    with open(os.path.join(common.BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def _tiny():
    return _json("tests", "tiny_lfm2_moe.json")


def _run_tiny(config, seed=7):
    from harness.cell_run import run_cell

    return run_cell(
        CELL, seed, 0.6, False, devices=jax.devices(), peak=common.FAKE_PEAK,
        t_process=time.perf_counter(),
        overrides={"config": config, "traffic": TINY_TRAFFIC},
    )


# -- reference against program, tiny, on the CPU -----------------------------------


def test_logits_match_the_programs_forward():
    cfg = _tiny()
    leaves, bias = ref.init_params(cfg, 3), ref.select_bias(cfg, 3)
    tokens, _ = traffic.token_batch(TINY_TRAFFIC, cfg["vocab_size"], 3, 0)
    got = run.build_model(cfg).apply(run.to_program_tree(leaves, bias, cfg), tokens)[0]
    want = ref.logits(leaves, bias, tokens, cfg)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_run_is_correct_in_f32_and_not_in_bf16():
    sound = _run_tiny(_tiny(), seed=2**31 + 5)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 3
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    lower = _tiny()
    lower["program"]["compute_dtype"] = "bfloat16"
    assert not _run_tiny(lower)["correct"]


def test_control_fails_a_number():
    """The reference one step down (bf16 router logits, bf16 state) in the
    program's place: not correct."""
    cfg = _tiny()
    batches = [traffic.token_batch(TINY_TRAFFIC, cfg["vocab_size"], 5, i) for i in range(3)]
    followed = ref.follow(cfg, cfg["program"], 5, batches)
    control = ref.follow(cfg, cfg["program"], 5, batches, ref.CONTROL)
    assert [c["name"] for c in compare(control, followed, cfg["correct_limits"]) if not c["ok"]]
    assert all(c["ok"] for c in compare(followed, followed, cfg["correct_limits"]))


def test_a_unit_that_drops_an_assignment_counts_as_failed(monkeypatch):
    from akka_allreduce_tpu.train import MoETrainer

    real = MoETrainer.train_step

    def dropping(self, tokens, labels, valid=None):
        m = real(self, tokens, labels, valid)
        m.dropped = 0.01
        return m

    monkeypatch.setattr(MoETrainer, "train_step", dropping)
    result = _run_tiny(_tiny())
    assert result["failed"] == result["attempted"] and not result["correct"]


# -- the readers of the new per-layer metrics ----------------------------------------


def test_readers_on_a_made_up_record():
    real = _json("configs", "lfm2_24b_a2b_ep8_d5.json")
    tr = _json("traffic", "closed_b1_t8192.json")
    rows = [[512.0] * 7 + [1024.0]] * 4  # 4608 rows a layer, one expert at twice the rest
    units = [{"t0": i * 0.1, "t1": i * 0.1 + 0.1, "work": 8192, "ok": True,
              "expert_rows": rows} for i in range(10)]
    record = {
        "cell": types.SimpleNamespace(config=real, traffic=tr), "chips": 1,
        "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9},
        "window": {"units": units, "start": 0.0, "paused": 0.0},
    }

    class Trace:  # 10 steps; 12 ms of grouped products and 14 ms of flash a step
        def main_module(self):
            return [(i * 0.1, 0.09) for i in range(10)]

        def matching(self, name=None, kind=None):
            import re

            ops = {"gmm.3": [240, 0.08], "tgmm.1": [120, 0.04], "fusion.1": [10, 0.5],
                   "flash_attention.2": [10, 0.04], "flash_mha_bwd_dq.1": [10, 0.10]}
            hits = [v for k, v in ops.items() if re.search(name, k)]
            return sum(h[0] for h in hits), sum(h[1] for h in hits)

    read = lambda n: spec.load_module("layer_metrics", n).compute(record, Trace())  # noqa: E731
    assert read("moe_load_max_over_mean") == pytest.approx(1024 / 576)
    assert read("moe_gmm_ms") == pytest.approx(12.0)
    from harness.moe_flops import grouped_products, train_flops_per_token

    need = grouped_products(real, 4608)
    least = 4 * max(need["flops"] / 197e12, need["bytes"] / 819e9)
    assert read("moe_gmm_roofline_pct") == pytest.approx(100 * least / 0.012)
    assert 0 < read("moe_gmm_roofline_pct") < 100
    assert read("flash_attn_roofline_pct.moe") == pytest.approx(
        100 * 6 * 8192 * 8192 * 2048 / 197e12 / 0.014
    )
    per_token = train_flops_per_token(real, 8192, 4 * 4608 / 8192)["total"]
    assert read("mfu_pct.moe") == pytest.approx(100 * per_token * 81920 / 197e12)
    # a program that counts no rows, a trace without the kernels: nothing, no raise
    bare = dict(record, window=dict(record["window"], units=[
        {k: v for k, v in u.items() if k != "expert_rows"} for u in units]))
    for name in ("moe_load_max_over_mean", "mfu_pct.moe", "moe_gmm_roofline_pct"):
        assert spec.load_module("layer_metrics", name).compute(bare, Trace()) is None


# -- real size, for a described v5e:2x2 ------------------------------------------------


def _planned_gb(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
            "temporaries": round(mem.temp_size_in_bytes / 1e9, 2),
            "sum": round((mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 2)}


def test_cell_step_fits_with_its_kernels(topo, as_tpu):  # noqa: F811
    cfg, tr = _json("configs", "lfm2_24b_a2b_ep8_d5.json"), _json("traffic", "closed_b1_t8192.json")
    _, lowered = run.lower_step_on_shapes(cfg, tr, topo.devices[0])
    compiled = lowered.compile()
    moe_layers = len(ref.expert_layers(cfg))
    assert compiled.as_text().count("tpu_custom_call") >= 3 + 9 * moe_layers
    planned = _planned_gb(compiled)
    print("cell step planned GB", planned)
    assert planned["sum"] < 15.0
    assert planned == {k: cfg["memory_plan"]["batch1_t8192_gb"][k] for k in planned}


def test_reference_step_fits_the_freed_chip(topo, as_tpu):  # noqa: F811
    cfg, tr = _json("configs", "lfm2_24b_a2b_ep8_d5.json"), _json("traffic", "closed_b1_t8192.json")
    chip = SingleDeviceSharding(topo.devices[0])
    leaves = {
        n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
        for n, s in ref.param_shapes(cfg).items()
    }
    fixed = jax.ShapeDtypeStruct(
        (len(ref.expert_layers(cfg)), cfg["router_num_experts"]), jnp.float32, sharding=chip)
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32, sharding=chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    compiled = ref.make_step(cfg, cfg["program"]).lower(
        leaves, leaves, leaves, fixed, t, tokens, tokens
    ).compile()
    planned = _planned_gb(compiled)
    print("reference step planned GB", planned)
    assert planned["sum"] < 15.0  # leaves room for what outlives the trainer


def test_configuration_keeps_every_published_number():
    """Every key of the catalog row's ``config`` is in the file under the same
    name, equal or listed in ``reduced``; no width is reduced."""
    cfg = _json("configs", "lfm2_24b_a2b_ep8_d5.json")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("the catalog is not on this machine")
    with open(catalog, encoding="utf-8") as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    differs = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differs == set(cfg["reduced"]) == set(cfg["reduced_from"])
    assert not [k for k in differs if k.endswith(("_size", "_dim", "_rank"))
                and k != "vocab_size"]
    bench = _json("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2_24b_a2b_ep8_d5")
    assert entry["reduced"] == cfg["reduced"] and entry["source"] == row["source_url"]
    assert copy.deepcopy(cfg)["held_experts"] == list(range(cfg["num_experts"]))

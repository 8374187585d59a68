"""The ``joyai_llm_flash_ep32_d5_mtp1`` configuration's files: a whole tiny
run on the CPU and the control that has to fail, and the cell's step and the
reference's step compiled at real size for a DESCRIBED ``v5e:2x2`` (no chip
attached, nothing runs) inside the configuration's memory rule. The readers
of its per-layer metrics, the reference against the program and the
configuration's count are in tier-1, ``tests/test_latent_decoder.py``.

Run by hand (tier-1 does not collect ``benchmarks/tests``), in one process:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_joyai_mla_moe.py -q
"""

from __future__ import annotations

import json
import os
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common
import jax
import jax.numpy as jnp
from harness import spec, traffic
from jax.sharding import SingleDeviceSharding
from test_cells_compile import as_tpu, topo  # noqa: F401  (fixtures)

CELL = "joyai_ep32_train_b1_t8192"
TINY_TRAFFIC = {"loop": "closed", "unit": "train_step", "batch": 2, "seq_len": 32,
                "tokens": "copy_half", "warmup_units": 3, "trace_seconds": 0.5}
ref = spec.load_module("reference", "joyai_mla_moe_plain")
run = spec.load_module("runners", "mla_moe_train")
compare = spec.load_module("runners", "lm_train").compare


def _json(*parts):
    with open(os.path.join(common.BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def _run_tiny(config, seed=7):
    from harness.cell_run import run_cell

    return run_cell(
        CELL, seed, 0.6, False, devices=jax.devices(), peak=common.FAKE_PEAK,
        t_process=time.perf_counter(),
        overrides={"config": config, "traffic": TINY_TRAFFIC},
    )


def test_run_is_correct_in_f32_and_not_in_bf16():
    sound = _run_tiny(_json("tests", "tiny_joyai_mla_moe.json"), seed=2**31 + 5)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 3
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    lower = _json("tests", "tiny_joyai_mla_moe.json")
    lower["program"]["compute_dtype"] = "bfloat16"
    assert not _run_tiny(lower)["correct"]


def test_control_fails_a_number():
    cfg = _json("tests", "tiny_joyai_mla_moe.json")
    batches = [traffic.token_batch(TINY_TRAFFIC, cfg["vocab_size"], 5, i) for i in range(3)]
    followed = ref.follow(cfg, cfg["program"], 5, batches)
    control = ref.follow(cfg, cfg["program"], 5, batches, ref.CONTROL)
    assert [c["name"] for c in compare(control, followed, cfg["correct_limits"]) if not c["ok"]]
    assert all(c["ok"] for c in compare(followed, followed, cfg["correct_limits"]))


def _planned_gb(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
            "temporaries": round(mem.temp_size_in_bytes / 1e9, 2),
            "sum": round((mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 2)}


def test_cell_step_fits_with_its_kernels(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "joyai_llm_flash_ep32_d5_mtp1.json")
    tr = _json("traffic", "closed_b1_t8192.json")
    _, lowered = run.lower_step_on_shapes(cfg, tr, topo.devices[0])
    compiled = lowered.compile()
    text, moe_layers = compiled.as_text(), len(ref.expert_layers(cfg))
    # two attention kernels a layer (six with the module's), nine grouped
    # products in each of the five expert layers' first rung
    assert text.count("tpu_custom_call") >= 2 * 6 + 9 * moe_layers
    for scope in ("mla_down", "mla_up", "mla_out", "mla_attention", "shared_expert", "mtp"):
        assert f"/{scope}/" in text, scope
    planned = _planned_gb(compiled)
    print("cell step planned GB", planned)
    rule = cfg["memory_plan"]
    assert planned["sum"] <= 14.5 and not cfg["program"]["remat"]  # the rule's first case
    assert planned == {k: rule["batch1_t8192_gb"][k] for k in planned}


def test_reference_step_fits_the_freed_chip(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "joyai_llm_flash_ep32_d5_mtp1.json")
    tr = _json("traffic", "closed_b1_t8192.json")
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

"""The ``qwen3_next_80b_a3b_ep16_d4`` configuration's files: a whole tiny run
on the CPU in float32 and not in bf16, and the cell's step and the reference's
step compiled at real size for a DESCRIBED ``v5e:2x2`` (no chip attached,
nothing runs) inside the configuration's memory rule. The op, the readers on a
made-up record, the reference against the program, the controls and the
configuration's count are in tier-1, ``tests/test_delta_rule.py`` and
``tests/test_linear_attention_decoder.py``.

Run by hand (tier-1 does not collect ``benchmarks/tests``), in one process:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_qwen3_next_moe.py -q
"""

from __future__ import annotations

import json
import os
import re
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common
import jax
import jax.numpy as jnp
from harness import spec
from jax.sharding import SingleDeviceSharding
from test_cells_compile import as_tpu, topo  # noqa: F401  (fixtures)

CELL = "qwen3_next_ep16_train_b1_t8192"
TINY_TRAFFIC = {"loop": "closed", "unit": "train_step", "batch": 2, "seq_len": 128,
                "tokens": "copy_half", "warmup_units": 3, "trace_seconds": 0.5}
ref = spec.load_module("reference", "qwen3_next_moe_plain")
run = spec.load_module("runners", "qwen3_next_moe_train")


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
    sound = _run_tiny(_json("tests", "tiny_qwen3_next_moe.json"), seed=2**31 + 5)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 3
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    lower = _json("tests", "tiny_qwen3_next_moe.json")
    lower["program"]["compute_dtype"] = "bfloat16"
    assert not _run_tiny(lower)["correct"]


def _planned_gb(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
            "temporaries": round(mem.temp_size_in_bytes / 1e9, 2),
            "sum": round((mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 2)}


def test_cell_step_fits_with_its_kernels(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "qwen3_next_80b_a3b_ep16_d4.json")
    tr = _json("traffic", "closed_b1_t8192.json")
    trainer, lowered = run.lower_step_on_shapes(cfg, tr, topo.devices[0])
    assert trainer.param_count == 625_667_136
    compiled = lowered.compile()
    text = compiled.as_text()
    # the full layer's splash kernels at 16 heads of 256 on 2, and nine grouped
    # products in each of four layers' first rung
    assert "splash_mha_fwd" in text and text.count("tpu_custom_call") >= 2 + 9 * 4
    for scope in ("linear_attention", "gdn_in", "gdn_conv", "gdn_core", "gdn_out",
                  "attention", "attn_qkv", "attn_core", "attn_out", "shared_expert",
                  "moe_route", "moe_experts", "moe_combine", "optimizer"):
        assert f"/{scope}/" in text, scope
    # q goes in at 16 heads of 256, K/V compact at 2; the state of 32 heads is
    # float32; ONE loop over the 128 chunks each way
    assert re.search(r"bf16\[1,16,8192,256\]", text) and re.search(r"bf16\[1,2,8192,256\]", text)
    assert re.search(r"f32\[1,32,128,128\]", text)
    loops = [line for line in text.splitlines() if " while(" in line and "gdn_core" in line]
    assert len(loops) == 3 * 3  # a linear layer: forward, forward again, backward
    assert "[128,1,32,64,128]" in text  # what a loop is handed: 128 chunks of 64
    planned = _planned_gb(compiled)
    print("cell step planned GB", planned)
    rule = cfg["memory_plan"]
    assert planned["sum"] <= 14.5 and not cfg["program"]["remat"]  # the rule's first side
    assert planned == {k: rule["batch1_t8192_gb"][k] for k in planned}


def test_reference_step_fits_the_freed_chip(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "qwen3_next_80b_a3b_ep16_d4.json")
    tr = _json("traffic", "closed_b1_t8192.json")
    chip = SingleDeviceSharding(topo.devices[0])
    leaves = {
        n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
        for n, s in ref.param_shapes(cfg).items()
    }
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32, sharding=chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    compiled = ref.make_step(cfg, cfg["program"]).lower(
        leaves, leaves, leaves, t, tokens, tokens
    ).compile()
    planned = _planned_gb(compiled)
    print("reference step planned GB", planned)
    assert planned["sum"] < 15.0  # leaves room for what outlives the trainer

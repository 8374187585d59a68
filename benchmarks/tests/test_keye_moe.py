"""The ``keye_vl2_30b_a3b_ep8`` configuration's files: a whole tiny run on the
CPU and the controls that have to fail, the new readers on a recorded trace
of another program, and the cell's step and the reference's step compiled at
real size for a DESCRIBED ``v5e:2x2`` (no chip attached, nothing runs) inside
the configuration's memory rule (ISSUE 41 asked for the plan in
``test_cells_compile.py``; that file is the accepted benchmark's and is not
edited, so the plan is held here). The masked kernels alone, the readers on a
made-up record, the reference against the program and the configuration's
count are in tier-1, ``tests/test_with_learned_mask_attention.py`` and
``tests/test_with_learned_mask_decoder.py``.

Run by hand (tier-1 does not collect ``benchmarks/tests``), in one process:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_keye_moe.py -q
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
import pytest
from harness import spec, traffic
from jax.sharding import SingleDeviceSharding
from test_cells_compile import as_tpu, topo  # noqa: F401  (fixtures)

CELL = "keye_vl2_ep8_train_b1_t8192"
TINY_TRAFFIC = {"loop": "closed", "unit": "train_step", "batch": 2, "seq_len": 32,
                "tokens": "copy_half", "warmup_units": 3, "trace_seconds": 0.5}
NEW_READERS = ("indexer_ms", "indexer_select_ms", "indexer_target_ms",
               "attn_kernel_roofline_pct.keye", "sparse_tile_useful_pct", "mfu_pct.keye",
               "moe_gmm_roofline_pct.keye")
ref = spec.load_module("reference", "keye_moe_plain")
run = spec.load_module("runners", "keye_moe_train")
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
    sound = _run_tiny(_json("tests", "tiny_keye_moe.json"), seed=2**31 + 5)
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 3
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    lower = _json("tests", "tiny_keye_moe.json")
    lower["program"]["compute_dtype"] = "bfloat16"
    assert not _run_tiny(lower)["correct"]


@pytest.mark.parametrize("control", ["CONTROL", "NO_SELECTION", "HALF_TOPK", "NO_INDEXER_LOSS"])
def test_each_control_fails_a_number(control):
    cfg = _json("tests", "tiny_keye_moe.json")
    batches = [traffic.token_batch(TINY_TRAFFIC, cfg["vocab_size"], 5, i) for i in range(3)]
    followed = ref.follow(cfg, cfg["program"], 5, batches)
    wrongly = ref.follow(cfg, cfg["program"], 5, batches, getattr(ref, control))
    assert [c["name"] for c in compare(wrongly, followed, cfg["correct_limits"]) if not c["ok"]]
    assert all(c["ok"] for c in compare(followed, followed, cfg["correct_limits"]))


def test_new_readers_say_nothing_on_a_recorded_trace_of_another_program():
    """The repo's recorded trace (an LM cell's, ``data/*.xplane.pb``): no scope
    map, no gauges, no masked kernel - every new reader returns None and does
    not raise."""
    import types

    from harness.trace_reduce import reduce_trace

    data = os.path.join(common.TESTS, "data")
    planes = [f for f in os.listdir(data) if f.endswith(".xplane.pb")] if os.path.isdir(data) else []
    if not planes:
        pytest.skip("no recorded trace in benchmarks/tests/data")
    reduced = reduce_trace(os.path.join(data, planes[0]))
    record = {
        "cell": types.SimpleNamespace(
            config=_json("configs", "keye_vl2_30b_a3b_ep8.json"),
            traffic=_json("traffic", "closed_b1_t8192.json")),
        "chips": 1, "peak": common.FAKE_PEAK,
        "window": {"units": [{"t0": 0.0, "t1": 0.2, "work": 8192, "ok": True}],
                   "start": 0.0, "paused": 0.0},
    }
    for name in NEW_READERS:
        reader = spec.load_module("layer_metrics", name)
        value = reader.compute(record, reduced)
        assert value is None or (name == "attn_kernel_roofline_pct.keye" and value > 0), name


def _planned_gb(compiled) -> dict:
    mem = compiled.memory_analysis()
    return {"arguments": round(mem.argument_size_in_bytes / 1e9, 2),
            "temporaries": round(mem.temp_size_in_bytes / 1e9, 2),
            "sum": round((mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9, 2)}


def test_cell_step_fits_with_its_kernels(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "keye_vl2_30b_a3b_ep8.json")
    tr = _json("traffic", "closed_b1_t8192.json")
    trainer, lowered = run.lower_step_on_shapes(cfg, tr, topo.devices[0])
    assert trainer.param_count == 562_290_560
    compiled = lowered.compile()
    text, layers = compiled.as_text(), cfg["num_hidden_layers"]
    # the three masked kernels of five layers, nine grouped products in each
    # layer's first rung
    assert layers == 5 and text.count("tpu_custom_call") >= (3 + 9) * layers
    for kernel in ("flash_mha_sparse_fwd", "flash_mha_sparse_dq", "flash_mha_sparse_dkv"):
        assert len(re.findall(rf'custom_call_target="tpu_custom_call".*{kernel}|{kernel}', text)) >= layers, kernel
    for scope in ("sparse_attention", "attn_qkv", "attn_core", "attn_out", "attn_indexer",
                  "indexer_proj", "indexer_scores", "indexer_select", "indexer_target",
                  "moe_route", "moe_experts", "moe_combine", "optimizer"):
        assert f"/{scope}/" in text, scope
    assert "/shared_expert/" not in text and "splash_mha" not in text
    # q goes in at 32 heads, K/V compact at 4, the mask as int8, never a
    # (heads, T, T) array
    assert re.search(r"bf16\[1,32,8192,128\]", text) and re.search(r"bf16\[1,4,8192,128\]", text)
    assert re.search(r"s8\[1,8192,8192\]", text)
    assert not re.search(r"\[(?:16|32|4,8),8192,8192\]", text)
    planned = _planned_gb(compiled)
    print("cell step planned GB", planned)
    rule = cfg["memory_plan"]
    assert planned["sum"] <= 14.2 and not cfg["program"]["remat"]  # the rule's first side
    assert planned == {k: rule["batch1_t8192_gb"][k] for k in planned}


def test_reference_step_fits_the_freed_chip(topo, as_tpu):  # noqa: F811
    cfg = _json("configs", "keye_vl2_30b_a3b_ep8.json")
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

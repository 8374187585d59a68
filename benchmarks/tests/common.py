"""Shared by the benchmark's own tests: paths, tiny cells, a CPU run."""

import json
import os
import sys
import time

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

FAKE_PEAK = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11,
             "ici_bits_per_s": 1e11, "hbm_bytes": 1e10}
TINY_TRAFFIC = {
    "lm": {"loop": "closed", "unit": "train_step", "batch": 2, "seq_len": 32,
           "tokens": "copy_half", "warmup_units": 3, "trace_seconds": 0.5},
    "allreduce": {"loop": "closed", "unit": "round", "masked_per_round": 1,
                  "warmup_units": 2, "probe_elements": 32, "trace_seconds": 0.5},
}
CELL_OF = {"lm": "sc2_3b_train_b2_t4096", "allreduce": "allreduce_256m_mask1"}


def tiny_config(kind: str) -> dict:
    with open(os.path.join(TESTS, f"tiny_{kind}.json"), encoding="utf-8") as f:
        return json.load(f)


def run_tiny(kind: str, *, seed=7, seconds=0.6, trace=False, config=None, tmp=None):
    """Everything of a run but ``run.py``'s look for a chip, at a tiny size on
    the CPU's devices, through the real cell's entry in BENCHMARK.json."""
    import jax

    from harness.cell_run import run_cell

    return run_cell(
        CELL_OF[kind], seed, seconds, trace, devices=jax.devices(),
        peak=FAKE_PEAK, t_process=time.perf_counter(),
        overrides={"config": config or tiny_config(kind),
                   "traffic": TINY_TRAFFIC[kind]},
        scratch=tmp,
    )

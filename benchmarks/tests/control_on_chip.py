"""Readings on the chip at a cell's own size, for setting the limits of its
``correct``: what sound runs of the program give over many seeds, and what
the control gives. Run by hand (the benchmark's own runs do not run it):

    python3 benchmarks/tests/control_on_chip.py --workload <cell> \
        --seeds 11,12,13 --control-seeds 3

- A cell that trains: in one process, for each seed, the runner's set-up
  (the trainer's first three steps through the window's own call, no window)
  against the plain reference; for the first ``--control-seeds`` seeds also
  the reference one step down in the program's place (``lm_plain.CONTROL`` and
  its two halves alone) against the reference.
- A cell that times the collective: a short window at the cell's own load
  with the program's own lower-precision path (``compress="bf16"``) switched
  on, beside a sound one.
Prints one JSON line per reading.
"""

from __future__ import annotations

import argparse
import copy
import gc
import json
import os
import time

import common  # noqa: F401
from harness import cell_run, spec


def _ctx(cell, seed, devices, peak):
    return cell_run.Context(cell, seed, 0.0, False, list(devices[: cell.chips]), peak)


def lm_train(cell, seeds, control_seeds, devices, peak):
    runner_mod = spec.load_module("runners", "lm_train")
    ref_mod = spec.load_module("reference", cell.config["reference"])
    cfg, no_limit = cell.config, {k: float("inf") for k in cell.config["correct_limits"]}
    variants = {
        "control": ref_mod.CONTROL,
        "int8_matmuls_only": {"matmul": "int8", "store": "float32"},
        "bf16_state_only": {"matmul": "highest", "store": "bfloat16"},
    }
    for n, seed in enumerate(seeds):
        runner = runner_mod.Runner(_ctx(cell, seed, devices, peak))
        t = time.perf_counter()
        runner.setup()
        observed, first = runner.observed, runner.first
        runner.trainer = None
        del runner
        gc.collect()
        ref = ref_mod.follow(cfg, cfg["program"], seed, first)
        gaps = {c["name"]: c["value"] for c in runner_mod.compare(observed, ref, no_limit)
                if "value" in c}
        print(json.dumps({"seed": seed, "who": "program", **gaps,
                          "seconds": time.perf_counter() - t}), flush=True)
        if n < control_seeds:
            for who, precision in variants.items():
                t = time.perf_counter()
                try:
                    low = ref_mod.follow(cfg, cfg["program"], seed, first, precision)
                    gaps = {c["name"]: c["value"]
                            for c in runner_mod.compare(low, ref, no_limit) if "value" in c}
                except Exception as e:  # a control that crashes has failed
                    gaps = {"crashed": repr(e)[:300]}
                print(json.dumps({"seed": seed, "who": who, **gaps,
                                  "seconds": time.perf_counter() - t}), flush=True)


def allreduce(cell, seeds, control_seeds, devices, peak):
    for n, seed in enumerate(seeds):
        for who, compress in (("program", cell.config["compress"]), ("control", "bf16")):
            if who == "control" and n >= control_seeds:
                continue
            config = copy.deepcopy(cell.config)
            config["compress"] = compress
            result = cell_run.run_cell(
                cell.name, seed, 2.0, False, devices=devices, peak=peak,
                t_process=time.perf_counter(), overrides={"config": config, "traffic": cell.traffic},
            )
            print(json.dumps({"seed": seed, "who": who, "correct": result["correct"],
                              "attempted": result["attempted"],
                              "failed": result["failed"]}), flush=True)


def main() -> None:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--control-seeds", type=int, default=3)
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    with open(os.path.join(common.BENCH, "peaks.json"), encoding="utf-8") as f:
        peak = json.load(f)[jax.devices()[0].device_kind]
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    {"lm_train": lm_train, "allreduce": allreduce}[cell.config["runner"]](
        cell, seeds, args.control_seeds, jax.devices(), peak
    )


if __name__ == "__main__":
    main()

"""Readings on the chip for a cell that trains a decoder of mixed windowed
and full attention with held experts and a shared expert, at the cell's own
size. Run by hand (the benchmark's own runs do not run it):

    python3 benchmarks/tests/laguna_on_chip.py sweep --workload <cell>
    python3 benchmarks/tests/laguna_on_chip.py load --workload <cell> --seeds 1,2,3
    python3 benchmarks/tests/laguna_on_chip.py breakdown --workload <cell>
    python3 benchmarks/tests/laguna_on_chip.py limits --workload <cell> \
        --seeds 11,12,13 --control-seeds 3 [--variants control,no_window]

``sweep``: one windowed layer's attention at the cell's shape (T, the
windowed layers' heads on the K/V heads, the head size, the window), forward
alone and forward + backward, through the library's splash kernel under the
band mask at each of a list of tiles, with the fused backward and with the
two-kernel one; then a full layer's shape at the tiles in question.

``load``: for each seed ONE trainer's steps through the window's own call
and feed, ``--steps`` of them (a 30 s window holds ~150): the rows routed to
each layer's held experts (least, mean, most, the step of the most) and the
steps in which a layer's row buffer was not the first rung. One JSON line per
seed.

``breakdown``: the runner's own set-up, then a few of the window's steps
under the profiler; every device op of the step with its time and the
``op_name`` its HLO instruction carries and each step's row counters, written
to ``chiprun_out/``; the time under each named scope, per step, and what the
cell's readers say of the same steps.

``limits``: for each seed ONE trainer's first three steps through the
window's own call against the plain reference (the gaps ``correct`` limits),
and the share of (token, choice) pairs that program and reference select
differently; for the first ``--control-seeds`` seeds also the readings that
have to come out as not correct, each the reference run wrongly and held
against the reference run rightly: one step down in precision (``control``),
the window left out of the sliding layers (``no_window``), YaRN's factor left
at 1 (``no_attention_factor``), a step that returns its state unchanged.
One JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import re
import time

import common  # noqa: F401
from harness import spec, traffic
from mla_moe_on_chip import _ms  # ms a call, after one warm call


def sweep(cell) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    # the package exports the function under the module's name
    la = importlib.import_module("akka_allreduce_tpu.ops.local_attention")
    cfg, t, b = cell.config, cell.traffic["seq_len"], cell.traffic["batch"]
    d, kv, window = cfg["head_dim"], cfg["num_key_value_heads"], cfg["sliding_window"]
    by_kind = dict(zip(cfg["layer_types"], cfg["num_attention_heads_per_layer"]))

    def blocks(tile, compute, fused=True):
        return BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=min(compute, tile),
            block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=min(compute, tile),
            **({} if fused else {"block_q_dq": tile, "block_kv_dq": tile}),
            use_fused_bwd_kernel=fused)

    cases = [("sliding_attention", window, "taken", None)] + [
        ("sliding_attention", window,
         f"{tile} x {tile}, compute {compute}, {'fused' if fused else 'two-kernel'} backward",
         blocks(tile, compute, fused))
        for fused in (True, False) for tile in (256, 512, 1024) for compute in (256, 512)
        if compute <= tile
    ] + [("full_attention", None, "taken", None)] + [
        ("full_attention", None, f"{tile} x {tile}, compute 512, fused backward",
         blocks(tile, 512)) for tile in (512, 1024)
    ]
    real = la._splash_blocks
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    for kind, w, name, tiles in cases:
        h = by_kind[kind]
        q = jax.random.normal(key[0], (b, t, h, d), jnp.bfloat16)
        kk, v = (jax.random.normal(key[i], (b, t, kv, d), jnp.bfloat16) for i in (1, 2))
        la._splash_blocks = real if tiles is None else (lambda *a, tiles=tiles: tiles)
        # new functions each time: jit keeps a function's trace, tiles and all
        attend = lambda q, kk, v, w=w: la.local_attention(  # noqa: E731
            q, kk, v, causal=True, window=w)
        loss = lambda *a, f=attend: f(*a).astype(jnp.float32).sum()  # noqa: E731
        line = {"kind": kind, "tiles": name, "shape": [b, t, h, kv, d], "window": w}
        if tiles is None:
            line["taken"] = str(real(t, d, d, 2, w))
        try:
            line["forward_ms"] = _ms(jax.jit(attend), q, kk, v)
            line["forward_backward_ms"] = _ms(
                jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kk, v)
        except Exception as e:  # tiles the compiler refuses
            line["failed"] = repr(e)[:300]
        print(json.dumps(line), flush=True)
    la._splash_blocks = real


def _seeded(run, ref, cfg, cell, seed, trainer, devices):
    """The seed's weights in ``trainer`` (built where there is none yet)."""
    variables = run.to_program_tree(ref.init_params(cfg, seed), None, cfg)
    if trainer is None:
        return run.build_trainer(cfg, cell.traffic["seq_len"], variables, devices)
    trainer.params, trainer.opt_state = variables, trainer.tx.init(variables)
    return trainer


def load(cell, seeds, devices, steps: int) -> None:
    from akka_allreduce_tpu.ops.moe import row_rungs

    run = spec.load_module("runners", cell.config["runner"])
    ref = spec.load_module("reference", cell.config["reference"])
    cfg = {**cell.config, "use_expert_bias": False}
    rungs = row_rungs(
        cell.traffic["batch"] * cell.traffic["seq_len"] * cfg["num_experts_per_tok"],
        cfg["num_experts"], cfg["router_num_experts"])
    trainer = None
    for seed in seeds:
        t = time.perf_counter()
        trainer = _seeded(run, ref, cfg, cell, seed, trainer, devices)
        rows, buffers, losses = [], [], []
        for i in range(steps):
            m = trainer.train_step(
                *traffic.token_batch(cell.traffic, cfg["vocab_size"], seed, i))
            rows.append(m.expert_rows.sum(axis=1).tolist())
            buffers.append(m.buffer_rows.tolist())
            losses.append(m.loss)
        per_layer = list(zip(*rows))
        print(json.dumps({
            "seed": seed, "steps": steps, "rungs": rungs,
            "rows_least": [min(r) for r in per_layer],
            "rows_mean": [sum(r) / len(r) for r in per_layer],
            "rows_most": [max(r) for r in per_layer],
            "step_of_most": [r.index(max(r)) for r in per_layer],
            "steps_past_first_rung": [
                sum(1 for b in layer if b > rungs[0]) for layer in zip(*buffers)],
            "rows_every_10th_step": rows[::10],
            "loss_first_last": [losses[0], losses[-1]],
            "seconds": time.perf_counter() - t}), flush=True)


SCOPES = ("attn_qkv", "attn_core", "attn_out", "shared_expert",
          "moe_route", "moe_experts", "moe_combine")
READERS = ("attn_kernel_ms", "attn_kernel_roofline_pct.swa", "swa_kernel_ms",
           "swa_kernel_roofline_pct", "gqa_proj_ms", "gqa_around_kernel_ms",
           "moe_gmm_ms", "moe_row_buffer_fill_pct")


def breakdown(cell, devices, out_path: str, steps: int = 10) -> None:
    import os
    import tempfile

    import jax

    from harness import cell_run
    from harness.trace_reduce import find_xplane, reduce_trace

    with open(os.path.join(common.BENCH, "peaks.json"), encoding="utf-8") as f:
        peak = json.load(f)[devices[0].device_kind]
    ctx = cell_run.Context(cell, 2805, 0.0, True, list(devices[:1]), peak)
    runner = spec.load_module("runners", cell.config["runner"]).Runner(ctx)
    runner.setup()
    trace_dir = tempfile.mkdtemp(prefix="laguna_breakdown_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    units = []
    for i in range(steps):
        runner.prepare(i)
        units.append(runner.unit(i))
    jax.profiler.stop_trace()
    reduced = reduce_trace(find_xplane(trace_dir))
    runner.close_window()  # fills the scopes
    # what the cell's readers of the program's scopes and counters say of the
    # same steps, to hold against the table below
    record = {"cell": cell, "peak": peak, "chips": 1, "window": {"units": units}}
    readers = {
        name: spec.load_module("layer_metrics", name).compute(record, reduced)
        for name in READERS
    }
    scopes = runner.scopes
    runs = reduced.main_module()
    ops = [
        {"op": k, "count": v[0], "seconds": v[1], "opcode": v[2],
         "op_name": scopes.get(k, "")}
        for k, v in sorted(reduced.ops.items(), key=lambda kv: -kv[1][1])
    ]
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"steps": len(runs), "step_device_s": [r[1] for r in runs],
                   "window_s": reduced.window_s, "busy_s": reduced.busy_s,
                   "expert_rows": [u["expert_rows"] for u in units],
                   "buffer_rows": [u["buffer_rows"] for u in units],
                   "readers": readers, "ops": ops}, f)
    per_step = lambda s: round(1e3 * s / len(runs), 3)  # noqa: E731
    table: dict[str, float] = {}
    for o in ops:
        name = o["op_name"]
        hit = [s for s in SCOPES if re.search(rf"(?:^|/){s}(?:/|$)", name)]
        kind = re.search(r"(?:^|/)(full|sliding)_attention(?:/|$)", name)
        kernel = re.match(r"^(splash_m[hq]a|t?gmm)", o["op"])
        where = (hit[-1] if hit else "outside the scopes") + (
            f" ({kind.group(1)})" if kind else "") + (" (kernels)" if kernel else "")
        if not hit and re.search(r"layers_0_mlp", name):
            where = "dense mlp"
        table[where] = table.get(where, 0.0) + o["seconds"]
    print(json.dumps({
        "steps": len(runs), "ops": len(ops),
        "named": sum(1 for o in ops if o["op_name"]),
        "step_device_ms": per_step(sum(r[1] for r in runs)),
        "all_ops_ms": per_step(sum(o["seconds"] for o in ops)),
        "readers": readers,
        "ms_per_step": {k: per_step(v) for k, v in sorted(
            table.items(), key=lambda kv: -kv[1])},
        "top_ops": [[o["op"], per_step(o["seconds"]), o["op_name"][-70:]]
                    for o in ops[:25]],
    }), flush=True)


def limits(cell, seeds, control_seeds, devices, only=()) -> None:
    import jax
    import jax.numpy as jnp

    run = spec.load_module("runners", cell.config["runner"])
    ref = spec.load_module("reference", cell.config["reference"])
    compare = spec.load_module("runners", "lm_train").compare
    cfg = {**cell.config, "use_expert_bias": False}
    names = list(ref.param_shapes(cfg))
    no_limit = {k: float("inf") for k in cfg["correct_limits"]}
    # (followed, seed, batches) -> what a program with the fault would have observed
    variants = {
        "control": lambda f, s, b: ref.follow(cfg, cfg["program"], s, b, ref.CONTROL),
        "no_window": lambda f, s, b: ref.follow(cfg, cfg["program"], s, b, ref.NO_WINDOW),
        "no_attention_factor": lambda f, s, b: ref.follow(
            cfg, cfg["program"], s, b, ref.NO_ATTENTION_FACTOR),
        # no leaf moved (the losses after the first step are not made for it)
        "state_left_unchanged": lambda f, s, b: dict(
            f, delta_norms={n: 0.0 for n in f["delta_norms"]}),
    }
    variants = {k: v for k, v in variants.items() if k in (only or variants)}
    model = run.build_model(cfg)
    layers = [f"layers_{i}_moe" for i in ref.expert_layers(cfg)]

    @jax.jit
    def program_picks(variables, tokens):
        _, state = model.apply(variables, tokens, mutable=["intermediates"])
        return jnp.stack([state["intermediates"][m]["selected"][0] for m in layers])

    reference_picks = jax.jit(lambda p, b: ref.selections(p, b, cfg))
    trainer = None
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        leaves = ref.init_params(cfg, seed)
        batches = [
            traffic.token_batch(cell.traffic, cfg["vocab_size"], seed, i)
            for i in range(3)
        ]
        tokens = jnp.asarray(batches[0][0])
        mine = program_picks(run.to_program_tree(leaves, None, cfg), tokens)
        theirs = reference_picks(leaves, tokens)
        # a pair differs when the program's expert is not among the reference's
        differ = float(jnp.mean(~(mine[..., :, None] == theirs[..., None, :]).any(-1)))
        del leaves, mine, theirs
        trainer = _seeded(run, ref, cfg, cell, seed, trainer, devices)
        observed = run.first_steps(trainer, ref, cfg, seed, batches, names)
        trainer.params = trainer.opt_state = None  # the reference needs the room
        gc.collect()
        followed = ref.follow(cfg, cfg["program"], seed, batches)
        gaps = {c["name"]: c["value"] for c in compare(observed, followed, no_limit)
                if "value" in c}
        print(json.dumps({"seed": seed, "who": "program", **gaps,
                          "picks_differing_share": differ,
                          "losses": observed["losses"],
                          "seconds": time.perf_counter() - t}), flush=True)
        if n < control_seeds:
            for who, wrongly in variants.items():
                t = time.perf_counter()
                checks = compare(
                    wrongly(followed, seed, batches), followed, cfg["correct_limits"])
                gaps = {c["name"]: c["value"] for c in checks if "value" in c}
                print(json.dumps({"seed": seed, "who": who, **gaps,
                                  "fails": [c["name"] for c in checks if not c["ok"]],
                                  "seconds": time.perf_counter() - t}), flush=True)


def main() -> None:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("what", choices=("sweep", "load", "breakdown", "limits"))
    p.add_argument("--out", default="chiprun_out/laguna_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--variants", default="", help="of limits' wrong runs; all if empty")
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.what == "sweep":
        sweep(cell)
    elif args.what == "load":
        load(cell, seeds, jax.devices(), args.steps)
    elif args.what == "breakdown":
        breakdown(cell, jax.devices(), args.out)
    else:
        limits(cell, seeds, args.control_seeds, jax.devices(),
               tuple(v for v in args.variants.split(",") if v))


if __name__ == "__main__":
    main()

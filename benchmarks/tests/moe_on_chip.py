"""Readings on the chip for a cell that trains a decoder with held experts,
at the cell's own size. Run by hand (the benchmark's own runs do not run it):

    python3 benchmarks/tests/moe_on_chip.py products --workload <cell>
    python3 benchmarks/tests/moe_on_chip.py limits --workload <cell> \
        --seeds 11,12,13 --control-seeds 3

``products``: one expert layer's gated FFN over the worst-case row buffer
(tokens x experts per token rows), forward and backward, with an eighth, a
half and all of the buffer filled, through each implementation of the
grouped products (``lax.ragged_dot``; the megablox kernels at several
tilings) - what empty rows cost, and which implementation the chip prefers.

``breakdown``: the runner's own set-up, then a few of the window's steps
under the profiler; every device op of the step with its time, HLO opcode,
result shape and the ``op_name`` its HLO instruction carries
(named scopes, flax module names), written to ``chiprun_out/`` for the
per-scope shares of a step.

``limits``: for each seed, ONE trainer's first three steps through the
window's own call against the plain reference (the gaps ``correct`` limits),
and the share of (token, choice) pairs that program and reference select
differently on the seed's weights; for the first ``--control-seeds`` seeds
also the reference one step down (``CONTROL`` and its two halves alone) in
the program's place. Prints one JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import time

import common  # noqa: F401
from harness import spec, traffic


def _ms(fn, *args, repeat=10):
    import jax

    jax.block_until_ready(fn(*args))
    t = time.perf_counter()
    for _ in range(repeat):
        out = fn(*args)
    jax.block_until_ready(out)
    return 1e3 * (time.perf_counter() - t) / repeat


def products(cell) -> None:
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import moe

    cfg = cell.config
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    rows = cell.traffic["batch"] * cell.traffic["seq_len"] * cfg["num_experts_per_tok"]
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    xs = jax.random.normal(k[0], (rows, d), jnp.bfloat16)
    w1, w3 = (0.02 * jax.random.normal(k[i], (held, d, fe)) for i in (1, 2))
    w2 = 0.02 * jax.random.normal(k[3], (held, fe, d))

    def build(impl):
        def ffn(xs, w1, w3, w2, sizes):
            gate = moe.grouped_matmul(xs, w1, sizes, impl=impl)
            up = moe.grouped_matmul(xs, w3, sizes, impl=impl)
            return moe.grouped_matmul(jax.nn.silu(gate) * up, w2, sizes, impl=impl)

        def loss(xs, w1, w3, w2, sizes):
            return ffn(xs, w1, w3, w2, sizes).astype(jnp.float32).sum()

        return jax.jit(ffn), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    variants = [("ragged_dot", None, None)] + [
        ("gmm", g, t) for g, t in (
            ((128, 128, 128), (128, 128, 128)),
            ((256, 512, 512), (256, 512, 512)),
            ((512, 512, 512), (512, 512, 512)),
            ((512, 1024, 1024), (512, 512, 512)),
            ((512, 1024, 1024), (512, 1024, 1024)),
            ((512, 2048, 512), (1024, 512, 512)),
            ((1024, 1024, 512), (512, 1024, 512)),
            ((256, 2048, 1536), (256, 1024, 1024)),
        )
    ]
    for impl, gmm_tiles, tgmm_tiles in variants:
        if gmm_tiles:
            moe.GMM_TILING, moe.TGMM_TILING = gmm_tiles, tgmm_tiles
        line = {"impl": impl, "gmm_tiling": gmm_tiles, "tgmm_tiling": tgmm_tiles}
        try:
            fwd, grad = build(impl)
            for share in (8, 2, 1):
                each = rows // share // held
                sizes = jnp.asarray([each] * held + [rows - each * held], jnp.int32)
                line[f"filled_1/{share}"] = {
                    "rows": each * held,
                    "forward_ms": _ms(fwd, xs, w1, w3, w2, sizes),
                    "backward_ms": _ms(grad, xs, w1, w3, w2, sizes),
                }
        except Exception as e:  # a tiling the compiler refuses
            line["failed"] = repr(e)[:400]
        print(json.dumps(line), flush=True)


def breakdown(cell, devices, out_path: str, steps: int = 10) -> None:
    import os
    import re
    import tempfile

    import jax

    from harness import cell_run
    from harness.trace_reduce import find_xplane, reduce_trace

    with open(os.path.join(common.BENCH, "peaks.json"), encoding="utf-8") as f:
        peak = json.load(f)[devices[0].device_kind]
    ctx = cell_run.Context(cell, 2805, 0.0, False, list(devices[:1]), peak)
    runner = spec.load_module("runners", "moe_train").Runner(ctx)
    runner.setup()
    trace_dir = tempfile.mkdtemp(prefix="moe_breakdown_")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    for i in range(steps):
        runner.prepare(i)
        runner.unit(i)
    jax.profiler.stop_trace()
    reduced = reduce_trace(find_xplane(trace_dir))
    t = runner.trainer
    from akka_allreduce_tpu.train.trainer import normalize_valid, place_mask, place_tokens

    xd, yd = place_tokens(*runner.batch, t._data_sharding, seq_len=t.seq_len, dp=1)
    vd = place_mask(normalize_valid(None, t.dp), t._valid_sharding)
    text = t._step.lower(t.params, t.opt_state, xd, yd, vd).compile().as_text()
    meta = {}
    for line in text.splitlines():
        hit = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = (\S+) ", line)
        if hit:
            name = re.search(r'op_name="([^"]*)"', line)
            meta[hit.group(1)] = (hit.group(2)[:80], name.group(1) if name else "")
    ops = [
        {"op": k, "count": v[0], "seconds": v[1], "opcode": v[2],
         "shape": meta.get(k, ("", ""))[0], "op_name": meta.get(k, ("", ""))[1]}
        for k, v in sorted(reduced.ops.items(), key=lambda kv: -kv[1][1])
    ]
    runs = reduced.main_module()
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump({"steps": len(runs), "step_device_s": [r[1] for r in runs],
                   "window_s": reduced.window_s, "busy_s": reduced.busy_s,
                   "ops": ops}, f)
    print(json.dumps({"steps": len(runs), "ops": len(ops), "named": sum(
        1 for o in ops if o["op_name"]), "busy_s": reduced.busy_s}), flush=True)


def limits(cell, seeds, control_seeds, devices) -> None:
    import jax
    import jax.numpy as jnp

    run = spec.load_module("runners", "moe_train")
    ref = spec.load_module("reference", cell.config["reference"])
    compare = spec.load_module("runners", "lm_train").compare
    cfg, names = cell.config, list(ref.param_shapes(cell.config))
    no_limit = {k: float("inf") for k in cfg["correct_limits"]}
    variants = {
        "control": ref.CONTROL,
        "bf16_router_only": {"router": "bfloat16", "store": "float32"},
        "bf16_state_only": {"router": "float32", "store": "bfloat16"},
    }
    model = run.build_model(cfg)
    layers = [f"layers_{i}_moe" for i in ref.expert_layers(cfg)]

    @jax.jit
    def program_picks(variables, tokens):
        _, state = model.apply(variables, tokens, mutable=["intermediates"])
        return jnp.stack([state["intermediates"][m]["selected"][0] for m in layers])

    reference_picks = jax.jit(lambda p, b, t: ref.selections(p, b, t, cfg))
    trainer = None
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        leaves, bias = ref.init_params(cfg, seed), ref.select_bias(cfg, seed)
        variables = run.to_program_tree(leaves, bias, cfg)
        batches = [
            traffic.token_batch(cell.traffic, cfg["vocab_size"], seed, i)
            for i in range(3)
        ]
        tokens = jnp.asarray(batches[0][0])
        mine = program_picks(variables, tokens)
        theirs = reference_picks(leaves, bias, tokens)
        # a pair differs when the program's expert is not among the reference's
        differ = float(jnp.mean(~(mine[..., :, None] == theirs[..., None, :]).any(-1)))
        del leaves, mine, theirs
        if trainer is None:
            trainer = run.build_trainer(cfg, cell.traffic["seq_len"], variables, devices)
        else:
            trainer.params, trainer.opt_state = variables, trainer.tx.init(variables)
        del variables
        observed = run.first_steps(trainer, ref, cfg, seed, batches, names)
        trainer.params = trainer.opt_state = None  # the reference needs the room
        gc.collect()
        followed = ref.follow(cfg, cfg["program"], seed, batches)
        gaps = {c["name"]: c["value"] for c in compare(observed, followed, no_limit)
                if "value" in c}
        print(json.dumps({"seed": seed, "who": "program", **gaps,
                          "picks_differing_share": differ,
                          "seconds": time.perf_counter() - t}), flush=True)
        if n < control_seeds:
            for who, precision in variants.items():
                t = time.perf_counter()
                low = ref.follow(cfg, cfg["program"], seed, batches, precision)
                gaps = {c["name"]: c["value"]
                        for c in compare(low, followed, no_limit) if "value" in c}
                print(json.dumps({"seed": seed, "who": who, **gaps,
                                  "seconds": time.perf_counter() - t}), flush=True)


def main() -> None:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("what", choices=("products", "breakdown", "limits"))
    p.add_argument("--out", default="chiprun_out/moe_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", type=int, default=3)
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.what == "products":
        products(cell)
    elif args.what == "breakdown":
        breakdown(cell, jax.devices(), args.out)
    else:
        limits(cell, [int(s) for s in args.seeds.split(",")],
               args.control_seeds, jax.devices())


if __name__ == "__main__":
    main()

"""Readings on the chip for a cell that trains a latent-attention decoder
with held experts, a shared expert and a prediction module, at the cell's
own size. Run by hand (the benchmark's own runs do not run it):

    python3 benchmarks/tests/mla_moe_on_chip.py splash --workload <cell>
    python3 benchmarks/tests/mla_moe_on_chip.py products --workload <cell>
    python3 benchmarks/tests/mla_moe_on_chip.py breakdown --workload <cell>
    python3 benchmarks/tests/mla_moe_on_chip.py limits --workload <cell> \
        --seeds 11,12,13 --control-seeds 3 [--variants control,mtp_loss_weight_0]

``splash``: one layer's attention at the cell's shape (T, heads, the 192-wide
query/key head against the 128-wide value head), forward alone and forward +
backward, through the library's splash kernel at each of a list of tiles.

``products``: one expert layer's gated FFN over the FIRST rung of the row
buffer (``ops.moe.row_rungs``), forward and backward, filled as a uniform
router fills it and completely, through the megablox kernels at each of a
list of tile rules in ``ops.moe.grouped_tiles``'s place.

``breakdown``: the runner's own set-up, then a few of the window's steps
under the profiler; every device op of the step with its time and the
``op_name`` its HLO instruction carries (the runner's ``op_scopes``) and each
step's row counters, written to ``chiprun_out/``; the time under each named
scope, per step, and what the cell's readers say of the same steps.

``limits``: as ``moe_on_chip.py limits`` - for each seed ONE trainer's first
three steps through the window's own call against the plain reference (the
gaps ``correct`` limits, the one loss gap over both losses), and the share of
(token, choice) pairs that program and reference select differently; for the
first ``--control-seeds`` seeds also the readings that have to come out as
not correct, each the reference run wrongly and held against the reference
run rightly, as the control is: the reference one step down in precision,
and the faults a step of this cell can have (``--variants`` picks among
them) - the second half of every sequence left out of the batch, the
prediction module's loss left out of the total (``mtp_loss_weight`` 0), a
step that returns its state unchanged. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import re
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


def splash(cell) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    # the package exports the function under the module's name
    la = importlib.import_module("akka_allreduce_tpu.ops.local_attention")
    cfg, t = cell.config, cell.traffic["seq_len"]
    h, dv = cfg["num_attention_heads"], cfg["v_head_dim"]
    d = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    k = jax.random.split(jax.random.PRNGKey(0), 3)
    b = cell.traffic["batch"]
    q, kk = (jax.random.normal(k[i], (b, t, h, d), jnp.bfloat16) for i in (0, 1))
    v = jax.random.normal(k[2], (b, t, h, dv), jnp.bfloat16)
    taken = la._splash_blocks(t, d, dv)
    fwd_tiles = [(512, 512, 512), (1024, 512, 512), (512, 1024, 512), (1024, 1024, 512),
                 (1024, 1024, 1024), (2048, 512, 512), (2048, 1024, 512), (1024, 2048, 512),
                 (2048, 2048, 512), (1024, 1024, 256)]
    bwd_tiles = [(512, 512, 512), (1024, 512, 512), (512, 1024, 512), (512, 1024, 1024),
                 (1024, 1024, 512), (1024, 1024, 1024), (2048, 512, 512), (256, 1024, 512),
                 (512, 2048, 512), (2048, 1024, 512)]
    cases = [("taken", taken)] + [
        (f"fwd {f}", BlockSizes(
            block_q=f[0], block_kv=f[1], block_kv_compute=f[2],
            block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512,
            use_fused_bwd_kernel=True)) for f in fwd_tiles
    ] + [
        (f"bwd {g}", BlockSizes(
            block_q=512, block_kv=512, block_kv_compute=512,
            block_q_dkv=g[0], block_kv_dkv=g[1], block_kv_dkv_compute=g[2],
            use_fused_bwd_kernel=True)) for g in bwd_tiles
    ] + [
        ("unfused 512", BlockSizes(
            block_q=512, block_kv=512, block_kv_compute=512,
            block_q_dkv=512, block_kv_dkv=512, block_kv_dkv_compute=512,
            block_q_dq=512, block_kv_dq=512, use_fused_bwd_kernel=False)),
    ]
    real = la._splash_blocks
    for name, blocks in cases:
        la._splash_blocks = lambda *a, blocks=blocks: blocks
        # new functions each time: jit keeps a function's trace, tiles and all
        attend = lambda q, kk, v: la.local_attention(  # noqa: E731
            q, kk, v, causal=True, sm_scale=d ** -0.5)
        loss = lambda *a, f=attend: f(*a).astype(jnp.float32).sum()  # noqa: E731
        line = {"tiles": name, "shape": [b, t, h, d, dv]}
        try:
            if not name.startswith("bwd"):
                line["forward_ms"] = _ms(jax.jit(attend), q, kk, v)
            if not name.startswith("fwd"):
                line["forward_backward_ms"] = _ms(
                    jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kk, v)
        except Exception as e:  # tiles the compiler refuses
            line["failed"] = repr(e)[:300]
        print(json.dumps(line), flush=True)
    la._splash_blocks = real


def _fit(n: int, most: int) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``n``."""
    return next(t for t in range(most, 0, -128) if n % t == 0)


#: tile rules tried in ``ops.moe.grouped_tiles``'s place: (m, k, n) -> tiles
TILE_RULES = {
    f"{tm} x {name}": (lambda m, k, n, tm=tm, rule=rule: (tm,) + rule(k, n))
    for tm in (128, 256, 512)
    for name, rule in {
        "512 x 512": lambda k, n: (512, 512),
        "512 x fit1024": lambda k, n: (512, _fit(n, 1024)),
        "fit1024 x fit1024": lambda k, n: (_fit(k, 1024), _fit(n, 1024)),
        "fit1024 x 256": lambda k, n: (_fit(k, 1024), 256),
    }.items()
}


def products(cell) -> None:
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import moe

    cfg = cell.config
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["n_routed_experts"]
    pairs = cell.traffic["batch"] * cell.traffic["seq_len"] * cfg["num_experts_per_tok"]
    rows = moe.row_rungs(pairs, held, cfg["router_num_experts"])[0]
    uniform = pairs // cfg["router_num_experts"]
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    xs = jax.random.normal(k[0], (rows, d), jnp.bfloat16)
    w1, w3 = (0.02 * jax.random.normal(k[i], (held, d, fe)) for i in (1, 2))
    w2 = 0.02 * jax.random.normal(k[3], (held, fe, d))

    def build():  # new functions each time: jit keeps a function's trace
        def ffn(xs, w1, w3, w2, sizes):
            gate = moe.grouped_matmul(xs, w1, sizes, impl="gmm")
            up = moe.grouped_matmul(xs, w3, sizes, impl="gmm")
            return moe.grouped_matmul(jax.nn.silu(gate) * up, w2, sizes, impl="gmm")

        def loss(xs, w1, w3, w2, sizes):
            return ffn(xs, w1, w3, w2, sizes).astype(jnp.float32).sum()

        return jax.jit(ffn), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))

    real = moe.grouped_tiles
    fills = {"uniform": uniform, "full": rows // held}
    # the whole of K in one tile: for the products alone (the weights'
    # gradient at 2048 overruns VMEM in the described-v5e compile)
    whole_k = {f"{tm} x fit2048 x fit1024": (
        lambda m, k, n, tm=tm: (tm, _fit(k, 2048), _fit(n, 1024))) for tm in (256, 512)}
    TILE_RULES.update(whole_k)
    base = "512 x 512 x 512"
    cases = [("taken", "taken")] + [(g, g) for g in TILE_RULES if g not in whole_k] + [
        (g, base) for g in whole_k
    ] + [  # the weights' gradient alone varied, the other products at 512^3
        (base, t) for t in TILE_RULES if t != base and t not in whole_k
    ]
    for gmm_rule, tgmm_rule in cases:
        def tiles(kind, m, k, n, groups, g=gmm_rule, t=tgmm_rule):
            if g == "taken":
                return real(kind, m, k, n, groups)
            return TILE_RULES[t if kind == "tgmm" else g](m, k, n)

        moe.grouped_tiles = tiles
        line = {"gmm": gmm_rule, "tgmm": tgmm_rule, "rows": rows}
        line["tiles_at_2048x768"] = [
            tiles("gmm", rows, d, fe, held), tiles("gmm", rows, fe, d, held),
            tiles("tgmm", rows, d, fe, held), tiles("tgmm", rows, fe, d, held),
        ]
        try:
            fwd, grad = build()
            for name, each in fills.items():
                sizes = jnp.asarray([each] * held + [rows - each * held], jnp.int32)
                line[name] = {
                    "rows_an_expert": each,
                    "forward_ms": _ms(fwd, xs, w1, w3, w2, sizes),
                    "backward_ms": _ms(grad, xs, w1, w3, w2, sizes),
                }
        except Exception as e:  # a tiling the compiler refuses
            line["failed"] = repr(e)[:300]
        print(json.dumps(line), flush=True)
    moe.grouped_tiles = real


SCOPES = ("mla_down", "mla_up", "mla_out", "mla_attention", "shared_expert",
          "moe_route", "moe_experts", "moe_combine")


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
    trace_dir = tempfile.mkdtemp(prefix="mla_breakdown_")
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
        for name in ("attn_kernel_ms", "attn_kernel_roofline_pct.mla", "moe_gmm_ms",
                     "moe_gmm_roofline_pct.mla", "mla_proj_ms", "mtp_share_pct")
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
        kernel = re.match(r"^(splash_m[hq]a|t?gmm)", o["op"])
        where = (hit[-1] if hit else "outside the scopes") + (
            " (kernels)" if kernel else "")
        if re.search(r"(?:^|/)mtp(?:/|$)", name):
            table["under mtp"] = table.get("under mtp", 0.0) + o["seconds"]
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
    cfg, names = cell.config, list(ref.param_shapes(cell.config))
    no_limit = {k: float("inf") for k in cfg["correct_limits"]}
    no_mtp = {**cfg, "program": {**cfg["program"], "mtp_loss_weight": 0.0}}
    half = cell.traffic["seq_len"] // 2
    # (followed, seed, batches) -> what a program with the fault would have observed
    variants = {
        "control": lambda f, s, b: ref.follow(cfg, cfg["program"], s, b, ref.CONTROL),
        "bf16_router_only": lambda f, s, b: ref.follow(
            cfg, cfg["program"], s, b, {"router": "bfloat16", "store": "float32"}),
        "bf16_state_only": lambda f, s, b: ref.follow(
            cfg, cfg["program"], s, b, {"router": "float32", "store": "bfloat16"}),
        "half_the_batch_left_out": lambda f, s, b: ref.follow(
            cfg, cfg["program"], s, [(x[:, :half], y[:, :half]) for x, y in b]),
        "mtp_loss_weight_0": lambda f, s, b: ref.follow(no_mtp, no_mtp["program"], s, b),
        # no leaf moved (the losses after the first step are not made for it)
        "state_left_unchanged": lambda f, s, b: dict(
            f, delta_norms={n: 0.0 for n in f["delta_norms"]}),
    }
    variants = {k: v for k, v in variants.items() if k in (only or variants)}
    model = run.build_model(cfg)
    layers = [run._module_prefix(p.rstrip(".")) + "moe" for p in ref.expert_layers(cfg)]

    @jax.jit
    def program_picks(variables, tokens, nxt):
        _, state = model.apply(variables, tokens, nxt, mutable=["intermediates"])
        return jnp.stack([state["intermediates"][m]["selected"][0] for m in layers])

    reference_picks = jax.jit(lambda p, b, t, n: ref.selections(p, b, t, n, cfg))
    trainer = None
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        leaves, bias = ref.init_params(cfg, seed), ref.select_bias(cfg, seed)
        variables = run.to_program_tree(leaves, bias, cfg)
        batches = [
            traffic.token_batch(cell.traffic, cfg["vocab_size"], seed, i)
            for i in range(3)
        ]
        tokens, nxt = (jnp.asarray(a) for a in batches[0])
        mine = program_picks(variables, tokens, nxt)
        theirs = reference_picks(leaves, bias, tokens, nxt)
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
    p.add_argument("what", choices=("splash", "products", "breakdown", "limits"))
    p.add_argument("--out", default="chiprun_out/mla_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--control-seeds", type=int, default=3)
    p.add_argument("--variants", default="", help="of limits' wrong runs; all if empty")
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    if args.what == "splash":
        splash(cell)
    elif args.what == "products":
        products(cell)
    elif args.what == "breakdown":
        breakdown(cell, jax.devices(), args.out)
    else:
        limits(cell, [int(s) for s in args.seeds.split(",")], args.control_seeds,
               jax.devices(), tuple(v for v in args.variants.split(",") if v))


if __name__ == "__main__":
    main()

"""Readings on the chip for a cell that trains a decoder of windowed and full
attention by the layer's kind with held experts in every layer (``mellum``'s
keys), at the cell's own size. Run by hand (the benchmark's own runs do not
run it):

    python3 benchmarks/tests/mellum_on_chip.py sweep --workload <cell> [--only band|products]
    python3 benchmarks/tests/mellum_on_chip.py load --workload <cell> --seeds 1,2,3
    python3 benchmarks/tests/mellum_on_chip.py breakdown --workload <cell>
    python3 benchmarks/tests/mellum_on_chip.py limits --workload <cell> \
        --seeds 11,12,13 --control-seeds 2 [--variants control,no_window]

``sweep``, the band: one windowed layer's attention at the cell's shape (T,
the query heads on the K/V heads, the head size, the window), forward alone
and forward + backward, through the library's splash kernel under the band
mask at tiles of 512 and 1024 (compute blocks 256 and 512), with the fused
backward and with the two-kernel one, and as the program's rule takes it;
then the full layer as taken. The products: one expert layer's gated FFN over
the FIRST rung of the row buffer (``ops.moe.row_rungs``), forward and
backward, filled as a uniform router fills it and completely, through the
megablox kernels at each of a list of tile rules in
``ops.moe.grouped_tiles``'s place, and the weights' gradient's rule varied
alone.

``sweep --only rungs``: that FFN over each rung of the ladder through the
kernels and through ``lax.ragged_dot``, at the uniform load and at the fullest
the rung holds of 30,000 rows.

``load`` and ``breakdown`` are ``laguna_on_chip.py``'s, the second with this
cell's readers; ``limits`` is its ``limits`` with this reference's controls:
one step down in precision (``control``), the window left out of the sliding
layers (``no_window``), the window halved (``half_window``: 512), YaRN's
factor left at 1 (``no_attention_factor``), a step that returns its state
unchanged. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import time

import common  # noqa: F401
import laguna_on_chip as base
from harness import spec, traffic
from mla_moe_on_chip import _fit, _ms  # a dividing tile; ms a call, after one warm call

base.SCOPES = ("attn_qkv", "attn_core", "attn_out", "moe_route", "moe_experts",
               "moe_combine", "optimizer")
base.READERS = (
    "attn_kernel_ms", "attn_kernel_roofline_pct.mellum", "swa_kernel_ms",
    "swa_kernel_roofline_pct.mellum", "gqa_proj_ms", "gqa_around_kernel_ms",
    "moe_gmm_ms", "moe_gmm_roofline_pct.mellum", "moe_path_ms",
    "moe_row_buffer_fill_pct", "moe_load_max_over_mean", "optimizer_own_pass_ms",
    "swa_tile_useful_pct",
)


def band(cell) -> None:
    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    la = importlib.import_module("akka_allreduce_tpu.ops.local_attention")
    cfg, t, b = cell.config, cell.traffic["seq_len"], cell.traffic["batch"]
    d, h, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    window = cfg["sliding_window"]

    def blocks(tile, compute, fused):
        return BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=compute,
            block_q_dkv=tile, block_kv_dkv=tile, block_kv_dkv_compute=compute,
            **({} if fused else {"block_q_dq": tile, "block_kv_dq": tile}),
            use_fused_bwd_kernel=fused)

    cases = [(window, "taken", None)] + [
        (window,
         f"{tile} x {tile}, compute {compute}, {'fused' if fused else 'two-kernel'} backward",
         blocks(tile, compute, fused))
        for fused in (False, True) for tile in (512, 1024) for compute in (256, 512)
    ] + [  # the forward's compute block alone at 512, the backward's at the tile
        (window, "1024 x 1024, compute 512 forward / 1024 backward, two-kernel backward",
         BlockSizes(block_q=1024, block_kv=1024, block_kv_compute=512, block_q_dkv=1024,
                    block_kv_dkv=1024, block_kv_dkv_compute=1024, block_q_dq=1024,
                    block_kv_dq=1024, use_fused_bwd_kernel=False)),
        (None, "taken", None),
    ]
    real = la._splash_blocks
    key = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(key[0], (b, t, h, d), jnp.bfloat16)
    kk, v = (jax.random.normal(key[i], (b, t, kv, d), jnp.bfloat16) for i in (1, 2))
    for w, name, tiles in cases:
        la._splash_blocks = real if tiles is None else (lambda *a, tiles=tiles: tiles)
        # new functions each time: jit keeps a function's trace, tiles and all
        attend = lambda q, kk, v, w=w: la.local_attention(  # noqa: E731
            q, kk, v, causal=True, window=w)
        loss = lambda *a, f=attend: f(*a).astype(jnp.float32).sum()  # noqa: E731
        line = {"sweep": "band", "tiles": name, "shape": [b, t, h, kv, d], "window": w}
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


#: tile rules tried in ``ops.moe.grouped_tiles``'s place: (m, k, n) -> tiles
TILE_RULES = {"512 x 512 x 512": lambda m, k, n: (512, 512, 512)} | {
    f"{tm} x fit{most} x fit{most}": (
        lambda m, k, n, tm=tm, most=most: (tm, _fit(k, most), _fit(n, most)))
    for tm in (256, 512, 1024) for most in (512, 1024, 1280, 2304)
}


def products(cell) -> None:
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import moe

    cfg = cell.config
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
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
    today = "512 x 512 x 512"
    cases = [("taken", "taken")] + [(g, g) for g in TILE_RULES] + [
        # the weights' gradient alone varied, the other products as today
        (today, t) for t in TILE_RULES if t != today and t.startswith("512 ")
    ]
    for gmm_rule, tgmm_rule in cases:
        def tiles(kind, m, k, n, groups, g=gmm_rule, t=tgmm_rule):
            if g == "taken":
                return real(kind, m, k, n, groups)
            return TILE_RULES[t if kind == "tgmm" else g](m, k, n)

        moe.grouped_tiles = tiles
        line = {"sweep": "products", "gmm": gmm_rule, "tgmm": tgmm_rule, "rows": rows,
                "tiles": {
                    f"gmm {d}x{fe}": tiles("gmm", rows, d, fe, held),
                    f"gmm {fe}x{d}": tiles("gmm", rows, fe, d, held),
                    f"tgmm {d}x{fe}": tiles("tgmm", rows, d, fe, held),
                    f"tgmm {fe}x{d}": tiles("tgmm", rows, fe, d, held)}}
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


def rungs(cell) -> None:
    """One expert layer's gated FFN over each rung of the ladder, forward and
    backward, through the kernels and through ``lax.ragged_dot``, at the
    uniform router's load and at the fullest the rung holds of 30,000 rows."""
    import jax
    import jax.numpy as jnp

    from akka_allreduce_tpu.ops import moe

    cfg = cell.config
    d, fe, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    pairs = cell.traffic["batch"] * cell.traffic["seq_len"] * cfg["num_experts_per_tok"]
    uniform = pairs // cfg["router_num_experts"]
    k = jax.random.split(jax.random.PRNGKey(0), 4)
    w1, w3 = (0.02 * jax.random.normal(k[i], (held, d, fe)) for i in (1, 2))
    w2 = 0.02 * jax.random.normal(k[3], (held, fe, d))
    for rows in moe.row_rungs(pairs, held, cfg["router_num_experts"]):
        xs = jax.random.normal(k[0], (rows, d), jnp.bfloat16)
        for impl in ("gmm", "ragged_dot"):
            def ffn(xs, w1, w3, w2, sizes, impl=impl):
                gate = moe.grouped_matmul(xs, w1, sizes, impl=impl)
                up = moe.grouped_matmul(xs, w3, sizes, impl=impl)
                return moe.grouped_matmul(jax.nn.silu(gate) * up, w2, sizes, impl=impl)

            loss = lambda *a, f=ffn: f(*a).astype(jnp.float32).sum()  # noqa: E731
            fwd, grad = jax.jit(ffn), jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3)))
            for each in sorted({uniform, min(30000, rows) // held}):
                sizes = jnp.asarray([each] * held + [rows - each * held], jnp.int32)
                line = {"sweep": "rungs", "rung": rows, "impl": impl, "rows_an_expert": each}
                try:
                    line["forward_ms"] = _ms(fwd, xs, w1, w3, w2, sizes)
                    line["backward_ms"] = _ms(grad, xs, w1, w3, w2, sizes)
                except Exception as e:
                    line["failed"] = repr(e)[:300]
                print(json.dumps(line), flush=True)


def limits(cell, seeds, control_seeds, devices, only=()) -> None:
    import jax
    import jax.numpy as jnp

    run = spec.load_module("runners", cell.config["runner"])
    ref = spec.load_module("reference", cell.config["reference"])
    compare = spec.load_module("runners", "lm_train").compare
    cfg = {**cell.config, "use_expert_bias": False}
    names = list(ref.param_shapes(cfg))
    no_limit = {k: float("inf") for k in cfg["correct_limits"]}
    wrongly = lambda how: (  # noqa: E731
        lambda f, s, b: ref.follow(cfg, cfg["program"], s, b, how))
    # (followed, seed, batches) -> what a program with the fault would have observed
    variants = {
        "control": wrongly(ref.CONTROL), "no_window": wrongly(ref.NO_WINDOW),
        "half_window": wrongly(ref.HALF_WINDOW),
        "no_attention_factor": wrongly(ref.NO_ATTENTION_FACTOR),
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
        trainer = base._seeded(run, ref, cfg, cell, seed, trainer, devices)
        observed = run.first_steps(trainer, ref, cfg, seed, batches, names)
        trainer.params = trainer.opt_state = None  # the reference needs the room
        gc.collect()
        followed = ref.follow(cfg, cfg["program"], seed, batches)
        gaps = {c["name"]: c["value"] for c in compare(observed, followed, no_limit)
                if "value" in c}
        for key in ("grad_norms", "delta_norms"):  # the leaf that decides each gap
            mine, theirs = observed[key], followed[key]
            middle = sorted(theirs.values())[len(theirs) // 2]
            gaps["worst_" + key[:-6]] = max(
                theirs, key=lambda n: abs(mine[n] - theirs[n]) / max(theirs[n], middle))
        print(json.dumps({"seed": seed, "who": "program", **gaps,
                          "picks_differing_share": differ,
                          "losses": observed["losses"],
                          "seconds": time.perf_counter() - t}), flush=True)
        if n < control_seeds:
            for who, fault in variants.items():
                t = time.perf_counter()
                checks = compare(
                    fault(followed, seed, batches), followed, cfg["correct_limits"])
                gaps = {c["name"]: c["value"] for c in checks if "value" in c}
                print(json.dumps({"seed": seed, "who": who, **gaps,
                                  "fails": [c["name"] for c in checks if not c["ok"]],
                                  "seconds": time.perf_counter() - t}), flush=True)


def main() -> None:
    import jax

    p = argparse.ArgumentParser()
    p.add_argument("what", choices=("sweep", "load", "breakdown", "limits"))
    p.add_argument("--out", default="chiprun_out/mellum_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--only", default="",
                   help="of sweep: band, products or rungs; the first two if empty")
    p.add_argument("--seeds", default="")
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--control-seeds", type=int, default=2)
    p.add_argument("--variants", default="", help="of limits' wrong runs; all if empty")
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.what == "sweep":
        if args.only in ("", "band"):
            band(cell)
        if args.only in ("", "products"):
            products(cell)
        if args.only == "rungs":
            rungs(cell)
    elif args.what == "load":
        base.load(cell, seeds, jax.devices(), args.steps)
    elif args.what == "breakdown":
        base.breakdown(cell, jax.devices(), args.out)
    else:
        limits(cell, seeds, args.control_seeds, jax.devices(),
               tuple(v for v in args.variants.split(",") if v))


if __name__ == "__main__":
    main()

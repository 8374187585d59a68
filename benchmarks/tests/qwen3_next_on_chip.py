"""Readings on the chip for a cell that trains a decoder of linear-attention
layers (the gated delta rule) among gated full-attention layers, with held
experts and a gated shared expert in every layer (``qwen3_next``), at the
cell's own size. Run by hand (the benchmark's own runs do not run it):

    python3 benchmarks/tests/qwen3_next_on_chip.py sweep --workload <cell>
    python3 benchmarks/tests/qwen3_next_on_chip.py load --workload <cell> --seeds 1,2,3 \
        [--spreads 1,2,4,8] [--steps 120]
    python3 benchmarks/tests/qwen3_next_on_chip.py breakdown --workload <cell>
    python3 benchmarks/tests/qwen3_next_on_chip.py limits --workload <cell> \
        --seeds 11,12 --control-seeds 2 [--variants control,no_state_carry]

``sweep``: the full layer's attention at the cell's shape (T, 16 heads on 2
K/V heads, q, k AND v at head 256) through the library's splash kernel at 1024
and at 512 tiles, forward alone and forward + backward; then one linear
layer's gated delta rule at the cell's shape, forward alone and forward +
backward. ``load`` is ``laguna_on_chip.py``'s (rows routed to each layer's held
experts and the rungs taken over ``--steps`` steps of each seed), once for each
``embedding_initializer_range`` of ``--spreads`` (the file's own where left
out). ``breakdown`` is its ``breakdown`` with this cell's scopes and readers.
``limits`` is ``mellum_on_chip.py``'s with this reference's controls: one step
down in precision (``control``), the state set to zero at every 64th position
(``no_state_carry``), the delta correction left out (``no_delta``), ``alpha``
held at 1 (``no_decay``), attention's gate off (``no_out_gate``), all 256
columns turned (``full_rotary``), the shared expert unweighted
(``ungated_shared``), a step that returns its state unchanged. One JSON line
per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import time

import common  # noqa: F401
import laguna_on_chip as base
from harness import spec, traffic
from mla_moe_on_chip import _ms  # ms a call, after one warm call

base.SCOPES = ("gdn_in", "gdn_conv", "gdn_core", "gdn_out", "attn_qkv", "attn_core",
               "attn_out", "shared_expert", "moe_route", "moe_experts", "moe_combine",
               "optimizer")
base.READERS = (
    "gdn_ms", "gdn_proj_ms", "gdn_conv_ms", "gdn_core_ms", "gdn_core_roofline_pct",
    "attn_kernel_ms", "attn_kernel_roofline_pct.qwen3next", "gqa_proj_ms",
    "gqa_around_kernel_ms", "moe_gmm_ms", "moe_path_ms", "moe_row_buffer_fill_pct",
    "moe_load_max_over_mean", "optimizer_own_pass_ms",
)
_laguna_seeded = base._seeded


def _seeded(run, ref, cfg, cell, seed, trainer, devices):
    """``laguna_on_chip._seeded`` after the last seed's state has gone: the
    arguments of this cell's step are 7.5 GB, and two of them do not fit."""
    if trainer is not None:
        trainer.params = trainer.opt_state = None
        gc.collect()
    return _laguna_seeded(run, ref, cfg, cell, seed, trainer, devices)


base._seeded = _seeded  # what ``load`` calls for each seed, and ``limits`` below
CONTROLS = ("control", "no_state_carry", "no_delta", "no_decay", "no_out_gate",
            "full_rotary", "ungated_shared")


def sweep(cell) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

    from akka_allreduce_tpu.ops.delta_rule import gated_delta_rule

    la = importlib.import_module("akka_allreduce_tpu.ops.local_attention")
    cfg, t, b = cell.config, cell.traffic["seq_len"], cell.traffic["batch"]
    d, h, kv = cfg["head_dim"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    key = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(key[0], (b, t, h, d), jnp.bfloat16)
    kk, v = (jax.random.normal(key[i], (b, t, kv, d), jnp.bfloat16) for i in (1, 2))
    real = la._splash_blocks
    cases = [("taken", None)] + [
        (f"{tile} x {tile}, compute 512, fused backward", BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=512, block_q_dkv=tile,
            block_kv_dkv=tile, block_kv_dkv_compute=tile, use_fused_bwd_kernel=True))
        for tile in (1024, 512)
    ]
    for name, tiles in cases:
        la._splash_blocks = real if tiles is None else (lambda *a, tiles=tiles: tiles)
        # new functions each time: jit keeps a function's trace, tiles and all
        attend = lambda q, kk, v: la.local_attention(q, kk, v, causal=True)  # noqa: E731
        loss = lambda *a, f=attend: f(*a).astype(jnp.float32).sum()  # noqa: E731
        line = {"what": "full_attention", "tiles": name, "shape": [b, t, h, kv, d]}
        if tiles is None:
            line["taken"] = str(real(t, d, d, 2, None))
        try:
            line["forward_ms"] = _ms(jax.jit(attend), q, kk, v)
            line["forward_backward_ms"] = _ms(
                jax.jit(jax.grad(loss, argnums=(0, 1, 2))), q, kk, v)
        except Exception as e:  # tiles the compiler refuses
            line["failed"] = repr(e)[:300]
        print(json.dumps(line), flush=True)
    la._splash_blocks = real
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    unit = lambda x: (x / jnp.linalg.norm(x.astype(jnp.float32), axis=-1, keepdims=True)  # noqa: E731
                      ).astype(jnp.bfloat16)
    q = unit(jax.random.normal(key[0], (b, hk, t, dk))) * dk ** -0.5
    k = unit(jax.random.normal(key[1], (b, hk, t, dk)))
    v = jax.random.normal(key[2], (b, hv, t, dv), jnp.bfloat16)
    g = -0.8 * jax.random.uniform(key[3], (b, hv, t))
    beta = jax.random.uniform(key[4], (b, hv, t))
    rule = lambda *a: gated_delta_rule(*a)[0]  # noqa: E731
    loss = lambda *a: rule(*a).astype(jnp.float32).sum()  # noqa: E731
    print(json.dumps({
        "what": "gated_delta_rule", "shape": [b, hk, hv, t, dk, dv],
        "forward_ms": _ms(jax.jit(rule), q, k, v, g, beta),
        "forward_backward_ms": _ms(
            jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))), q, k, v, g, beta),
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
    wrongly = lambda how: (  # noqa: E731
        lambda f, s, b: ref.follow(cfg, cfg["program"], s, b, how))
    # (followed, seed, batches) -> what a program with the fault would have observed
    variants = {name: wrongly(getattr(ref, name.upper())) for name in CONTROLS}
    # no leaf moved (the losses after the first step are not made for it)
    variants["state_left_unchanged"] = lambda f, s, b: dict(
        f, delta_norms={n: 0.0 for n in f["delta_norms"]})
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
            got, want = observed[key], followed[key]
            middle = sorted(want.values())[len(want) // 2]
            gaps["worst_" + key[:-6]] = max(
                want, key=lambda n: abs(got[n] - want[n]) / max(want[n], middle))
        print(json.dumps({"seed": seed, "who": "program", **gaps,
                          "picks_differing_share": differ,
                          "losses": observed["losses"],
                          "reference_losses": followed["losses"],
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
    p.add_argument("--out", default="chiprun_out/qwen3_next_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--spreads", default="",
                   help="of load: embedding_initializer_range values; the file's if empty")
    p.add_argument("--steps", type=int, default=150)
    p.add_argument("--control-seeds", type=int, default=2)
    p.add_argument("--variants", default="", help="of limits' wrong runs; all if empty")
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.what == "sweep":
        sweep(cell)
    elif args.what == "load":
        spreads = [float(s) for s in args.spreads.split(",") if s]
        for spread in spreads or [cell.config["embedding_initializer_range"]]:
            cell.config["embedding_initializer_range"] = spread
            print(json.dumps({"embedding_initializer_range": spread}), flush=True)
            base.load(cell, seeds, jax.devices(), args.steps)
    elif args.what == "breakdown":
        base.breakdown(cell, jax.devices(), args.out)
    else:
        limits(cell, seeds, args.control_seeds, jax.devices(),
               tuple(v for v in args.variants.split(",") if v))


if __name__ == "__main__":
    main()

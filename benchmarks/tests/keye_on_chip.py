"""Readings on the chip for a cell that trains a decoder whose attention runs
under a mask a learned indexer makes, with held experts in every layer
(``KeyeVL2``'s language model), at the cell's own size. Run by hand (the
benchmark's own runs do not run it):

    python3 benchmarks/tests/keye_on_chip.py load --workload <cell> --seeds 1,2,3 \
        [--spreads 1,2,4,8] [--steps 120]
    python3 benchmarks/tests/keye_on_chip.py breakdown --workload <cell>
    python3 benchmarks/tests/keye_on_chip.py limits --workload <cell> \
        --seeds 11,12 --control-seeds 2 [--variants control,no_selection]

``load`` is ``laguna_on_chip.py``'s (rows routed to each layer's held experts
and the rungs taken over ``--steps`` steps of each seed), once for each
``embedding_initializer_range`` of ``--spreads`` (the file's own where left
out): one compiled step serves them all, only the seeded weights differ.
``breakdown`` is its ``breakdown`` with this cell's scopes and readers.
``limits`` is ``mellum_on_chip.py``'s with this reference's controls: one
step down in precision (``control``), every causal key visible
(``no_selection``), half the keys kept (``half_topk``: 1,024), the indexer's
loss at weight 0 (``no_indexer_loss``), a step that returns its state
unchanged; beside the share of (token, choice) pairs the program routes
differently from the reference it prints the share of (query, key) pairs the
program's masks keep and the reference's do not. One JSON line per reading.
"""

from __future__ import annotations

import argparse
import gc
import json
import time

import common  # noqa: F401
import laguna_on_chip as base
from harness import spec, traffic

base.SCOPES = ("attn_qkv", "attn_core", "attn_out", "attn_indexer", "indexer_proj",
               "indexer_scores", "indexer_select", "indexer_target", "moe_route",
               "moe_experts", "moe_combine", "optimizer")
base.READERS = (
    "attn_kernel_ms", "attn_kernel_roofline_pct.keye", "gqa_proj_ms",
    "gqa_around_kernel_ms", "indexer_ms", "indexer_select_ms", "indexer_target_ms",
    "moe_gmm_ms", "moe_path_ms", "moe_row_buffer_fill_pct", "moe_load_max_over_mean",
    "optimizer_own_pass_ms", "sparse_tile_useful_pct",
)


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
        "control": wrongly(ref.CONTROL), "no_selection": wrongly(ref.NO_SELECTION),
        "half_topk": wrongly(ref.HALF_TOPK),
        "no_indexer_loss": wrongly(ref.NO_INDEXER_LOSS),
        # no leaf moved (the losses after the first step are not made for it)
        "state_left_unchanged": lambda f, s, b: dict(
            f, delta_norms={n: 0.0 for n in f["delta_norms"]}),
    }
    variants = {k: v for k, v in variants.items() if k in (only or variants)}
    model = run.build_model(cfg)
    layers = [f"layers_{i}" for i in ref.expert_layers(cfg)]

    @jax.jit
    def program_picks(variables, tokens):
        _, state = model.apply(variables, tokens, mutable=["intermediates"])
        sown = state["intermediates"]
        return (jnp.stack([sown[m + "_moe"]["selected"][0] for m in layers]),
                jnp.stack([sown[m + "_attn"]["mask"][0] for m in layers]))

    @jax.jit
    def reference_picks(p, b):
        picks = ref.hidden_states(p, b, cfg, ref.REFERENCE)[2]
        return (jnp.stack([chosen for chosen, _ in picks]),
                jnp.stack([seen for _, seen in picks]))

    trainer = None
    for n, seed in enumerate(seeds):
        t = time.perf_counter()
        leaves = ref.init_params(cfg, seed)
        batches = [
            traffic.token_batch(cell.traffic, cfg["vocab_size"], seed, i)
            for i in range(3)
        ]
        tokens = jnp.asarray(batches[0][0])
        mine, my_masks = program_picks(run.to_program_tree(leaves, None, cfg), tokens)
        theirs, their_masks = reference_picks(leaves, tokens)
        # a pair differs when the program's expert is not among the reference's
        differ = float(jnp.mean(~(mine[..., :, None] == theirs[..., None, :]).any(-1)))
        # of the pairs the program keeps, those the reference does not, per layer
        kept = jnp.sum(my_masks != 0, axis=(1, 2, 3))
        other = jnp.sum((my_masks != 0) & (their_masks == 0), axis=(1, 2, 3))
        masks_differ = (other / kept).tolist()
        del leaves, mine, theirs, my_masks, their_masks
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
                          "mask_pairs_differing_share_by_layer": masks_differ,
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
    p.add_argument("what", choices=("load", "breakdown", "limits"))
    p.add_argument("--out", default="chiprun_out/keye_breakdown.json")
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", default="")
    p.add_argument("--spreads", default="",
                   help="of load: embedding_initializer_range values; the file's if empty")
    p.add_argument("--steps", type=int, default=120)
    p.add_argument("--control-seeds", type=int, default=2)
    p.add_argument("--variants", default="", help="of limits' wrong runs; all if empty")
    args = p.parse_args()
    cell = spec.load_cell(args.workload)
    from akka_allreduce_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",") if s]
    if args.what == "load":
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

"""Runner for cells that train a decoder whose attention runs under a mask a
learned indexer makes (``models.hybrid_decoder`` from the Qwen3-MoE keys with
``sa_config``: ``KeyeVL2``'s language model) with held experts in every layer,
through ``MoETrainer``, one host-loop ``train_step`` after another, as
``train-moe --config`` does.

It is ``mellum_moe_train``'s runner (``moe_train``'s set-up, window and check
with the counters, the rungs and the scope map ``mellum_moe_train`` adds) on
private copies of both modules that are given this configuration's name map
and first steps. What differs: the first steps keep the indexer's loss beside
the main one (``losses`` holds the three main losses and then the three of
the indexer, so ``loss_gap`` limits both); each unit's dict carries
``indexer_loss`` and ``selected_pairs``, and a non-finite indexer loss fails
the unit; the counters handed with the first unit include
``trainer.indexer.selected_pairs`` and the gauges ``attention.sparse.*``.
The cell's sequences are text: the trainer passes no position rows and the
model takes the three equal rows 0 .. T - 1, as the reference does.
"""

from __future__ import annotations

import math

from harness import spec

mellum = spec.load_module("runners", "mellum_moe_train")  # a copy of this runner's own
base = mellum.base  # and its copy of ``moe_train``

#: reference leaf (after ``layers.<i>.``) -> path under ``layers_<i>_...``
base._LAYER = {
    "op_norm.scale": ("op_norm", "scale"), "ffn_norm.scale": ("ffn_norm", "scale"),
    "q.w": ("attn", "q", "kernel"), "k.w": ("attn", "k", "kernel"),
    "v.w": ("attn", "v", "kernel"), "o.w": ("attn", "out", "kernel"),
    "q_norm.scale": ("attn", "q_norm", "scale"),
    "k_norm.scale": ("attn", "k_norm", "scale"),
    "index_q.w": ("attn", "index_q", "kernel"),
    "index_k.w": ("attn", "index_k", "kernel"),
    "index_k_norm.scale": ("attn", "index_k_norm", "scale"),
    "index_k_norm.bias": ("attn", "index_k_norm", "bias"),
    "index_w.w": ("attn", "index_w", "kernel"),
    "router.w": ("moe", "router"), "experts.w1": ("moe", "w1"),
    "experts.w3": ("moe", "w3"), "experts.w2": ("moe", "w2"),
}
# what the tests and the by-hand readings take from a runner
to_program_tree, build_model, build_trainer = (
    base.to_program_tree, base.build_model, base.build_trainer
)
by_reference_name, leaf_norms = base.by_reference_name, base.leaf_norms
lower_step_on_shapes = base.lower_step_on_shapes

mellum.COUNTERS += ("trainer.indexer.selected_pairs",)
mellum.GAUGES = ("attention.sparse.visited_pairs", "attention.sparse.mask_pairs")


def first_steps(trainer, ref, cfg: dict, seed: int, batches, names) -> dict:
    """As ``moe_train.first_steps``, which keeps the main loss alone: drive
    ``trainer`` through ``batches`` and keep what the check needs, the
    indexer's losses after the main ones."""
    main, index, grad_norms = [], [], None
    for x, y in batches:
        m = trainer.train_step(x, y)
        main.append(m.loss)
        index.append(m.indexer_loss)
        if grad_norms is None:
            # Adam's first moment after one step from zero is (1 - b1) g
            mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
            scale = 1.0 - cfg["program"]["adam_b1"]
            grad_norms = {n: v / scale for n, v in leaf_norms(mu, names).items()}
    return {
        "losses": main + index, "grad_norms": grad_norms,
        "delta_norms": ref.delta_norms(
            by_reference_name(trainer.params, names), cfg, seed
        ),
    }


base.first_steps = first_steps  # what ``moe_train.Runner.setup`` calls


class Runner(mellum.Runner):
    def setup(self) -> dict:
        # first, so that a program without the masked kernels or this reader
        # fails before any weight is made
        from akka_allreduce_tpu.ops.sparse_attention import sparse_attention  # noqa: F401

        if build_model(self.cfg).indexer is None:
            raise ValueError("the configuration's sa_config built no indexer")
        return super().setup()

    def unit(self, i: int) -> dict:
        out = super().unit(i)
        out["indexer_loss"] = self.last.indexer_loss
        out["selected_pairs"] = self.last.selected_pairs.tolist()
        out["ok"] = out["ok"] and math.isfinite(self.last.indexer_loss)
        return out

    def close_window(self) -> dict:
        facts = super().close_window()
        facts["last_indexer_loss"] = self.last.indexer_loss
        facts["last_selected_pairs"] = self.last.selected_pairs.tolist()
        return facts

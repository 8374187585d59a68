"""Runner for cells that train a decoder of windowed and full attention by
the layer's kind with held experts in every layer and nothing beside them
(``models.hybrid_decoder`` from ``mellum``'s keys) through ``MoETrainer``, one
host-loop ``train_step`` after another, as ``train-moe --config`` does.

It is ``moe_train``'s runner (its set-up, its window, its check,
``first_steps``, ``leaf_norms``, ``lower_step_on_shapes``,
``lm_train.compare``) on a private copy of that module that is given this
configuration's name map: no gate, no dense MLP, no shared expert. The tree
and model builders are ``moe_train``'s own (``from_config`` picks the reader
by the keys; no selection bias, so no ``fixed`` collection). What differs:
each unit's dict carries ``buffer_rows`` beside ``expert_rows``; the window's
line carries the rows routed to each layer's held experts over the units, the
rungs taken, and the change of the program's ``trainer.moe.*`` counters and
the value of its ``attention.band.*`` gauges, which the first unit of a run
hands the readers too (``counters``); a traced run hands them, with its first
unit, the ``op_name`` that each instruction of the compiled step carries
(``mla_moe_train.op_scopes``).
"""

from __future__ import annotations

from harness import spec

base = spec.load_module("runners", "moe_train")  # a copy of this runner's own

#: reference leaf (after ``layers.<i>.``) -> path under ``layers_<i>_...``
base._LAYER = {
    "op_norm.scale": ("op_norm", "scale"), "ffn_norm.scale": ("ffn_norm", "scale"),
    "q.w": ("attn", "q", "kernel"), "k.w": ("attn", "k", "kernel"),
    "v.w": ("attn", "v", "kernel"), "o.w": ("attn", "out", "kernel"),
    "router.w": ("moe", "router"), "experts.w1": ("moe", "w1"),
    "experts.w3": ("moe", "w3"), "experts.w2": ("moe", "w2"),
}
# what the tests and the by-hand readings take from a runner
to_program_tree, build_model, build_trainer = (
    base.to_program_tree, base.build_model, base.build_trainer
)
by_reference_name, first_steps = base.by_reference_name, base.first_steps
lower_step_on_shapes = base.lower_step_on_shapes

COUNTERS = ("trainer.steps", "trainer.moe.routed_rows", "trainer.moe.buffer_rows",
            "trainer.moe.layers_past_first_rung")
GAUGES = ("attention.band.visited_pairs", "attention.band.mask_pairs")


def program_counters() -> dict:
    """The program's registry as it stands: the counters and gauges above,
    those it has (a program that writes none of them: an empty dict)."""
    from akka_allreduce_tpu.obs.metrics import REGISTRY

    now = REGISTRY.snapshot()
    return {k: now[k] for k in COUNTERS + GAUGES if k in now}


class Runner(base.Runner):
    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        # this router selects by its scores alone: ``moe_train``'s key for that
        self.cfg = {**self.cfg, "use_expert_bias": False}
        #: filled after a traced window, read by the per-layer readers
        self.scopes: dict[str, str] = {}
        #: filled after the window: the counters' change over it, the gauges
        self.counters: dict[str, float] = {}
        #: rows routed to the held experts, per expert layer, unit by unit
        self.routed: list[list[float]] = []
        #: (unit, layer) pairs by the row buffer taken: the rungs in use
        self.buffers: dict[float, int] = {}

    def setup(self) -> dict:
        # first, so that a program without the softmax router or this reader
        # fails before any weight is made
        from akka_allreduce_tpu.ops.moe import softmax_topk_route  # noqa: F401

        build_model(self.cfg)  # and one that cannot read these keys
        return super().setup()

    def prepare(self, i: int) -> None:
        if i == 0:  # off the unit's clock
            self.at_start = program_counters()
        super().prepare(i)

    def unit(self, i: int) -> dict:
        out = super().unit(i)
        out["buffer_rows"] = self.last.buffer_rows.tolist()
        self.routed.append(self.last.expert_rows.sum(axis=1).tolist())
        for taken in out["buffer_rows"]:
            self.buffers[taken] = self.buffers.get(taken, 0) + 1
        if i == 0:
            out["counters"] = self.counters
            if self.ctx.trace:
                out["op_scopes"] = self.scopes
        return out

    def close_window(self) -> dict:
        facts = super().close_window()
        rows = list(zip(*self.routed))  # per expert layer, over the units
        facts["routed_rows_min_max"] = [[min(r), max(r)] for r in rows]
        facts["routed_rows_every_8th_unit"] = self.routed[::8]
        facts["layer_steps_by_buffer_rows"] = dict(sorted(self.buffers.items()))
        now = program_counters()
        self.counters.update({
            k: v - self.at_start.get(k, 0) if k in COUNTERS else v
            for k, v in now.items()
        })
        facts["program_counters"] = dict(self.counters)
        if self.ctx.trace:  # outside the window: the step's text, from the cache
            op_scopes = spec.load_module("runners", "mla_moe_train").op_scopes
            self.scopes.update(op_scopes(self.trainer.step_text(*self.batch)))
            facts["ops_with_a_scope"] = sum(1 for v in self.scopes.values() if v)
        return facts

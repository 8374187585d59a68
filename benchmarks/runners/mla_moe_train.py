"""Runner for cells that train a latent-attention decoder with held experts,
a shared expert and a multi-token-prediction module (``models.hybrid_decoder``
in the ``joyai_llm_flash`` dialect) through ``MoETrainer``, one host-loop
``train_step`` after another, as ``train-moe --config`` does.

It is ``moe_train``'s runner (its set-up, its window, its check,
``leaf_norms``, ``lower_step_on_shapes``, ``lm_train.compare``) on a private
copy of that module that is given this configuration's name map, tree
builder, model builder and first steps. What differs: the first steps keep
the prediction module's loss beside the main one (``losses`` holds the three
main losses and then the three of the module, so ``loss_gap`` limits both);
each unit's dict carries ``mtp_loss`` and ``buffer_rows`` beside
``expert_rows``, and a non-finite loss of the module fails the unit; a traced
run hands the readers, with its first unit, the ``op_name`` that each
instruction of the compiled step carries (named scopes and module names), so
that ``mla_proj_ms`` and ``mtp_share_pct`` can give a trace's ops to their
scopes.
"""

from __future__ import annotations

import math
import re

from harness import spec

base = spec.load_module("runners", "moe_train")  # a copy of this runner's own

#: reference leaf (after the layer's prefix) -> path under ``<prefix>_...``
_LAYER = {
    "op_norm.scale": ("op_norm", "scale"), "ffn_norm.scale": ("ffn_norm", "scale"),
    "q_a.w": ("attn", "q_a", "kernel"), "q_a_norm.scale": ("attn", "q_a_norm"),
    "q_b.w": ("attn", "q_b"), "kv_a.w": ("attn", "kv_a", "kernel"),
    "kv_a_norm.scale": ("attn", "kv_a_norm"), "kv_b.w": ("attn", "kv_b"),
    "o.w": ("attn", "out", "kernel"),
    "mlp.w1": ("mlp", "w1", "kernel"), "mlp.w3": ("mlp", "w3", "kernel"),
    "mlp.w2": ("mlp", "w2", "kernel"),
    "router.w": ("moe", "router"), "experts.w1": ("moe", "w1"),
    "experts.w3": ("moe", "w3"), "experts.w2": ("moe", "w2"),
    "shared.w1": ("moe", "shared", "w1", "kernel"),
    "shared.w3": ("moe", "shared", "w3", "kernel"),
    "shared.w2": ("moe", "shared", "w2", "kernel"),
}
_TOP = {
    "embed": ("embed", "embedding"), "final_norm.scale": ("final_norm", "scale"),
    "head.w": ("head",), "mtp.enorm.scale": ("mtp_enorm", "scale"),
    "mtp.hnorm.scale": ("mtp_hnorm", "scale"),
    "mtp.eh_proj.w": ("mtp_eh_proj", "kernel"),
    "mtp.final_norm.scale": ("mtp_final_norm", "scale"),
}
_LAYER_NAME = re.compile(r"^(layers\.\d+|mtp\.layer)\.(.+)$")


def _module_prefix(reference_prefix: str) -> str:
    """``layers.3`` -> ``layers_3_``; ``mtp.layer`` -> ``mtp_``."""
    return "mtp_" if reference_prefix.startswith("mtp") else (
        reference_prefix.replace(".", "_") + "_"
    )


def program_path(name: str) -> tuple[str, ...]:
    hit = _LAYER_NAME.match(name)
    if hit:
        module, *rest = _LAYER[hit.group(2)]
        return ("params", _module_prefix(hit.group(1)) + module, *rest)
    return ("params",) + _TOP[name]


def to_program_tree(leaves: dict, select_bias, cfg: dict) -> dict:
    """The reference's flat leaves and its (expert layers, E) selection bias
    as the program's variables: ``params`` and the ``fixed`` collection."""
    ref = spec.load_module("reference", cfg["reference"])
    tree: dict = {"params": {}}
    for name, leaf in leaves.items():
        node, path = tree, program_path(name)
        for key in path[:-1]:
            node = node.setdefault(key, {})
        node[path[-1]] = leaf
    tree["fixed"] = {
        _module_prefix(pre.rstrip(".")) + "moe": {"select_bias": select_bias[j]}
        for j, pre in enumerate(ref.expert_layers(cfg))
    }
    return tree


def build_model(cfg: dict):
    import jax.numpy as jnp

    from akka_allreduce_tpu.models.hybrid_decoder import HybridDecoderLM

    return HybridDecoderLM.from_config(
        cfg, compute_dtype=jnp.dtype(cfg["program"]["compute_dtype"])
    )


base.program_path, base.to_program_tree, base.build_model = (
    program_path, to_program_tree, build_model
)
by_reference_name, leaf_norms = base.by_reference_name, base.leaf_norms
build_trainer, lower_step_on_shapes = base.build_trainer, base.lower_step_on_shapes


def first_steps(trainer, ref, cfg: dict, seed: int, batches, names) -> dict:
    """As ``moe_train.first_steps``, which keeps the main loss alone: drive
    ``trainer`` through ``batches`` and keep what the check needs, the
    prediction module's losses after the main ones."""
    main, further, grad_norms = [], [], None
    for x, y in batches:
        m = trainer.train_step(x, y)
        main.append(m.loss)
        further.append(m.mtp_loss)
        if grad_norms is None:
            # Adam's first moment after one step from zero is (1 - b1) g
            mu = next(s.mu for s in trainer.opt_state if hasattr(s, "mu"))
            scale = 1.0 - cfg["program"]["adam_b1"]
            grad_norms = {n: v / scale for n, v in leaf_norms(mu, names).items()}
    return {
        "losses": main + further, "grad_norms": grad_norms,
        "delta_norms": ref.delta_norms(
            by_reference_name(trainer.params, names), cfg, seed
        ),
    }


base.first_steps = first_steps  # what ``moe_train.Runner.setup`` calls


_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%([\w.\-]+) \(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.\-]+)")
_MATMUL = re.compile(r"\s(?:convolution|dot)\(")


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> the ``op_name`` its metadata carries, for every
    instruction of a compiled program's text (a kernel's instruction runs
    over several lines: its metadata follows its kernel's). A fusion is given
    the ``op_name`` of the matrix product inside it where it has one (XLA
    fuses a weight's gradient into its Adam update, whose root is no part of
    the model), else its own."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    product_of: dict[str, str] = {}  # computation -> op_name of a product in it
    inside = current = None
    for line in hlo_text.splitlines():
        start = _COMPUTATION.match(line)
        if start:
            inside, current = start.group(1), None
            continue
        hit = _INSTRUCTION.match(line)
        named = _OP_NAME.search(line)
        if not hit:  # the rest of the instruction above
            if current and named and not own[current]:
                own[current] = named.group(1)
            continue
        current, name = hit.group(1), named.group(1) if named else ""
        own[current] = name
        if _MATMUL.search(line) and name:
            product_of.setdefault(inside, name)
        if " fusion(" in line:
            called = _CALLS.search(line)
            if called:
                calls[current] = called.group(1)
    return {op: product_of.get(calls.get(op), name) or name for op, name in own.items()}


class Runner(base.Runner):
    def __init__(self, ctx) -> None:
        super().__init__(ctx)
        # ``noaux_tc`` selects by a bias: ``moe_train``'s key for one
        self.cfg = {**self.cfg, "use_expert_bias": True}
        #: filled after a traced window, read by the per-layer readers
        self.scopes: dict[str, str] = {}
        #: rows routed to the held experts, per expert layer, unit by unit
        self.routed: list[list[float]] = []

    def setup(self) -> dict:
        # first, so that a program without latent attention fails before any
        # weight is made
        from akka_allreduce_tpu.models.hybrid_decoder import LatentAttention  # noqa: F401

        return super().setup()

    def unit(self, i: int) -> dict:
        out = super().unit(i)
        out["mtp_loss"] = self.last.mtp_loss
        out["buffer_rows"] = self.last.buffer_rows.tolist()
        self.routed.append(self.last.expert_rows.sum(axis=1).tolist())
        out["ok"] = out["ok"] and math.isfinite(self.last.mtp_loss)
        if i == 0 and self.ctx.trace:
            out["op_scopes"] = self.scopes
        return out

    def close_window(self) -> dict:
        facts = super().close_window()
        facts["last_mtp_loss"] = self.last.mtp_loss
        facts["routed_rows_every_8th_unit"] = self.routed[::8]
        if self.ctx.trace:  # outside the window: the step's text, from the cache
            self.scopes.update(op_scopes(self.trainer.step_text(*self.batch)))
            facts["ops_with_a_scope"] = sum(1 for v in self.scopes.values() if v)
        return facts

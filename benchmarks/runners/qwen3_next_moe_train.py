"""Runner for cells that train a decoder of linear-attention layers (the
gated delta rule) among gated full-attention layers, with held experts and a
gated shared expert in every layer (``models.hybrid_decoder`` from
``qwen3_next``'s keys), through ``MoETrainer``, one host-loop ``train_step``
after another, as ``train-moe --config`` does.

It is ``mellum_moe_train``'s runner (``moe_train``'s set-up, window and check
with the counters, the rungs and the scope map ``mellum_moe_train`` adds) on
private copies of both modules that are given this configuration's name map.
What differs: each unit's dict carries the two readings of the linear layers'
state that the step fetched anyway (``log_decay_mean``, ``state_rms``:
``MoEStepMetrics``), and a non-finite one fails the unit; the gauges handed
with the first unit are ``trainer.linear_attention.*``.
"""

from __future__ import annotations

import math

from harness import spec

mellum = spec.load_module("runners", "mellum_moe_train")  # a copy of this runner's own
base = mellum.base  # and its copy of ``moe_train``

#: reference leaf (after ``layers.<i>.``) -> path under ``layers_<i>_...``
base._LAYER = {
    "op_norm.scale": ("op_norm", "scale"), "ffn_norm.scale": ("ffn_norm", "scale"),
    "gdn.qkvz.w": ("linear", "qkvz", "kernel"), "gdn.ba.w": ("linear", "ba", "kernel"),
    "gdn.conv": ("linear", "conv"), "gdn.A_log": ("linear", "A_log"),
    "gdn.dt_bias": ("linear", "dt_bias"), "gdn.norm.scale": ("linear", "norm"),
    "gdn.o.w": ("linear", "out", "kernel"),
    "q.w": ("attn", "q", "kernel"), "k.w": ("attn", "k", "kernel"),
    "v.w": ("attn", "v", "kernel"), "o.w": ("attn", "out", "kernel"),
    "q_norm.scale": ("attn", "q_norm", "scale"),
    "k_norm.scale": ("attn", "k_norm", "scale"),
    "router.w": ("moe", "router"), "experts.w1": ("moe", "w1"),
    "experts.w3": ("moe", "w3"), "experts.w2": ("moe", "w2"),
    "shared.w1": ("moe", "shared", "w1", "kernel"),
    "shared.w3": ("moe", "shared", "w3", "kernel"),
    "shared.w2": ("moe", "shared", "w2", "kernel"),
    "shared_gate.w": ("moe", "shared_gate"),
}
# what the tests and the by-hand readings take from a runner
to_program_tree, build_model, build_trainer = (
    base.to_program_tree, base.build_model, base.build_trainer
)
by_reference_name, first_steps = base.by_reference_name, base.first_steps
lower_step_on_shapes = base.lower_step_on_shapes

mellum.GAUGES = ("trainer.linear_attention.log_decay_mean",
                 "trainer.linear_attention.state_rms")


class Runner(mellum.Runner):
    def setup(self) -> dict:
        # first, so that a program without the chunked rule or this reader
        # fails before any weight is made
        import akka_allreduce_tpu.ops.delta_rule  # noqa: F401

        if build_model(self.cfg).linear_attention is None:
            raise ValueError("the configuration's linear_* keys built no linear layer")
        return super().setup()

    def unit(self, i: int) -> dict:
        out = super().unit(i)
        out["log_decay_mean"] = self.last.log_decay_mean
        out["state_rms"] = self.last.state_rms
        out["ok"] = out["ok"] and all(
            math.isfinite(out[k]) for k in ("log_decay_mean", "state_rms")
        )
        return out

    def close_window(self) -> dict:
        facts = super().close_window()
        facts["last_log_decay_mean"] = self.last.log_decay_mean
        facts["last_state_rms"] = self.last.state_rms
        return facts

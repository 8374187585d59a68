"""Expert-parallel MoE trainer: DP x EP over a (data, expert) mesh, or
DP x SP x EP over (data, seq, expert) with ring/Ulysses attention.

Beyond-parity capability (the reference is DP-only, SURVEY.md §3). The dense
non-MoE parts treat the data and expert axes as data parallelism — the
global batch's rows shard over data x expert jointly (and its sequence over
``seq`` when present) — while each MoE layer's all_to_all pair (ops/moe.py)
rides the ``expert`` axis. The step, its gradient sync and the host loop
are ``train/sharded_lm.py``'s, shared with ``LongContextTrainer``: expert
weights enter shard_map sharded on ``expert`` (ep_param_specs), so their
grads sum over ``data`` only; replicated leaves sum over both axes — the
threshold-masked allreduce with the same contributor-mask semantics as
every other trainer (mask per DP replica row). This file is what experts
add: the mesh axes, the specs, the auxiliary loss and the routing
statistics.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.comm.allreduce import validate_trainer_compress
from akka_allreduce_tpu.obs import metrics as obs_metrics
from akka_allreduce_tpu.train.sharded_lm import ShardedLMTrainer, step_check_vma


# written by MoETrainer.train_step from the step's own ``MoEStepMetrics``,
# for a model that reports the rows routed (OBSERVABILITY.md)
_ROUTED_ROWS = obs_metrics.counter("trainer.moe.routed_rows")
_BUFFER_ROWS = obs_metrics.counter("trainer.moe.buffer_rows")
_PAST_FIRST_RUNG = obs_metrics.counter("trainer.moe.layers_past_first_rung")
# for a model whose attention runs under a learned mask (``model.indexer``):
# the indexer's loss of the last step, and the (query, key) pairs its masks
# kept, summed over the layers and the steps
_INDEXER_LOSS = obs_metrics.gauge("trainer.indexer.loss")
_SELECTED_PAIRS = obs_metrics.counter("trainer.indexer.selected_pairs")
# for a model with linear-attention layers (``model.linear_attention``), of
# the last step: the mean log decay over tokens, heads and layers (how long
# the memory is), and the largest root-mean-square of a layer's final state
# (a state dying or blowing up shows here before the loss)
_LOG_DECAY = obs_metrics.gauge("trainer.linear_attention.log_decay_mean")
_STATE_RMS = obs_metrics.gauge("trainer.linear_attention.state_rms")


@dataclasses.dataclass
class MoEStepMetrics:
    step: int
    loss: float  # masked per-token cross-entropy (aux not included)
    aux_loss: float  # Switch load-balancing loss (global weighted mean)
    dropped: float  # fraction of routing ASSIGNMENTS past expert capacity
    # (denominator k*T under top-k) — the capacity_factor tuning knob; 0 by
    # construction for a dropless model
    contributors: float  # contributing DP replica rows
    # rows each held expert received, (expert layers, held experts) — only
    # from a model that reports them (``model=``), else None
    expert_rows: np.ndarray | None = None
    # rows of the row buffer each expert layer moved and multiplied for them
    # (``ops.moe.row_rungs``: the smallest rung that held the rows routed),
    # (expert layers,), summed over the replicas as ``expert_rows`` is
    buffer_rows: np.ndarray | None = None
    # masked per-token cross-entropy of a model's multi-token-prediction
    # module against the token after the next (a sequence's last position
    # has none and counts 0); in the gradient with the model's
    # ``mtp_weight``, not in ``loss``. None from a model without one
    mtp_loss: float | None = None
    # the indexer's loss of a model whose attention runs under a learned mask
    # (``models.hybrid_decoder``: per layer the mean over the tokens of KL(the
    # head mean of attention's probabilities || the indexer's distribution
    # over the kept keys), summed over the layers); in the gradient, not in
    # ``loss``. None from a model without an indexer
    indexer_loss: float | None = None
    # (query, key) pairs each layer's mask kept, (layers,), summed over the
    # replicas
    selected_pairs: np.ndarray | None = None
    # of a model with linear-attention layers: the mean log decay of the
    # gated delta rule over tokens, heads and layers, and the largest
    # root-mean-square of a layer's final state (means over the replicas).
    # None from a model without such layers
    log_decay_mean: float | None = None
    state_rms: float | None = None


class MoETrainer(ShardedLMTrainer):
    """DP (x EP) trainer for :class:`~akka_allreduce_tpu.models.MoETransformerLM`.

    Args:
      mesh: a 1-axis (data,) mesh for dense MoE, a 2-axis (data, expert)
        mesh for expert parallelism, or a 3-axis (data, seq, expert) mesh
        composing sequence parallelism with EP — ring/Ulysses attention
        shards the sequence over ``seq`` while each MoE layer's all_to_all
        rides ``expert``. Routing stays per-device, so expert capacity is
        computed over LOCAL tokens (T/sp per device), while the aux
        load-balancing statistics are psum-averaged over the seq shards —
        so with ample ``capacity_factor`` the whole step is exactly
        partition-independent (the tests' oracle); under capacity pressure,
        drops depend on the sharding, as in any capacity-based MoE system.
      seq_len: GLOBAL per-sample sequence length (divisible by the seq
        axis size when present).
      aux_coef: weight of the Switch load-balancing loss.
      model: a built module to train in place of the ``MoETransformerLM``
        the size arguments describe — ``apply(variables, tokens) -> (logits,
        aux, dropped, expert_rows, buffer_rows)`` (``models.hybrid_decoder``:
        dropless routing over a held subset of the experts, no auxiliary
        loss). It runs without an expert exchange, so the mesh is (data,)
        only. A model with ``mtp_depth`` is applied to ``(variables,
        tokens, labels)`` and returns its prediction module's logits last:
        their cross-entropy against the labels one further on enters the
        total with ``model.mtp_weight`` and is reported as ``mtp_loss``. A
        model with an ``indexer`` returns its indexer's loss and the pairs
        its masks kept last of all: the loss enters the total as it is
        (its gradient reaches the indexer's leaves alone) and is reported as
        ``indexer_loss``, the pairs as ``selected_pairs``. A model with
        ``linear_attention`` layers returns their mean log decay and their
        largest final state's root-mean-square last of all: reported as
        ``log_decay_mean`` and ``state_rms``, in no loss.
      params: with ``model``, its variables (seeded weights handed in);
        left out, ``model.init`` runs jitted from ``seed``.
    """

    metrics_cls = MoEStepMetrics
    _mean_names = ("loss", "aux_loss", "dropped")

    def __init__(
        self,
        mesh: Mesh,
        *,
        vocab: int = 64,
        d_model: int = 64,
        n_heads: int = 4,
        n_kv_heads: int | None = None,
        n_layers: int = 2,
        n_experts: int = 4,
        seq_len: int = 64,
        capacity_factor: float = 1.25,
        router_topk: int = 1,
        seq_impl: str = "ring",
        aux_coef: float = 0.01,
        optimizer: optax.GradientTransformation | None = None,
        learning_rate: float = 1e-2,
        mu_dtype=None,
        seed: int = 0,
        compute_dtype=jnp.float32,
        compress: str | None = None,
        overlap: bool = False,
        dispatch_impl: str = "auto",
        model=None,
        params=None,
    ) -> None:
        from akka_allreduce_tpu.models.transformer import (
            MoETransformerLM,
            ep_param_specs,
        )

        self.compress = validate_trainer_compress(compress, overlap=overlap)
        self.overlap = overlap

        if len(mesh.axis_names) not in (1, 2, 3):
            raise ValueError(
                f"need a (data[, expert] | data, seq, expert) mesh, got "
                f"axes {mesh.axis_names}"
            )
        self.mesh = mesh
        self.data_axis = mesh.axis_names[0]
        if len(mesh.axis_names) == 3:
            # (data, seq, expert): sequence parallelism composed with EP —
            # ring/Ulysses attention over `seq`, expert all_to_all over
            # `expert`, the dense parts data-parallel over data x expert
            self.seq_axis = mesh.axis_names[1]
            self.expert_axis = mesh.axis_names[2]
        else:
            self.seq_axis = None
            self.expert_axis = (
                mesh.axis_names[1] if len(mesh.axis_names) == 2 else None
            )
        self.dp = int(mesh.shape[self.data_axis])
        self.sp = int(mesh.shape[self.seq_axis]) if self.seq_axis else 1
        self.ep = int(mesh.shape[self.expert_axis]) if self.expert_axis else 1
        if n_experts % self.ep:
            raise ValueError(f"{n_experts=} not divisible by ep={self.ep}")
        if seq_len % self.sp:
            raise ValueError(
                f"{seq_len=} not divisible by seq shards {self.sp}"
            )
        self.n_devices = self.dp * self.sp * self.ep
        self.data_shards = self.dp
        self.seq_len = seq_len
        self.vocab = vocab
        self.aux_coef = aux_coef
        if model is not None:
            if self.ep > 1 or self.sp > 1:
                raise ValueError(
                    "a model handed in holds its own subset of the experts "
                    "and runs no exchange: give it a (data,) mesh, got "
                    f"{dict(mesh.shape)}"
                )
            self.model = model
        else:
            self.model = MoETransformerLM(
                vocab=vocab,
                d_model=d_model,
                n_heads=n_heads,
                n_kv_heads=n_kv_heads,
                n_layers=n_layers,
                n_experts=n_experts,
                capacity_factor=capacity_factor,
                compute_dtype=compute_dtype,
                expert_axis=self.expert_axis if self.ep > 1 else None,
                ep_size=self.ep,
                router_topk=router_topk,
                seq_axis=self.seq_axis if self.sp > 1 else None,
                seq_impl=seq_impl,
                dispatch_impl=dispatch_impl,
            )
        # mu_dtype=bfloat16 halves the first-moment read+write traffic of
        # the adam update — the LARGEST single cost of a single-chip MoE
        # step, because the optimizer touches ALL E experts' params every
        # step while only the active ones did compute (xprof breakdown in
        # BENCHMARKS.md round 4); nu (the variance) stays f32
        self.tx = optimizer or optax.adam(learning_rate, mu_dtype=mu_dtype)

        tokens0 = jnp.zeros((1, seq_len // self.sp), jnp.int32)
        mtp = bool(getattr(model, "mtp_depth", 0))
        indexer = getattr(model, "indexer", None) is not None
        linear = getattr(model, "linear_attention", None) is not None
        if model is not None:
            self.params = (
                params if params is not None
                else jax.jit(model.init)(jax.random.PRNGKey(seed), tokens0)
            )
        else:
            # full-shape init (ep=1 twin); shard_map in_specs slice expert
            # leaves
            init_model = MoETransformerLM(
                vocab=vocab,
                d_model=d_model,
                n_heads=n_heads,
                n_kv_heads=n_kv_heads,
                n_layers=n_layers,
                n_experts=n_experts,
                capacity_factor=capacity_factor,
                compute_dtype=compute_dtype,
                router_topk=router_topk,
            )
            self.params = init_model.init(jax.random.PRNGKey(seed), tokens0)
        self._place_state(
            (lambda tree: ep_param_specs(tree, self.expert_axis))
            if self.ep > 1 else None
        )

        axis_names = tuple(mesh.axis_names)
        if self.seq_axis is not None:
            # rows over data x expert, the sequence dim over seq
            batch_spec = P((self.data_axis, self.expert_axis), self.seq_axis)
        elif len(axis_names) > 1:
            batch_spec = P(axis_names)
        else:
            batch_spec = P(axis_names[0])
        if model is not None:  # it reports the rows routed, beside `dropped`
            self._sum_names = ("expert_rows", "buffer_rows")
        if mtp:
            self._mean_names = (*self._mean_names, "mtp_loss")
            mtp_weight = float(model.mtp_weight)
        if indexer:
            self._mean_names = (*self._mean_names, "indexer_loss")
            self._sum_names = (*self._sum_names, "selected_pairs")
        if linear:
            self._mean_names = (*self._mean_names, "log_decay_mean", "state_rms")
        model_apply = self.model.apply
        aux_coef = self.aux_coef
        token_ce = optax.softmax_cross_entropy_with_integer_labels

        def local_loss(p, x, y, tokens_local):
            # a prediction module embeds the next tokens, which the labels are
            logits, aux, dropped, *rows = model_apply(p, x, *((y,) if mtp else ()))
            ce = token_ce(logits, y).sum()
            # aux and dropped are per-device means: weighted by local tokens,
            # their global sum / denom is the masked token-weighted mean
            total = ce + aux_coef * aux * tokens_local
            means = (ce, aux * tokens_local, dropped * tokens_local)
            if linear:  # last of all of the model's outputs
                state_rms, log_decay = rows.pop(), rows.pop()
            if indexer:  # last of the model's outputs; the pairs stay a sum
                pairs, index_kl = rows.pop(), rows.pop() * tokens_local
            if mtp:
                # position i saw labels[i] and predicts labels[i + 1]; the
                # last has no target, so its term weighs 0 (all T positions
                # stay in the shapes) and the denominator stays the tokens
                with jax.named_scope("mtp"):
                    has_target = jnp.arange(y.shape[1]) < y.shape[1] - 1
                    further = jnp.sum(
                        token_ce(rows.pop(), jnp.roll(y, -1, axis=1)) * has_target
                    )
                total = total + mtp_weight * further
                means += (further,)
            if indexer:
                total = total + index_kl
                means += (index_kl,)
                rows.append(pairs)
            if linear:
                means += (log_decay * tokens_local, state_rms * tokens_local)
            return total, (means, tuple(rows))

        self._build_step(
            local_loss,
            batch_spec=batch_spec,
            check_vma=step_check_vma(
                seq_len=seq_len,
                head_dim=getattr(self.model, "head_dim", None)
                or d_model // n_heads,
                sp=self.sp, seq_impl=seq_impl,
                compress=compress, overlap=overlap,
                # a decoder with held experts (``ops.moe.moe_dropless_held``)
                pallas_grouped=getattr(self.model, "held_count", 0) > 0,
            ),
        )

    def train_step(self, tokens, labels, valid=None) -> MoEStepMetrics:
        """The skeleton's step; where the model holds experts and reports the
        rows routed to them (``models.hybrid_decoder``), three counters move
        by what the step fetched anyway: the rows routed to held experts, the
        rows of the row buffers moved for them, and the expert layers whose
        buffer was larger than the model's ``first_rung`` at a replica's
        tokens (summed over the replicas, so a layer counts where any replica
        left the rung); where it has an indexer or linear-attention layers,
        their gauges."""
        out = super().train_step(tokens, labels, valid)
        first_rung = getattr(self.model, "first_rung", None)
        if first_rung is not None and out.buffer_rows is not None:
            first = first_rung(tokens.size // self.dp) * self.dp
            _ROUTED_ROWS.inc(float(out.expert_rows.sum()))
            _BUFFER_ROWS.inc(float(out.buffer_rows.sum()))
            _PAST_FIRST_RUNG.inc(int((out.buffer_rows > first).sum()))
        if out.selected_pairs is not None:
            _INDEXER_LOSS.set(out.indexer_loss)
            _SELECTED_PAIRS.inc(float(out.selected_pairs.sum()))
        if out.state_rms is not None:
            _LOG_DECAY.set(out.log_decay_mean)
            _STATE_RMS.set(out.state_rms)
        return out

    def train_chain(
        self, sampler, steps: int, rows_per_device: int, *, valid=None, seed=0
    ) -> list[MoEStepMetrics]:
        """The skeleton's chain under this trainer's name for the rows: each
        data x expert coordinate draws ``rows_per_device`` a step (its seq
        shards share them), not each replica."""
        return super().train_chain(
            sampler, steps, rows_per_device, valid=valid, seed=seed
        )

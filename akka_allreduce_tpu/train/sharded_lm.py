"""The sharded LM train step and its host loop, once.

``LongContextTrainer`` (DP x SP x TP) and ``MoETrainer`` (DP x EP x SP, or a
built decoder on a data mesh) are this skeleton plus what differs between
them: the mesh axes, which function gives the parameter specs, and what the
model returns beside the logits (one more term in the loss, more statistics).
Everything else — mask and denominator, the gradient sync
(``comm.allreduce.synced_value_and_grad``), the metric psums, the optimizer
update, state placement, ``shard_map`` + ``jit``, ``train_step``,
``train_chain`` — is defined here, so a change to the step or to the host
loop reaches every cell that trains an LM.

Gradient collective: the v-weighted *local token-loss sum* over
``psum(v * local_token_count)`` is differentiated w.r.t. device-local views
of the params and summed over each leaf's replication axes, which yields the
exact masked per-token-average gradient — the reference's threshold
allreduce (SURVEY.md §8.1 step 3) with the mask applied per DP replica row.
"""

from __future__ import annotations

from typing import Callable, Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.binder.api import flatten_pytree
from akka_allreduce_tpu.comm.allreduce import synced_value_and_grad
from akka_allreduce_tpu.obs import metrics as obs_metrics
from akka_allreduce_tpu.obs import trace as obs_trace
from akka_allreduce_tpu.ops.local_attention import flash_vma_relax
from akka_allreduce_tpu.train.checkpoint import state_shardings
from akka_allreduce_tpu.train.trainer import (
    normalize_valid,
    place_mask,
    place_tokens,
    run_chain_cached,
)

# train_step's spans also go into the profiler's host plane, beside the
# device's ops, in every profile anyone takes of a process that trains
obs_trace.set_annotator(jax.profiler.TraceAnnotation)

# written by train_step, so every caller of it feeds them (OBSERVABILITY.md)
_STEPS = obs_metrics.counter("trainer.steps")
_TOKENS = obs_metrics.counter("trainer.tokens")
_LOSS = obs_metrics.gauge("trainer.loss")
_STEP_TIME = obs_metrics.histogram("trainer.step_time_s")


def step_check_vma(
    *,
    seq_len: int,
    head_dim: int,
    sp: int = 1,
    seq_impl: str = "ring",
    compress: str | None = None,
    overlap: bool = False,
    hand_scheduled: bool = False,
    pallas_grouped: bool = False,
) -> bool:
    """Whether ``shard_map``'s static varying-axes check can stay on for a
    trainer's step (LongContext / MoE / Pipeline / FSDP). It is the static
    safety net, so it is relaxed ONLY for what it cannot type:

    - ``overlap``: the per-leaf sync's ``custom_vjp`` erases the typing;
    - ``compress="int8"``: so does the explicit ring's ppermute loop;
    - ``hand_scheduled``: and the 1f1b / interleaved pipeline schedules'
      hand-rolled ppermute plumbing (the GPipe-equivalence test is their
      oracle);
    - a Pallas kernel in the step, whose outputs carry no varying-axes
      annotation: flash attention, when it can dispatch for this attention
      shape on this backend (``ops.local_attention.flash_vma_relax``), and
      the held experts' grouped products (``pallas_grouped``), a kernel on
      the chip only.
    """
    return not (
        overlap
        or compress == "int8"
        or hand_scheduled
        or flash_vma_relax(seq_len, head_dim, sp=sp, seq_impl=seq_impl)
        or (pallas_grouped and jax.default_backend() == "tpu")
    )


class ShardedLMTrainer:
    """What ``LongContextTrainer`` and ``MoETrainer`` share. A subclass's
    ``__init__`` builds ``mesh``, the axis attributes, ``model``, ``tx`` and
    ``params``, then calls :meth:`_place_state` and :meth:`_build_step`."""

    #: the step-metrics dataclass: ``step``, then a field per name below
    metrics_cls: type
    #: masked token-weighted means the step reports, then (after
    #: ``contributors``) the sums over the contributing replicas
    _mean_names: tuple[str, ...]
    _sum_names: tuple[str, ...] = ()

    def _place_state(self, specs_of: Callable | None) -> None:
        """Optimizer state, the spec trees (``specs_of(tree)``; None =
        everything replicated) and both trees placed on their shardings NOW:
        every step can then donate the buffers in place instead of
        resharding (and warning) on first use."""
        self.opt_state = self.tx.init(self.params)
        if specs_of is None:
            specs_of = lambda tree: jax.tree.map(lambda _: P(), tree)  # noqa: E731
        self._param_specs = specs_of(self.params)
        self._opt_specs = specs_of(self.opt_state)
        p_sh, o_sh = state_shardings(self)
        self.params = jax.device_put(self.params, p_sh)
        self.opt_state = jax.device_put(self.opt_state, o_sh)
        self.param_count = int(
            sum(np.prod(p.shape) for p in jax.tree.leaves(self.params))
        )
        self.step_num = 0

    def _build_step(
        self,
        local_loss: Callable,
        *,
        batch_spec: P,
        check_vma: bool,
    ) -> None:
        """Build the jitted SPMD step around ``local_loss(params, x, y,
        tokens_local) -> (total, (means, sums))``: ``total`` is this
        device's UNMASKED loss sum (what the gradient is taken of);
        ``means`` (one per ``_mean_names``) are per-device token-weighted
        sums, reported as masked means over the contributing tokens;
        ``sums`` (one per ``_sum_names``) are reported summed over the
        contributing replicas. ``batch_spec`` shards the tokens: rows over
        its first entry's axes, the sequence over ``seq_axis`` if any."""
        mesh = self.mesh
        axis_names = tuple(mesh.axis_names)
        data_axis = self.data_axis
        vary_axes = tuple(n for n in axis_names if n != data_axis)
        tx, param_specs = self.tx, self._param_specs
        compress, overlap = self.compress, self.overlap

        def step(params, opt_state, x, y, valid):
            # The mask arrives sharded on `data` only; mark it varying on the
            # other axes too so the all-axes psums below are well-typed (the
            # contributor count keeps the data-only form so its psum over
            # `data` is provably replicated). Under TP every model shard of a
            # (data, seq) coordinate computes the identical loss term, so the
            # all-axes denominator carries the same tp-fold factor as the
            # all-axes loss/grad sums — the ratio (and the per-leaf psum
            # transposes) come out exactly right at any tp.
            v0 = valid.reshape(())
            v = v0
            for ax in vary_axes:
                v = lax.pcast(v, ax, to="varying")
            tokens_local = jnp.float32(x.shape[0] * x.shape[1])
            denom = jnp.maximum(lax.psum(v * tokens_local, axis_names), 1.0)

            def loss_fn(p):
                total, stats = local_loss(p, x, y, tokens_local)
                return total / denom, stats

            (_, (means, sums)), gavg = synced_value_and_grad(
                loss_fn, params, param_specs, axis_names, v,
                compress=compress, overlap=overlap, has_aux=True,
            )
            # what is left under this scope in a compiled step are the
            # optimizer's passes of their own: an update that XLA fuses into
            # a weight-gradient product keeps the product's scope
            with jax.named_scope("optimizer"):
                updates, new_opt = tx.update(gavg, opt_state, params)
                new_params = optax.apply_updates(params, updates)
            return (
                new_params,
                new_opt,
                *(lax.psum(m * v / denom, axis_names) for m in means),
                lax.psum(v0, data_axis),  # contributing replica rows
                *(lax.psum(s * v, axis_names) for s in sums),
            )

        self._n_scalars = len(self._mean_names) + 1
        rows = batch_spec[0]
        self._row_axes = rows if isinstance(rows, tuple) else (rows,)
        self._row_shards = int(np.prod([mesh.shape[a] for a in self._row_axes]))
        self._data_sharding = NamedSharding(mesh, batch_spec)
        self._valid_sharding = NamedSharding(mesh, P(data_axis))
        self._replicated = NamedSharding(mesh, P())
        self._check_vma = check_vma
        mapped = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(
                self._param_specs, self._opt_specs, batch_spec, batch_spec,
                P(data_axis),
            ),
            out_specs=(
                self._param_specs, self._opt_specs,
                *(P(),) * (self._n_scalars + len(self._sum_names)),
            ),
            check_vma=check_vma,
        )
        self._step = jax.jit(mapped, donate_argnums=(0, 1))
        self._raw_step = step  # reused by train_chain's on-device loop
        self._chains: dict = {}

    # -- stepping ------------------------------------------------------------

    def _place(self, x, y):
        return place_tokens(
            x, y, self._data_sharding,
            seq_len=self.seq_len, dp=self._row_shards,
        )

    def _metrics(self, values):
        """The next step's metrics from its fetched outputs, in the step's
        order: the means, ``contributors``, then the sums it reports."""
        self.step_num += 1
        names = (*self._mean_names, "contributors", *self._sum_names)
        n = self._n_scalars
        return self.metrics_cls(
            step=self.step_num,
            **{k: float(val) for k, val in zip(names[:n], values)},
            **dict(zip(names[n:], values[n:])),
        )

    def train_step(
        self,
        tokens: np.ndarray,
        labels: np.ndarray,
        valid: Sequence[float] | None = None,
    ):
        """One step on a GLOBAL (batch, seq_len) token array, its rows
        divisible by the mesh's row shards (on a pod: this process's
        HOST-LOCAL rows, ``place_tokens``' seam).

        ``valid``: per-DP-replica-row contributor mask of shape (dp,);
        None = all rows contribute.

        One trace a step (``obs.trace``): the root ``trainer.step`` and a
        child around each call that can wait, none of which adds a sync.
        """
        span = obs_trace.span
        with span("trainer.step", root=True, step=self.step_num + 1) as root:
            valid = normalize_valid(valid, self.dp)
            with span("trainer.step.place"):
                xd, yd = self._place(tokens, labels)
                vd = place_mask(valid, self._valid_sharding)
            with span("trainer.step.dispatch"):  # returns when enqueued
                self.params, self.opt_state, *metrics = self._step(
                    self.params, self.opt_state, xd, yd, vd
                )
            with span("trainer.step.fetch"):
                # one fetch for all of the step's metrics, not one sync each
                values = jax.device_get(metrics)
            out = self._metrics(values)
        _STEPS.inc()
        _TOKENS.inc(tokens.size)
        _LOSS.set(out.loss)
        _STEP_TIME.observe(root.dur)
        return out

    def train(self, batches: Iterable) -> list:
        return [self.train_step(x, y) for x, y in batches]

    def step_text(self, tokens: np.ndarray, labels: np.ndarray) -> str:
        """The compiled step's HLO text for such a batch: every instruction
        with the ``op_name`` its metadata carries (named scopes and module
        names), which is what joins a device trace's ops to the program's
        scopes. After a step has run it comes from the compile cache."""
        xd, yd = self._place(tokens, labels)
        vd = place_mask(normalize_valid(None, self.dp), self._valid_sharding)
        return self._step.lower(
            self.params, self.opt_state, xd, yd, vd
        ).compile().as_text()

    def get_flat_params(self) -> np.ndarray:
        return flatten_pytree(self.params)[0]

    # -- on-device training chain (data-loader path, no host I/O per step) ---

    def _build_chain(self, sampler, steps: int, rows: int):
        raw_step = self._raw_step
        row_axes, seq_axis = self._row_axes, self.seq_axis
        t_local = self.seq_len // self.sp
        n = self._n_scalars

        def chain(params, opt_state, key, valid):
            # one independent stream per coordinate of the axes that carry
            # rows; the seq shards of a coordinate fold the SAME key — they
            # must agree on the rows' tokens — and each slices its own
            # T_local columns from the sampler's GLOBAL sequences
            rkey = key
            for ax in row_axes:
                rkey = jax.random.fold_in(rkey, lax.axis_index(ax))
            s = lax.axis_index(seq_axis) if seq_axis is not None else None

            def body(carry, i):
                p, o = carry
                x, y = sampler(jax.random.fold_in(rkey, i), rows)
                if s is not None:
                    x = lax.dynamic_slice_in_dim(x, s * t_local, t_local, axis=1)
                    y = lax.dynamic_slice_in_dim(y, s * t_local, t_local, axis=1)
                p, o, *metrics = raw_step(p, o, x, y, valid)
                return (p, o), tuple(metrics[:n])

            (params, opt_state), outs = lax.scan(
                body, (params, opt_state), jnp.arange(steps)
            )
            return params, opt_state, *outs

        mapped = jax.shard_map(
            chain,
            mesh=self.mesh,
            in_specs=(
                self._param_specs, self._opt_specs, P(), P(self.data_axis),
            ),
            out_specs=(self._param_specs, self._opt_specs, *(P(),) * n),
            check_vma=self._check_vma,  # as the step's (see step_check_vma)
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def train_chain(
        self,
        sampler,
        steps: int,
        rows_per_replica: int,
        *,
        valid: Sequence[float] | None = None,
        seed: int = 0,
    ) -> list:
        """Run ``steps`` steps entirely on device in ONE dispatch.

        ``sampler`` is a traced ``(key, rows) -> (tokens, labels)`` producing
        GLOBAL (rows, seq_len) sequences (``SyntheticCopyLM.device_sampler``);
        each coordinate of the row axes draws its own stream of
        ``rows_per_replica`` rows a step and its seq shards slice their local
        columns, so nothing crosses the host inside the loop.
        """
        outs = run_chain_cached(
            self, sampler, steps, rows_per_replica,
            lambda: self._build_chain(sampler, steps, rows_per_replica),
            valid, self.dp, self._valid_sharding, seed,
        )
        return [self._metrics(values) for values in zip(*outs)]

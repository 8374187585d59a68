"""Expert-parallel MoE trainer: DP x EP over a (data, expert) mesh, or
DP x SP x EP over (data, seq, expert) with ring/Ulysses attention.

Beyond-parity capability (the reference is DP-only, SURVEY.md §3). The dense
non-MoE parts treat the data and expert axes as data parallelism — the
global batch's rows shard over data x expert jointly (and its sequence over
``seq`` when present) — while each MoE layer's all_to_all pair (ops/moe.py)
rides the ``expert`` axis. Gradient plumbing reuses the
framework's one mechanism: expert weights enter shard_map device-varying on
``expert`` (ep_param_specs), so shard_map autodiff psums their grads over
``data`` only; replicated leaves psum over both axes — the threshold-masked
allreduce with the same contributor-mask semantics as every other trainer
(mask per DP replica row).
"""

from __future__ import annotations

import dataclasses
from typing import Iterable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P


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


class MoETrainer:
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
        only.
      params: with ``model``, its variables (seeded weights handed in);
        left out, ``model.init`` runs jitted from ``seed``.
    """

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

        from akka_allreduce_tpu.comm.allreduce import validate_trainer_compress

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
        self.opt_state = self.tx.init(self.params)
        self.param_count = int(
            sum(np.prod(p.shape) for p in jax.tree.leaves(self.params))
        )
        self.step_num = 0

        if self.ep > 1:
            assert self.expert_axis is not None
            self._param_specs = ep_param_specs(self.params, self.expert_axis)
            self._opt_specs = ep_param_specs(self.opt_state, self.expert_axis)
        else:
            self._param_specs = jax.tree.map(lambda _: P(), self.params)
            self._opt_specs = jax.tree.map(lambda _: P(), self.opt_state)
        is_spec = lambda x: isinstance(x, P)  # noqa: E731
        self.params = jax.device_put(
            self.params,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._param_specs,
                is_leaf=is_spec,
            ),
        )
        self.opt_state = jax.device_put(
            self.opt_state,
            jax.tree.map(
                lambda s: NamedSharding(mesh, s), self._opt_specs,
                is_leaf=is_spec,
            ),
        )

        axis_names = tuple(mesh.axis_names)
        if self.seq_axis is not None:
            # rows over data x expert, the sequence dim over seq
            batch_spec = P((self.data_axis, self.expert_axis), self.seq_axis)
        elif len(axis_names) > 1:
            batch_spec = P(axis_names)
        else:
            batch_spec = P(axis_names[0])
        self._data_sharding = NamedSharding(mesh, batch_spec)
        self._valid_sharding = NamedSharding(mesh, P(self.data_axis))
        data_axis = self.data_axis
        vary_axes = tuple(n for n in axis_names if n != data_axis)
        n_rows = 0 if model is None else 2  # outputs past (logits, aux, dropped)

        def model_apply(p, x):
            logits, aux, *stats = self.model.apply(p, x)
            return logits, aux, tuple(stats)  # (dropped[, expert_rows, buffer_rows])

        tx = self.tx
        aux_coef = self.aux_coef
        param_specs = self._param_specs
        wire_dtype = jnp.bfloat16 if compress == "bf16" else None

        def step(params, opt_state, x, y, valid):
            v0 = valid.reshape(())
            v = v0
            for ax in vary_axes:
                v = lax.pcast(v, ax, to="varying")
            tokens_local = jnp.float32(x.shape[0] * x.shape[1])
            denom = jnp.maximum(lax.psum(v * tokens_local, axis_names), 1.0)

            def masked_loss(p):
                logits, aux, stats = model_apply(p, x)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                ).sum()
                # aux is a per-device mean: weight by local tokens so the
                # global sum / denom is its masked token-weighted mean
                total = (ce + aux_coef * aux * tokens_local) * v / denom
                return total, (ce, aux, stats)

            if overlap:
                # per-leaf in-backward collectives (SURVEY.md §8.4): the
                # loss is UNMASKED — each leaf's sync masks its cotangent
                # itself; the metric psums below re-apply v explicitly
                from akka_allreduce_tpu.comm.allreduce import (
                    overlap_value_and_grad,
                )

                def unmasked_loss(ps):
                    logits, aux, stats = model_apply(ps, x)
                    ce = optax.softmax_cross_entropy_with_integer_labels(
                        logits, y
                    ).sum()
                    total = (ce + aux_coef * aux * tokens_local) / denom
                    return total, (ce, aux, stats)

                (_, (ce, aux, stats)), gavg = overlap_value_and_grad(
                    unmasked_loss, params, param_specs, axis_names, v,
                    has_aux=True, wire_dtype=wire_dtype,
                )
            elif compress in ("bf16", "int8"):
                # explicit grouped collective (see long_context.py);
                # expert-sharded leaves reduce over data/seq only; int8
                # rides the explicit ring per reduce axis
                from akka_allreduce_tpu.comm.allreduce import (
                    compressed_value_and_grad,
                )

                (_, (ce, aux, stats)), gavg = compressed_value_and_grad(
                    masked_loss, params, param_specs, axis_names,
                    has_aux=True,
                    wire_dtype=compress,
                )
            else:
                # explicit grouped psums even uncompressed: the automatic
                # transpose-psum for replicated params does not run under
                # check_vma=False (flash-relax configs) — see
                # long_context.py / tests/test_vma_replication.py
                from akka_allreduce_tpu.comm.allreduce import (
                    compressed_value_and_grad,
                )

                (_, (ce, aux, stats)), gavg = compressed_value_and_grad(
                    masked_loss, params, param_specs, axis_names,
                    has_aux=True,
                    wire_dtype=None,
                )
            dropped, *rows = stats
            loss_avg = lax.psum(ce * v / denom, axis_names)
            aux_avg = lax.psum(aux * tokens_local * v / denom, axis_names)
            dropped_avg = lax.psum(
                dropped * tokens_local * v / denom, axis_names
            )
            contributors = lax.psum(v0, data_axis)
            updates, new_opt = tx.update(gavg, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return (
                new_params, new_opt, loss_avg, aux_avg, dropped_avg,
                contributors,
                # rows per held expert and per row buffer, summed over the
                # contributing replicas
                *(lax.psum(r * v, axis_names) for r in rows),
            )

        from akka_allreduce_tpu.ops.local_attention import flash_vma_relax

        head_dim = getattr(model, "head_dim", None) or d_model // n_heads
        self._check_vma = (
            not overlap
            and compress != "int8"
            and not flash_vma_relax(
                seq_len, head_dim, sp=self.sp, seq_impl=seq_impl
            )
            # a handed-in model's grouped products are a Pallas kernel on
            # the chip, whose outputs carry no varying-axes annotation
            and not (model is not None and jax.default_backend() == "tpu")
        )
        mapped = jax.shard_map(
            step,
            mesh=mesh,
            in_specs=(
                self._param_specs,
                self._opt_specs,
                batch_spec,
                batch_spec,
                P(self.data_axis),
            ),
            out_specs=(
                self._param_specs, self._opt_specs, P(), P(), P(), P(),
                *(P(),) * n_rows,
            ),
            # off when the overlap custom_vjp erases varying-axes typing OR
            # the flash kernel can dispatch (outputs carry no vma —
            # ops.local_attention.flash_vma_relax, LongContext's discipline)
            check_vma=self._check_vma,
        )
        self._step = jax.jit(mapped, donate_argnums=(0, 1))
        self._raw_step = step  # reused by train_chain's on-device loop
        self._replicated = NamedSharding(mesh, P())
        self._chains: dict = {}

    # -- stepping ------------------------------------------------------------

    def train_step(
        self,
        tokens: np.ndarray,
        labels: np.ndarray,
        valid: Sequence[float] | None = None,
    ) -> MoEStepMetrics:
        """One step on a GLOBAL (batch, seq_len) token array; batch divisible
        by dp * ep. ``valid``: per-DP-replica-row mask of shape (dp,)."""
        row_shards = self.dp * self.ep  # rows shard over data x expert only
        if (
            self._data_sharding.is_fully_addressable
            and tokens.shape[0] % row_shards
        ):
            # pod runtime: callers pass HOST-LOCAL rows, so the global
            # divisibility check belongs to place_tokens' seam, not here
            raise ValueError(
                f"global batch {tokens.shape[0]} not divisible by "
                f"{row_shards} row shards (data x expert)"
            )
        if tokens.shape[1] != self.seq_len:
            raise ValueError(
                f"sequence length {tokens.shape[1]} != {self.seq_len}"
            )
        from akka_allreduce_tpu.train.trainer import (
            normalize_valid,
            place_mask,
            place_tokens,
        )

        valid_arr = normalize_valid(valid, self.dp)
        xd, yd = place_tokens(
            tokens, labels, self._data_sharding,
            seq_len=self.seq_len, dp=1,  # row divisibility checked above
        )
        vd = place_mask(valid_arr, self._valid_sharding)
        self.params, self.opt_state, *metrics = self._step(
            self.params, self.opt_state, xd, yd, vd
        )
        self.step_num += 1
        # one fetch for all of the step's metrics, not one sync each
        loss, aux, dropped, cnt, *rows = jax.device_get(metrics)
        return MoEStepMetrics(
            step=self.step_num,
            loss=float(loss),
            aux_loss=float(aux),
            dropped=float(dropped),
            contributors=float(cnt),
            expert_rows=rows[0] if rows else None,
            buffer_rows=rows[1] if rows else None,
        )

    def train(self, batches: Iterable) -> list[MoEStepMetrics]:
        return [self.train_step(x, y) for x, y in batches]

    # -- on-device training chain (no host I/O per step) ---------------------

    def _build_chain(self, sampler, steps: int, rows_per_device: int):
        raw_step = self._raw_step
        data_axis, expert_axis = self.data_axis, self.expert_axis
        seq_axis = self.seq_axis
        t_local = self.seq_len // self.sp

        def chain(params, opt_state, key, valid):
            # one independent stream per (data, expert) COORDINATE: both
            # those axes carry data rows for the dense parts. On the 3-axis
            # mesh the seq shards of a coordinate fold the SAME key — they
            # must agree on the rows' tokens — and each slices its own
            # T_local columns from the sampler's GLOBAL sequences
            # (LongContextTrainer._build_chain's discipline)
            rkey = jax.random.fold_in(key, lax.axis_index(data_axis))
            if expert_axis is not None:
                rkey = jax.random.fold_in(rkey, lax.axis_index(expert_axis))
            s = lax.axis_index(seq_axis) if seq_axis is not None else None

            def body(carry, i):
                p, o = carry
                k = jax.random.fold_in(rkey, i)
                x, y = sampler(k, rows_per_device)
                if s is not None:
                    x = lax.dynamic_slice_in_dim(
                        x, s * t_local, t_local, axis=1
                    )
                    y = lax.dynamic_slice_in_dim(
                        y, s * t_local, t_local, axis=1
                    )
                p, o, loss, aux, dropped, cnt, *_ = raw_step(p, o, x, y, valid)
                return (p, o), (loss, aux, dropped, cnt)

            (params, opt_state), outs = lax.scan(
                body, (params, opt_state), jnp.arange(steps)
            )
            return params, opt_state, *outs

        mapped = jax.shard_map(
            chain,
            mesh=self.mesh,
            in_specs=(
                self._param_specs,
                self._opt_specs,
                P(),
                P(self.data_axis),
            ),
            out_specs=(
                self._param_specs,
                self._opt_specs,
                P(),
                P(),
                P(),
                P(),
            ),
            # same vma caveats as the step's shard_map (overlap / flash)
            check_vma=self._check_vma,
        )
        return jax.jit(mapped, donate_argnums=(0, 1))

    def train_chain(
        self,
        sampler,
        steps: int,
        rows_per_device: int,
        *,
        valid: Sequence[float] | None = None,
        seed: int = 0,
    ) -> list[MoEStepMetrics]:
        """Run ``steps`` DP x EP (x SP) steps entirely on device in ONE
        dispatch.

        ``sampler`` is a traced ``(key, rows) -> (tokens, labels)``
        producing GLOBAL (rows, seq_len) sequences (e.g.
        ``SyntheticCopyLM.device_sampler``); each (data, expert) coordinate
        draws its own stream and, on the 3-axis mesh, its seq shards slice
        their local columns — zero host I/O either way.
        """
        from akka_allreduce_tpu.train.trainer import run_chain_cached

        losses, auxes, droppeds, cnts = run_chain_cached(
            self,
            sampler,
            steps,
            rows_per_device,
            lambda: self._build_chain(sampler, steps, rows_per_device),
            valid,
            self.dp,
            self._valid_sharding,
            seed,
        )
        out = []
        for loss, aux, dropped, cnt in zip(losses, auxes, droppeds, cnts):
            self.step_num += 1
            out.append(
                MoEStepMetrics(
                    step=self.step_num,
                    loss=float(loss),
                    aux_loss=float(aux),
                    dropped=float(dropped),
                    contributors=float(cnt),
                )
            )
        return out

    def get_flat_params(self) -> np.ndarray:
        from akka_allreduce_tpu.binder.api import flatten_pytree

        return flatten_pytree(self.params)[0]

"""FSDP / ZeRO-3 LM trainer: params AND optimizer state sharded 1/n.

Beyond-parity capability (the reference is DP-only, SURVEY.md §3), completing
the ZeRO family next to ``Zero1DPTrainer``: stage 1 shards only the optimizer
state; this shards the trunk *parameters* too, so per-device memory for the
model's bulk is ``(params + moments)/n`` — the knob that lets a data-parallel
group train models larger than one chip's HBM.

Built the TPU way, on the same stacked-trunk substrate as the pipeline
trainer: the transformer trunk's L layers stack into one params tree with a
leading layer dim, and each trunk leaf ``(L, *S)`` is stored flattened and
sharded ``(L, n, per)`` with ``P(None, 'data')`` — device d holds the d-th
1/n slice of EVERY layer. The forward is a ``lax.scan`` over layers whose
body ``all_gather``s ONE layer's shards into the full layer, applies the
block, and discards the gathered copy — so a full layer is materialized only
transiently. Autodiff does the rest: the transpose of a tiled ``all_gather``
IS ``psum_scatter``, so each layer's gradient arrives reduce-scattered,
shard-local, exactly ZeRO-3's gradient flow, with no hand-written collective.
``remat=True`` additionally recomputes each layer on backward (one layer's
activations + one layer's params live at a time — the full FSDP memory
profile).

Embed/head (the small edge leaves) stay replicated with the standard
transpose-psum gradient, like every other trainer here. Threshold masking is
per DP device, the same contributor semantics as DPTrainer.

Checkpoints serialize the trunk UNSHARDED (gather-then-reshard at checkpoint
scale, the ZeRO-1 discipline), so an n-device checkpoint restores onto any
other device count.
"""

from __future__ import annotations

import dataclasses
from typing import Sequence

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.models.transformer import Block
from akka_allreduce_tpu.train.pipeline import _LMHead
from akka_allreduce_tpu.train.sharded_lm import step_check_vma
from akka_allreduce_tpu.train.trainer import (
    TrainStepMetrics,
    normalize_valid,
    place_mask,
    place_tokens,
)


def _shard_leaf(leaf: jax.Array, n: int) -> jax.Array:
    """(L, *S) -> (L, n, per): flatten, pad to n equal slices per layer."""
    flat = leaf.reshape(leaf.shape[0], -1)
    per = -(-flat.shape[1] // n)
    return jnp.pad(flat, ((0, 0), (0, per * n - flat.shape[1]))).reshape(
        leaf.shape[0], n, per
    )


def _unshard_leaf(leaf: jax.Array, full_shape: tuple) -> jax.Array:
    """(L, n, per) -> (L, *S): inverse of :func:`_shard_leaf`."""
    size = int(np.prod(full_shape[1:]))
    return leaf.reshape(leaf.shape[0], -1)[:, :size].reshape(full_shape)


def _shard_leaf_tp(
    leaf: jax.Array, n: int, tp: int, tp_dim: int
) -> jax.Array:
    """(L, *S) -> (L, tp, n, per) for a tensor-parallel trunk leaf: split
    the Megatron-sharded dim (``tp_dim``, 0-based within the per-layer
    shape) into ``tp`` slices, flatten each slice's remaining dims in
    original order, and pad to ``n`` equal FSDP shards. Dim 1 shards over
    ``model``, dim 2 over the gather (data[, seq]) axes — so each device
    stores 1/(tp*n) of every layer and the in-scan all_gather over the
    gather axes reassembles exactly this model shard's TP-LOCAL layer."""
    length, s = leaf.shape[0], leaf.shape[1:]
    loc = s[tp_dim] // tp
    x = leaf.reshape(
        *leaf.shape[: 1 + tp_dim], tp, loc, *s[tp_dim + 1 :]
    )
    x = jnp.moveaxis(x, 1 + tp_dim, 1)  # (L, tp, ...S with loc at tp_dim...)
    flat = x.reshape(length, tp, -1)
    per = -(-flat.shape[2] // n)
    return jnp.pad(
        flat, ((0, 0), (0, 0), (0, per * n - flat.shape[2]))
    ).reshape(length, tp, n, per)


def _unshard_leaf_tp(
    leaf: jax.Array, full_shape: tuple, tp_dim: int
) -> jax.Array:
    """(L, tp, n, per) -> (L, *S): inverse of :func:`_shard_leaf_tp`.

    Module-agnostic: numpy input stays on host (the checkpoint writer
    thread unshards captured host leaves without touching a device)."""
    xp = jnp if isinstance(leaf, jax.Array) else np
    length = leaf.shape[0]
    tp = leaf.shape[1]
    s = full_shape[1:]
    loc = s[tp_dim] // tp
    local_s = s[:tp_dim] + (loc,) + s[tp_dim + 1 :]
    size = int(np.prod(local_s))
    x = leaf.reshape(length, tp, -1)[:, :, :size].reshape(
        length, tp, *local_s
    )
    x = xp.moveaxis(x, 1, 1 + tp_dim)
    return x.reshape(full_shape)


class FSDPLMTrainer:
    """Fully-sharded data-parallel trainer for a decoder-only LM.

    Args:
      mesh: a 1-axis (data,) mesh, or a 2-axis (data, seq) mesh — FSDP x SP,
        the modern long-context recipe: params shard over the WHOLE mesh
        (dp*sp slices) while ring/Ulysses attention shards the sequence over
        ``seq``.
      n_layers: trunk depth (the FSDP-sharded bulk).
      seq_impl: attention schedule over the seq axis ("ring" | "ulysses"),
        used when the mesh has one.
      remat: ``True`` (or ``"full"``) recomputes each layer on backward
        (jax.checkpoint — one layer's activations at a time, maximum memory
        savings, ~1 extra forward of FLOPs). ``"params"`` drops the
        gathered full-layer params from the residuals and re-gathers them
        on backward (``dots_saveable`` policy: matmul outputs — the
        layer's real activations — stay saved; the gather chain and cheap
        elementwise ops recompute). This is the ZeRO-3 sweet spot when
        activations fit: without it the scan saves every iteration's
        gathered layer (L full layers resident — the no-remat OOM), with
        full remat the step pays ~25-30 % MFU for matmul recompute the
        model didn't need.
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
        seq_len: int = 64,
        seq_impl: str = "ring",
        optimizer: optax.GradientTransformation | None = None,
        learning_rate: float = 1e-2,
        seed: int = 0,
        compute_dtype=jnp.float32,
        remat: bool | str = False,
        compress: str | None = None,
        prefetch: bool = False,
    ) -> None:
        if remat is True:
            remat = "full"
        if remat not in (False, "full", "params"):
            raise ValueError(
                f"remat must be False, True/'full', or 'params', got {remat!r}"
            )
        axes = tuple(mesh.axis_names)
        # accepted meshes (by axis NAME — "model" selects Megatron TP, in
        # ANY order after the leading data axis, so the repo's canonical
        # data_seq_model_mesh layout with model innermost works too):
        #   (data,) | (data, seq) | (data, model) | (data, {model, seq})
        ok = (
            len(axes) in (1, 2, 3)
            and axes[0] not in ("model", "seq")
            and set(axes[1:]) <= {"model", "seq"}
            and len(set(axes)) == len(axes)
        )
        if not ok:
            raise ValueError(
                "FSDP needs a (data[, model][, seq]) mesh — leading data "
                "axis, then any of 'model' (Megatron TP) and 'seq' — got "
                f"{axes}"
            )
        if compress not in (None, "bf16", "int8"):
            raise ValueError(
                f"compress must be None, 'bf16' or 'int8', got {compress!r}"
            )
        if prefetch and remat == "full":
            raise ValueError(
                "prefetch and full remat do not compose: the prefetched "
                "gathered layer rides the scan CARRY, and scan saves every "
                "iteration's carry as a backward residual — all L gathered "
                "layers would stay resident, defeating exactly the memory "
                "profile full remat buys. prefetch DOES compose with "
                "remat='params' (the trunk unrolls so forward AND backward "
                "re-gathers can run behind neighboring layers' matmuls)"
            )
        self.compress = compress
        self.prefetch = prefetch
        self.mesh = mesh
        self.axes = axes
        self.data_axis = axes[0]
        self.model_axis = "model" if "model" in axes else None
        self.seq_axis = "seq" if "seq" in axes else None
        # params gather over every NON-model axis: each Megatron shard
        # FSDP-shards (and re-gathers) only its own tp-local slice
        self.gather_axes = tuple(a for a in axes if a != self.model_axis)
        self.dp = int(mesh.shape[self.data_axis])
        self.sp = int(mesh.shape[self.seq_axis]) if self.seq_axis else 1
        self.tp = int(mesh.shape[self.model_axis]) if self.model_axis else 1
        self.n_devices = self.dp * self.sp * self.tp
        n = self.dp * self.sp  # FSDP shards per tp-local slice
        self.gather_shards = n
        self.data_shards = self.dp
        if seq_len % self.sp:
            raise ValueError(
                f"{seq_len=} not divisible by seq shards {self.sp}"
            )
        self.seq_len = seq_len
        self.vocab = vocab
        self.n_layers = n_layers
        self.tx = optimizer or optax.adam(learning_rate)

        block = Block(
            n_heads=n_heads,
            n_kv_heads=n_kv_heads,
            compute_dtype=compute_dtype,
            seq_axis=self.seq_axis if self.sp > 1 else None,
            seq_impl=seq_impl,
            model_axis=self.model_axis if self.tp > 1 else None,
            tp_size=self.tp,
        )
        embed = nn.Embed(vocab, d_model, dtype=compute_dtype)
        head = _LMHead(vocab, compute_dtype=compute_dtype)
        rng = jax.random.PRNGKey(seed)
        # init with the DENSE twin (param shapes are T- and axis-independent)
        init_block = Block(
            n_heads=n_heads, n_kv_heads=n_kv_heads,
            compute_dtype=compute_dtype,
        )
        x0 = jnp.zeros((1, seq_len // self.sp, d_model), jnp.float32)
        tok0 = jnp.zeros((1, seq_len // self.sp), jnp.int32)
        layer_ps = [
            init_block.init(jax.random.fold_in(rng, 1000 + i), x0)["params"]
            for i in range(n_layers)
        ]
        trunk_full = jax.tree.map(lambda *ls: jnp.stack(ls), *layer_ps)
        # static pytree of full trunk shapes, for the in-scan ungather
        # (tuple leaves survive tree.map via flatten_up_to; never
        # jax.tree.leaves this tree — the tuples would flatten into ints)
        self._trunk_shapes = jax.tree.map(lambda l: l.shape, trunk_full)
        # per-leaf Megatron dim (0-based within the per-layer shape; -1 =
        # replicated across model — None would vanish as an empty pytree)
        # from the SAME rule tp_param_specs uses, so the FSDP storage can
        # never drift from the TP module's layout
        if self.tp > 1:
            from akka_allreduce_tpu.models.transformer import tp_param_specs

            tp_specs = tp_param_specs(layer_ps[0], self.model_axis)
            self._trunk_tp_dims = jax.tree.map(
                lambda s: (
                    s.index(self.model_axis) if self.model_axis in s else -1
                ),
                tp_specs,
                is_leaf=lambda x: isinstance(x, P),
            )
        else:
            self._trunk_tp_dims = jax.tree.map(lambda _: -1, layer_ps[0])
        tp = self.tp

        def store_leaf(leaf, tp_dim):
            if tp_dim < 0:
                return _shard_leaf(leaf, n)
            return _shard_leaf_tp(leaf, n, tp, tp_dim)

        # local (this model shard's) per-layer shapes, for the in-scan
        # ungather: the TP dim shrinks by tp on Megatron-sharded leaves
        def local_shape(shape, tp_dim):
            if tp_dim < 0:
                return shape
            s = list(shape)
            s[1 + tp_dim] //= tp
            return tuple(s)

        self._trunk_local_shapes = jax.tree.map(
            local_shape, self._trunk_shapes, self._trunk_tp_dims,
            is_leaf=lambda x: isinstance(x, tuple),
        )
        trunk_count = int(sum(l.size for l in jax.tree.leaves(trunk_full)))
        self.params = {
            "embed": embed.init(jax.random.fold_in(rng, 1), tok0)["params"],
            "trunk": jax.tree.map(
                store_leaf, trunk_full, self._trunk_tp_dims
            ),
            "head": head.init(jax.random.fold_in(rng, 2), x0)["params"],
        }
        self.param_count = trunk_count + int(
            sum(
                np.prod(p.shape)
                for k in ("embed", "head")
                for p in jax.tree.leaves(self.params[k])
            )
        )
        self.opt_state = self.tx.init(self.params)

        gather_axes = self.gather_axes

        def spec_for(path, leaf):
            names = [
                str(getattr(k, "key", getattr(k, "name", k))) for k in path
            ]
            if "trunk" in names and np.ndim(leaf) == 4:
                # (L, tp, n, per): Megatron slice dim on `model`, FSDP
                # shard dim jointly over the gather axes
                return P(None, self.model_axis, gather_axes)
            if "trunk" in names and np.ndim(leaf) == 3:
                # (L, n, per): shard dim 1 over the gather axes (data-major,
                # matching the tuple-axis all_gather order in the scan
                # body); model-replicated when a model axis exists
                return P(None, gather_axes)
            return P()

        self._param_specs = jax.tree_util.tree_map_with_path(
            spec_for, self.params
        )
        self._opt_specs = jax.tree_util.tree_map_with_path(
            spec_for, self.opt_state
        )
        self.params = self._place(self.params, self._param_specs)
        self.opt_state = self._place(self.opt_state, self._opt_specs)
        self._replicated = NamedSharding(mesh, P())
        batch_spec = (
            P(self.data_axis, self.seq_axis)
            if self.seq_axis
            else P(self.data_axis)
        )
        self._data_sharding = NamedSharding(mesh, batch_spec)
        self._valid_sharding = NamedSharding(mesh, P(self.data_axis))
        self.step_num = 0

        axes = self.axes
        data_axis = self.data_axis
        seq_axis = self.seq_axis
        vary_axes = tuple(a for a in axes if a != data_axis)
        g_axes = self.gather_axes
        param_specs = self._param_specs
        # the in-scan ungather targets THIS model shard's local layer
        # shapes (the TP dim shrinks by tp on Megatron-sharded leaves)
        trunk_shapes = self._trunk_local_shapes
        block_apply = block.apply
        embed_apply = embed.apply
        head_apply = head.apply
        tx = self.tx

        int8_gather = None
        if compress == "int8":
            from akka_allreduce_tpu.comm.allreduce import (
                ring_reduce_scatter_sum,
            )
            from akka_allreduce_tpu.ops.ring import int8_quantize

            n_shards = self.gather_shards
            # tile order of a multi-axis tiled all_gather is row-major over
            # the axis tuple (first axis outermost), so its transpose
            # decomposes into SEQUENTIAL per-axis rings: reduce-scatter the
            # outer axis first (segments of inner_size*shard), then the
            # inner axis — each ring carries int8 per-hop payloads. This
            # closes the old FSDP x SP exclusion (VERDICT r4 #4b): gathers
            # over (data, seq) now run quarter-width both ways.
            axis_sizes = [int(self.mesh.shape[a]) for a in g_axes]

            @jax.custom_vjp
            def int8_gather(flat):
                q, sc = int8_quantize(flat)
                qf = lax.all_gather(q, g_axes, tiled=True)
                scf = lax.all_gather(sc.reshape(1), g_axes, tiled=True)
                return (
                    qf.reshape(n_shards, -1).astype(jnp.float32)
                    * scf[:, None]
                ).reshape(-1)

            def _fwd(flat):
                return int8_gather(flat), None

            def _bwd(_, ct):
                # the all_gather's transpose is reduce-scatter; ride the
                # explicit int8 ring(s) so the backward wire is
                # quarter-width too (per-hop scales; ct length =
                # prod(axis_sizes) * shard, so segments align with the
                # tiled gather layout exactly, outer axis first)
                out = ct
                for ax, sz in zip(g_axes, axis_sizes):
                    out = ring_reduce_scatter_sum(
                        out, ax, sz, compress="int8"
                    )
                return (out,)

            int8_gather.defvjp(_fwd, _bwd)

        def step(params, opt_state, x, y, valid):
            v0 = valid.reshape(())
            v = v0
            for ax in vary_axes:
                # the mask is per DP replica row; mark it varying on the
                # seq/model axes so the all-axes psums below are well-typed
                # (LongContext's discipline — under TP every model shard of
                # a data coordinate computes the identical loss term, so
                # the tp-fold factors cancel in the ratio)
                v = lax.pcast(v, ax, to="varying")
            contributors = lax.psum(v0, data_axis)
            tokens_local = jnp.float32(x.shape[0] * x.shape[1])
            denom = jnp.maximum(lax.psum(v * tokens_local, axes), 1.0)

            def masked_loss(p):
                h = embed_apply({"params": p["embed"]}, x)

                def gather_leaf(s, shape):
                    # gather ONE layer's shard over the NON-model axes —
                    # the all_gather's transpose is psum_scatter, so this
                    # layer's grad comes back reduce-scattered shard-local
                    # (Megatron-sharded leaves reassemble only their own
                    # tp-local slice; their grads stay model-local too).
                    # compress="bf16" runs the gather at half width; its
                    # transpose then reduce-scatters the grads in bf16 too
                    # (FSDP's collectives ARE its bandwidth cost), while
                    # the stored master params and moments stay f32.
                    # compress="int8" quarters the wire both ways:
                    # forward = ONE quantization per shard (int8 payload +
                    # a per-shard f32 scale on a second all_gather — no
                    # per-hop requantization: all_gather forwards original
                    # payloads); backward = the explicit int8 ring
                    # reduce-scatter (per-hop scales, custom transpose).
                    flat = s.reshape(-1)
                    if compress == "bf16":
                        flat = flat.astype(jnp.bfloat16)
                    if compress == "int8":
                        full = int8_gather(flat)
                    else:
                        full = lax.all_gather(flat, g_axes, tiled=True)
                    if compress == "bf16":
                        full = full.astype(s.dtype)
                    size = int(np.prod(shape[1:]))
                    return full[:size].reshape(shape[1:])

                if prefetch and remat == "params":
                    # Prefetch x regather remat (VERDICT r3 #5, closing the
                    # old exclusion): the trunk UNROLLS — without a loop
                    # boundary the latency-hiding scheduler is free to run
                    # layer k+1's forward gather behind layer k's matmuls
                    # AND layer k-1's backward RE-gather behind layer k's
                    # backward matmuls (the regathers already run twice
                    # under remat='params'; hiding the second copy is pure
                    # win). Each layer keeps its own
                    # jax.checkpoint(dots_saveable), so the residual
                    # profile is exactly scan-mode remat='params': matmul
                    # outputs saved, gathered params + cheap elementwise
                    # recomputed. Cost: n_layers copies of the layer in the
                    # program (compile time), fine at trunk depths that fit
                    # one chip.
                    trunk = p["trunk"]
                    n_l = jax.tree.leaves(trunk)[0].shape[0]

                    def one_layer(hh, layer_shards):
                        layer_p = jax.tree.map(
                            gather_leaf, layer_shards, trunk_shapes
                        )
                        return block_apply({"params": layer_p}, hh)

                    layer_fn = jax.checkpoint(
                        one_layer,
                        policy=jax.checkpoint_policies.dots_saveable,
                    )
                    for i in range(n_l):
                        h = layer_fn(
                            h, jax.tree.map(lambda s: s[i], trunk)
                        )
                elif prefetch:
                    # Software-pipelined parameter prefetch (the FSDP form
                    # of SURVEY §8.4 overlap): iteration k issues layer
                    # k+1's all_gather BEFORE computing layer k, and the
                    # two have no data dependence — the latency-hiding
                    # scheduler can run next layer's gather behind this
                    # layer's compute. A plain scan-over-xs serializes them
                    # (a layer's gather can only start in its own
                    # iteration). Same math; the trade is the gathered
                    # layer riding the scan carry (hence the full-remat
                    # guard in __init__). The scan covers n_l - 1
                    # iterations and the last layer applies AFTER it, so no
                    # iteration gathers a layer it then discards.
                    trunk = p["trunk"]
                    n_l = jax.tree.leaves(trunk)[0].shape[0]

                    def gather_layer(i):
                        return jax.tree.map(
                            lambda s, shape: gather_leaf(
                                lax.dynamic_index_in_dim(
                                    s, i, 0, keepdims=False
                                ),
                                shape,
                            ),
                            trunk,
                            trunk_shapes,
                        )

                    def body(carry, i):
                        hh, cur = carry
                        nxt = gather_layer(i + 1)
                        hh = block_apply({"params": cur}, hh)
                        return (hh, nxt), None

                    (h, last), _ = lax.scan(
                        body, (h, gather_layer(0)), jnp.arange(n_l - 1)
                    )
                    h = block_apply({"params": last}, h)
                else:

                    def body(carry, layer_shards):
                        layer_p = jax.tree.map(
                            gather_leaf, layer_shards, trunk_shapes
                        )
                        return block_apply({"params": layer_p}, carry), None

                    if remat == "full":
                        body_fn = jax.checkpoint(body)
                    elif remat == "params":
                        # drop the gathered full layers from the residuals
                        # and re-gather them on backward. Mechanism: an
                        # ALLOWLIST policy (dots_saveable) — matmul outputs
                        # (the layer's real activations) are saved, while
                        # the gather chain (all_gather + reshapes, not
                        # dots) is recomputed, i.e. the collective runs
                        # twice. A blocklist policy
                        # (save_anything_except_these_names) cannot express
                        # this: the un-named twin the producing eqn emits
                        # is itself saveable, so partial-eval just saves
                        # that same-size copy and the regather buys
                        # nothing (measured: temp bytes identical to
                        # no-remat). Cheap elementwise chains (gelu,
                        # layernorm) recompute alongside — that is
                        # dots_saveable's standard trade.
                        body_fn = jax.checkpoint(
                            body,
                            policy=jax.checkpoint_policies.dots_saveable,
                        )
                    else:
                        body_fn = body
                    h, _ = lax.scan(body_fn, h, p["trunk"])
                logits = head_apply({"params": p["head"]}, h)
                ce = optax.softmax_cross_entropy_with_integer_labels(
                    logits, y
                )
                return ce.sum() * v / denom

            # EXPLICIT psums for the replicated (embed/head) leaves:
            # localize_tree makes them device-varying so their grads stay
            # LOCAL, then grouped_tree_psum reduces them over the mesh —
            # shard_map's automatic transpose-psum for replicated params
            # DOES NOT RUN under check_vma=False (the int8/flash-relax
            # configs silently trained on per-device local embed/head
            # grads until the runtime replica assert caught it —
            # tests/test_vma_replication.py, VERDICT r4 #6). Trunk leaves
            # shard over every axis: localize and the grouped psum are
            # no-ops for them (their reduction IS the gather transpose).
            from akka_allreduce_tpu.comm.allreduce import (
                grouped_tree_psum,
                localize_tree,
            )

            params_in = localize_tree(params, param_specs, axes)
            loss, grads = jax.value_and_grad(masked_loss)(params_in)
            grads = grouped_tree_psum(grads, param_specs, axes)
            loss_avg = lax.psum(loss, axes)  # masked, already /denom
            updates, new_opt = tx.update(grads, opt_state, params)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt, loss_avg, contributors

        data_spec = batch_spec
        # with sp == 1 (or Ulysses) the blocks run FULL local attention, so
        # the flash kernel can dispatch
        self._check_vma = step_check_vma(
            seq_len=seq_len, head_dim=d_model // n_heads, sp=self.sp,
            seq_impl=seq_impl, compress=compress,
        )
        self._step = jax.jit(
            jax.shard_map(
                step,
                mesh=mesh,
                in_specs=(
                    self._param_specs,
                    self._opt_specs,
                    data_spec,
                    data_spec,
                    P(data_axis),
                ),
                out_specs=(self._param_specs, self._opt_specs, P(), P()),
                check_vma=self._check_vma,
            ),
            donate_argnums=(0, 1),
        )
        self._raw_step = step  # reused by train_chain's on-device loop
        self._chains: dict = {}

    def _place(self, tree, specs):
        """device_put every leaf onto its PartitionSpec over this mesh."""
        return jax.device_put(
            tree,
            jax.tree.map(
                lambda s: NamedSharding(self.mesh, s),
                specs,
                is_leaf=lambda s: isinstance(s, P),
            ),
        )

    # -- stepping ------------------------------------------------------------

    def _place_batch_tokens(self, tokens, labels):
        return place_tokens(
            tokens, labels, self._data_sharding,
            seq_len=self.seq_len, dp=self.dp,
        )

    def train_step(
        self,
        tokens: np.ndarray,
        labels: np.ndarray,
        valid: Sequence[float] | None = None,
    ) -> TrainStepMetrics:
        """One step on a GLOBAL (batch, seq_len) token array; ``valid`` is
        the per-DP-replica-row contributor mask, shape (dp,)."""
        valid_arr = normalize_valid(valid, self.dp)
        xd, yd = self._place_batch_tokens(tokens, labels)
        vd = place_mask(valid_arr, self._valid_sharding)
        self.params, self.opt_state, loss, cnt = self._step(
            self.params, self.opt_state, xd, yd, vd
        )
        self.step_num += 1
        return TrainStepMetrics(
            step=self.step_num, loss=float(loss), contributors=float(cnt)
        )

    # -- on-device training chain (no host I/O per step) ---------------------

    def _build_chain(self, sampler, steps: int, rows_per_replica: int):
        raw_step = self._raw_step
        data_axis, seq_axis = self.data_axis, self.seq_axis
        t_local = self.seq_len // self.sp

        def chain(params, opt_state, key, valid):
            # one stream per DP replica ROW: model/seq shards of a row fold
            # the same data coordinate so they agree on its tokens; seq
            # shards slice their own T_local columns (the LongContext
            # chain's discipline)
            rkey = jax.random.fold_in(key, lax.axis_index(data_axis))
            s = lax.axis_index(seq_axis) if seq_axis is not None else None

            def body(carry, i):
                p, o = carry
                k = jax.random.fold_in(rkey, i)
                x, y = sampler(k, rows_per_replica)
                if s is not None:
                    x = lax.dynamic_slice_in_dim(
                        x, s * t_local, t_local, axis=1
                    )
                    y = lax.dynamic_slice_in_dim(
                        y, s * t_local, t_local, axis=1
                    )
                p, o, loss, cnt = raw_step(p, o, x, y, valid)
                return (p, o), (loss, cnt)

            (params, opt_state), (losses, cnts) = lax.scan(
                body, (params, opt_state), jnp.arange(steps)
            )
            return params, opt_state, losses, cnts

        mapped = jax.shard_map(
            chain,
            mesh=self.mesh,
            in_specs=(
                self._param_specs,
                self._opt_specs,
                P(),
                P(self.data_axis),
            ),
            out_specs=(self._param_specs, self._opt_specs, P(), P()),
            check_vma=self._check_vma,
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
    ) -> list[TrainStepMetrics]:
        """Run ``steps`` FSDP steps entirely on device in ONE dispatch.

        ``sampler`` is a traced ``(key, rows) -> (tokens, labels)``
        producing GLOBAL (rows, seq_len) sequences
        (``SyntheticCopyLM.device_sampler``)."""
        from akka_allreduce_tpu.train.trainer import run_chain_cached

        losses, cnts = run_chain_cached(
            self,
            sampler,
            steps,
            rows_per_replica,
            lambda: self._build_chain(sampler, steps, rows_per_replica),
            valid,
            self.dp,
            self._valid_sharding,
            seed,
        )
        out = []
        for loss, cnt in zip(losses, cnts):
            self.step_num += 1
            out.append(
                TrainStepMetrics(
                    step=self.step_num,
                    loss=float(loss),
                    contributors=float(cnt),
                )
            )
        return out

    # -- gathered views (tests / checkpoint seam) ----------------------------

    def gathered_params(self) -> dict:
        """Full (unsharded) params pytree on the host — checkpoint scale."""
        return self.checkpoint_state()["params"]

    @property
    def trunk_shard_elems(self) -> int:
        """Per-device element count of the sharded trunk (layers x per-shard
        slice — the last dim — for both the 3D and the TP 4D layout)."""
        return int(
            sum(
                l.shape[0] * l.shape[-1]
                for l in jax.tree.leaves(self.params["trunk"])
            )
        )

    # -- checkpoint seam (mesh-size-independent, the ZeRO-1 discipline) ------

    @staticmethod
    def _is_params_container(t) -> bool:
        """A dict mirroring the params layout (optax moments do) — its
        trunk subtree holds the FSDP-sharded leaves."""
        return isinstance(t, dict) and "trunk" in t

    def checkpoint_capture(self) -> dict:
        """Shard-local device state for the async checkpoint path: each
        leaf is 1/(dp·sp[·tp]) of the trunk, already on device. The async
        checkpointer copies these HBM-to-HBM and drains them to host in the
        background — no gather, no step-loop stall (VERDICT r4 #1);
        :meth:`checkpoint_assemble` unshards on the writer thread."""
        return {"params": self.params, "opt_state": self.opt_state}

    def checkpoint_assemble(self, host: dict) -> dict:
        """Pure-host (numpy) unshard of a captured tree into the
        mesh-size-independent serialized form. Runs on the checkpoint
        writer thread — must not touch a device."""

        def unshard_leaf(s, shape, tp_dim):
            s = np.asarray(s)
            if tp_dim < 0:
                return np.asarray(_unshard_leaf(s, shape))
            return np.asarray(_unshard_leaf_tp(s, shape, tp_dim))

        def unshard_trunk(container):
            out = dict(container)
            out["trunk"] = jax.tree.map(
                unshard_leaf,
                container["trunk"],
                self._trunk_shapes,
                self._trunk_tp_dims,
            )
            return out

        params = unshard_trunk(host["params"])
        opt_state = jax.tree.map(
            lambda t: unshard_trunk(t) if self._is_params_container(t) else t,
            host["opt_state"],
            is_leaf=self._is_params_container,
        )
        return {"params": params, "opt_state": opt_state}

    def checkpoint_state(self) -> dict:
        """Mesh-size-independent: trunk leaves (params AND optimizer
        moments) gather to their full shapes on the host (the ZeRO-1
        gather-then-reshard discipline). Synchronous — the async
        checkpointer uses capture/assemble directly."""
        host = jax.tree.map(
            lambda x: np.asarray(jax.device_get(x)), self.checkpoint_capture()
        )
        return self.checkpoint_assemble(host)

    def checkpoint_template(self) -> dict:
        """Abstract (ShapeDtypeStruct-only) twin of :meth:`checkpoint_state`
        for the restore target: without it, TrainerCheckpointer.restore
        would gather the throwaway freshly-initialized full trunk AND both
        adam moments to host just to learn the shapes (ADVICE r2)."""

        def tmpl_container(container):
            out = {
                k: jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(
                        jnp.shape(l), jnp.asarray(l).dtype
                    ),
                    v,
                )
                for k, v in container.items()
                if k != "trunk"
            }
            out["trunk"] = jax.tree.map(
                lambda s, shape: jax.ShapeDtypeStruct(shape, s.dtype),
                container["trunk"],
                self._trunk_shapes,
            )
            return out

        opt_state = jax.tree.map(
            lambda t: (
                tmpl_container(t)
                if self._is_params_container(t)
                else jax.ShapeDtypeStruct(jnp.shape(t), jnp.asarray(t).dtype)
            ),
            self.opt_state,
            is_leaf=self._is_params_container,
        )
        return {
            "params": tmpl_container(self.params),
            "opt_state": opt_state,
        }

    def _reshard_trunk(self, container: dict) -> dict:
        """FULL (unsharded) trunk leaves -> this mesh's 1/(dp·sp[·tp])
        storage shards — the mesh-size-independent restore step, shared by
        checkpoint restore and the flat-params deposit seam."""
        n = self.dp * self.sp

        def reshard_leaf(full, tp_dim):
            full = jnp.asarray(full)
            if tp_dim < 0 or self.tp == 1:
                return _shard_leaf(full, n)
            return _shard_leaf_tp(full, n, self.tp, tp_dim)

        out = dict(container)
        out["trunk"] = jax.tree.map(
            reshard_leaf, container["trunk"], self._trunk_tp_dims
        )
        return out

    def restore_checkpoint_state(self, state: dict) -> None:
        # checkpoints carry FULL (unsharded) trunk leaves, so restore
        # reshards for THIS mesh's geometry — any (dp, sp, tp) combination
        self.params = self._place(
            self._reshard_trunk(state["params"]), self._param_specs
        )
        opt_state = jax.tree.map(
            lambda t: (
                self._reshard_trunk(t) if self._is_params_container(t) else t
            ),
            state["opt_state"],
            is_leaf=self._is_params_container,
        )
        self.opt_state = self._place(opt_state, self._opt_specs)

    # -- weights as a flat buffer (binder deposit seam) ----------------------

    def get_flat_params(self) -> np.ndarray:
        from akka_allreduce_tpu.binder.api import flatten_pytree

        return flatten_pytree(self.gathered_params())[0]

    def set_flat_params(self, vec: np.ndarray) -> None:
        """Inverse of :meth:`get_flat_params`: a flat vector of the FULL
        (unsharded) params unflattens and re-shards 1/(dp·sp[·tp]) onto
        the current mesh. Optimizer state is untouched (the
        elastic-averaging pull adjusts weights only)."""
        from jax.flatten_util import ravel_pytree

        full = self.gathered_params()
        flat, unravel = ravel_pytree(full)
        if vec.shape != flat.shape:
            raise ValueError(
                f"expected flat params of shape {flat.shape}, got {vec.shape}"
            )
        new_full = unravel(jnp.asarray(vec, jnp.float32))
        self.params = self._place(
            self._reshard_trunk(new_full), self._param_specs
        )

"""Threshold-masked allreduce over a device mesh.

Semantics (the reference's, recast in SPMD — SURVEY.md §3 "Collective semantics"):
every device contributes ``(payload, valid)`` where ``valid`` is 1.0 for a live
contributor and 0.0 for a straggler/dropout whose data must not count. The
round computes ``sum = psum(masked payload)`` and ``count = psum(valid)``;
consumers divide sum by count to get the partial average. Where the mask is
applied depends on where the code stands, not on an option: inside a
trainer's step (:func:`masked_psum`) it is ``payload * valid``, which XLA
fuses into whatever is still producing the gradient; at the host-facing entry
(:func:`build_threshold_allreduce`) the payload is a buffer already in HBM,
and a whole-payload mask is applied in place (:func:`mask_zero_inplace`: no
traffic on a live device, a write-only pass of zeros on a masked one, so a
masked device's NaN or Inf cannot reach the sum). This reproduces the
reference's ``ReduceBlock.count`` normalization without leaving XLA, and the
validity mask may be per *bucket* (the ``max_chunk_size`` granularity), matching
the reference's per-chunk contribution counting.

Chip loss is NOT handled here — XLA collectives are all-or-nothing across the
mesh. Masks absorb within-round straggling/invalid data; actual membership change
is the control plane's job (re-mesh via the PrepareAllreduce handshake,
SURVEY.md §8.4).

Schedules:

- ``"psum"``      — single fused AllReduce over all given axes (XLA picks the
  ICI algorithm: ring on a 1D torus axis, combined for 2D). The fast default.
- ``"butterfly"`` — staged per-axis psums on a 2D grid mesh: reduce along
  ``rows`` then ``cols``, the reference's two-stage grid/butterfly
  (SURVEY.md §4.3; BASELINE.json:8).
- ``"ring"``      — explicit ppermute ring (reduce-scatter + all-gather),
  the reference's "ring schedule" for large chunked buffers (BASELINE.json:9);
  also the substrate for later overlap/pipelining work.
- ``"pallas_ring"`` — the same ring schedule as a Pallas remote-DMA kernel
  (ops/ring.py): double-buffered ICI transfers with semaphore back-pressure,
  streamed through VMEM in max_chunk_size-ish buckets.
"""

from __future__ import annotations

import dataclasses
import math
from collections import OrderedDict
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from akka_allreduce_tpu.obs import metrics as obs_metrics
from akka_allreduce_tpu.parallel.mesh import LINE_AXIS

# which mask path each host-facing program was traced with (the mask's rank
# decides, once per program, so these count programs and not rounds)
_MASK_INPLACE_BUILDS = obs_metrics.counter("comm.mask_inplace_builds")
_MASK_MULTIPLY_BUILDS = obs_metrics.counter("comm.mask_multiply_builds")

Axes = tuple[str, ...]


def _normalize_axes(mesh: Mesh, axes: str | Sequence[str] | None) -> Axes:
    if axes is None:
        names = tuple(mesh.axis_names)
    elif isinstance(axes, str):
        names = (axes,)
    else:
        names = tuple(axes)
    for name in names:
        if name not in mesh.axis_names:
            raise ValueError(f"axis {name!r} not in mesh axes {mesh.axis_names}")
    return names


def _num_buckets(data_size: int, bucket_size: int | None) -> int:
    if bucket_size is None:
        return 1
    if bucket_size <= 0:
        raise ValueError(f"bucket_size must be positive, got {bucket_size}")
    return math.ceil(data_size / bucket_size)


# --------------------------------------------------------------------------
# Inner primitives — call these INSIDE shard_map / a pjit-ed step.
# --------------------------------------------------------------------------


def _multiply_mask(
    x: jax.Array, mask: jax.Array, bucket_size: int | None
) -> jax.Array:
    """``x`` times a scalar mask, or times a per-bucket mask ``(n_buckets,)``
    bucket by bucket (buckets need not align to anything: pad, multiply,
    slice)."""
    if bucket_size is None:
        return x * mask
    n_buckets = _num_buckets(x.shape[0], bucket_size)
    if mask.shape != (n_buckets,):
        raise ValueError(
            f"per-bucket mask must have shape ({n_buckets},), got {mask.shape}"
        )
    pad = n_buckets * bucket_size - x.shape[0]
    xp = jnp.pad(x, (0, pad)).reshape(n_buckets, bucket_size)
    return (xp * mask[:, None]).reshape(-1)[: x.shape[0]]


def mask_zero_inplace(x: jax.Array, valid: jax.Array) -> jax.Array:
    """``x`` where the scalar ``valid`` is non-zero, zeros where it is 0 — in
    ``x``'s own buffer, for a payload that already lies in HBM.

    ``x * valid`` reads and rewrites the whole payload to leave it as it was
    (a live contributor) or to make it zeros (a straggler): 3.27 ms for 1 GiB
    on a v5e, on every device of every round (PERF.md, PR 26). This is a loop
    of no trip or one, whose one trip fills the buffer with zeros: a ``while``
    carries its state in place, so a device whose ``valid`` is non-zero moves
    no memory and a masked one writes its payload once and reads nothing
    (1.65 ms). ``lax.cond`` would say the same and does not do: XLA copies
    the payload in front of a conditional, the multiply's traffic again
    (ISSUE 26). Where the caller does not donate ``x``, XLA copies it in
    front of the loop, which costs what the multiply did.

    Equal to ``x * valid`` for a 0/1 ``valid`` on finite payloads (but for
    the sign of a zero). Where they differ, this is what a mask means: a
    masked payload that holds NaN or Inf comes out as zeros (``0 * nan`` is
    ``nan``), and a ``valid`` that is neither 0 nor 1 leaves the payload as
    it is instead of scaling it.

    Not for a trainer's step: there ``x`` is a gradient that a fusion is
    still producing, XLA fuses the multiply into that producer for nothing,
    and a loop would force the gradient out to HBM first.
    """
    with jax.named_scope("mask_zero_inplace"):
        return lax.fori_loop(
            0, (valid == 0).astype(jnp.int32), lambda _, x: jnp.zeros_like(x), x
        )


def masked_psum(
    x: jax.Array,
    valid: jax.Array,
    axis_names: str | Axes,
    *,
    bucket_size: int | None = None,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Fused threshold-masked allreduce; use inside ``shard_map``.

    Args:
      x: this device's flat payload, shape ``(data,)``.
      valid: scalar 0/1 contribution mask, or per-bucket mask ``(n_buckets,)``
        when ``bucket_size`` is given.
      axis_names: mesh axis (or axes) to reduce over.
      wire_dtype: optional dtype (e.g. ``jnp.bfloat16``) the PAYLOAD collective
        runs in — halves ICI bytes at bf16. The count collective ALWAYS runs
        float32: 0/1 sums must stay exact on meshes larger than bf16's
        contiguous-integer range (256).
    Returns:
      ``(sum, count)`` — both replicated across the axes; ``sum`` has x's shape
      and dtype, ``count`` is float32 with the mask's shape (per-element
      expansion is the caller's choice via :func:`expand_counts`).
    """
    valid = jnp.asarray(valid, dtype=jnp.float32)
    masked = _multiply_mask(x, valid.astype(x.dtype), bucket_size)
    if wire_dtype is not None and masked.dtype != wire_dtype:
        total = lax.psum(masked.astype(wire_dtype), axis_names).astype(x.dtype)
    else:
        total = lax.psum(masked, axis_names)
    count = lax.psum(valid, axis_names)
    return total, count


def spec_axes(spec: P) -> Axes:
    """Mesh axis names a PartitionSpec shards over (flattening tuples)."""
    axes: list[str] = []
    for entry in spec:
        if entry is None:
            continue
        if isinstance(entry, (tuple, list)):
            axes.extend(entry)
        else:
            axes.append(entry)
    return tuple(axes)


def localize_tree(tree, specs, axis_names: Axes):
    """Make every leaf fully device-varying (``lax.pcast``) on the mesh axes
    its spec does NOT shard over — grads of a loss w.r.t. the result stay
    LOCAL instead of triggering shard_map autodiff's implicit psum, so the
    caller can run the cross-device sum explicitly (e.g. compressed, via
    :func:`grouped_tree_psum`). Use inside ``shard_map``."""

    def loc(p, s):
        for ax in axis_names:
            if ax not in spec_axes(s):
                p = lax.pcast(p, ax, to="varying")
        return p

    return jax.tree.map(loc, tree, specs, is_leaf=lambda x: isinstance(x, P))


def grouped_tree_psum(grads, specs, axis_names: Axes, wire_dtype=None):
    """Explicit allreduce of a gradient pytree with sharded leaves.

    Each leaf is summed over the mesh axes its spec does NOT shard over
    (replicated leaves over all axes; TP/EP/PP-sharded leaves only over the
    remaining ones). Leaves are grouped by reduce-axes and each group is
    summed by ONE ``psum`` over all its leaves: JAX emits an all-reduce per
    leaf and XLA's all-reduce combiner merges them into a few large ones
    (18 for the 404M flagship's step on four chips) — the bucketing is the
    compiler's, with no hand-built staging buffer. Only the int8 ring still
    flattens a group into one buffer (it segments by position).
    ``wire_dtype``
    (e.g. ``jnp.bfloat16``) casts each group's payload for the collective,
    halving ICI/DCN bytes — or the string ``"int8"``, which runs each
    group through the explicit int8 ring (quarter-width hops with
    per-segment scales, :func:`ring_allreduce_sum`) over each of its
    reduce axes in sequence; a multi-axis class pays one ring per axis,
    re-quantizing between them (error compounds like a longer ring).
    Results are always handed back in the leaf dtype.

    This is the sharded-param trainers' wire-compression path: the implicit
    autodiff psum (differentiating w.r.t. replicated params) cannot change
    its wire dtype, so compression requires :func:`localize_tree` + this.
    int8 callers must relax ``check_vma`` on the enclosing shard_map (the
    ring's ppermute loop erases varying-axes typing).
    """
    leaves, treedef = jax.tree.flatten(grads)
    spec_leaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    if len(spec_leaves) != len(leaves):
        raise ValueError(
            f"specs tree has {len(spec_leaves)} leaves, grads {len(leaves)}"
        )
    # the trainers pass their `compress` string straight through: "bf16"
    # maps to the half-width psum dtype here (ONE place owns the
    # compress-mode vocabulary), "int8" selects the explicit ring
    if wire_dtype == "bf16":
        wire_dtype = jnp.bfloat16
    int8 = isinstance(wire_dtype, str)
    if int8 and wire_dtype != "int8":
        raise ValueError(f"unknown wire mode {wire_dtype!r}")
    groups: dict = {}
    for i, s in enumerate(spec_leaves):
        reduce_over = tuple(a for a in axis_names if a not in spec_axes(s))
        # group by dtype too: concatenate would silently promote mixed-dtype
        # groups and hand every leaf back in the promoted type
        groups.setdefault((reduce_over, leaves[i].dtype), []).append(i)
    out: list = [None] * len(leaves)
    for (reduce_over, _), idxs in groups.items():
        if not reduce_over:  # sharded over every axis: already local-final
            for i in idxs:
                out[i] = leaves[i]
            continue
        if not int8:
            # the leaves as they are, no flat staging buffer:
            # concatenating 286M-404M gradient elements cost a copy in and
            # a copy out, and libtpu 0.0.34 laid the MoE's flat buffer out
            # as f32[N/8, 8] after its (d_model, 8) router leaf — 16x lane
            # padding, 18.3 GB, refused on a 16 GB v5e (PR 21)
            group = [leaves[i] for i in idxs]
            if wire_dtype is not None:
                group = [g.astype(wire_dtype) for g in group]
            for i, total in zip(idxs, lax.psum(group, reduce_over)):
                out[i] = total.astype(leaves[i].dtype)
            continue
        # the int8 ring segments ONE flat buffer; its hop decompression
        # accumulates in f32, so run the whole schedule there and hand
        # back the leaf dtype
        flat = jnp.concatenate([leaves[i].reshape(-1) for i in idxs])
        total = flat.astype(jnp.float32)
        for ax in reduce_over:
            total = ring_allreduce_sum(
                total, ax, lax.axis_size(ax), compress="int8"
            )
        total = total.astype(flat.dtype)
        offset = 0
        for i in idxs:
            n = leaves[i].size
            out[i] = total[offset : offset + n].reshape(leaves[i].shape)
            offset += n
    return jax.tree.unflatten(treedef, out)


def backward_psum_sync(axis_names: str | Axes, wire_dtype=None):
    """An identity whose BACKWARD masked-psums the cotangent — the
    comm/compute-overlap primitive (SURVEY.md §8.4 "Overlap").

    Wrap each param leaf with the returned ``sync(p, v)`` before the loss:
    in reverse-mode, leaf k's collective then depends ONLY on leaf k's
    backward subgraph, not on the whole gradient like a single fused psum.
    That dependence structure is what lets XLA's latency-hiding scheduler
    (TPU: async ``all-reduce-start``/``-done`` pairs) run layer k's grad
    collective while layer k-1's backward still computes. The trade is one
    collective per leaf instead of one fused launch — more dispatches,
    hideable behind compute.

    ``v`` is the scalar 0/1 contributor mask; the synced cotangent is
    ``sum_d(v_d * g_d)``, exactly the trainers' masked grad collective.
    ``wire_dtype`` (e.g. bf16) compresses each leaf's payload.

    The custom_vjp erases varying-axes typing, so enclosing shard_maps need
    ``check_vma=False`` (same caveat as the ring schedules).
    """

    @jax.custom_vjp
    def sync(p, v):
        return p

    def fwd(p, v):
        return p, v

    def bwd(res, ct):
        v = res
        masked = ct * v.astype(ct.dtype)
        if wire_dtype is not None and masked.dtype != wire_dtype:
            total = lax.psum(
                masked.astype(wire_dtype), axis_names
            ).astype(ct.dtype)
        else:
            total = lax.psum(masked, axis_names)
        return total, jnp.zeros_like(v)

    sync.defvjp(fwd, bwd)
    return sync


def ring_ef_residual(c, v, hop_err):
    """Next-step error-feedback residual for a per-hop-accounted ring
    sync: a masked device's WHOLE folded contribution carries forward
    (``c·(1−v)``), plus every quantization error this device injected
    while sending or relaying (``hop_err`` from
    ``ring_allreduce_sum(..., return_residual=True)``). One definition so
    the fused step, the accumulation step, and the per-leaf overlap sync
    can never diverge on the invariant."""
    return c * (1.0 - v.astype(c.dtype)) + hop_err.reshape(c.shape)


def backward_sync_ef(axis_names: str | Axes, wire_dtype=None):
    """:func:`backward_psum_sync` with error feedback riding the autodiff
    pass (VERDICT r4 #4a — overlap no longer excludes EF).

    ``sync(p, e, v)`` is an identity on ``p``; in reverse-mode the leaf's
    cotangent folds the residual in (``c = g + e``), the masked compressed
    payload ``cast(c·v)`` rides ONE psum inside the backward subgraph, and
    the COTANGENT RETURNED FOR ``e`` carries the new residual
    ``c − cast(c·v)`` out of the backward — so differentiating the loss
    w.r.t. (params, residuals) yields (synced grads, next residuals) in
    the same pass, preserving the per-leaf dependence structure overlap
    needs. A masked device's cotangent (v=0) sends nothing and its whole
    ``c`` carries forward, the same invariant as the fused EF path."""

    @jax.custom_vjp
    def sync(p, e, v):
        return p

    def fwd(p, e, v):
        return p, (e, v)

    def bwd(res, ct):
        e, v = res
        c = ct + e
        m = c * v.astype(c.dtype)
        if wire_dtype is not None and m.dtype != wire_dtype:
            sent = m.astype(wire_dtype)
            total = lax.psum(sent, axis_names).astype(c.dtype)
            new_e = c - sent.astype(c.dtype)
        else:
            total = lax.psum(m, axis_names)
            new_e = c - m  # lossless wire: only masking withholds
        return total, new_e, jnp.zeros_like(v)

    sync.defvjp(fwd, bwd)
    return sync


def backward_ring_sync(
    axis_name: str, axis_size: int, *, compress: str = "int8",
    error_feedback: bool = False,
):
    """Per-leaf IN-BACKWARD compressed ring — overlap × int8 (VERDICT r4
    #4a: the exclusion is gone; each leaf's cotangent rides its own
    (payload, scale) int8 ring inside its backward subgraph, exactly like
    :func:`ring_allreduce_sum` does for the fused flat buffer).

    Without EF: ``sync(p, v)``, backward = ring-allreduce of ``ct·v``.
    With EF: ``sync(p, e, v)`` — the ring's per-hop residual
    (``return_residual=True``) plus the masked-out carry comes back as
    the cotangent of ``e`` (same mechanism as :func:`backward_sync_ef`),
    so overlap × int8 × error_feedback compose too."""
    if compress not in ("bf16", "int8"):
        raise ValueError(f"ring sync needs a compress mode, got {compress!r}")

    if not error_feedback:

        @jax.custom_vjp
        def sync(p, v):
            return p

        def fwd(p, v):
            return p, v

        def bwd(v, ct):
            m = (ct * v.astype(ct.dtype)).reshape(-1)
            total = ring_allreduce_sum(
                m, axis_name, axis_size, compress=compress
            )
            return total.reshape(ct.shape).astype(ct.dtype), jnp.zeros_like(v)

        sync.defvjp(fwd, bwd)
        return sync

    @jax.custom_vjp
    def sync_ef(p, e, v):
        return p

    def fwd_ef(p, e, v):
        return p, (e, v)

    def bwd_ef(res, ct):
        e, v = res
        c = ct + e
        m = (c * v.astype(c.dtype)).reshape(-1)
        total, hop_err = ring_allreduce_sum(
            m, axis_name, axis_size, compress=compress, return_residual=True
        )
        new_e = ring_ef_residual(c, v, hop_err)
        return (
            total.reshape(ct.shape).astype(ct.dtype),
            new_e,
            jnp.zeros_like(v),
        )

    sync_ef.defvjp(fwd_ef, bwd_ef)
    return sync_ef


def backward_tree_sync(specs, axis_names: Axes, wire_dtype=None):
    """Per-leaf in-backward sync for a SHARDED params tree.

    Returns ``apply(tree_local, v)``: wraps each leaf with a
    :func:`backward_psum_sync` over the axes its spec does NOT shard (the
    same reduce-axes classes as :func:`grouped_tree_psum`), so leaf k's
    masked collective fires in leaf k's backward subgraph — the overlap
    dependence structure — while TP/EP/PP-sharded leaves still reduce over
    only their replication axes. One custom_vjp per reduce-axes class.

    The wrapped loss must NOT also multiply by ``v``: the sync masks each
    leaf's cotangent itself (``sum_d(v_d * g_d)``), and double-masking would
    square the mask. A leaf sharded over EVERY axis would silently skip that
    masking, so it is rejected loudly (no current trainer shards params over
    the data axis).
    """
    syncs: dict = {}

    def sync_for(spec):
        reduce_over = tuple(a for a in axis_names if a not in spec_axes(spec))
        if not reduce_over:
            raise ValueError(
                f"leaf spec {spec} shards over every mesh axis: its grad "
                "has no replication axes to sync over, and the in-backward "
                "mask would be skipped — overlap does not support it"
            )
        if reduce_over not in syncs:
            syncs[reduce_over] = backward_psum_sync(reduce_over, wire_dtype)
        return syncs[reduce_over]

    def apply(tree_local, v):
        return jax.tree.map(
            lambda p, s: sync_for(s)(p, v),
            tree_local,
            specs,
            is_leaf=lambda x: isinstance(x, P),
        )

    return apply


def overlap_value_and_grad(
    loss_fn,
    params,
    specs,
    axis_names: Axes,
    v,
    *,
    has_aux: bool = False,
    wire_dtype=None,
):
    """``value_and_grad`` with per-leaf IN-BACKWARD masked collectives.

    The one-call form of :func:`localize_tree` + :func:`backward_tree_sync`
    (the sibling of :func:`compressed_value_and_grad`, trading its one
    grouped launch per sharding class for overlap-capable per-leaf
    dependence). ``loss_fn`` must be UNMASKED — each leaf's sync multiplies
    its cotangent by ``v`` itself, and a ``v`` in the loss would square the
    mask. The returned loss value is LOCAL and unmasked; callers fold ``v``
    into their metric psums."""
    sync = backward_tree_sync(specs, axis_names, wire_dtype)
    params_local = localize_tree(params, specs, axis_names)

    def wrapped(pt):
        return loss_fn(sync(pt, v))

    return jax.value_and_grad(wrapped, has_aux=has_aux)(params_local)


def compressed_value_and_grad(
    loss_fn,
    params,
    specs,
    axis_names: Axes,
    *,
    has_aux: bool = False,
    wire_dtype=jnp.bfloat16,
):
    """``value_and_grad`` with an explicit wire-compressed grad collective.

    The one-call form of :func:`localize_tree` + :func:`grouped_tree_psum`
    for the sharded-param trainers: params enter the loss device-varying so
    grads stay shard-local, then each sharding class rides ONE collective
    with a ``wire_dtype`` payload. The loss value comes back LOCAL (callers
    psum it with whatever weighting their metrics need)."""
    params_local = localize_tree(params, specs, axis_names)
    out, grads = jax.value_and_grad(loss_fn, has_aux=has_aux)(params_local)
    return out, grouped_tree_psum(grads, specs, axis_names, wire_dtype)


def validate_trainer_compress(
    compress: str | None, *, overlap: bool = False
) -> str | None:
    """Shared guard for the sharded-param trainers' ``compress`` knob."""
    if compress not in (None, "bf16", "int8"):
        raise ValueError(
            f"compress must be None, 'bf16' or 'int8', got {compress!r}"
        )
    if compress == "int8" and overlap:
        raise ValueError(
            "overlap excludes compress='int8' for SHARDED-param trainers: "
            "their leaves reduce over per-sharding-class axis SETS, and "
            "the int8 ring schedule reduces over one axis (DPTrainer's "
            "1-axis mesh composes overlap with int8 via "
            "backward_ring_sync)"
        )
    return compress


def synced_value_and_grad(
    loss_fn,
    params,
    specs,
    axis_names: Axes,
    v,
    *,
    compress: str | None,
    overlap: bool,
    has_aux: bool = False,
):
    """``value_and_grad`` of a sharded-param trainer's loss, with the
    gradient summed over the contributing replicas: the ONE place that
    chooses how (``train/long_context.py``, ``moe.py``, ``pipeline.py``).

    ``loss_fn(params)`` is this device's UNMASKED loss term, already over
    the masked denominator; ``v`` is the device's contributor mask (0/1).

    - ``overlap``: per-leaf in-backward collectives
      (:func:`overlap_value_and_grad`, SURVEY.md §8.4): each leaf's sync
      masks its cotangent itself, at half width under ``compress="bf16"``.
    - otherwise the loss is multiplied by ``v`` and the gradient rides ONE
      explicit grouped collective per sharding class
      (:func:`compressed_value_and_grad`) at ``wire_dtype=compress`` — also
      when ``compress`` is None: shard_map's automatic transpose-psum for
      replicated params DOES NOT RUN under ``check_vma=False`` (the
      flash-relax configs), so relying on it would silently leave every
      device with its LOCAL gradient — found by the runtime replica assert
      (tests/test_vma_replication.py), VERDICT r4 #6.

    Either way the loss value comes back LOCAL and masked (callers psum it,
    or the statistics ``has_aux`` carries, with the weighting their metrics
    need); counts and denominators stay f32.
    """
    validate_trainer_compress(compress, overlap=overlap)

    def mask(out):
        return (out[0] * v, out[1]) if has_aux else out * v

    if overlap:
        out, grads = overlap_value_and_grad(
            loss_fn, params, specs, axis_names, v, has_aux=has_aux,
            wire_dtype=jnp.bfloat16 if compress == "bf16" else None,
        )
        return mask(out), grads
    return compressed_value_and_grad(
        lambda p: mask(loss_fn(p)), params, specs, axis_names,
        has_aux=has_aux, wire_dtype=compress,
    )


def expand_counts(
    count: jax.Array, data_size: int, bucket_size: int | None
) -> jax.Array:
    """Expand a per-bucket count vector to per-element counts of ``data_size``."""
    if count.ndim == 0:
        return jnp.full((data_size,), count)
    return jnp.repeat(count, bucket_size)[:data_size]


def _staged_psum(
    masked: jax.Array,
    valid: jax.Array,
    axis_names: Axes,
    wire_dtype=None,
) -> tuple[jax.Array, jax.Array]:
    """Butterfly: reduce one grid axis at a time (dim-0 sink feeds dim-1 source,
    SURVEY.md §4.3) — the masked payload and, beside it, the mask itself.
    Numerically equals the fused psum; structurally it is the reference's
    staged grid round and lets each stage ride a different ICI axis.
    ``wire_dtype`` (e.g. bf16) compresses each stage's collective payload;
    counts always ride float32 (see :func:`masked_psum`)."""
    total = masked
    count = jnp.asarray(valid, dtype=jnp.float32)
    for name in axis_names:
        if wire_dtype is not None and total.dtype != wire_dtype:
            total = lax.psum(total.astype(wire_dtype), name).astype(masked.dtype)
        else:
            total = lax.psum(total, name)
        count = lax.psum(count, name)
    return total, count


def _compress_seg(seg: jax.Array, mode: str) -> tuple[jax.Array, jax.Array]:
    """Quantize one ring segment for the wire: (payload, scale).

    ``bf16``: truncate mantissa, scale unused (sent as 1.0 to keep one code
    path). ``int8``: symmetric per-segment max-abs scaling — the classic
    gradient-compression scheme; an all-zero segment maps to scale 1 so the
    dequantize never divides by zero.
    """
    if mode == "bf16":
        return seg.astype(jnp.bfloat16), jnp.ones((), jnp.float32)
    from akka_allreduce_tpu.ops.ring import int8_quantize

    return int8_quantize(seg)


def _decompress_seg(payload: jax.Array, scale: jax.Array, mode: str) -> jax.Array:
    if mode == "bf16":
        return payload.astype(jnp.float32)
    return payload.astype(jnp.float32) * scale


def _compressed_hop(
    block, axis_name: str, fwd, compress: str | None, *, with_sent=False
):
    """One ring hop: (optionally compress,) ppermute(, decompress).

    THE compress-then-send protocol — every ring stage that quantizes a
    FRESH value for the wire (reduce-scatter steps, the reduce-scatter
    alignment hop) moves payloads through here, so a change to the wire
    format happens exactly once; the all-gather phase, which FORWARDS an
    already-quantized (payload, scale) pair without requantizing, rides
    the sibling :func:`_forward_hop`. int8 rides a second ppermute for
    the per-segment scale; bf16 has no scale to carry.

    ``with_sent=True`` additionally returns the SENDER's local
    reconstruction of what the receiver will decode (``block`` itself when
    uncompressed) — ``block - sent`` is exactly the quantization error
    this hop injects, the quantity per-hop error feedback re-sends next
    round (VERDICT r4 #4c).
    """
    if compress is None:
        recv = lax.ppermute(block, axis_name, fwd)
        return (recv, block) if with_sent else recv
    payload, scale = _compress_seg(block, compress)
    sent = _decompress_seg(payload, scale, compress)
    payload = lax.ppermute(payload, axis_name, fwd)
    if compress == "int8":
        scale = lax.ppermute(scale, axis_name, fwd)
    recv = _decompress_seg(payload, scale, compress)
    return (recv, sent) if with_sent else recv


def _forward_hop(payload, scale, axis_name: str, fwd, compress: str):
    """One FORWARD-ONLY ring hop of an already-quantized segment: the
    (payload, scale) pair moves unchanged — no requantization, so every
    device eventually dequantizes identical inputs (the bit-exact
    all-gather). bf16 carries no scale, so its dummy scale is not
    permuted."""
    payload = lax.ppermute(payload, axis_name, fwd)
    if compress == "int8":
        scale = lax.ppermute(scale, axis_name, fwd)
    return payload, scale


def _rs_phase(segs, idx, n: int, axis_name: str, fwd, compress):
    """The shared ring reduce-scatter phase: ``n - 1`` hops, each sending
    this device's current partial of a rotating segment and accumulating
    the neighbor's, with the per-hop quantization error recorded at the
    segment it affected (the residual both ring collectives return for
    per-hop error feedback). Returns ``(segs, errs)``; after it, device
    ``i`` owns fully-reduced segment ``(i + 1) mod n``."""

    def rs_step(s, carry):
        segs, errs = carry
        send_i = jnp.mod(idx - s, n)
        block = lax.dynamic_slice_in_dim(segs, send_i, 1, axis=0)
        recv, sent = _compressed_hop(
            block, axis_name, fwd, compress, with_sent=True
        )
        errs = lax.dynamic_update_slice_in_dim(
            errs, block - sent, send_i, axis=0
        )
        recv_i = jnp.mod(idx - s - 1, n)
        cur = lax.dynamic_slice_in_dim(segs, recv_i, 1, axis=0)
        return (
            lax.dynamic_update_slice_in_dim(segs, cur + recv, recv_i, axis=0),
            errs,
        )

    return lax.fori_loop(
        0, n - 1, rs_step, (segs, jnp.zeros_like(segs))
    )


def ring_allreduce_sum(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    *,
    compress: str | None = None,
    return_residual: bool = False,
):
    """Explicit bidirectional-naive ring allreduce of ``x`` over ``axis_name``.

    Reduce-scatter then all-gather via ``ppermute``, each in ``axis_size - 1``
    steps — the reference's ring schedule for large buffers (BASELINE.json:9)
    expressed as a compiled XLA loop. Payload is padded to ``axis_size`` equal
    segments.

    ``compress`` ("bf16" | "int8") quantizes every reduce-scatter hop's
    payload, halving (bf16) or quartering (int8) the bytes each ICI/DCN
    transfer moves while accumulation stays float32. Partial sums are
    re-quantized per RS hop, so the error grows ~linearly in ring length —
    the standard compressed-ring trade. The reduced segment is quantized
    ONCE more by its owner, and the all-gather phase FORWARDS that
    (payload, scale) pair unchanged, so every device dequantizes
    identical inputs: the result is bit-identical across the ring for
    both modes (round 5 — the earlier re-quantizing gather drifted
    devices ~1 ulp apart).

    ``return_residual=True`` (VERDICT r4 #4c — per-hop error feedback)
    additionally returns this device's locally-computable injected
    quantization error: for every reduce-scatter hop the error of the
    partial sum it SENT (``block - dequantize(quantize(block))``), plus the
    owner's final-requantization error of its reduced segment, scattered
    back to the segment positions they affected. By telescoping, the f32
    ring result minus the compressed ring result equals the SUM of all
    devices' residuals per element (the forwarding gather adds no error
    of its own). A trainer that folds this residual into its next
    contribution compensates the per-hop noise the first-hop-only
    residual cannot see — including error a MASKED device injects while
    relaying others' partial sums. Requires ``compress``.
    """
    n = axis_size
    if return_residual and compress is None:
        raise ValueError("return_residual needs a compress mode")
    if n == 1:
        return (x, jnp.zeros_like(x)) if return_residual else x
    if compress not in (None, "bf16", "int8"):
        raise ValueError(f"unknown compress mode {compress!r}")
    data = x.shape[0]
    seg = math.ceil(data / n)
    segs = jnp.pad(x, (0, n * seg - data)).reshape(n, seg)
    idx = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    segs, errs = _rs_phase(segs, idx, n, axis_name, fwd, compress)
    # device i now owns fully-reduced segment (i + 1) mod n

    if compress is not None:
        # one final quantization of the reduced segment; the gather then
        # FORWARDS the (payload, scale) pair unchanged — no per-hop
        # requantization in the all-gather phase, so every device
        # dequantizes identical inputs and the result is BIT-IDENTICAL
        # across the ring (the pre-round-5 re-quantizing gather drifted
        # devices ~1 ulp apart per step, caught by the runtime replica
        # assert in tests/test_vma_replication.py). The owner's final
        # quantization error is the last term of the residual.
        own_i = jnp.mod(idx + 1, n)
        own = lax.dynamic_slice_in_dim(segs, own_i, 1, axis=0)
        payload, scale = _compress_seg(own, compress)
        own_q = _decompress_seg(payload, scale, compress)
        prev = lax.dynamic_slice_in_dim(errs, own_i, 1, axis=0)
        errs = lax.dynamic_update_slice_in_dim(
            errs, prev + (own - own_q), own_i, axis=0
        )
        payloads = jnp.zeros((n,) + payload.shape[1:], payload.dtype)
        payloads = lax.dynamic_update_slice_in_dim(
            payloads, payload, own_i, axis=0
        )
        scales = jnp.zeros((n,), jnp.float32)
        scales = lax.dynamic_update_slice_in_dim(
            scales, scale.reshape(1), own_i, axis=0
        )

        def ag_step_q(s, carry):
            payloads, scales = carry
            send_i = jnp.mod(idx + 1 - s, n)
            block = lax.dynamic_slice_in_dim(payloads, send_i, 1, axis=0)
            sc = lax.dynamic_slice_in_dim(scales, send_i, 1, axis=0)
            recv_p, recv_s = _forward_hop(block, sc, axis_name, fwd, compress)
            recv_i = jnp.mod(idx - s, n)
            return (
                lax.dynamic_update_slice_in_dim(
                    payloads, recv_p, recv_i, axis=0
                ),
                lax.dynamic_update_slice_in_dim(
                    scales, recv_s, recv_i, axis=0
                ),
            )

        payloads, scales = lax.fori_loop(
            0, n - 1, ag_step_q, (payloads, scales)
        )
        segs = _decompress_seg(payloads, scales[:, None], compress)
    else:

        def ag_step(s, segs):
            send_i = jnp.mod(idx + 1 - s, n)
            block = lax.dynamic_slice_in_dim(segs, send_i, 1, axis=0)
            recv = _compressed_hop(block, axis_name, fwd, compress)
            recv_i = jnp.mod(idx - s, n)
            return lax.dynamic_update_slice_in_dim(segs, recv, recv_i, axis=0)

        segs = lax.fori_loop(0, n - 1, ag_step, segs)
    out = segs.reshape(-1)[:data]
    if return_residual:
        return out, errs.reshape(-1)[:data]
    return out


def ring_reduce_scatter_sum(
    x: jax.Array,
    axis_name: str,
    axis_size: int,
    *,
    compress: str | None = None,
    return_residual: bool = False,
):
    """Ring REDUCE-SCATTER of ``x`` over ``axis_name``: device ``i``
    returns the fully-reduced segment ``i`` (shape ``(ceil(data/n),)``,
    zero-padded tail when ``data % n != 0``).

    The reduce half of :func:`ring_allreduce_sum` — same per-hop
    ``compress`` ("bf16" | "int8" with per-segment scales on a second
    ppermute), same per-hop requantization trade — plus one final
    (compressed) hop that moves each reduced segment from its ring owner
    ``(i+1) mod n`` back to device ``i``, aligning with the tiled
    ``all_gather`` layout whose transpose this implements (FSDP's int8
    backward — VERDICT r3 next-round #7b).

    ``return_residual=True`` mirrors :func:`ring_allreduce_sum`'s per-hop
    error-feedback accounting (VERDICT r4 #4c): the second output is this
    device's FULL-length ``(n*seg,)`` injected quantization error — its
    reduce-scatter hop errors plus the alignment hop's requantization of
    the segment it owned — positioned at the elements they affected. The
    f32 reduce-scatter of the residuals equals the f32 result minus the
    compressed result, segment by segment. Requires ``compress``.
    """
    n = axis_size
    data = x.shape[0]
    seg = math.ceil(data / n)
    if return_residual and compress is None:
        raise ValueError("return_residual needs a compress mode")
    if n == 1:
        out = jnp.pad(x, (0, seg * n - data))
        return (out, jnp.zeros_like(out)) if return_residual else out
    if compress not in (None, "bf16", "int8"):
        raise ValueError(f"unknown compress mode {compress!r}")
    segs = jnp.pad(x, (0, n * seg - data)).reshape(n, seg)
    idx = lax.axis_index(axis_name)
    fwd = [(i, (i + 1) % n) for i in range(n)]
    segs, errs = _rs_phase(segs, idx, n, axis_name, fwd, compress)
    # device i owns reduced segment (i + 1) mod n; one more hop hands
    # segment j to device j
    own_i = jnp.mod(idx + 1, n)
    own = lax.dynamic_slice_in_dim(segs, own_i, 1, axis=0)
    out, sent = _compressed_hop(
        own, axis_name, fwd, compress, with_sent=True
    )
    if return_residual:
        errs = lax.dynamic_update_slice_in_dim(
            errs, own - sent, own_i, axis=0
        )
        return out.reshape(-1), errs.reshape(-1)
    return out.reshape(-1)


# --------------------------------------------------------------------------
# Host-facing jitted collective
# --------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AllreduceResult:
    """Mirror of the sink payload (protocol.AllReduceOutput) on device."""

    sum: jax.Array  # (data,) — masked sum across contributors
    count: jax.Array  # (data,) — per-element contributor count

    def average(self) -> jax.Array:
        return self.sum / jnp.maximum(self.count, 1.0)


_CACHE_MAX = 64
_CACHE: OrderedDict = OrderedDict()


def build_threshold_allreduce(
    mesh: Mesh,
    *,
    axes: str | Sequence[str] | None = None,
    bucket_size: int | None = None,
    schedule: str = "psum",
    donate: bool = True,
    compress: str | None = None,
):
    """Build a jitted ``(xs, valid) -> (sum, count)`` collective over ``mesh``.

    ``xs`` has shape ``(n_devices, data)`` sharded on its first dim across all
    of ``axes``; ``valid`` is ``(n_devices,)`` (whole-payload mask) or
    ``(n_devices, n_buckets)`` (per-chunk mask). Outputs are replicated.

    The mask's rank picks how it is applied, on every schedule. A
    whole-payload mask (with ``bucket_size`` or without: only the count is
    expanded) goes through :func:`mask_zero_inplace`: with ``donate=True`` a
    live device's payload is not touched and a masked device's is overwritten
    with zeros, so a masked device may hold NaN or Inf and the sum stays
    finite. ``valid`` is 0.0 or 1.0 (module docstring); a value that is
    neither cannot raise, being traced: the payload is zeroed on
    ``valid == 0`` and left alone otherwise, ``count`` stays ``psum(valid)``,
    so a fractional weight no longer scales the payload here. A per-bucket
    mask multiplies, bucket by bucket, as :func:`masked_psum` does (and there
    a weight still scales, and ``0 * nan`` is still ``nan``). With
    ``donate=False`` the caller's buffer is left as it was: XLA copies it in
    front of the in-place mask, which costs what the multiply did.

    ``compress`` trades precision for wire bytes on bandwidth-bound syncs:
    ``"bf16"`` runs the psum/butterfly collective in bfloat16 (or bf16 ring
    hops), halving ICI/DCN traffic; ``"int8"`` (ring only — a summed int8
    collective has no shared scale) quarters it with per-segment max-abs
    scaling. Counts always stay float32, so threshold semantics are exact.
    """
    axis_names = _normalize_axes(mesh, axes)
    if set(axis_names) != set(mesh.axis_names):
        raise ValueError(
            "host-facing allreduce reduces over the full mesh (output is "
            f"replicated); got axes {axis_names} of {mesh.axis_names}. For "
            "partial-axis reduction call masked_psum inside your own shard_map."
        )
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    if schedule not in ("psum", "butterfly", "ring", "pallas_ring"):
        raise ValueError(f"unknown schedule {schedule!r}")
    if schedule == "butterfly" and len(axis_names) < 2:
        raise ValueError("butterfly schedule needs a 2D grid mesh")
    if schedule in ("ring", "pallas_ring") and len(axis_names) != 1:
        raise ValueError("ring schedules reduce over exactly one axis")
    if compress not in (None, "bf16", "int8"):
        raise ValueError(f"unknown compress mode {compress!r}")
    if compress == "int8" and schedule not in ("ring", "pallas_ring"):
        raise ValueError(
            "int8 compression needs per-hop scales: only the ring schedules "
            "carry them (psum/butterfly sum on the wire)"
        )

    spec_in = P(axis_names if len(axis_names) > 1 else axis_names[0])

    wire_dtype = jnp.bfloat16 if compress else None

    def kernel(xs, valid):
        x = xs.reshape(xs.shape[-1])  # (1, data) block -> (data,)
        data_size = x.shape[0]
        if valid.ndim > 1:  # (1, n_buckets) block -> per-bucket mask
            if bucket_size is None:
                raise ValueError("per-bucket valid mask requires bucket_size")
            v = valid.reshape(valid.shape[1:])
            _MASK_MULTIPLY_BUILDS.inc()
            masked = _multiply_mask(x, v.astype(x.dtype), bucket_size)
        else:  # (1,) block -> whole-payload scalar mask, bucket_size or not
            v = valid.reshape(())
            _MASK_INPLACE_BUILDS.inc()
            masked = mask_zero_inplace(x, v)
        if schedule in ("ring", "pallas_ring"):
            if schedule == "pallas_ring":
                from akka_allreduce_tpu.ops.ring import (
                    _DEF_SEG_ROWS,
                    LANE,
                    pallas_ring_allreduce_sum,
                )

                # max_chunk_size doubles as the kernel's VMEM staging size:
                # one ring step moves bucket_size/n elements per neighbor
                seg_rows = (
                    max(1, bucket_size // (n_devices * LANE))
                    if bucket_size is not None
                    else _DEF_SEG_ROWS
                )
                total = pallas_ring_allreduce_sum(
                    masked, axis_names[0], n_devices, seg_rows=seg_rows,
                    compress=compress,
                    # decide interpret mode by the MESH's platform, not the
                    # process default backend: with the TPU plugin loaded a
                    # virtual CPU mesh still reports default_backend()=="tpu"
                    interpret=mesh.devices.flat[0].platform != "tpu",
                )
            else:
                total = ring_allreduce_sum(
                    masked, axis_names[0], n_devices, compress=compress
                )
            count = lax.psum(jnp.asarray(v, x.dtype), axis_names)
        elif schedule == "butterfly":
            total, count = _staged_psum(masked, v, axis_names, wire_dtype)
        else:
            # through the module's `masked_psum`, so whatever stands in that
            # name's place wraps this schedule's payload collective too; the
            # payload is masked already, so its weight here is the constant 1
            # (XLA folds the multiply away: tests/test_aot_tpu_compile.py) and
            # the count is the real mask's
            total, _ = masked_psum(
                masked, jnp.ones((), jnp.float32), axis_names,
                wire_dtype=wire_dtype,
            )
            count = lax.psum(jnp.asarray(v, jnp.float32), axis_names)
        return total, expand_counts(count, data_size, bucket_size)

    mapped = jax.shard_map(
        kernel,
        mesh=mesh,
        in_specs=(spec_in, spec_in),
        out_specs=(P(), P()),
        # The rings' all-gather produces a replicated result, but the static
        # varying-axes check cannot prove it; the numeric tests do.
        check_vma=(schedule not in ("ring", "pallas_ring")),
    )
    return jax.jit(mapped, donate_argnums=(0,) if donate else ())


def threshold_allreduce(
    mesh: Mesh,
    xs,
    valid=None,
    *,
    axes: str | Sequence[str] | None = None,
    bucket_size: int | None = None,
    schedule: str = "psum",
    compress: str | None = None,
) -> AllreduceResult:
    """Convenience entry: threshold-masked allreduce of per-device payloads.

    ``xs``: ``(n_devices, data)`` (host or device). ``valid``: None (all
    contribute), ``(n_devices,)``, or ``(n_devices, n_buckets)``.
    ``compress``: None | "bf16" | "int8" — see :func:`build_threshold_allreduce`.

    Never donates ``xs``, so the caller's array stays readable and unchanged;
    the in-place mask then works on XLA's copy of it (no worse than the
    multiply it replaced). A round loop that owns its payloads builds the
    function once with ``donate=True``.
    """
    axis_names = _normalize_axes(mesh, axes)
    key = (mesh, axis_names, bucket_size, schedule, compress)
    if key not in _CACHE:
        # full-mesh-axes validation happens inside the build
        _CACHE[key] = build_threshold_allreduce(
            mesh,
            axes=axis_names,
            bucket_size=bucket_size,
            schedule=schedule,
            compress=compress,
            # never donate here: the caller may hand us an already-correctly-
            # sharded device array that device_put returns unchanged, and the
            # convenience API must not invalidate the caller's buffer
            donate=False,
        )
        if len(_CACHE) > _CACHE_MAX:
            _CACHE.popitem(last=False)
    else:
        _CACHE.move_to_end(key)
    fn = _CACHE[key]
    n_devices = int(np.prod([mesh.shape[a] for a in axis_names]))
    xs = jnp.asarray(xs, dtype=jnp.float32)
    if xs.ndim != 2 or xs.shape[0] != n_devices:
        raise ValueError(
            f"xs must be (n_devices={n_devices}, data), got {xs.shape}"
        )
    if valid is None:
        valid = jnp.ones((n_devices,), dtype=jnp.float32)
    valid = jnp.asarray(valid, dtype=jnp.float32)
    spec = P(axis_names if len(axis_names) > 1 else axis_names[0])
    xs = jax.device_put(xs, NamedSharding(mesh, spec))
    valid = jax.device_put(valid, NamedSharding(mesh, spec))
    total, count = fn(xs, valid)
    return AllreduceResult(sum=total, count=count)

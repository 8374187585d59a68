"""Mixture-of-experts dispatch: Switch-style top-1 routing + expert-parallel
all-to-all.

The reference has no model parallelism of any kind (SURVEY.md §3 — DP is its
entire point); MoE/EP is a beyond-parity capability of the TPU rebuild, built
the TPU way:

- **static shapes**: routing uses a fixed per-(device, expert) capacity
  ``C = ceil(T_local * capacity_factor / n_experts)``; overflow tokens are
  dropped (their residual path passes through untouched) — the Switch
  Transformer discipline, which keeps every einsum MXU-shaped and lets XLA
  compile one program regardless of routing decisions;
- **dispatch is matmul**: tokens move into expert slots via one-hot
  einsums, not gathers — exactly what the MXU is good at;
- **EP = all_to_all over a mesh axis**: with experts sharded over
  ``expert_axis`` (ep devices x E/ep experts each), one ``lax.all_to_all``
  carries every device's per-expert slot block to the expert's owner and a
  second one brings outputs back — the standard a2a pair riding ICI.

All functions are pure and shard_map-compatible; the dense (no-EP) path is
the oracle the EP path is tested against.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax


class RouteResult(NamedTuple):
    dispatch: jax.Array  # (T, E, C) one-hot token->slot assignment
    combine: jax.Array  # (T, E, C) dispatch scaled by the router gate
    aux_loss: jax.Array  # scalar Switch load-balancing loss
    dropped: jax.Array  # fraction of (token, choice) ASSIGNMENTS past
    # capacity — denominator k*T, so under top-2 a secondary-only drop
    # contributes half what losing a token entirely would


class RouteIndices(NamedTuple):
    """Index-form routing decision — O(T·k), never materializes (T, E, C)."""

    idx: jax.Array  # (T, k) int32 chosen expert per rank
    slot: jax.Array  # (T, k) int32 queue position within the expert (clamped)
    keep: jax.Array  # (T, k) float32 1.0 iff the assignment fit in capacity
    gates: jax.Array  # (T, k) float32 router gate per kept assignment
    aux_loss: jax.Array  # scalar Switch load-balancing loss
    dropped: jax.Array  # dropped assignments / (k * T)


def switch_route(
    logits: jax.Array, capacity: int
) -> RouteResult:
    """Top-1 (Switch) routing with static capacity.

    ``logits``: (T, E) router scores for T tokens over E experts.
    ``capacity``: max tokens per expert (this device's contribution).
    """
    return topk_route(logits, capacity, k=1)


def route_indices(
    logits: jax.Array, capacity: int, k: int = 1
) -> RouteIndices:
    """Top-k routing with static capacity, in index form (k=1 Switch,
    k=2 GShard).

    Each token is dispatched to its ``k`` highest-scoring experts with gates
    renormalized over the chosen k. Expert queue slots are assigned rank-
    major (every token's primary choice takes slots before any secondary
    choice — the GShard priority discipline), so under capacity pressure
    secondary assignments drop first. ``dropped`` counts dropped
    (token, choice) pairs as a fraction of all ``k * T`` assignments.

    This is the single source of routing truth: both the one-hot einsum
    dispatch (:func:`topk_route`, the small-shape oracle) and the
    scatter/gather dispatch (the large-shape fast path) consume it.
    """
    t, e = logits.shape
    if not 1 <= k <= e:
        raise ValueError(f"need 1 <= k <= {e} experts, got {k}")
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    gate_vals, idx = lax.top_k(probs, k)  # (T, k)
    if k == 1:
        gates = gate_vals  # Switch: raw router probability scales the output
    else:
        # GShard: renormalize over the chosen k so the mix sums to 1
        gates = gate_vals / jnp.maximum(
            gate_vals.sum(axis=-1, keepdims=True), 1e-9
        )
    slots = []
    keeps = []
    kept = jnp.float32(0.0)
    base = jnp.zeros((e,), jnp.float32)  # slots consumed by earlier ranks
    for r in range(k):
        onehot = jax.nn.one_hot(idx[:, r], e, dtype=jnp.float32)  # (T, E)
        # position within this rank's queue, offset by earlier ranks' fill
        within = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (T, E)
        pos_t = (within + base[None, :] * onehot).sum(axis=-1)  # (T,)
        keep = (pos_t < capacity).astype(jnp.float32)
        slots.append(jnp.minimum(pos_t, capacity - 1).astype(jnp.int32))
        keeps.append(keep)
        kept = kept + keep.sum()
        base = base + onehot.sum(axis=0)
    # Switch/GShard aux loss on the PRIMARY assignment: E * sum_e f_e * P_e
    primary = jax.nn.one_hot(idx[:, 0], e, dtype=jnp.float32)
    aux = e * jnp.sum(primary.mean(axis=0) * probs.mean(axis=0))
    dropped = 1.0 - kept / (k * t)
    return RouteIndices(
        idx,
        jnp.stack(slots, axis=1),
        jnp.stack(keeps, axis=1),
        gates,
        aux,
        dropped,
    )


def _dense_route_from_indices(
    r: RouteIndices, n_experts: int, capacity: int
) -> tuple[jax.Array, jax.Array]:
    """(T, E, C) one-hot dispatch/combine tensors from index-form routing."""
    t, k = r.idx.shape
    dispatch = jnp.zeros((t, n_experts, capacity), jnp.float32)
    combine = jnp.zeros((t, n_experts, capacity), jnp.float32)
    for rank in range(k):
        d_r = (
            jax.nn.one_hot(
                r.idx[:, rank], n_experts, dtype=jnp.float32
            )[:, :, None]
            * jax.nn.one_hot(r.slot[:, rank], capacity)[:, None, :]
            * r.keep[:, rank, None, None]
        )  # (T, E, C)
        dispatch = dispatch + d_r
        combine = combine + d_r * r.gates[:, rank, None, None]
    return dispatch, combine


def topk_route(logits: jax.Array, capacity: int, k: int = 2) -> RouteResult:
    """Dense (T, E, C) one-hot form of :func:`route_indices` — the oracle
    the scatter path is tested against; only viable at small T·E·C."""
    _, e = logits.shape
    r = route_indices(logits, capacity, k)
    dispatch, combine = _dense_route_from_indices(r, e, capacity)
    return RouteResult(dispatch, combine, r.aux_loss, r.dropped)


def dispatch_scatter(
    x: jax.Array, route: RouteIndices, n_experts: int, capacity: int
) -> jax.Array:
    """Move tokens into expert slots by scatter-add: (T, d) -> (E, C, d).

    Slot positions are unique per (expert, slot) by construction (rank-major
    cumulative fill), so the scatter has no collisions; dropped assignments
    are sent to an out-of-range index and discarded by ``mode="drop"``.
    O(T·k·d) memory traffic vs the einsum path's 2·T·E·C·d FLOPs — the
    difference between ~0.7 TFLOP and ~64 MB per layer at the flagship
    bench shape (T=16384, E=8, C=2560, d=1024).
    """
    t, d = x.shape
    k = route.idx.shape[1]
    flat = route.idx * capacity + route.slot  # (T, k)
    oob = jnp.int32(n_experts * capacity)
    tgt = jnp.where(route.keep > 0, flat.astype(jnp.int32), oob)
    src = jnp.broadcast_to(x[:, None, :], (t, k, d)).reshape(t * k, d)
    slots = jnp.zeros((n_experts * capacity, d), x.dtype)
    slots = slots.at[tgt.reshape(-1)].add(src, mode="drop")
    return slots.reshape(n_experts, capacity, d)


def combine_gather(
    ys: jax.Array, route: RouteIndices, capacity: int
) -> jax.Array:
    """Bring expert outputs back to their tokens: (E, C, d) -> (T, d).

    The gather transpose of :func:`dispatch_scatter`; each token mixes its
    k kept slots weighted by the router gates (dropped assignments carry
    weight 0, so the clamped out-of-range gather contributes nothing).
    """
    e, c, d = ys.shape
    flat = ys.reshape(e * c, d)
    tgt = (route.idx * capacity + route.slot).astype(jnp.int32)  # (T, k)
    g = jnp.take(
        flat, tgt.reshape(-1), axis=0, mode="clip"
    ).reshape(*tgt.shape, d)  # (T, k, d)
    w = (route.gates * route.keep).astype(ys.dtype)
    return (g * w[..., None]).sum(axis=1)


def expert_ffn(xs: jax.Array, w1, b1, w2) -> jax.Array:
    """Batched per-expert 2-layer MLP: (E_local, N, d) -> (E_local, N, d)."""
    h = jnp.einsum("end,edh->enh", xs, w1) + b1[:, None, :]
    h = jax.nn.gelu(h)
    return jnp.einsum("enh,ehd->end", h, w2)


def moe_dispatch_compute(
    x: jax.Array,
    router_w: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    *,
    n_experts: int,
    capacity_factor: float = 1.25,
    expert_axis: str | None = None,
    router_topk: int = 1,
    seq_axis: str | None = None,
    dispatch_impl: str = "auto",
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Route ``x`` (T, d) through the expert MLPs; returns (y, aux, dropped).

    Expert weights are LOCAL shards: ``w1`` is (E/ep, d, hidden) when
    ``expert_axis`` names an ep-sized mesh axis (run inside shard_map), or the
    full (E, d, hidden) dense form when ``expert_axis`` is None.
    ``router_topk``: 1 = Switch, 2 = GShard top-2 (capacity scales with k so
    the same capacity_factor means the same slack per assignment).
    ``seq_axis``: under sequence parallelism the aux statistics (fraction
    routed, mean router prob) are psum-averaged over the seq shards, so the
    load-balancing loss is computed over the GLOBAL token population — the
    bilinear E·Σf·p of per-shard means would depend on the partition.
    ``dispatch_impl``: ``"einsum"`` moves tokens via (T, E, C) one-hot
    matmuls (the original GShard form — MXU-shaped but O(T·E·C·d) FLOPs and
    a materialized (T, E, C) tensor), ``"scatter"`` via scatter-add/gather
    (O(T·k·d) traffic), ``"auto"`` picks scatter once the one-hot tensor
    would exceed ~2²² elements. Both compute the identical routing
    (:func:`route_indices`); they differ only in data movement.
    """
    t = x.shape[0]
    capacity = max(
        1, -(-int(t * capacity_factor) * router_topk // n_experts)
    )
    if dispatch_impl not in ("auto", "einsum", "scatter"):
        raise ValueError(f"unknown {dispatch_impl=}")
    if dispatch_impl == "auto":
        dispatch_impl = (
            "scatter" if t * n_experts * capacity > (1 << 22) else "einsum"
        )
    # routing numerics (softmax/cumsum) stay float32; the heavy einsums below
    # run in x's dtype so bf16 compute flows through the expert path
    logits = x.astype(jnp.float32) @ router_w  # (T, E) — router always full E
    route_idx = route_indices(logits, capacity, k=router_topk)
    aux = route_idx.aux_loss
    if seq_axis is not None:
        probs = jax.nn.softmax(logits, axis=-1)
        primary = jax.nn.one_hot(
            jnp.argmax(probs, axis=-1), n_experts, dtype=jnp.float32
        )
        t_global = lax.psum(jnp.float32(t), seq_axis)
        f = lax.psum(primary.sum(axis=0), seq_axis) / t_global
        p = lax.psum(probs.sum(axis=0), seq_axis) / t_global
        aux = n_experts * jnp.sum(f * p)
    w1, b1, w2 = (w.astype(x.dtype) for w in (w1, b1, w2))
    # tokens -> per-expert slots: (E, C, d)
    if dispatch_impl == "scatter":
        slots = dispatch_scatter(x, route_idx, n_experts, capacity)
    else:
        dispatch, combine = _dense_route_from_indices(
            route_idx, n_experts, capacity
        )
        slots = jnp.einsum("tec,td->ecd", dispatch.astype(x.dtype), x)
    if expert_axis is None:
        ys = expert_ffn(slots, w1, b1, w2)  # dense: all experts local
    else:
        ep = lax.psum(1, expert_axis)
        e_local = n_experts // ep
        c = slots.shape[1]
        d = slots.shape[2]
        # (E, C, d) -> exchange so each device holds ITS experts' slots from
        # every peer: tiled a2a splits dim 0 into ep blocks of e_local
        inbound = lax.all_to_all(
            slots, expert_axis, split_axis=0, concat_axis=0, tiled=True
        )  # (ep * e_local, C, d): block p = peer p's slots for my experts
        inbound = inbound.reshape(ep, e_local, c, d).transpose(1, 0, 2, 3)
        inbound = inbound.reshape(e_local, ep * c, d)
        outbound = expert_ffn(inbound, w1, b1, w2)
        outbound = outbound.reshape(e_local, ep, c, d).transpose(1, 0, 2, 3)
        outbound = outbound.reshape(ep * e_local, c, d)
        ys = lax.all_to_all(
            outbound, expert_axis, split_axis=0, concat_axis=0, tiled=True
        )  # back at the source device, (E, C, d)
    if dispatch_impl == "scatter":
        y = combine_gather(ys, route_idx, capacity)
    else:
        y = jnp.einsum("tec,ecd->td", combine.astype(x.dtype), ys)
    return y, aux, route_idx.dropped


# -- dropless routing over a held subset of the experts -----------------------
#
# The published form of sigmoid-scored top-k routing with NO capacity: every
# assignment to an expert this device holds is computed, whatever the
# imbalance. The layer is told which contiguous range of the experts it
# holds, routes over ALL of them, and computes its own experts' part of the
# result; what the absent experts would add is left out (with ep == 1 there
# is no exchange and nothing stands in for the absent devices).


class HeldRoute(NamedTuple):
    """A routing decision laid out for the held experts' grouped products."""

    selected: jax.Array  # (T, k) int32 expert ids over all E, best first
    weights: jax.Array  # (T, k) float32, normalised over all k selected
    order: jax.Array  # (T*k,) int32: sorted row -> flat (token, choice)
    inverse: jax.Array  # (T*k,) int32: flat (token, choice) -> sorted row
    group_sizes: jax.Array  # (H+1,) int32 rows per held expert; last: absent


def sigmoid_topk_route(
    logits: jax.Array,
    select_bias: jax.Array | None,
    k: int,
    *,
    renormalise: bool = True,
    scale: float = 1.0,
) -> tuple[jax.Array, jax.Array]:
    """``p = sigmoid(logits)``; the ``k`` experts with the largest
    ``p + select_bias`` are selected (ties: the lower index), and weighed by
    ``p`` alone: the bias picks but does not weigh. Returns
    ``(selected, weights)``, each (T, k); everything float32."""
    p = jax.nn.sigmoid(logits.astype(jnp.float32))
    score = p if select_bias is None else p + select_bias.astype(jnp.float32)
    _, selected = lax.top_k(score, k)
    w = jnp.take_along_axis(p, selected, axis=-1)
    if renormalise:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-6)
    return selected.astype(jnp.int32), w * scale


def held_route(
    selected: jax.Array, weights: jax.Array, held_first: int, held_count: int
) -> HeldRoute:
    """Sort the (token, choice) assignments expert-major: the rows of held
    expert 0 first, then 1, ..., and the assignments to absent experts
    last. The row buffer is all T*k assignments — the worst case, every
    token choosing only held experts — so nothing can overflow."""
    local = selected.reshape(-1) - held_first
    key = jnp.where((local >= 0) & (local < held_count), local, held_count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    # a histogram by comparison: a scatter-add of T*k ones serialises on a TPU
    sizes = (key[:, None] == jnp.arange(held_count + 1)).sum(axis=0, dtype=jnp.int32)
    return HeldRoute(selected, weights, order, inverse, sizes)


@jax.custom_vjp
def permute_rows(rows: jax.Array, perm: jax.Array, inverse: jax.Array):
    """``rows[perm]`` for a permutation whose inverse is known: the
    transpose is the gather by ``inverse``, not a scatter-add."""
    return jnp.take(rows, perm, axis=0)


def _permute_fwd(rows, perm, inverse):
    return jnp.take(rows, perm, axis=0), (perm, inverse)


def _permute_bwd(res, g):
    perm, inverse = res
    return jnp.take(g, inverse, axis=0), None, None


permute_rows.defvjp(_permute_fwd, _permute_bwd)

#: (rows, contracted, out) tiles of the megablox kernels: of those swept on
#: the v5e at the LFM2 cell's shapes (PERF.md, PR 28) the fastest with an
#: eighth of the buffer filled, and within 8 % of the fastest when it is full
GMM_TILING = (512, 512, 512)
TGMM_TILING = (512, 512, 512)


def _tiling(m: int, want: tuple[int, int, int]) -> tuple[int, int, int]:
    tm = next((t for t in (want[0], 256, 128) if t <= want[0] and m % t == 0), None)
    if tm is None:
        raise ValueError(
            f"the grouped-product kernel tiles its rows by 128: got {m} rows"
        )
    return (tm,) + tuple(want[1:])


def _gmm(lhs, rhs, group_sizes, interpret):
    """``interpret``: off the chip the Pallas kernels run interpreted."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    return gmm(
        lhs, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
        _tiling(lhs.shape[0], GMM_TILING), interpret=interpret,
    )


_gmm_vjp = jax.custom_vjp(_gmm, nondiff_argnums=(3,))


def _gmm_fwd(lhs, rhs, group_sizes, interpret):
    return _gmm(lhs, rhs, group_sizes, interpret), (lhs, rhs, group_sizes)


def _gmm_bwd(interpret, res, g):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm, tgmm

    lhs, rhs, group_sizes = res
    m = lhs.shape[0]
    d_lhs = gmm(
        g, rhs.astype(lhs.dtype), group_sizes, lhs.dtype,
        _tiling(m, GMM_TILING), transpose_rhs=True, interpret=interpret,
    )
    d_rhs = tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        _tiling(m, TGMM_TILING), num_actual_groups=rhs.shape[0],
        interpret=interpret,
    )
    return d_lhs, d_rhs.astype(rhs.dtype), None


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def grouped_matmul(
    lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array, *,
    impl: str = "auto",
) -> jax.Array:
    """``lhs`` (M, K) times ``rhs`` (H, K, N), rows ``[offset_h, offset_h +
    group_sizes[h])`` against ``rhs[h]``; ``group_sizes`` has H+1 entries and
    the rows of its last group (no expert here) come out zero. Output in
    ``lhs``'s dtype, accumulated in float32; ``rhs`` is cast to it.

    ``impl``: ``"gmm"`` is the megablox Pallas kernel, whose grid is sized
    from ``group_sizes`` at run time, so its device time follows the rows
    really routed, with ``tgmm`` for the weights' gradient;
    ``"ragged_dot"`` is ``lax.ragged_dot``; ``"auto"`` takes the kernel on a
    TPU and ``ragged_dot`` elsewhere (where the kernel runs interpreted).
    """
    from akka_allreduce_tpu.ops._platform import interpret_default

    off_chip = interpret_default(lhs)
    if impl == "auto":
        impl = "ragged_dot" if off_chip else "gmm"
    if impl == "gmm":
        return _gmm_vjp(lhs, rhs, group_sizes, off_chip)
    if impl != "ragged_dot":
        raise ValueError(f"unknown grouped-product {impl=}")
    return lax.ragged_dot(
        lhs, rhs.astype(lhs.dtype), group_sizes[: rhs.shape[0]],
        preferred_element_type=lhs.dtype,
    )


def moe_dropless_held(
    x: jax.Array,
    router_w: jax.Array,
    select_bias: jax.Array | None,
    w1: jax.Array,
    w3: jax.Array,
    w2: jax.Array,
    *,
    k: int,
    held_first: int = 0,
    renormalise: bool = True,
    scale: float = 1.0,
    impl: str = "auto",
) -> tuple[jax.Array, HeldRoute, jax.Array]:
    """``x`` (T, d) through the gated experts this device holds.

    ``router_w`` is (d, E) over ALL experts; ``w1``/``w3`` (H, d, f) and
    ``w2`` (H, f, d) are experts ``held_first .. held_first + H - 1``. Each
    token's result is ``sum over its selected AND held experts of
    w_e * W2_e(silu(W1_e x) * W3_e x)``, ``w`` normalised over all ``k``
    selected. Returns ``(y, route, dropped)``: ``dropped`` counts held
    assignments beyond the row buffer as a share of all held assignments —
    0 by construction, the buffer being every assignment there is.
    """
    t, d = x.shape
    held = w1.shape[0]
    with jax.named_scope("moe_route"):
        # the router is float32 end to end: on a TPU a default-precision
        # f32 product would round its operands to bf16
        logits = jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        selected, weights = sigmoid_topk_route(
            logits, select_bias, k, renormalise=renormalise, scale=scale
        )
        route = held_route(selected, weights, held_first, held)
        buffer_rows = t * k
        routed_here = route.group_sizes[:held].sum()
        dropped = jnp.maximum(routed_here - buffer_rows, 0) / jnp.maximum(
            routed_here, 1
        ).astype(jnp.float32)
    with jax.named_scope("moe_experts"):
        rows = jnp.broadcast_to(x[:, None, :], (t, k, d)).reshape(t * k, d)
        xs = permute_rows(rows, route.order, route.inverse)
        gate = grouped_matmul(xs, w1, route.group_sizes, impl=impl)
        up = grouped_matmul(xs, w3, route.group_sizes, impl=impl)
        ys = grouped_matmul(
            jax.nn.silu(gate) * up, w2, route.group_sizes, impl=impl
        )
    with jax.named_scope("moe_combine"):
        back = permute_rows(ys, route.inverse, route.order).reshape(t, k, d)
        y = (back * weights[..., None].astype(back.dtype)).sum(axis=1)
    return y, route, dropped

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

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax import lax

from akka_allreduce_tpu.ops._platform import interpret_default


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
    rung: jax.Array  # () int32: which of the ladder's row buffers holds them
    buffer_rows: jax.Array  # () int32: that buffer's rows


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


def softmax_topk_route(
    logits: jax.Array, k: int, *, renormalise: bool = True, scale: float = 1.0
) -> tuple[jax.Array, jax.Array]:
    """``p = softmax(logits)`` over all experts; the ``k`` largest are
    selected (ties: the lower index) and weighed by ``p``, over their own sum
    where ``renormalise`` (no epsilon: eight of 256 softmax scores sum to
    1/32 at the least). Returns ``(selected, weights)`` as
    :func:`sigmoid_topk_route` does; everything float32."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    w, selected = lax.top_k(p, k)
    if renormalise:
        w = w / w.sum(axis=-1, keepdims=True)
    return selected.astype(jnp.int32), w * scale


def row_rungs(rows: int, held: int, experts: int) -> tuple[int, ...]:
    """The row-buffer sizes, smallest first, for ``rows`` (token, choice)
    assignments routed over ``experts`` of which ``held`` are here. The
    first rung is the load under uniform routing, ``rows * held / experts``,
    and a quarter more (the count scatters around its mean from layer to
    layer), rounded up to the grouped-product kernels' row tile at this
    buffer; the last is ``rows``, the worst case — every token choosing
    only held experts — so nothing can overflow. Every rung is a body of
    the expert layer the step program carries, 2.1 s of each start's set-up
    at the LFM2 cell (PERF.md, PR 29), so there are two and none between
    them — except where the last is more than eight times the first (a
    thirty-second of the experts held: 2,560 and 65,536 at the JoyAI cell):
    there one more, at four times the first. A rung's rows are all moved and
    multiplied whatever was routed, so a layer a little past the first rung
    paid for 25 times its rows, 11.5 ms of a 318 ms step, and a layer's load
    did leave the first rung there: 0 to 13,872 rows within 110 steps, with
    the router trained or frozen, the selection bias seeded, balanced at the
    start or under the balancing rule (PERF.md, PR 32). A device that holds
    every expert gets the last rung alone."""
    tile = next((t for t in (GMM_TILING[0], 256, 128) if rows % t == 0), None)
    if tile is None:  # no kernel tiles such a buffer: one size
        return (rows,)
    first = -(-5 * rows * held // (4 * experts * tile)) * tile
    if first >= rows:
        return (rows,)
    return (first, 4 * first, rows) if rows > 8 * first else (first, rows)


def held_route(
    selected: jax.Array, weights: jax.Array, held_first: int, held_count: int,
    rungs: tuple[int, ...],
) -> HeldRoute:
    """Sort the (token, choice) assignments expert-major: the rows of held
    expert 0 first, then 1, ..., and the assignments to absent experts
    last; and pick the smallest of ``rungs`` (:func:`row_rungs`) that holds
    the rows of the held experts."""
    local = selected.reshape(-1) - held_first
    key = jnp.where((local >= 0) & (local < held_count), local, held_count)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    # a histogram by comparison: a scatter-add of T*k ones serialises on a TPU
    sizes = (key[:, None] == jnp.arange(held_count + 1)).sum(axis=0, dtype=jnp.int32)
    routed = sizes[:held_count].sum()
    rung = (routed > jnp.asarray(rungs[:-1], jnp.int32)).sum(dtype=jnp.int32)
    return HeldRoute(
        selected, weights, order, inverse, sizes, rung,
        jnp.asarray(rungs, jnp.int32)[rung],
    )


#: (rows, contracted, out) tiles of the megablox kernels where an expert's
#: rows fill a 512-row tile: of those swept on the v5e at the LFM2 cell's
#: shapes the fastest with an eighth of the whole buffer filled, within 8 %
#: of the fastest when it is full (PERF.md, PR 28), and the fastest at the
#: cell's first rung of 5,120 rows (PERF.md, PR 29)
GMM_TILING = (512, 512, 512)
TGMM_TILING = (512, 512, 512)


def _fit(n: int, most: int, otherwise: int) -> int:
    """The largest multiple of 128 up to ``most`` that divides ``n``."""
    return next((t for t in range(most, 0, -128) if n % t == 0), otherwise)


def grouped_tiles(
    kind: str, m: int, k: int, n: int, groups: int
) -> tuple[int, int, int]:
    """The (rows, contracted, out) tiles of one grouped product from its
    shapes: ``kind`` "gmm" (rows times an expert's (k, n) matrix) or "tgmm"
    (the weights' gradient, (k, n) an expert); ``m`` rows of the row buffer
    over ``groups`` experts. Where an expert's share of the buffer is a
    512-row tile or more, the tiles swept there (:data:`GMM_TILING`). Where
    it is less (320 rows an expert at the JoyAI cell's first rung, ~256 of
    them real: two experts meet in every 512-row tile and the kernel visits
    it twice), 256 rows and the widest tiles up to 1024 that divide the
    expert's matrix - (256, 1024, 768) at 2048 x 768 - which took 39 % off
    one layer's products there, in both kinds (CHANGES.md, PR 32). Where a
    side of the expert's matrix is no multiple of the swept tile (2304 x 896:
    eighteen and seven 128-lanes), the widest tile up to 1024 that divides
    it in that tile's place - (512, 768, 896) and (512, 896, 768) - so that
    no tile is a remainder: 18 % off the forward and 21 % off the backward
    of one layer's products at 20,480 rows, 1,024 real rows an expert
    (CHANGES.md, PR 39; 1152-wide tiles and 1024-row ones overran VMEM)."""
    want = {"gmm": GMM_TILING, "tgmm": TGMM_TILING}[kind]
    if m // groups < want[0]:
        want = (256, _fit(k, 1024, want[1]), _fit(n, 1024, want[2]))
    else:
        want = (want[0],) + tuple(
            t if side % t == 0 else _fit(side, 1024, t)
            for side, t in ((k, want[1]), (n, want[2]))
        )
    tm = next((t for t in (want[0], 256, 128) if t <= want[0] and m % t == 0), None)
    if tm is None:
        raise ValueError(
            f"the grouped-product kernel tiles its rows by 128: got {m} rows"
        )
    return (tm,) + tuple(want[1:])


def _product(lhs, rhs, group_sizes, impl, transpose_rhs=False):
    """Rows of group ``h`` times ``rhs[h]`` (its transpose on request), for
    ``impl`` "gmm" or "ragged_dot"; the rows of the last group of
    ``group_sizes`` (no expert here) come out zero."""
    rhs = rhs.astype(lhs.dtype)
    if impl == "ragged_dot":
        sizes = group_sizes[: rhs.shape[0]]
        out = lax.ragged_dot(
            lhs, rhs.swapaxes(1, 2) if transpose_rhs else rhs, sizes,
            preferred_element_type=lhs.dtype,
        )
        # on a TPU the rows past the last group come back unwritten (NaN
        # among them; PERF.md, PR 29), on the CPU zero
        rows = lax.broadcasted_iota(jnp.int32, (lhs.shape[0], 1), 0)
        return jnp.where(rows < sizes.sum(), out, 0)
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    # off the chip the Pallas kernels run interpreted
    k, n = rhs.shape[1:][::-1] if transpose_rhs else rhs.shape[1:]
    return gmm(
        lhs, rhs, group_sizes, lhs.dtype,
        grouped_tiles("gmm", lhs.shape[0], k, n, rhs.shape[0]),
        transpose_rhs=transpose_rhs, interpret=interpret_default(lhs),
    )


def _weight_gradient(lhs, g, group_sizes, groups: int, impl):
    """``lhs[rows of h].T @ g[rows of h]`` for each of ``groups`` experts:
    (M, K), (M, N) -> (groups, K, N) float32."""
    if impl == "ragged_dot":
        contract_rows = lax.RaggedDotDimensionNumbers(
            dot_dimension_numbers=(((0,), (0,)), ((), ())),
            lhs_ragged_dimensions=[0], rhs_group_dimensions=[],
        )
        return lax.ragged_dot_general(
            lhs, g, group_sizes[:groups], contract_rows,
            preferred_element_type=jnp.float32,
        )
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm

    return tgmm(
        lhs.swapaxes(0, 1), g, group_sizes, jnp.float32,
        grouped_tiles("tgmm", lhs.shape[0], lhs.shape[1], g.shape[1], groups),
        num_actual_groups=groups, interpret=interpret_default(lhs),
    )


@jax.custom_vjp
def _gmm_vjp(lhs, rhs, group_sizes):
    return _product(lhs, rhs, group_sizes, "gmm")


def _gmm_fwd(lhs, rhs, group_sizes):
    return _product(lhs, rhs, group_sizes, "gmm"), (lhs, rhs, group_sizes)


def _gmm_bwd(res, g):
    lhs, rhs, group_sizes = res
    d_lhs = _product(g, rhs, group_sizes, "gmm", transpose_rhs=True)
    d_rhs = _weight_gradient(lhs, g, group_sizes, rhs.shape[0], "gmm")
    return d_lhs, d_rhs.astype(rhs.dtype), None


_gmm_vjp.defvjp(_gmm_fwd, _gmm_bwd)


def _grouped_impl(impl: str, like: jax.Array) -> str:
    if impl == "auto":
        return "ragged_dot" if interpret_default(like) else "gmm"
    if impl not in ("gmm", "ragged_dot"):
        raise ValueError(f"unknown grouped-product {impl=}")
    return impl


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
    if _grouped_impl(impl, lhs) == "gmm":
        return _gmm_vjp(lhs, rhs, group_sizes)
    return _product(lhs, rhs, group_sizes, "ragged_dot")


# -- the held experts' gated FFN over one rung of the ladder -------------------
#
# A rung's buffer holds the first ``rows`` of the sorted assignments: every
# row of a held expert (the route took the rung for that) and then rows of
# absent experts, which the grouped products leave zero. ``lax.switch`` under
# ``jax.grad`` would make every rung return every rung's residuals, zero-
# filled for those not taken; so the rung is picked inside a custom_vjp,
# once forward and once backward, and the backward is written out. What the
# forward keeps for it has one shape whatever the rung: its arguments, the
# weights as the products take them, and the gate and up products of the
# FIRST rung, the one a balanced router takes; a larger rung hands back
# zeros in their place and recomputes the two products on the way to the
# gradients. Both directions are jitted, so that the expert layers of a
# model (same shapes) trace and lower each rung once.


def _rows_to_sources(rows: jax.Array, inverse: jax.Array, n: int) -> jax.Array:
    """The transpose of gathering rows from ``n`` sources, (R, d) -> (n, d):
    ``inverse`` holds, for each source's slots in turn, the row that slot
    went to, and a slot past R went nowhere. One gather of n rows per slot
    (a scatter-add of the R rows takes twice as long on a TPU), summed in
    float32."""
    slots = inverse.reshape(n, -1)
    return sum(
        jnp.take(rows, slots[:, j], axis=0, mode="fill", fill_value=0)
        .astype(jnp.float32) for j in range(slots.shape[1])
    ).astype(rows.dtype)


def _rung_rows(rows, x, weights, route):
    """A rung's rows: ``(at, sizes, xs, ws)`` — the flat (token, choice) of
    each sorted row, the rows per held expert closed by the absent rows,
    the rows' tokens' ``x`` and the rows' routing weights (R, 1)."""
    at = route.order[:rows]
    held = route.group_sizes[:-1]
    sizes = jnp.concatenate([held, rows - held.sum(keepdims=True)])
    xs = x.at[at // weights.shape[1]].get(mode="promise_in_bounds")
    ws = weights.reshape(-1).at[at].get(mode="promise_in_bounds")
    return at, sizes, xs, ws[:, None]


@functools.partial(jax.jit, static_argnames=("rows", "keep", "impl"))
def _rung_forward(x, weights, w1, w3, w2, route, *, rows, keep, impl):
    """The held experts' part of every token's result, moving and
    multiplying ``rows`` rows: right where the route took a rung this large
    or a smaller one. Also the gate and up products, (``keep``, f) each,
    where this is the rung of ``keep`` rows, else zeros."""
    with jax.named_scope("moe_experts"):
        _, sizes, xs, ws = _rung_rows(rows, x, weights, route)
        gate = _product(xs, w1, sizes, impl)
        up = _product(xs, w3, sizes, impl)
        ys = _product(jax.nn.silu(gate) * up, w2, sizes, impl)
    with jax.named_scope("moe_combine"):
        y = _rows_to_sources(ys * ws.astype(ys.dtype), route.inverse, x.shape[0])
    if rows != keep:
        zeros = jnp.zeros((keep, gate.shape[1]), gate.dtype)
        # under shard_map's check every rung's result has to vary alike
        varying = tuple(sorted(jax.typeof(gate).vma))
        gate = up = lax.pcast(zeros, varying, to="varying") if varying else zeros
    return y, (gate, up)


@functools.partial(jax.jit, static_argnames=("rows", "keep", "impl"))
def _rung_backward(x, weights, w1, w3, w2, route, kept, g, *, rows, keep, impl):
    """The gradients of :func:`_rung_forward`'s five array arguments (those
    of the weights in float32) from ``g``, its result's; ``kept`` is what it
    handed back."""
    f32, held = jnp.float32, w1.shape[0]
    with jax.named_scope("moe_experts"):
        at, sizes, xs, ws = _rung_rows(rows, x, weights, route)
        gate, up = kept if rows == keep else (
            _product(xs, w1, sizes, impl), _product(xs, w3, sizes, impl)
        )
        gate, up, ws = gate.astype(f32), up.astype(f32), ws.astype(f32)
        sig = jax.nn.sigmoid(gate)
        h = gate * sig * up
    with jax.named_scope("moe_combine"):
        gz = g.at[at // weights.shape[1]].get(mode="promise_in_bounds")
        # d ys = ws gz, so d h = ws (gz W2^T) and d ws = <ys, gz> = <h, gz W2^T>
        u = _product(gz, w2, sizes, impl, transpose_rhs=True).astype(f32)
        d_weights = jnp.zeros(weights.size, f32).at[at].add((h * u).sum(axis=-1))
    with jax.named_scope("moe_experts"):
        d_h = u * ws
        d_w2 = _weight_gradient((h * ws).astype(x.dtype), gz, sizes, held, impl)
        d_gate = (d_h * up * sig * (1.0 + gate * (1.0 - sig))).astype(x.dtype)
        d_up = (d_h * gate * sig).astype(x.dtype)
        d_xs = _product(d_gate, w1, sizes, impl, transpose_rhs=True) + _product(
            d_up, w3, sizes, impl, transpose_rhs=True
        )
        d_x = _rows_to_sources(d_xs, route.inverse, x.shape[0])
        d_w1 = _weight_gradient(xs, d_gate, sizes, held, impl)
        d_w3 = _weight_gradient(xs, d_up, sizes, held, impl)
    return d_x, d_weights.reshape(weights.shape).astype(weights.dtype), d_w1, d_w3, d_w2


def _on_rung(rungs, route, fn, impl, *operands):
    # a rung past the first is the exception (a balanced router never takes
    # it) and multiplies through ``lax.ragged_dot``, within 12 % of the
    # kernels at every fill at LFM2's shape (PERF.md, PR 28; 1.7 to 2.7 times
    # them at 2304 x 896, PR 39): a second set of Mosaic bodies would cost
    # every start's set-up for it (PERF.md, PR 29)
    each = [functools.partial(fn, rows=rungs[0], keep=rungs[0], impl=impl)] + [
        functools.partial(fn, rows=r, keep=rungs[0], impl="ragged_dot")
        for r in rungs[1:]
    ]
    if len(rungs) == 1:
        return each[0](*operands)
    return lax.switch(route.rung, each, *operands)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _held_ffn(rungs, impl, x, weights, w1, w3, w2, route):
    return _held_ffn_fwd(rungs, impl, x, weights, w1, w3, w2, route)[0]


def _held_ffn_fwd(rungs, impl, x, weights, w1, w3, w2, route):
    cast = tuple(w.astype(x.dtype) for w in (w1, w3, w2))
    y, kept = _on_rung(rungs, route, _rung_forward, impl, x, weights, *cast, route)
    return y, (x, weights, (w1, w3, w2), cast, route, kept)


def _held_ffn_bwd(rungs, impl, res, g):
    x, weights, stored, cast, route, kept = res
    d_x, d_weights, *d_w = _on_rung(
        rungs, route, _rung_backward, impl, x, weights, *cast, route, kept, g
    )
    return (
        d_x, d_weights, *(d.astype(w.dtype) for d, w in zip(d_w, stored)),
        jax.tree.map(lambda _: None, route),
    )


_held_ffn.defvjp(_held_ffn_fwd, _held_ffn_bwd)


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
    score: str = "sigmoid",
) -> tuple[jax.Array, HeldRoute, jax.Array]:
    """``x`` (T, d) through the gated experts this device holds.

    ``router_w`` is (d, E) over ALL experts; ``w1``/``w3`` (H, d, f) and
    ``w2`` (H, f, d) are experts ``held_first .. held_first + H - 1``. Each
    token's result is ``sum over its selected AND held experts of
    w_e * W2_e(silu(W1_e x) * W3_e x)``, ``w`` normalised over all ``k``
    selected. The rows gathered, multiplied and summed back are those of
    the smallest rung of :func:`row_rungs` (from T*k, H and E) that holds
    the rows routed here, picked on the device each call; the last rung is
    every assignment there is. Returns ``(y, route, dropped)``: ``dropped``
    counts held assignments beyond the rung taken as a share of all held
    assignments — 0 by construction. ``score`` names the router's score
    function: "sigmoid" (:func:`sigmoid_topk_route`, with ``select_bias``)
    or "softmax" (:func:`softmax_topk_route`, which has no bias).
    """
    held = w1.shape[0]
    rungs = row_rungs(x.shape[0] * k, held, router_w.shape[1])
    with jax.named_scope("moe_route"):
        # the router is float32 end to end: on a TPU a default-precision
        # f32 product would round its operands to bf16
        logits = jnp.matmul(
            x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=lax.Precision.HIGHEST,
        )
        if score == "sigmoid":
            selected, weights = sigmoid_topk_route(
                logits, select_bias, k, renormalise=renormalise, scale=scale
            )
        elif score == "softmax" and select_bias is None:
            selected, weights = softmax_topk_route(
                logits, k, renormalise=renormalise, scale=scale
            )
        else:
            raise ValueError(f"router score {score!r} (selection bias: "
                             f"{select_bias is not None}) is not built")
        route = held_route(selected, weights, held_first, held, rungs)
        routed_here = route.group_sizes[:held].sum()
        dropped = jnp.maximum(routed_here - route.buffer_rows, 0) / jnp.maximum(
            routed_here, 1
        ).astype(jnp.float32)
    y = _held_ffn(rungs, _grouped_impl(impl, x), x, weights, w1, w3, w2, route)
    return y, route, dropped

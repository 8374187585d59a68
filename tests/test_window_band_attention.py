"""The repo's own band kernel (``ops/band_attention.py``): causal attention
under a window of up to 1,024 keys, each query tile against one contiguous
slab of keys and a plain softmax. Interpreted on the CPU at small shapes
against the dense oracle, forward and the three gradients; the rule on shapes
that engages it (``local_attention._takes_band``), the two entries that reach
it, and the gauges its build writes."""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from akka_allreduce_tpu.ops.ring_attention import attention_reference, repeat_kv

# the package exports the function under the module's name
la = importlib.import_module("akka_allreduce_tpu.ops.local_attention")


def _swap(x):
    return x.transpose(0, 2, 1, 3)


def _operands(t, h, h_kv, d, dtype, seed=0):
    """Heads-first q (B, H, T, D) WITH the scale in it, compact k and v, and
    a probe for the gradients."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    q = (jax.random.normal(keys[0], (1, h, t, d)) * d ** -0.5).astype(dtype)
    k = jax.random.normal(keys[1], (1, h_kv, t, d)).astype(dtype)
    v = jax.random.normal(keys[2], (1, h_kv, t, d)).astype(dtype)
    return q, k, v, jax.random.normal(keys[3], (1, h, t, d))


def _oracle(q, k, v, window):
    """``attention_reference`` under the window on the same (rounded)
    operands, in float32."""
    h = q.shape[1]
    q, k, v = (_swap(x.astype(jnp.float32)) for x in (q, k, v))
    return _swap(attention_reference(
        q, repeat_kv(k, h), repeat_kv(v, h), causal=True, sm_scale=1.0, window=window
    ))


def _value_and_grads(fn, q, k, v, probe):
    loss = lambda *a: (fn(*a).astype(jnp.float32) * probe).sum()  # noqa: E731
    out = fn(q, k, v).astype(jnp.float32)
    return out, jax.grad(loss, (0, 1, 2))(q, k, v)


def _close(got, want, tol):
    got, want = (np.asarray(x, np.float32) for x in (got, want))
    assert np.abs(got - want).max() <= tol * (np.abs(want).max() + 1e-30)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("deep", [False, True], ids=["a_tile_past", "many_windows"])
@pytest.mark.parametrize("group", [1, 4])
@pytest.mark.parametrize("window", [128, 256])
def test_band_kernel_matches_the_dense_masked_softmax(window, group, deep, dtype):
    """Through the branch's own rule (``_splash_heads_first``, interpreted):
    forward and ``dq``, ``dk``, ``dv`` against the dense oracle, at T one
    tile past the window (every slab clamped at one end) and many windows
    deep, a query group of 1 and of 4 on two K/V heads; the rows before the
    first full window are among them and are held to the same tolerance."""
    t = window + 128 if not deep else 1024 + window
    assert la._takes_band(t, 32, 32, window)
    q, k, v, probe = _operands(t, 2 * group, 2, 32, dtype, seed=window + group)
    kernel = lambda q, k, v: la._splash_heads_first(  # noqa: E731
        q, k, v, causal=True, interpret=True, window=window)
    got, got_grads = _value_and_grads(kernel, q, k, v, probe)
    want, want_grads = _value_and_grads(lambda *a: _oracle(*a, window), q, k, v, probe)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    assert got.shape == q.shape and bool(jnp.isfinite(got).all())
    _close(got, want, tol)
    _close(got[:, :, :window], want[:, :, :window], tol)  # before the first full window
    for a, b in zip(got_grads, want_grads):
        assert a.dtype == dtype
        _close(a, b, 2.5 * tol)
    # and it is the band, not the causal mask: the window matters at this T
    causal = _oracle(q, k, v, t)
    assert float(jnp.abs(causal - want).max()) > 1e-2


@pytest.mark.parametrize("group,stack_rows", [(12, 1024), (4, 256), (3, 128)])
def test_a_group_wider_than_a_stack_goes_through_in_parts(group, stack_rows, monkeypatch):
    """The forward and ``dq`` stack at most ``_STACK_ROWS`` query rows of a
    group's heads into one product: a group of 12 in parts of 8 and 4, of 4
    in twos, of 3 head by head, the same numbers."""
    from akka_allreduce_tpu.ops import band_attention as ba

    monkeypatch.setattr(ba, "_STACK_ROWS", stack_rows)
    assert [s.stop - s.start for s in ba._stacks(group)] == {
        12: [8, 4], 4: [2, 2], 3: [1, 1, 1]}[group]
    q, k, v, probe = _operands(384, group, 1, 32, jnp.float32, seed=group)
    got, got_grads = _value_and_grads(
        lambda *a: ba.band_attention(*a, 256, True), q, k, v, probe)
    want, want_grads = _value_and_grads(lambda *a: _oracle(*a, 256), q, k, v, probe)
    _close(got, want, 2e-3)
    for a, b in zip(got_grads, want_grads):
        _close(a, b, 5e-3)


def _kernel_names(fn, *args) -> list[str]:
    """The ``name`` of every ``pallas_call`` in ``fn``'s jaxpr, nested ones
    (``custom_vjp``, ``jit``, ``vmap``) included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


BAND_KERNELS = ["flash_mha_band_fwd", "flash_mha_band_dq", "flash_mha_band_dkv"]


def test_both_entries_reach_the_one_band_kernel(monkeypatch):
    """``heads_first_attention`` (heads-first, the scale in q) and
    ``local_attention`` (sequence-first, the scale its own) under a window,
    where the kernel branch takes the shape: both trace the three band
    kernels and nothing of the library's, and give the same numbers."""
    real = la._splash_heads_first
    monkeypatch.setattr(la, "_flash_ok", lambda *a, **k: True)
    monkeypatch.setattr(  # the same branch, its kernels interpreted
        la, "_splash_heads_first", lambda *a, **k: real(*a, **{**k, "interpret": True})
    )
    t, d, window = 1024, 32, 256
    q, k, v, probe = _operands(t, 4, 2, d, jnp.float32, seed=9)
    first = lambda q, k, v: la.heads_first_attention(  # noqa: E731
        q, k, v, causal=True, window=window)
    second = lambda q, k, v: _swap(la.local_attention(  # noqa: E731
        _swap(q), _swap(k), _swap(v), causal=True, sm_scale=1.0, window=window))
    for fn in (first, second):
        grad = jax.grad(lambda *a: (fn(*a) * probe).sum(), (0, 1, 2))  # noqa: B023
        assert _kernel_names(grad, q, k, v) == BAND_KERNELS
    (got, got_grads), (want, want_grads) = (
        _value_and_grads(fn, q, k, v, probe) for fn in (first, second))
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for a, b in zip(got_grads, want_grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    _close(got, _oracle(q, k, v, window), 2e-3)


@pytest.mark.parametrize("window,taken", [
    (512, True), (1024, True), (256, True), (128, True),
    (2048, False), (None, False), (200, False), (1000, False), (4096, False),
])
def test_the_shape_rule_at_the_cells_sequence(window, taken):
    """At (T 8192, head 128): a window of up to 1,024 keys that a tile
    divides takes the band kernel; a wider one, none, or one the tile does
    not divide goes to the library's kernel, at the tiles its sweeps chose."""
    assert la._takes_band(8192, 128, 128, window) == taken
    if taken:
        return
    # the library's tiles: without a window and past 1,024 PR 31's and its
    # fused backward, under a narrow band 512 and two kernels (PRs 35, 39)
    lib = la._splash_blocks(8192, 128, 128, 2, window)
    tile, fused = (1024, True) if window is None or window > 1024 else (512, False)
    assert (lib.block_q, lib.block_kv, lib.block_q_dkv, lib.block_kv_dkv) == (tile,) * 4
    assert (lib.block_kv_compute, lib.block_kv_dkv_compute) == (512, tile)
    assert lib.use_fused_bwd_kernel == fused
    assert (lib.block_q_dq, lib.block_kv_dq) == ((None, None) if fused else (tile, tile))


@pytest.mark.parametrize("t,d,dv,window", [
    (8192, 192, 128, 512), (8192, 128, 192, 512), (8192, 256, 256, 1024),  # wide heads
    (512, 128, 128, 512), (1024, 128, 128, 1024),  # T holds no window and a tile
])
def test_the_shape_rule_leaves_what_the_slab_does_not_fit(t, d, dv, window):
    assert not la._takes_band(t, d, dv, window)


@pytest.mark.parametrize("window", [None, 2048, 200])
def test_off_the_rule_the_branch_builds_the_librarys_kernel(window):
    """No window, one past 1,024 and one no tile divides: the library's
    kernels and none of the band's, forward and backward."""
    q, k, v, probe = _operands(4096 if window == 2048 else 1024, 4, 2, 32, jnp.float32)
    fn = lambda q, k, v: la._splash_heads_first(  # noqa: E731
        q, k, v, causal=True, interpret=True, window=window)
    names = _kernel_names(jax.grad(lambda *a: (fn(*a) * probe).sum(), (0, 1, 2)), q, k, v)
    assert names and not set(names) & set(BAND_KERNELS)
    assert all(n.startswith("splash_mha") for n in names)


def _band_gauges():
    from akka_allreduce_tpu.obs import metrics

    return {k.rsplit(".", 1)[1]: v for k, v in metrics.REGISTRY.snapshot().items()
            if k.startswith("attention.band.")}


@pytest.mark.parametrize("window,mask_pairs,share", [
    (512, 4_063_488, 77.50), (1024, 7_864_832, 83.34),
])
def test_the_band_kernels_build_writes_its_own_geometry(window, mask_pairs, share):
    """``attention.band.*`` at T 8,192, a head's: every tile of 128 queries
    visits its slab of ``window + 128`` keys; the band leaves ``sum_i min(i +
    1, window)`` of them, a little under ``window / (window + 128)`` by the
    rows before the first full window (under the library's 512 tiles the
    shares were 50.0 and 66.7). Written when the branch first takes a shape,
    once a shape."""
    from akka_allreduce_tpu.obs import metrics

    la._gauge_band.cache_clear()
    for name in ("visited_pairs", "mask_pairs"):
        metrics.gauge(f"attention.band.{name}").set(0)
    la._gauge_band(8192, window)
    assert mask_pairs == sum(min(i + 1, window) for i in range(8192))
    assert _band_gauges() == {
        "visited_pairs": 64 * 128 * (window + 128), "mask_pairs": mask_pairs}
    assert 100 * mask_pairs / _band_gauges()["visited_pairs"] == pytest.approx(share, abs=0.01)
    # once a shape: the same shape again writes nothing
    metrics.gauge("attention.band.visited_pairs").set(0)
    la._gauge_band(8192, window)
    assert _band_gauges()["visited_pairs"] == 0


def test_a_kernel_without_a_band_writes_no_gauge():
    from akka_allreduce_tpu.obs import metrics

    for name in ("visited_pairs", "mask_pairs"):
        metrics.gauge(f"attention.band.{name}").set(0)
    la._splash_kernel.cache_clear()
    la._splash_kernel(8192, 2, True, la._splash_blocks(8192, 128), True, None)
    la._splash_kernel(8192, 2, True, la._splash_blocks(8192, 128, 128, 2, 2048), True, 2048)
    assert _band_gauges() == {"visited_pairs": 0, "mask_pairs": 0}

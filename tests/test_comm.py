"""Data-plane tests on the 8-device virtual CPU mesh (SURVEY.md §8.3).

Oracle: numpy masked sum / count of the per-device inputs — the same oracle the
reference's specs use for threshold rounds, minus the actors.
"""

import jax
import numpy as np
import pytest

from akka_allreduce_tpu.comm import threshold_allreduce
from akka_allreduce_tpu.parallel import grid_factors, grid_mesh, line_mesh


@pytest.fixture(scope="module")
def line8():
    return line_mesh(8)


@pytest.fixture(scope="module")
def grid24():
    return grid_mesh(2, 4)


def rand(n, d, seed=0):
    return np.random.default_rng(seed).standard_normal((n, d)).astype(np.float32)


class TestThresholdAllreduce:
    def test_full_participation_equals_sum(self, line8):
        xs = rand(8, 1000)
        res = threshold_allreduce(line8, xs)
        np.testing.assert_allclose(res.sum, xs.sum(0), rtol=1e-5)
        assert (np.asarray(res.count) == 8).all()
        np.testing.assert_allclose(res.average(), xs.mean(0), rtol=1e-5)

    def test_masked_devices_excluded(self, line8):
        xs = rand(8, 257)  # odd size
        valid = np.array([1, 1, 0, 1, 0, 1, 1, 1], dtype=np.float32)
        res = threshold_allreduce(line8, xs, valid)
        oracle = (xs * valid[:, None]).sum(0)
        np.testing.assert_allclose(res.sum, oracle, rtol=1e-5)
        assert (np.asarray(res.count) == 6).all()
        np.testing.assert_allclose(
            res.average(), oracle / 6.0, rtol=1e-5
        )

    def test_per_bucket_masks(self, line8):
        # data 100, bucket 30 -> 4 buckets (30/30/30/10); device d drops bucket d%4
        xs = rand(8, 100)
        valid = np.ones((8, 4), dtype=np.float32)
        for d in range(8):
            valid[d, d % 4] = 0.0
        res = threshold_allreduce(line8, xs, valid, bucket_size=30)
        counts = np.asarray(res.count)
        # each bucket dropped by exactly 2 of 8 devices
        assert (counts == 6).all()
        oracle = np.zeros(100, np.float32)
        for d in range(8):
            mask = np.repeat(valid[d], 30)[:100]
            oracle += xs[d] * mask
        np.testing.assert_allclose(res.sum, oracle, rtol=1e-5)

    def test_all_dropped_bucket_reads_zero(self, line8):
        xs = rand(8, 64)
        valid = np.ones((8, 2), dtype=np.float32)
        valid[:, 1] = 0.0  # nobody contributes bucket 1
        res = threshold_allreduce(line8, xs, valid, bucket_size=32)
        assert (np.asarray(res.count)[32:] == 0).all()
        np.testing.assert_allclose(np.asarray(res.average())[32:], 0.0)

    def test_rejects_wrong_shapes(self, line8):
        with pytest.raises(ValueError):
            threshold_allreduce(line8, rand(4, 10))  # wrong device count

    def test_caller_array_not_donated(self, line8):
        # passing an already-sharded device array twice must not hit a
        # donated/deleted buffer (convenience API never donates)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        xs = jax.device_put(
            rand(8, 64), NamedSharding(line8, P("line"))
        )
        r1 = threshold_allreduce(line8, xs)
        r2 = threshold_allreduce(line8, xs)  # would raise if xs was donated
        np.testing.assert_allclose(np.asarray(r1.sum), np.asarray(r2.sum))

    def test_ring_schedule_matches_psum(self, line8):
        xs = rand(8, 1003)  # not divisible by 8: exercises padding
        valid = np.array([1, 0, 1, 1, 1, 1, 0, 1], dtype=np.float32)
        res = threshold_allreduce(line8, xs, valid, schedule="ring")
        oracle = (xs * valid[:, None]).sum(0)
        np.testing.assert_allclose(res.sum, oracle, rtol=1e-4, atol=1e-4)
        assert (np.asarray(res.count) == 6).all()

    def test_butterfly_on_grid_matches_sum(self, grid24):
        xs = rand(8, 500)
        valid = np.array([1, 1, 1, 0, 1, 1, 1, 1], dtype=np.float32)
        res = threshold_allreduce(grid24, xs, valid, schedule="butterfly")
        oracle = (xs * valid[:, None]).sum(0)
        # staged psums reassociate fp32 sums; allow absolute slack near zero
        np.testing.assert_allclose(res.sum, oracle, rtol=1e-5, atol=1e-6)
        assert (np.asarray(res.count) == 7).all()

    def test_butterfly_requires_grid(self, line8):
        with pytest.raises(ValueError):
            threshold_allreduce(line8, rand(8, 16), schedule="butterfly")

    def test_partial_axis_reduce_rejected_at_host_api(self, grid24):
        # partial-axis reduction leaves the output unreplicated; the host API
        # refuses it (masked_psum inside shard_map is the supported route)
        with pytest.raises(ValueError, match="full mesh"):
            threshold_allreduce(grid24, rand(8, 20), axes="rows")

    def test_masked_psum_partial_axis_inside_shard_map(self, grid24):
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm import masked_psum

        xs = rand(8, 20)

        def kernel(x):
            s, c = masked_psum(x.reshape(-1), jnp.float32(1.0), "rows")
            return s[None], c[None]

        f = jax.shard_map(
            kernel,
            mesh=grid24,
            in_specs=P(("rows", "cols")),
            out_specs=(P("cols"), P("cols")),
        )
        with jax.set_mesh(grid24):
            sums, counts = f(xs)
        # grid (2,4): device (r, c) holds row-sum of column c
        sums = np.asarray(sums)
        assert sums.shape == (4, 20)
        for c in range(4):
            np.testing.assert_allclose(
                sums[c], xs[c] + xs[4 + c], rtol=1e-5
            )
        assert (np.asarray(counts) == 2).all()


SCHEDULE_MESH = {
    "psum": "line8", "ring": "line8", "pallas_ring": "line8",
    "butterfly": "grid24",
}
# the Pallas ring's interpreter runs in test time only with a small staging
# size (bucket_size sizes it); the mask given with it stays a scalar
SCHEDULE_KW = {"pallas_ring": {"bucket_size": 1024}}
MASKS = {
    "all_valid": np.ones(8, np.float32),
    "one_masked": np.array([1, 1, 1, 1, 1, 0, 1, 1], np.float32),
    "all_masked": np.zeros(8, np.float32),
}


def exact_payload(n, d, seed=0):
    """Whole numbers: every order of f32 additions gives the same bits, so
    each schedule can be held to ``==`` against numpy."""
    return np.random.default_rng(seed).integers(
        -1000, 1000, (n, d)
    ).astype(np.float32)


def host_entry(mesh, xs, valid, **kw):
    """``build_threshold_allreduce`` as a host loop drives it: sharded
    arguments in, the replicated pair out."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from akka_allreduce_tpu.comm import build_threshold_allreduce

    names = mesh.axis_names
    sh = NamedSharding(mesh, P(names if len(names) > 1 else names[0]))
    fn = build_threshold_allreduce(mesh, **kw)
    xs_dev = jax.device_put(xs, sh)
    total, count = fn(xs_dev, jax.device_put(valid, sh))
    return np.asarray(total), np.asarray(count), xs_dev


def mask_builds():
    """(in place, multiply): the host entry's programs traced so far."""
    from akka_allreduce_tpu.obs import metrics

    snap = metrics.REGISTRY.snapshot()
    return snap["comm.mask_inplace_builds"], snap["comm.mask_multiply_builds"]


class TestHostEntryMasksInPlace:
    """The host-facing entry applies a whole-payload mask without a multiply
    (``mask_zero_inplace``): the answers are the multiply's, bit for bit,
    wherever the multiply's were right."""

    @pytest.mark.parametrize("mask", sorted(MASKS))
    @pytest.mark.parametrize("schedule", sorted(SCHEDULE_MESH))
    def test_equals_the_plain_masked_sum(self, schedule, mask, request):
        mesh = request.getfixturevalue(SCHEDULE_MESH[schedule])
        xs, valid = exact_payload(8, 2048 + 24), MASKS[mask]
        total, count, _ = host_entry(
            mesh, xs, valid, schedule=schedule, donate=False,
            **SCHEDULE_KW.get(schedule, {}),
        )
        want = (xs * valid[:, None]).sum(0)
        assert np.array_equal(total, want)
        assert np.array_equal(count, np.full(xs.shape[1], valid.sum()))

    @pytest.mark.parametrize("schedule", sorted(SCHEDULE_MESH))
    def test_a_masked_devices_nan_and_inf_do_not_reach_the_sum(
        self, schedule, request
    ):
        """What ``0 * nan`` never gave: a straggler's data does not count."""
        mesh = request.getfixturevalue(SCHEDULE_MESH[schedule])
        xs, valid = exact_payload(8, 1024, seed=1), MASKS["one_masked"]
        clean = (xs * valid[:, None]).sum(0)
        xs[5, ::3], xs[5, 1::3], xs[5, 2::3] = np.nan, np.inf, -np.inf
        total, count, _ = host_entry(
            mesh, xs, valid, schedule=schedule, donate=False,
            **SCHEDULE_KW.get(schedule, {}),
        )
        assert np.array_equal(total, clean)
        assert (count == 7).all()

    def test_without_donation_the_callers_array_is_unchanged(self, line8):
        xs, valid = exact_payload(8, 4096, seed=2), MASKS["one_masked"]
        total, _, xs_dev = host_entry(line8, xs, valid, donate=False)
        assert not xs_dev.is_deleted()
        assert np.array_equal(np.asarray(xs_dev), xs)  # row 5 not zeroed
        assert np.array_equal(total, (xs * valid[:, None]).sum(0))

    @pytest.mark.parametrize("schedule", ["psum", "ring"])
    def test_a_per_bucket_mask_still_multiplies(self, line8, schedule):
        """Decided by the mask's rank: buckets need not align to a tile, and
        a per-bucket weight still scales its bucket."""
        xs = exact_payload(8, 100, seed=3)
        valid = np.ones((8, 4), np.float32)
        valid[np.arange(8), np.arange(8) % 4] = 0.0
        valid[0, 1] = 0.5  # a weight: exact on whole numbers
        in_place, multiply = mask_builds()
        total, count, _ = host_entry(
            line8, xs, valid, schedule=schedule, bucket_size=30, donate=False
        )
        per_element = np.repeat(valid, 30, axis=1)[:, :100]
        assert np.array_equal(total, (xs * per_element).sum(0))
        assert np.array_equal(count, per_element.sum(0))
        assert mask_builds() == (in_place, multiply + 1)

    def test_a_scalar_mask_with_bucket_size_is_still_a_scalar_mask(self, line8):
        xs, valid = exact_payload(8, 100, seed=4), MASKS["one_masked"]
        in_place, multiply = mask_builds()
        total, count, _ = host_entry(
            line8, xs, valid, bucket_size=30, donate=False
        )
        assert np.array_equal(total, (xs * valid[:, None]).sum(0))
        assert count.shape == (100,) and (count == 7).all()
        assert mask_builds() == (in_place + 1, multiply)

    def test_a_weight_that_is_not_0_or_1_counts_but_does_not_scale(self, line8):
        """Outside the contract (``valid`` is 0.0 or 1.0) and stated in the
        docstring: the payload is zeroed on ``valid == 0`` and left alone
        otherwise, ``count`` stays ``psum(valid)``."""
        xs = exact_payload(8, 512, seed=5)
        valid = np.array([1, 0.5, 1, 0, 1, 1, 2, 1], np.float32)
        total, count, _ = host_entry(line8, xs, valid, donate=False)
        assert np.array_equal(total, (xs * (valid != 0)[:, None]).sum(0))
        assert (count == valid.sum()).all()

    @pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
    @pytest.mark.parametrize("shape", [(1000,), (3, 7)], ids=["flat", "2d"])
    def test_mask_zero_inplace_alone(self, dtype, shape):
        """Any payload: zeros on ``valid == 0``, the payload itself (NaN and
        all) otherwise, under ``jit`` with the payload donated."""
        import jax.numpy as jnp

        from akka_allreduce_tpu.comm.allreduce import mask_zero_inplace

        x = np.arange(1, np.prod(shape) + 1).reshape(shape).astype(dtype)
        if dtype != "int32":
            x.flat[5] = np.nan
        fn = jax.jit(mask_zero_inplace, donate_argnums=(0,))
        for v, want in ((0.0, np.zeros_like(x)), (1.0, x), (0.5, x)):
            out = fn(jnp.asarray(x), jnp.float32(v))
            assert out.dtype == x.dtype and out.shape == x.shape
            assert np.array_equal(
                np.asarray(out).astype("float32"), want.astype("float32"),
                equal_nan=True,
            )


class TestMeshHelpers:
    def test_grid_factors(self):
        assert grid_factors(16) == (4, 4)
        assert grid_factors(8) == (2, 4)
        assert grid_factors(7) == (1, 7)

    def test_line_mesh_subset(self):
        m = line_mesh(4)
        assert m.shape == {"line": 4}

    def test_grid_mesh_auto(self):
        m = grid_mesh(devices=jax.devices()[:8])
        assert m.shape == {"rows": 2, "cols": 4}


class TestCompressedSchedules:
    """Wire compression: bf16 halves / int8 quarters the bytes per hop while
    counts (threshold semantics) stay exact float32."""

    def _oracle(self, xs, valid):
        return (xs * valid[:, None]).sum(0), valid.sum()

    def test_bf16_psum_close_and_counts_exact(self, line8):
        xs = rand(8, 513)
        valid = np.array([1, 1, 0, 1, 1, 1, 0, 1], dtype=np.float32)
        res = threshold_allreduce(line8, xs, valid, compress="bf16")
        want, n = self._oracle(xs, valid)
        scale = np.abs(want).max() + 1e-6
        assert np.abs(np.asarray(res.sum) - want).max() / scale < 2e-2
        assert (np.asarray(res.count) == n).all()  # counts never compressed

    def test_bf16_butterfly_close(self, grid24):
        xs = rand(8, 200)
        res = threshold_allreduce(
            grid24, xs, schedule="butterfly", compress="bf16"
        )
        want = xs.sum(0)
        scale = np.abs(want).max() + 1e-6
        assert np.abs(np.asarray(res.sum) - want).max() / scale < 2e-2

    @pytest.mark.parametrize("mode,tol", [("bf16", 2e-2), ("int8", 8e-2)])
    def test_compressed_ring_close_and_replicated(self, line8, mode, tol):
        xs = rand(8, 300, seed=3)
        valid = np.array([1, 0, 1, 1, 1, 1, 1, 1], dtype=np.float32)
        res = threshold_allreduce(
            line8, xs, valid, schedule="ring", compress=mode
        )
        want, n = self._oracle(xs, valid)
        scale = np.abs(want).max() + 1e-6
        assert np.abs(np.asarray(res.sum) - want).max() / scale < tol
        assert (np.asarray(res.count) == n).all()

    def test_compressed_ring_bucketed_masks(self, line8):
        xs = rand(8, 96, seed=5)
        valid = np.ones((8, 3), dtype=np.float32)
        valid[2, :] = 0.0  # device 2 contributes nothing
        valid[4, 1] = 0.0  # device 4 misses bucket 1
        res = threshold_allreduce(
            line8, xs, valid, bucket_size=32, schedule="ring", compress="bf16"
        )
        mask = np.repeat(valid, 32, axis=1)
        want = (xs * mask).sum(0)
        scale = np.abs(want).max() + 1e-6
        assert np.abs(np.asarray(res.sum) - want).max() / scale < 2e-2
        np.testing.assert_array_equal(
            np.asarray(res.count), mask.sum(0)
        )

    def test_int8_all_zero_segment_is_safe(self, line8):
        xs = np.zeros((8, 64), np.float32)
        res = threshold_allreduce(line8, xs, schedule="ring", compress="int8")
        assert np.isfinite(np.asarray(res.sum)).all()
        np.testing.assert_array_equal(np.asarray(res.sum), 0.0)

    def test_int8_requires_ring(self, line8):
        with pytest.raises(ValueError, match="int8"):
            threshold_allreduce(line8, rand(8, 16), compress="int8")

    def test_unknown_mode_rejected(self, line8):
        with pytest.raises(ValueError, match="compress"):
            threshold_allreduce(line8, rand(8, 16), compress="fp4")


class TestRingReduceScatter:
    """ring_reduce_scatter_sum: device i returns fully-reduced segment i
    (tiled all_gather alignment — FSDP's int8 backward transpose)."""

    @pytest.mark.parametrize("compress", [None, "bf16", "int8"])
    @pytest.mark.parametrize("data", [4096, 4100])  # exact + padded tail
    def test_matches_numpy_segments(self, compress, data):
        import jax
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import ring_reduce_scatter_sum
        from akka_allreduce_tpu.parallel import line_mesh

        n = 8
        mesh = line_mesh(n)
        rng = np.random.default_rng(0)
        xs = rng.standard_normal((n, data)).astype(np.float32)

        fn = jax.jit(
            jax.shard_map(
                lambda x: ring_reduce_scatter_sum(
                    x.reshape(-1), "line", n, compress=compress
                )[None],
                mesh=mesh,
                in_specs=P("line"),
                out_specs=P("line"),
                check_vma=False,
            )
        )
        out = np.asarray(fn(xs))  # (n, seg): row i = device i's segment
        seg = -(-data // n)
        want = np.pad(xs.sum(0), (0, n * seg - data)).reshape(n, seg)
        tol = {None: 1e-5, "bf16": 2e-2, "int8": 0.3}[compress]
        scale = np.abs(want).max()
        np.testing.assert_allclose(out, want, atol=tol * scale, rtol=0)


class TestRingPerHopResidual:
    """Per-hop error feedback (VERDICT r4 #4c): the compressed rings
    return each device's locally-computed injected quantization error, and
    the accounting is EXACT — summing every device's residual recovers the
    f32 result from the compressed result, element by element. This is the
    identity that makes re-sending the residual next round a full
    compensation of the per-hop noise (not just the first hop)."""

    N = 8

    def _allreduce(self, xs, compress):
        import jax
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import ring_allreduce_sum

        n = self.N
        mesh = line_mesh(n)
        fn = jax.jit(
            jax.shard_map(
                lambda x: tuple(
                    a[None]
                    for a in ring_allreduce_sum(
                        x.reshape(-1), "line", n, compress=compress,
                        return_residual=True,
                    )
                ),
                mesh=mesh,
                in_specs=P("line"),
                out_specs=(P("line"), P("line")),
                check_vma=False,
            )
        )
        out, resid = fn(xs)
        return np.asarray(out), np.asarray(resid)

    @pytest.mark.parametrize("compress", ["bf16", "int8"])
    @pytest.mark.parametrize("data", [4096, 4100])  # exact + padded tail
    def test_allreduce_residual_accounting_identity(self, compress, data):
        rng = np.random.default_rng(11)
        xs = rng.standard_normal((self.N, data)).astype(np.float32)
        out, resid = self._allreduce(xs, compress)
        want = xs.sum(0, dtype=np.float64).astype(np.float32)
        scale = np.abs(want).max()
        # the compressed result alone is off by the per-hop noise...
        assert np.abs(out[0] - want).max() > 1e-4 * scale
        # ...and adding every device's residual recovers f32 exactly
        # (up to reassociation dust + the gather's ~1-ulp scale drift)
        recovered = out[0] + resid.sum(0)
        np.testing.assert_allclose(
            recovered, want, atol=5e-5 * scale, rtol=0
        )

    def test_residual_is_per_device_local(self):
        """A device that contributes zeros still injects requantization
        error while RELAYING others' partial sums — its residual must be
        nonzero (what masked-device EF re-sends) and the identity must
        still hold."""
        rng = np.random.default_rng(12)
        xs = rng.standard_normal((self.N, 2048)).astype(np.float32)
        xs[3] = 0.0
        out, resid = self._allreduce(xs, "int8")
        assert np.abs(resid[3]).max() > 0.0
        want = xs.sum(0, dtype=np.float64).astype(np.float32)
        scale = np.abs(want).max()
        np.testing.assert_allclose(
            out[0] + resid.sum(0), want, atol=5e-5 * scale, rtol=0
        )

    @pytest.mark.parametrize("data", [4096, 4100])
    def test_reduce_scatter_residual_identity(self, data):
        import jax
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import ring_reduce_scatter_sum

        n = self.N
        mesh = line_mesh(n)
        rng = np.random.default_rng(13)
        xs = rng.standard_normal((n, data)).astype(np.float32)
        fn = jax.jit(
            jax.shard_map(
                lambda x: tuple(
                    a[None]
                    for a in ring_reduce_scatter_sum(
                        x.reshape(-1), "line", n, compress="int8",
                        return_residual=True,
                    )
                ),
                mesh=mesh,
                in_specs=P("line"),
                out_specs=(P("line"), P("line")),
                check_vma=False,
            )
        )
        out, resid = fn(xs)
        out, resid = np.asarray(out), np.asarray(resid)
        seg = -(-data // n)
        want = np.pad(
            xs.sum(0, dtype=np.float64).astype(np.float32),
            (0, n * seg - data),
        ).reshape(n, seg)
        scale = np.abs(want).max()
        # device i's segment + everyone's residual at segment i = f32
        resid_segs = resid.sum(0).reshape(n, seg)
        np.testing.assert_allclose(
            out + resid_segs, want, atol=5e-5 * scale, rtol=0
        )

    def test_residual_requires_compress(self):
        import jax
        from jax.sharding import PartitionSpec as P

        from akka_allreduce_tpu.comm.allreduce import ring_allreduce_sum

        with pytest.raises(ValueError, match="compress"):
            jax.shard_map(
                lambda x: ring_allreduce_sum(
                    x.reshape(-1), "line", 8, return_residual=True
                )[None],
                mesh=line_mesh(8),
                in_specs=P("line"),
                out_specs=P("line"),
            )(rand(8, 64))

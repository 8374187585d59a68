"""Real-width compiles for a DESCRIBED ``v5e:2x2`` — no chip attached.

The TPU compiler is installed beside the CPU backend and compiles for a
topology that is described, not attached (``/opt/skills/guides/
on-chip-measurement`` section 2). Interpret-mode tests cannot see what it
refuses: a slice not aligned to the tiling, a kernel over its VMEM budget, a
program over the chip's 16 GB. These cases keep the main path's kernels and
the flagship step compiling at the sizes ``chip_smoke.py`` runs them, at no
chip time. A compile that passes is not a chip run.

Nothing executes and no array can live on a described device, so every
case lowers ``jax.ShapeDtypeStruct``s. Code that asks
``jax.default_backend()`` would still see the CPU and take its CPU branch
(interpret-mode kernels, blockwise attention); the ``as_tpu`` fixture steers
it HERE, in the test, not through an option of the program.
"""

from __future__ import annotations

import json
import os
import re
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs to /tmp

import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM_BYTES = 16e9  # one v5e chip
BENCH = os.path.join(os.path.dirname(os.path.dirname(__file__)), "benchmarks")

FLOATS_PER_DEVICE = 64 * 1024 * 1024  # BASELINE config 2, chip_smoke's size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture
def as_tpu(monkeypatch):
    """Compile what the chip would run: ``jax.default_backend()`` answers
    "tpu" for the package's dispatch gates, and the persistent compile cache
    is off (such a compile could be written to it but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _reduce_kernels(topo, monkeypatch):
    """The fused threshold-reduce + elastic-average at 8 x 8M f32."""
    from akka_allreduce_tpu.ops import elastic_average_step, masked_average

    one = SingleDeviceSharding(topo.devices[0])
    x = jax.ShapeDtypeStruct((8, 8 * 1024 * 1024), jnp.float32, sharding=one)
    v = jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one)

    def fn(x, v):
        return elastic_average_step(x, v, 0.125), masked_average(x, v)

    return jax.jit(fn).lower(x, v).compile(), 2


def _pallas_ring(compress):
    def build(topo, monkeypatch):
        from akka_allreduce_tpu.comm.allreduce import build_threshold_allreduce
        from akka_allreduce_tpu.parallel import line_mesh

        mesh = line_mesh(devices=topo.devices)
        sh = NamedSharding(mesh, P("line"))
        xs = jax.ShapeDtypeStruct(
            (4, FLOATS_PER_DEVICE), jnp.float32, sharding=sh
        )
        valid = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=sh)
        fn = build_threshold_allreduce(
            mesh, schedule="pallas_ring", compress=compress
        )
        return fn.lower(xs, valid).compile(), 1

    return build


def _assert_splash_takes_compact_kv(text, b, t, h, h_kv, d, layers=1):
    """The attention kernels in a compiled text are the library's splash
    kernels, one forward and one fused backward a layer, and each takes K and
    V at ``h_kv`` heads (the operand constraints of its custom call)."""
    calls = [
        line for line in text.splitlines()
        if "tpu_custom_call" in line and re.search(r"%splash_m[hq]a_[\w.]+ = ", line)
    ]
    assert len(calls) == 2 * layers, [c[:60] for c in calls]
    assert "flash_attention" not in text and "flash_mha" not in text
    # (B, heads, T, D) operands; XLA drops a batch of one
    kv, q = (rf"bf16\[(?:{b},)?{n},{t},{d}\]" for n in (h_kv, h))
    for line in calls:
        operands = line.split("operand_layout_constraints=", 1)[1]
        assert len(re.findall(kv, operands)) == 2, line[:200]  # K and V, compact
        assert len(re.findall(q, operands)) in (1, 2), line[:200]  # q; do backward


def _kernel_attention(b, t, h, h_kv, d):
    """``local_attention``'s kernel branch, forward and backward, with K/V at
    their own head count."""

    def build(topo, monkeypatch):
        from akka_allreduce_tpu.ops.local_attention import local_attention

        one = SingleDeviceSharding(topo.devices[0])
        q = jax.ShapeDtypeStruct((b, t, h, d), jnp.bfloat16, sharding=one)
        kv = jax.ShapeDtypeStruct((b, t, h_kv, d), jnp.bfloat16, sharding=one)

        def loss(q, k, v):
            out = local_attention(q, k, v, causal=True)
            return out.astype(jnp.float32).sum()

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        compiled = grad.lower(q, kv, kv).compile()
        if h_kv != h:
            _assert_splash_takes_compact_kv(
                compiled.as_text(), b, t, h, h_kv, d
            )
            assert [g.shape for g in compiled.out_info] == [
                q.shape, kv.shape, kv.shape
            ]
        return compiled, 2  # forward, and the fused backward

    return build


def _kernel_attention_at_tiles(tile, *shape):
    """:func:`_kernel_attention` with the splash kernel's q and K/V blocks
    forced to ``tile`` (the forward's compute block stays 512): what a sweep
    on the chip holds against the tiles ``_splash_blocks`` takes."""

    def build(topo, monkeypatch):
        from jax.experimental.pallas.ops.tpu.splash_attention import BlockSizes

        import akka_allreduce_tpu.ops.local_attention  # noqa: F401  (the module, not the function)

        module = sys.modules["akka_allreduce_tpu.ops.local_attention"]
        monkeypatch.setattr(module, "_splash_blocks", lambda *a: BlockSizes(
            block_q=tile, block_kv=tile, block_kv_compute=512, block_q_dkv=tile,
            block_kv_dkv=tile, block_kv_dkv_compute=tile, use_fused_bwd_kernel=True))
        return _kernel_attention(*shape)(topo, monkeypatch)

    return build


def _gated_delta_rule_layer(topo, monkeypatch):
    """One linear layer's gated delta rule at the Qwen3-Next cell's shape (16
    key heads and 32 value heads of 128, T 8,192) as the mixer calls it: under
    its two scopes and a ``jax.checkpoint``, forward and backward. On the chip
    that is the rule's Pallas kernels (the pass made again with its
    residuals and the backward: a gradient alone needs no primal), both under
    both scopes the benchmark's
    readers match, a float32 state, no loop over the chunks, and less kept
    than the XLA form keeps at the same place."""
    from akka_allreduce_tpu.ops import delta_rule

    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731

    def compiled():
        @jax.checkpoint
        def rule(*operands):
            with jax.named_scope("linear_attention"), jax.named_scope("gdn_core"):
                return delta_rule.gated_delta_rule(*operands)

        def loss(*operands):
            out, state = rule(*operands)
            return out.astype(jnp.float32).sum() + state.sum()

        return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(
            sds((1, 16, 8192, 128), jnp.bfloat16), sds((1, 16, 8192, 128), jnp.bfloat16),
            sds((1, 32, 8192, 128), jnp.bfloat16), sds((1, 32, 8192), jnp.float32),
            sds((1, 32, 8192), jnp.float32),
        ).compile()

    assert delta_rule.takes_delta_rule(8192, 128, 128, 16, 32, jnp.bfloat16)
    by_kernels = compiled()
    text = by_kernels.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    names = sorted(re.search(r"%(gated_delta_rule_\w+?)[.\d]* = ", line).group(1) for line in calls)
    assert names == ["gated_delta_rule_bwd", "gated_delta_rule_fwd"]
    for line in calls:
        scope = re.search(r'op_name="([^"]*)"', line).group(1)
        assert re.search(r"(?:^|/)gdn_core(?:/|$)", scope) and "linear_attention" in scope, scope
    assert " while(" not in text and "[128,1,32,64,128]" not in text
    assert "f32[1,32,128,128]" in text
    with monkeypatch.context() as m:  # the portable form at the same place
        m.setattr(delta_rule, "_on_chip", lambda *arrays: False)
        xla_form = compiled()
    assert "tpu_custom_call" not in xla_form.as_text()
    assert "[128,1,32,64,128]" in xla_form.as_text()  # ONE loop over 128 chunks of 64
    kept = lambda c: c.memory_analysis().temp_size_in_bytes  # noqa: E731
    assert kept(by_kernels) < kept(xla_form)
    return by_kernels, 2


def _grouped_psum(topo, monkeypatch):
    """``grouped_tree_psum`` over gradients shaped like the 268M-param MoE's
    (d1024, 8 experts, 4 layers): when it staged them through one flat
    buffer, libtpu 0.0.34 laid that buffer out as f32[N/8, 8] after the
    (d_model, 8) router leaf — 16x lane padding, over 16 GB, refused
    (on the chip, PR 21)."""
    from akka_allreduce_tpu.comm.allreduce import grouped_tree_psum

    mesh = jax.make_mesh((4,), ("data",), devices=topo.devices)
    layer = {
        "experts_in": (8, 1024, 4096), "experts_out": (8, 4096, 1024),
        "router": (1024, 8), "bias": (1024,),
    }
    shapes = {f"{i}/{k}": s for i in range(4) for k, s in layer.items()}
    specs = {k: P() for k in shapes}
    grads = {
        k: jax.ShapeDtypeStruct(
            s, jnp.float32, sharding=NamedSharding(mesh, P())
        )
        for k, s in shapes.items()
    }

    def sync(grads):
        local = jax.tree.map(
            lambda g: jax.lax.pcast(g, ("data",), to="varying"), grads
        )
        return grouped_tree_psum(local, specs, ("data",))

    fn = jax.jit(
        jax.shard_map(sync, mesh=mesh, in_specs=(specs,), out_specs=specs)
    )
    return fn.lower(grads).compile(), 0  # collectives only, no kernel


class _ShapeOnlyLM:
    """``TransformerLM`` whose ``init`` returns shapes: a described device
    cannot hold the 404M parameters, and the step only needs their avals."""

    def __init__(self, **kw):
        from akka_allreduce_tpu.models.transformer import TransformerLM

        self._model = TransformerLM(**kw)
        self.apply = self._model.apply

    def init(self, *args):
        return jax.eval_shape(self._model.init, *args)


def _bench_json(*parts):
    with open(os.path.join(BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


def _lm_step(sizes, params_between):
    """A ``LongContextTrainer`` step (bf16, no remat, dp = sp = 1) on one
    described chip; ``sizes()`` gives vocab, widths, depth, T and batch."""

    def build(topo, monkeypatch):
        from akka_allreduce_tpu.parallel import data_seq_mesh
        from akka_allreduce_tpu.train import LongContextTrainer

        z = sizes()
        # the trainer places its state with device_put; shapes stay where they are
        monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
        adam = optax.adam(3e-3)
        mesh = data_seq_mesh(1, 1, devices=topo.devices[:1])
        t = LongContextTrainer(
            mesh, model_cls=_ShapeOnlyLM, vocab=z["vocab"],
            d_model=z["d_model"], n_heads=z["n_heads"],
            n_kv_heads=z["n_kv_heads"], n_layers=z["n_layers"],
            seq_len=z["seq_len"], compute_dtype=jnp.bfloat16,
            optimizer=optax.GradientTransformation(
                lambda p: jax.eval_shape(adam.init, p), adam.update
            ),
        )
        lo, hi = params_between
        assert lo < t.param_count < hi
        assert not t._check_vma  # the kernel's gate relaxed it: the TPU branch

        def sds(tree, specs):
            return jax.tree.map(
                lambda leaf, s: jax.ShapeDtypeStruct(
                    leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, s)
                ),
                tree, specs,
            )

        tokens = jax.ShapeDtypeStruct(
            (z["batch"], z["seq_len"]), jnp.int32, sharding=t._data_sharding
        )
        valid = jax.ShapeDtypeStruct(
            (1,), jnp.float32, sharding=t._valid_sharding
        )
        compiled = t._step.lower(
            sds(t.params, t._param_specs), sds(t.opt_state, t._opt_specs),
            tokens, tokens, valid,
        ).compile()
        if z["n_kv_heads"] != z["n_heads"]:
            _assert_splash_takes_compact_kv(
                compiled.as_text(), z["batch"], z["seq_len"], z["n_heads"],
                z["n_kv_heads"], z["d_model"] // z["n_heads"],
                layers=z["n_layers"],
            )
        return compiled, 2 * z["n_layers"]  # two attention kernels per layer

    return build


def _flagship_sizes():
    """d2048 x 8L x seq2048 x B8, what ``chip_smoke.py`` runs."""
    return dict(vocab=256, d_model=2048, n_heads=16, n_kv_heads=16,
                n_layers=8, seq_len=2048, batch=8)


def _b2_cell_sizes():
    """The benchmark's ``sc2_3b_train_b2_t4096``: memory full (8.232 GB of
    arguments + 4.258 of temporaries with the old kernel, PERF.md), so a
    backward that keeps more alive fails the case's 16 GB line here."""
    cfg = _bench_json("configs", "starcoder2_3b_d4.json")
    traffic = _bench_json("traffic", "closed_b2_t4096.json")
    return dict(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        n_layers=cfg["num_hidden_layers"], seq_len=traffic["seq_len"],
        batch=traffic["batch"],
    )


def _lfm2_moe_step(topo, monkeypatch):
    """The ``MoETrainer`` step of the benchmark's ``lfm2_ep8_train_b1_t8192``
    cell (conv/attention hybrid, 8 of 64 experts held, 1 x 8192 tokens,
    bf16, no remat) on one described chip: a later change that makes it too
    large for the chip fails here before it fails the cell."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_runner_moe_train", os.path.join(BENCH, "runners", "moe_train.py")
    )
    monkeypatch.syspath_prepend(BENCH)  # the runner imports the harness
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    t, lowered = runner.lower_step_on_shapes(
        _bench_json("configs", "lfm2_24b_a2b_ep8_d5.json"),
        _bench_json("traffic", "closed_b1_t8192.json"), topo.devices[0],
    )
    assert 486.0e6 < t.param_count < 486.2e6
    assert not t._check_vma  # attention and grouped-product kernels: the TPU branch
    # two attention kernels, and the nine grouped products of the first rung
    # of the row buffer in each of the four expert layers (three forward,
    # three and three ``tgmm`` backward; the last rung is ``lax.ragged_dot``)
    from akka_allreduce_tpu.ops.moe import row_rungs

    assert row_rungs(8192 * 4, 8, 64) == (5120, 8192 * 4)
    compiled = lowered.compile()
    text = compiled.as_text()
    _assert_splash_takes_compact_kv(text, 1, 8192, 32, 8, 64)
    # the products write the kernel's layout: no (B, T, H, D) activation anywhere
    assert "bf16[1,8192,32,64]" not in text and "bf16[1,8192,8,64]" not in text
    return compiled, 2 + 9 * 4


def _joyai_mla_moe_step(topo, monkeypatch):
    """The ``MoETrainer`` step of the benchmark's ``joyai_ep32_train_b1_t8192``
    cell (latent attention at 192 / 128 heads, 256 wide in the kernels, in
    six layers, a shared expert beside 8 of 256 routed ones held, a
    prediction module, 1 x 8192 tokens, bf16) on one described chip, inside its configuration's memory rule:
    arguments + temporaries at most 14.5 GB without recomputation."""
    import importlib.util

    monkeypatch.syspath_prepend(BENCH)  # the runner imports the harness
    spec = importlib.util.spec_from_file_location(
        "bench_runner_mla_moe_train", os.path.join(BENCH, "runners", "mla_moe_train.py")
    )
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    cfg = _bench_json("configs", "joyai_llm_flash_ep32_d5_mtp1.json")
    t, lowered = runner.lower_step_on_shapes(
        cfg, _bench_json("traffic", "closed_b1_t8192.json"), topo.devices[0],
    )
    assert 491.6e6 < t.param_count - 5 * 256 < 491.8e6
    assert not t._check_vma and not cfg["program"]["remat"]
    from akka_allreduce_tpu.ops.moe import row_rungs

    assert row_rungs(8192 * 8, 8, 256) == (2560, 10240, 8192 * 8)
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes <= 14.5e9
    text = compiled.as_text()
    # the library's kernels, a forward and a fused backward in each of the six
    # layers, q and K 256 wide (128 + the 64 rotary columns twice, under cos
    # and under sin) against V at 128
    calls = [
        line.split("operand_layout_constraints=", 1)[1] for line in text.splitlines()
        if "tpu_custom_call" in line and re.search(r"%splash_mha_[\w.]+ = ", line)
    ]
    assert len(calls) == 2 * 6
    for operands in calls:
        assert len(re.findall(r"bf16\[32,8192,256\]", operands)) >= 2  # q, K
        assert re.search(r"bf16\[32,8192,128\]", operands)  # V
    # and the products write that layout: no (B, T, H, .) activation anywhere
    assert "bf16[1,8192,32," not in text
    return compiled, 2 * 6 + 9 * 5


def _laguna_moe_step(topo, monkeypatch):
    """The ``MoETrainer`` step of the benchmark's ``laguna_xs2_train_b1_t8192``
    cell (two full-attention layers at 48 query heads and three windowed ones
    at 64, all on 8 K/V heads of 128, a per-head output gate, a shared expert
    beside the 16 (or 32: the file says) of 256 softmax-routed experts held,
    1 x 8192 tokens, bf16) on one described chip, inside its configuration's
    memory rule: arguments + temporaries at most 14.5 GB without recomputation."""
    import importlib.util

    monkeypatch.syspath_prepend(BENCH)  # the runner imports the harness
    spec = importlib.util.spec_from_file_location(
        "bench_runner_laguna_moe_train", os.path.join(BENCH, "runners", "laguna_moe_train.py")
    )
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    cfg = _bench_json("configs", "laguna_xs2_d5.json")
    held = cfg["num_experts"]
    t, lowered = runner.lower_step_on_shapes(
        cfg, _bench_json("traffic", "closed_b1_t8192.json"), topo.devices[0],
    )
    assert t.param_count == {32: 691_623_936, 16: 490_297_344}[held]
    assert not t._check_vma and not cfg["program"]["remat"]
    from akka_allreduce_tpu.ops.moe import row_rungs

    assert row_rungs(8192 * 8, held, 256)[0] == 320 * held
    compiled = lowered.compile()
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes <= 14.5e9
    text = compiled.as_text()
    # the two full layers (48 query heads) keep the library's kernels, a
    # forward and a fused backward each; the three windowed ones (64) run the
    # repo's band kernels, forward, dq and dkv; K/V compact at 8 everywhere
    calls = {
        family: [
            line.split("operand_layout_constraints=", 1)[1] for line in text.splitlines()
            if "tpu_custom_call" in line and re.search(rf"%{family}_[\w.]+ = ", line)
        ] for family in ("splash_mha", "flash_mha_band")
    }
    at = lambda family, heads: sum(  # noqa: E731
        1 for c in calls[family] if re.search(rf"bf16\[(?:1,)?{heads},8192,128\]", c))
    assert at("splash_mha", 48) == len(calls["splash_mha"]) == 2 * 2
    assert at("flash_mha_band", 64) == len(calls["flash_mha_band"]) == 3 * 3
    for name in ("fwd", "dq", "dkv"):
        assert len(re.findall(rf"%flash_mha_band_{name}[\w.]* = ", text)) == 3
    calls = calls["splash_mha"] + calls["flash_mha_band"]
    assert all(re.search(r"bf16\[(?:1,)?8,8192,128\]", c) for c in calls)
    # and the products write that layout: no (B, T, H, D) activation anywhere
    for heads in (64, 48, 8):
        assert f"bf16[1,8192,{heads},128]" not in text
    return compiled, len(calls) + 9 * 4


def _index_scores_layer(topo, monkeypatch):
    """One layer's indexer at the Keye cell's shape (16 index heads of 64, T
    8,192, ``topk`` 2,048): the mask and the indexer's loss with its gradient.
    The index scores run the repo's kernels inside the runs' loops, where the
    compiler gives a kernel 16 MB of VMEM whatever it asks for (the backward
    keeps a block's 512 rows resident inside that), and no (16, 512, keys)
    float32 array is left in the program."""
    from akka_allreduce_tpu.ops import sparse_attention as sa

    t, heads, d = 8192, 16, 64
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731

    def layer(q_i, k_i, w, q, k, lse):
        mask = sa.indexer_mask(q_i, k_i, w, 2048)
        loss, grads = jax.value_and_grad(sa.indexer_kl, argnums=(0, 1, 2))(
            q_i, k_i, w, mask, q, k, lse)
        return mask, loss, grads

    compiled = jax.jit(layer).lower(
        sds((heads, t, d), jnp.bfloat16), sds((t, d), jnp.bfloat16), sds((t, heads), jnp.float32),
        sds((32, t, 128), jnp.bfloat16), sds((4, t, 128), jnp.bfloat16), sds((4, 8, t), jnp.float32),
    ).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    # three runs of blocks make scores for the mask, four for the loss, each
    # of those with its backward
    assert sum("index_scores_fwd" in c for c in calls) == 3 + 4
    assert sum("index_scores_bwd" in c for c in calls) == 4
    assert not re.search(r"f32\[16,512,\d+\]", text)
    return compiled, 3 + 4 + 4


def _masked_attention_layer(topo, monkeypatch):
    """One layer's attention under a learned mask at the Keye cell's shape
    (32 query heads on 4 K/V heads of 128, T 8,192, bf16), forward and
    backward: one ``flash_mha_sparse_fwd`` and ONE ``flash_mha_sparse_bwd``,
    which keeps a K/V head's float32 ``dk`` and ``dv`` of the whole sequence
    in VMEM (two (8192, 128) accumulators under the kernels' 100 MB), and no
    (heads, T, T) array anywhere in the program."""
    from akka_allreduce_tpu.ops import sparse_attention as sa
    from akka_allreduce_tpu.ops.local_attention import heads_first_attention

    t, h, h_kv, d = 8192, 32, 4, 128
    chip = SingleDeviceSharding(topo.devices[0])
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=chip)  # noqa: E731

    def loss(q, k, v, mask):
        out, lse = heads_first_attention(q, k, v, causal=True, mask=mask)
        return out.astype(jnp.float32).sum() + jax.lax.stop_gradient(lse).sum()

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        sds((1, h, t, d), jnp.bfloat16), sds((1, h_kv, t, d), jnp.bfloat16),
        sds((1, h_kv, t, d), jnp.bfloat16), sds((1, t, t), jnp.int8),
    ).compile()
    text = compiled.as_text()
    calls = [line.split(" = ", 1)[0] for line in text.splitlines() if "tpu_custom_call" in line]
    assert sum("flash_mha_sparse_fwd" in c for c in calls) == 1, calls
    assert sum("flash_mha_sparse_bwd" in c for c in calls) == 1, calls
    assert "flash_mha_sparse_dq" not in text and "flash_mha_sparse_dkv" not in text
    assert not re.search(rf"\[(?:1,)?(?:{h}|{h_kv}|{h_kv},{h // h_kv}),{t},{t}\]", text)
    assert [g.shape for g in compiled.out_info] == [(1, h, t, d), (1, h_kv, t, d), (1, h_kv, t, d)]
    # the longest sequence the shapes' rule takes at this head, in float32:
    # the backward with its accumulators of twice the length still compiles
    long = 2 * t
    assert sa.takes_sparse(long, d, d) and not sa.takes_sparse(2 * long, d, d)
    rows = sds((1, h_kv, h // h_kv, long), jnp.float32)
    jax.jit(lambda *a: sa._backward(*a, False)).lower(
        sds((1, h, long, d), jnp.float32), sds((1, h_kv, long, d), jnp.float32),
        sds((1, h_kv, long, d), jnp.float32), sds((1, long, long), jnp.int8),
        sds((1, h, long, d), jnp.float32), rows, rows,
    ).compile()
    return compiled, 2


CASES = {
    "reduce_kernels_8x8M_f32": _reduce_kernels,
    "pallas_ring_4dev_64M_f32": _pallas_ring(None),
    "pallas_ring_4dev_64M_bf16": _pallas_ring("bf16"),
    "pallas_ring_4dev_64M_int8": _pallas_ring("int8"),
    "flash_attention_b8_h16_t2048_d128": _kernel_attention(8, 2048, 16, 16, 128),
    "grouped_psum_moe_shaped_grads_4dev": _grouped_psum,
    "flagship_lm_step": _lm_step(_flagship_sizes, (400e6, 410e6)),
    "lfm2_moe_cell_step": _lfm2_moe_step,
    "joyai_mla_moe_cell_step": _joyai_mla_moe_step,
    "laguna_moe_cell_step": _laguna_moe_step,
    "index_scores_kernels_t8192_j16_d64": _index_scores_layer,
    "masked_attention_kernels_t8192_h32_kv4": _masked_attention_layer,
    # the benchmark's own attention shapes, K/V compact into the kernel
    "splash_attention_b2_t4096_h24_kv2_d128": _kernel_attention(2, 4096, 24, 2, 128),
    "splash_attention_b1_t8192_h32_kv8_d64": _kernel_attention(1, 8192, 32, 8, 64),
    # q, k AND v at head 256 (the Qwen3-Next cell's full layer): the 1024
    # tiles ``_splash_blocks`` takes for bf16, and the 512 they were swept against
    "splash_attention_b1_t8192_h16_kv2_d256": _kernel_attention(1, 8192, 16, 2, 256),
    "splash_attention_b1_t8192_h16_kv2_d256_tiles512": _kernel_attention_at_tiles(
        512, 1, 8192, 16, 2, 256),
    "gated_delta_rule_t8192_h32_d128": _gated_delta_rule_layer,
    "sc2_b2_cell_step": _lm_step(_b2_cell_sizes, (685.9e6, 686.1e6)),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_compiles_for_described_v5e(case, topo, as_tpu, monkeypatch):
    compiled, min_kernels = CASES[case](topo, monkeypatch)
    # compiled mode, not the interpreter: the Mosaic kernels are in the text
    assert compiled.as_text().count("tpu_custom_call") >= min_kernels
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("preset,runner_file", [
    ("tiny_laguna_moe.json", "laguna_moe_train.py"),
    ("tiny_mellum_moe.json", "mellum_moe_train.py"),
])
def test_tiny_mixed_steps_hold_the_band_kernels(preset, runner_file, topo, as_tpu, monkeypatch):
    """The tiny Laguna and Mellum2 steps, widened to shapes the kernel branch
    takes (head 32, a window of 128, T 1,024) and compiled for the described
    chip: three band kernels a windowed layer (forward, dq, dkv), the
    library's forward and fused backward a full layer, and so no ``LocalMask``
    kernel, which would be a splash kernel in a windowed layer's scope."""
    import importlib.util

    monkeypatch.syspath_prepend(BENCH)  # the runner imports the harness
    spec = importlib.util.spec_from_file_location(
        "bench_runner_" + runner_file[:-3], os.path.join(BENCH, "runners", runner_file)
    )
    runner = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(runner)
    cfg = dict(_bench_json("tests", preset), head_dim=32, sliding_window=128,
               use_expert_bias=False)
    kinds = cfg["layer_types"]
    _, lowered = runner.lower_step_on_shapes(
        cfg, {"batch": 1, "seq_len": 1024, "tokens": "copy_half"}, topo.devices[0]
    )
    text = lowered.compile().as_text()
    calls = [line for line in text.splitlines() if "tpu_custom_call" in line]
    named = lambda family: [  # noqa: E731
        m.group(1) for m in (re.search(rf"%({family}\w*?)[.\d]* = ", c) for c in calls) if m]
    windowed, full = kinds.count("sliding_attention"), kinds.count("full_attention")
    assert sorted(named("flash_mha_band_")) == sorted(
        ["flash_mha_band_fwd", "flash_mha_band_dq", "flash_mha_band_dkv"] * windowed)
    assert len(named("splash_mha_")) == 2 * full  # a ``LocalMask`` kernel would add three a band


CELL_FLOATS = 268_435_456  # benchmarks/configs/threshold_allreduce_256m.json


def test_the_cells_allreduce_masks_its_payload_in_place(topo, as_tpu):
    """The program of ``allreduce_256m_mask1`` (psum schedule, 1 GiB of f32 a
    device, donated): the whole-payload mask is ``mask_zero_inplace``'s loop
    on the parameter's own buffer, and nothing in the optimised HLO reads and
    rewrites the payload around it — no payload-sized ``copy`` (what
    ``lax.cond`` brings), no ``select``, and no payload-sized ``multiply``
    (the parent's ``x * mask``, and the constant 1 the psum schedule hands
    ``masked_psum``, which XLA has to fold), alone or inside a fusion."""
    import re

    from akka_allreduce_tpu.comm.allreduce import build_threshold_allreduce
    from akka_allreduce_tpu.parallel import line_mesh

    mesh = line_mesh(devices=topo.devices)
    sh = NamedSharding(mesh, P("line"))
    xs = jax.ShapeDtypeStruct((4, CELL_FLOATS), jnp.float32, sharding=sh)
    valid = jax.ShapeDtypeStruct((4,), jnp.float32, sharding=sh)
    compiled = build_threshold_allreduce(mesh).lower(xs, valid).compile()
    text = compiled.as_text()
    payload_ops = re.findall(
        rf"= f32\[(?:1,)?{CELL_FLOATS}\]\S* ([\w-]+)\(", text
    )
    moves_memory = sorted(
        op for op in payload_ops
        if op not in ("parameter", "bitcast", "get-tuple-element")
    )
    # the zero fill inside the loop, the collective, the count's fill
    assert moves_memory == ["all-reduce", "broadcast", "broadcast"]
    assert len(re.findall(r" while\(", text)) == 1
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == 4 * CELL_FLOATS  # donated, and aliased
    assert mem.temp_size_in_bytes < 1 << 20  # no second payload anywhere

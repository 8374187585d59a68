"""Every cell's programs compiled at their real sizes for a DESCRIBED
``v5e:2x2`` - no chip attached, nothing runs. The rehearsal to pass before a
chip call: the chip's compiler accepts the shapes, the kernels are in the
text, and arguments + temporaries fit 16 GB. The plain reference's step is
compiled too: it has to fit the chip once the trainer's state is freed.

Run by hand (tier-1 does not collect ``benchmarks/tests``), in one process:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests/test_cells_compile.py -q
"""

from __future__ import annotations

import json
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import common  # noqa: F401  (puts the benchmark and the program on sys.path)
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

HBM = 16e9


def _json(*parts):
    with open(os.path.join(common.BENCH, *parts), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")


@pytest.fixture
def as_tpu(monkeypatch):
    """The package's dispatch gates see "tpu"; no persistent cache (such a
    compile can be written to it but never read back)."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _planned_gb(compiled) -> float:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.temp_size_in_bytes) / 1e9


class _ShapeOnlyLM:
    def __init__(self, **kw):
        from akka_allreduce_tpu.models.transformer import TransformerLM

        self._model = TransformerLM(**kw)
        self.apply = self._model.apply

    def init(self, *args):
        return jax.eval_shape(self._model.init, *args)


@pytest.mark.parametrize("traffic", ["closed_b2_t4096", "closed_b1_t4096"])
def test_lm_step_fits_with_its_flash_kernels(traffic, topo, as_tpu, monkeypatch):
    from akka_allreduce_tpu.parallel import data_seq_mesh
    from akka_allreduce_tpu.train import LongContextTrainer

    cfg, tr = _json("configs", "starcoder2_3b_d4.json"), _json("traffic", traffic + ".json")
    prog = cfg["program"]
    monkeypatch.setattr(jax, "device_put", lambda x, *a, **k: x)
    adam = optax.adam(prog["learning_rate"])
    mesh = data_seq_mesh(1, 1, devices=topo.devices[:1])
    t = LongContextTrainer(
        mesh, model_cls=_ShapeOnlyLM, vocab=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], n_layers=cfg["num_hidden_layers"],
        seq_len=tr["seq_len"], compute_dtype=jnp.dtype(prog["compute_dtype"]),
        optimizer=optax.GradientTransformation(
            lambda p: jax.eval_shape(adam.init, p), adam.update
        ),
    )
    from harness.flops import lm_param_counts

    assert t.param_count == lm_param_counts(cfg)["total"]

    def sds(tree, specs):
        return jax.tree.map(
            lambda leaf, s: jax.ShapeDtypeStruct(
                leaf.shape, leaf.dtype, sharding=NamedSharding(mesh, s)),
            tree, specs,
        )

    tokens = jax.ShapeDtypeStruct(
        (tr["batch"], tr["seq_len"]), jnp.int32, sharding=t._data_sharding)
    valid = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=t._valid_sharding)
    compiled = t._step.lower(
        sds(t.params, t._param_specs), sds(t.opt_state, t._opt_specs),
        tokens, tokens, valid,
    ).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3 * cfg["num_hidden_layers"]
    print(traffic, "planned GB", _planned_gb(compiled))
    assert _planned_gb(compiled) < HBM / 1e9


def test_allreduce_has_two_all_reduces_and_fits(topo, as_tpu):
    from akka_allreduce_tpu.comm.allreduce import build_threshold_allreduce
    from akka_allreduce_tpu.parallel import line_mesh

    cfg = _json("configs", "threshold_allreduce_256m.json")
    mesh = line_mesh(devices=topo.devices[: cfg["devices"]])
    sharded = NamedSharding(mesh, P(mesh.axis_names[0]))
    fn = build_threshold_allreduce(
        mesh, schedule=cfg["schedule"], compress=cfg["compress"],
        bucket_size=cfg["bucket_size"], donate=cfg["donate"],
    )
    xs = jax.ShapeDtypeStruct(
        (cfg["devices"], cfg["floats_per_device"]), jnp.float32, sharding=sharded)
    valid = jax.ShapeDtypeStruct((cfg["devices"],), jnp.float32, sharding=sharded)
    compiled = fn.lower(xs, valid).compile()
    text = compiled.as_text()
    assert sum(1 for line in text.splitlines() if " all-reduce(" in line) == 2
    mem = compiled.memory_analysis()
    planned = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + mem.temp_size_in_bytes) / 1e9
    print("allreduce planned GB per device", planned)
    assert planned < HBM / 1e9


def test_reference_step_fits_the_freed_chip(topo, as_tpu):
    from reference import lm_plain

    cfg, tr = _json("configs", "starcoder2_3b_d4.json"), _json("traffic", "closed_b2_t4096.json")
    chip = SingleDeviceSharding(topo.devices[0])
    leaves = {
        n: jax.ShapeDtypeStruct(s, jnp.float32, sharding=chip)
        for n, s in lm_plain.param_shapes(cfg).items()
    }
    tokens = jax.ShapeDtypeStruct((tr["batch"], tr["seq_len"]), jnp.int32, sharding=chip)
    t = jax.ShapeDtypeStruct((), jnp.float32, sharding=chip)
    compiled = lm_plain.make_step(cfg, cfg["program"]).lower(
        leaves, leaves, leaves, t, tokens, tokens
    ).compile()
    print("reference step planned GB", _planned_gb(compiled))
    assert _planned_gb(compiled) < 15.0  # leaves room for what outlives the trainer

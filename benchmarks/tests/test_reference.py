"""The plain references against the program at a tiny size on the CPU, the
controls that have to fail, and whole runs (everything but ``run.py``'s look
for a chip) with the timed path sound and broken.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        python -m pytest benchmarks/tests/test_reference.py -q
"""

import copy
import subprocess
import sys

import common
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from harness import spec, traffic
from reference import lm_plain, masked_sum

needs_four = pytest.mark.skipif(
    len(jax.devices()) < 4, reason="needs 4 (virtual) devices"
)


def _failing(result_or_compared):
    return [c["name"] for c in result_or_compared if not c["ok"]]


# -- the LM reference --------------------------------------------------------------


def test_logits_match_the_programs_forward():
    from akka_allreduce_tpu.models.transformer import TransformerLM

    lm_train = spec.load_module("runners", "lm_train")
    cfg = common.tiny_config("lm")
    leaves = lm_plain.init_params(cfg, 3)
    tokens, _ = traffic.token_batch(common.TINY_TRAFFIC["lm"], cfg["vocab_size"], 3, 0)
    model = TransformerLM(
        vocab=cfg["vocab_size"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], n_layers=cfg["num_hidden_layers"],
    )
    want = lm_plain.logits(leaves, tokens, cfg, cfg["program"])
    got = model.apply(lm_train.to_program_tree(leaves, cfg), tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4 * float(jnp.max(jnp.abs(want)))


def test_lm_run_is_correct_in_f32_and_not_in_bf16(capsys):
    sound = common.run_tiny("lm")
    assert sound["correct"] and sound["failed"] == 0 and sound["attempted"] > 3
    assert set(sound["metrics"]) == {"train_tokens_per_s", "setup_s"}
    lower = common.tiny_config("lm")
    lower["program"]["compute_dtype"] = "bfloat16"
    assert not common.run_tiny("lm", config=lower)["correct"]


def test_lm_control_fails_a_number():
    """The reference one step down (int8 matmuls, bf16 state) in the
    program's place: not correct."""
    lm_train = spec.load_module("runners", "lm_train")
    cfg = common.tiny_config("lm")
    batches = [
        traffic.token_batch(common.TINY_TRAFFIC["lm"], cfg["vocab_size"], 5, i)
        for i in range(3)
    ]
    ref = lm_plain.follow(cfg, cfg["program"], 5, batches)
    control = lm_plain.follow(cfg, cfg["program"], 5, batches, lm_plain.CONTROL)
    assert _failing(lm_train.compare(control, ref, cfg["correct_limits"]))
    assert not _failing(lm_train.compare(ref, ref, cfg["correct_limits"]))


def test_lm_step_that_returns_its_state_unchanged_is_not_correct(monkeypatch):
    from akka_allreduce_tpu.train import LongContextTrainer

    real = LongContextTrainer.train_step

    def frozen(self, tokens, labels, valid=None):
        params, opt = (jax.tree.map(jnp.copy, t) for t in (self.params, self.opt_state))
        m = real(self, tokens, labels, valid)
        if self.step_num > 1:  # the first gradient is read, then nothing moves
            self.params, self.opt_state = params, opt
        return m

    monkeypatch.setattr(LongContextTrainer, "train_step", frozen)
    assert not common.run_tiny("lm")["correct"]


# -- the allreduce reference -----------------------------------------------------------


def test_payload_is_the_same_on_host_and_device_and_sums_exactly():
    idx = np.arange(4096, dtype=np.uint32)
    for seed, round_, dev in ((0, 0, 0), (2**31 + 12345, 977, 3), (2**33 + 7, 5, 1)):
        off = masked_sum.stream_offset(seed, round_, dev)
        traced = jax.jit(lambda r, d, seed=seed: masked_sum.stream_offset_u32(
            seed, r, d, jnp))(np.uint32(round_), np.uint32(dev))
        assert int(traced) == off
        host = masked_sum.payload(idx, off, np)
        dev_ = jax.jit(lambda i, off=off: masked_sum.payload(i, off, jnp))(idx)
        assert np.array_equal(host, np.asarray(dev_))
        assert host.min() >= -1 and host.max() < 1 and len(np.unique(host)) > 3000
        assert np.array_equal(host * 2**15, np.round(host * 2**15))
    total, n = masked_sum.masked_sum(idx, 1, 2, [1, 0, 1, 1], np)
    parts = [masked_sum.payload(idx, masked_sum.stream_offset(1, 2, d), np)
             for d in (0, 2, 3)]
    assert n == 3.0 and np.array_equal(total, sum(p.astype(np.float64) for p in parts))


@needs_four
def test_allreduce_run_is_correct_and_a_bf16_wire_is_not():
    sound = common.run_tiny("allreduce")
    assert sound["correct"] and sound["failed"] == 0
    assert set(sound["metrics"]) == {"allreduce_bus_bw", "setup_s"}
    lower = common.tiny_config("allreduce")
    lower["compress"] = "bf16"
    control = common.run_tiny("allreduce", config=lower)
    assert not control["correct"] and control["failed"] == control["attempted"]


@needs_four
def test_allreduce_with_an_answer_altered_is_not_correct(monkeypatch):
    from akka_allreduce_tpu.comm import allreduce

    real = allreduce.masked_psum

    def altered(x, valid, axis_names, **k):
        total, count = real(x, valid, axis_names, **k)
        return total.at[-1].add(2.0 ** -15), count  # one bit of one element

    monkeypatch.setattr(allreduce, "masked_psum", altered)
    result = common.run_tiny("allreduce")
    assert not result["correct"] and result["failed"] == result["attempted"]


@needs_four
def test_a_traced_run_without_a_device_plane_is_refused(tmp_path):
    """The CPU's trace holds no ``/device:TPU:<n>`` plane: nothing to reduce,
    so no result (the window itself ran: the profiler starts and stops)."""
    with pytest.raises(ValueError, match="no /device:TPU"):
        common.run_tiny("allreduce", trace=True, tmp=str(tmp_path))


# -- run.py's look for a chip ---------------------------------------------------------


def test_run_py_refuses_to_run_without_a_tpu():
    import os

    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    done = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "sc2_3b_train_b2_t4096",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=common.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode != 0
    assert '"metrics"' not in done.stdout and "no TPU" in done.stderr

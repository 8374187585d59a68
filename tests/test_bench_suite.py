"""BASELINE config suite smoke tests (small payloads, CPU mesh)."""

from __future__ import annotations

from akka_allreduce_tpu import bench_suite


def test_config1_local_engine_record():
    rec = bench_suite.config1_local_engine(size=50_000, rounds=5)
    assert rec["config"] == 1 and rec["workers"] == 4
    assert rec["rounds"] == 5
    assert rec["throughput_mbs"] > 0


def test_config5_dropout_recovery_record():
    import jax

    before = jax.config.jax_compilation_cache_dir
    rec = bench_suite.config5_dropout_recovery(size=20_000)
    # config 5 owns no cache directory (the round-5 crash pair came from the
    # one it used to enable): it names whatever cache the process has —
    # none under the tests — and leaves the setting alone
    assert rec["compile_cache"] == before
    assert jax.config.jax_compilation_cache_dir == before
    assert rec["config"] == 5
    # th=0.75 of 4 workers with one fully dropped: all rounds complete
    assert rec["rounds_completed"] == 10
    # contributor counts reflect the threshold, not full participation
    assert 2.0 <= rec["mean_contributors"] <= 3.0
    # tier 2: the elastic trainer re-meshed off the lost node, stepped,
    # then re-meshed the late joiner back in and stepped again
    assert rec["dropped_remeshed"] is True
    assert rec["rejoin_remeshed"] is True
    assert rec["remeshed"] is True
    assert rec["remesh_nodes"] >= 1
    assert rec["drop_remesh_and_first_step_s"] > 0
    assert rec["rejoin_remesh_and_first_step_s"] > 0


def test_config3_mlp_step_record():
    rec = bench_suite.config3_mlp_step(steps=3, batch_per_device=4)
    assert rec["config"] == 3
    assert rec["step_ms"] > 0
    assert rec["loss_last"] <= rec["loss_first"] * 1.5  # sanity, not strict

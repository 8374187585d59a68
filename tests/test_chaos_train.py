"""Tier 7 — workload resilience (RESILIENCE.md, ISSUE 14).

Two layers of evidence, both in real subprocesses:

- the ``chaos-train`` drill's fastest (dp) arm: a real master + 3
  ``chaos-train-node`` processes, each driving an ElasticTrainer-wrapped
  REAL trainer; a seeded ``crash:node=2,at=round30`` kills one mid-step,
  every survivor re-meshes and its loss curve resumes inside the pinned
  band, rounds keep completing, the run ends gracefully. ``make
  chaos-train`` runs the pipeline arm — the restage headline — from the
  shell.
- the ElasticTrainer edge scenarios (tests/elastic_zoo_worker.py):
  compress-follows-policy with a REAL AdaptiveController driving a live
  trainer's ICI compress level mid-run (EF residual preserved, int8 step
  error <= the 0.15 budget), the min_nodes refusal/recovery cycle,
  back-to-back re-meshes, sharded snapshot determinism across a
  device-count change, and the pipeline restage rule with its DP-only
  fallback.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_WORKER = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "elastic_zoo_worker.py")


def _run_scenarios(*names: str, timeout: int = 420) -> str:
    proc = subprocess.run(
        [sys.executable, _WORKER, *names],
        cwd=_ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, (
        f"scenarios {names} failed:\n{proc.stdout[-4000:]}\n"
        f"{proc.stderr[-4000:]}"
    )
    for name in names:
        assert f"OK {name}" in proc.stdout, proc.stdout
    return proc.stdout


def test_wire_to_compress_covers_the_policy_ladder():
    """Every non-inherit RoundPolicy wire stamp maps to a valid trainer
    compress mode — the ONE map both planes degrade through."""
    from akka_allreduce_tpu.control.adapt import _WIRE_LADDER, WIRE_TO_COMPRESS
    from akka_allreduce_tpu.protocol import RoundPolicy

    assert set(WIRE_TO_COMPRESS) == set(RoundPolicy.WIRE_MODES) - {""}
    assert WIRE_TO_COMPRESS["f32"] is None
    assert WIRE_TO_COMPRESS["f16"] == "bf16"
    assert WIRE_TO_COMPRESS["int8"] == "int8"
    # the controller's ladder emits only mapped stamps
    assert set(_WIRE_LADDER) <= set(WIRE_TO_COMPRESS)


def test_compress_follows_policy_mid_run():
    """ISSUE 14 acceptance: an AdaptiveController degrade event changes a
    LIVE trainer's ICI compress level mid-run — through the
    trainer-factory rebuild path, EF residual preserved, int8 step error
    inside the 0.15 budget."""
    out = _run_scenarios("compress_follows_policy")
    assert "<= 0.15" in out


def test_elastic_trainer_edges():
    """min_nodes refusal then recovery on rejoin; a second membership
    change landing back-to-back; snapshot->restore determinism for the
    sharded (zero1/fsdp) protocol under a device-count change."""
    _run_scenarios(
        "min_nodes_refusal_recovery",
        "back_to_back_remesh",
        "sharded_snapshot_determinism",
    )


def test_pipeline_restage_and_dp_fallback():
    """The restage rule (L/S' layers per stage over the surviving pipe
    axis) and the DP-only floor — including a refusing factory degrading
    through fallback_mesh_factory instead of wedging."""
    _run_scenarios("pipeline_restage_fallback")


def test_chaos_train_dp_arm(tmp_path):
    """The chaos-train drill, dp arm (the tier-1-speed family): seeded
    mid-step node kill -> survivors re-mesh, loss continuity inside the
    band, zero wedged rounds, graceful completion. Same assertions the
    Makefile's pipeline arm runs, re-checked here from the summary JSON."""
    proc = subprocess.run(
        [
            sys.executable, "-m", "akka_allreduce_tpu", "chaos-train",
            "--seed", "1234", "--family", "dp",
            "--out-dir", str(tmp_path / "run"),
        ],
        cwd=_ROOT, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    assert lines, proc.stderr[-2000:]
    summary = json.loads(lines[-1])
    assert proc.returncode == 0, summary
    assert summary["failures"] == [], summary
    assert summary["victim_exit"] == 23  # the seeded chaos crash, pinned
    assert summary["master_done"] is True
    assert summary["survivor_rounds"] >= 25  # zero wedged rounds: progress
    # every survivor re-meshed and resumed inside the continuity band
    assert len(summary["continuity"]) == summary["nodes"] - 1
    for k, c in summary["continuity"].items():
        assert c["post_median"] <= c["bar"], (k, c)
    for k, s in summary["node_summaries"].items():
        assert s["remeshes"] >= 1 and s["generation"] >= 1, (k, s)

"""CLI bootstrap tests (SURVEY.md §2 L4 — the reference's role mains + run
scripts, exercised in-process on the virtual CPU mesh)."""

import json

import pytest

from akka_allreduce_tpu.__main__ import _COMPILE_CACHED, COMMANDS, main


class TestCLI:
    def test_help_and_unknown(self, capsys):
        assert main([]) == 0
        assert "commands:" in capsys.readouterr().out
        assert main(["no-such-cmd"]) == 2

    @pytest.mark.parametrize(
        "name", ["bench", "bench-suite", "bench-mfu", "bench-checkpoint"]
    )
    def test_retired_bench_commands_are_refused(self, name, capsys):
        """A rate is measured by ``benchmarks/run.py`` and by nothing else:
        the package's own harnesses went in PR 45, with their commands."""
        assert main([name]) == 2
        assert "expected one of" in capsys.readouterr().out
        assert name not in COMMANDS
        assert _COMPILE_CACHED <= set(COMMANDS)

    def test_local_demo(self, capsys):
        assert (
            main(
                ["local-demo", "--nodes", "4", "--size", "10000", "--rounds", "3"]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "rounds_completed=3" in out

    def test_train_mlp_with_metrics_and_resume(self, tmp_path, capsys):
        metrics = tmp_path / "m.jsonl"
        ckpt = tmp_path / "ckpt"
        args = [
            "train-mlp", "--steps", "2", "--batch", "16",
            "--hidden", "8",
            "--metrics-out", str(metrics),
            "--checkpoint-dir", str(ckpt), "--checkpoint-every", "1",
        ]
        assert main(args) == 0
        lines = [json.loads(l) for l in metrics.read_text().splitlines()]
        # round 3: a train_summary record (tflops/mfu) follows the steps
        steps = [l for l in lines if l.get("kind") == "train_step"]
        assert [l["step"] for l in steps] == [1, 2]
        assert all(l["contributors"] == 8.0 for l in steps)
        assert any(l.get("kind") == "train_summary" for l in lines)

        assert main(args) == 0  # second run resumes from the checkpoint
        assert "resumed from step 2" in capsys.readouterr().out

    def test_train_lm(self, tmp_path, capsys):
        metrics = tmp_path / "lm.jsonl"
        args = [
            "train-lm", "--steps", "2", "--batch", "4", "--seq-len", "32",
            "--d-model", "16", "--heads", "2", "--layers", "1",
            "--vocab", "16", "--metrics-out", str(metrics),
        ]
        assert main(args) == 0
        out = capsys.readouterr().out
        assert "dp=2 x sp=4" in out  # 8-device mesh factors to 2x4
        lines = [json.loads(l) for l in metrics.read_text().splitlines()]
        steps = [l for l in lines if l.get("kind") == "train_step"]
        assert [l["step"] for l in steps] == [1, 2]
        assert all(l["contributors"] == 2.0 for l in steps)

    def test_delta_checkpoint_cli_roundtrip(self, tmp_path, capsys):
        d = str(tmp_path / "delta")
        args = [
            "train-mlp", "--steps", "2", "--batch", "16", "--hidden", "8",
            "--checkpoint-dir", d, "--checkpoint-every", "1",
            "--delta-checkpoint",
        ]
        assert main(args) == 0
        capsys.readouterr()
        assert main(args) == 0  # second run resumes from the delta store
        assert "resumed from step 2" in capsys.readouterr().out
        # round 5: async composes with delta (AsyncDeltaCheckpointer) —
        # the combined flags train, save off-thread, and resume
        assert main(args + ["--async-checkpoint"]) == 0
        assert "resumed from step 4" in capsys.readouterr().out

    def test_train_pp_rejects_bad_virtual_schedule(self, capsys):
        import pytest

        # flag combinations the trainer rejects surface as argparse errors
        # (exit 2), not raw ValueError tracebacks
        def err_of(argv):
            with pytest.raises(SystemExit) as e:
                main(argv)
            assert e.value.code == 2
            return capsys.readouterr().err

        assert "interleaved" in err_of(
            ["train-pp", "--virtual", "2", "--schedule", "gpipe"]
        )
        assert "not divisible" in err_of(
            [
                "train-pp", "--schedule", "interleaved", "--virtual", "3",
                "--layers-per-stage", "2",
            ]
        )
        err_of(["train-pp", "--virtual", "0"])
        # interleaved with the default --virtual 1 is plain 1f1b
        assert "virtual_chunks >= 2" in err_of(
            ["train-pp", "--schedule", "interleaved"]
        )
        # a constraint never hand-copied into the CLI still converts
        assert "overlap" in err_of(
            ["train-pp", "--schedule", "1f1b", "--overlap"]
        )

    def test_elastic_demo_family_reshapes_mesh(self, capsys):
        """--family moe: the expert axis re-shapes with membership
        (ep 4 -> 2 -> 4 on the 8-device mesh) through the demo loop."""
        assert (
            main(
                [
                    "elastic-demo", "--family", "moe", "--steps", "10",
                    "--drop-at", "2", "--rejoin-at", "8",
                ]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "re-meshed to 3 nodes / dp3 x ep2" in out
        assert "re-meshed to 4 nodes / dp2 x ep4" in out

    def test_elastic_demo(self, capsys):
        # the drop window must outlast the phi detector's suspicion ramp
        # (~3-4 silent intervals at threshold 8), hence drop at 2, rejoin at 8
        assert (
            main(
                [
                    "elastic-demo", "--steps", "10", "--drop-at", "2",
                    "--rejoin-at", "8", "--batch-per-device", "4",
                ]
            )
            == 0
        )
        out = capsys.readouterr().out
        assert "re-meshed to 3 nodes" in out
        assert "re-meshed to 4 nodes" in out
        assert "final generation 2" in out

"""The no-fallback contract of ``chip_smoke.py`` and of the start-up code it
proves: no chip -> it fails; ``--multichip`` selects only the four-chip
phases; the compile cache is placed by one rule; an unknown TPU has no
silent MFU; one process per chip."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
import tempfile

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(ROOT, "chip_smoke.py")

sys.path.insert(0, ROOT)
import chip_smoke  # noqa: E402


def _run_smoke(script: str, cwd: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, script], cwd=cwd, capture_output=True, text=True,
        timeout=120, env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )


@pytest.mark.parametrize("where", ["checkout", "bare_directory"])
def test_smoke_fails_without_a_chip(where, tmp_path):
    """On the CPU the smoke must FAIL — non-zero, ``"ok": false`` as the
    last line, naming the missing chip — from the checkout and from a
    directory that holds the script and nothing else of the repo."""
    script, cwd = SMOKE, ROOT
    if where == "bare_directory":
        script, cwd = shutil.copy(SMOKE, tmp_path), str(tmp_path)
    proc = _run_smoke(script, cwd)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    verdict = json.loads(proc.stdout.strip().splitlines()[-1])
    assert verdict["ok"] is False
    assert verdict["failed"] == "device"
    assert "no accelerator" in verdict["error"] and "tpu" in verdict["error"]


def test_multichip_selects_only_the_four_chip_phases():
    assert chip_smoke.parse_args([]).multichip is False
    assert chip_smoke.parse_args(["--multichip"]).multichip is True
    assert chip_smoke.children_for(False) == ["main", "second_process"]
    assert chip_smoke.children_for(True) == ["multichip"]
    # the parent holds no chip: deciding what to run never imports JAX
    probe = subprocess.run(
        [sys.executable, "-c",
         "import sys, chip_smoke\n"
         "chip_smoke.children_for(chip_smoke.parse_args(['--multichip'])"
         ".multichip)\n"
         "print('jax' in sys.modules)"],
        cwd=ROOT, capture_output=True, text=True, timeout=60,
    )
    assert probe.stdout.strip() == "False", probe.stderr


@pytest.mark.parametrize("env_dir", ["/somewhere/placed/from/outside", None])
def test_compile_cache_rule(env_dir, monkeypatch):
    """``JAX_COMPILATION_CACHE_DIR`` set -> code sets NO directory (JAX
    reads the variable itself); unset -> one fixed path inside the checkout,
    never a temp dir. ``jax.config.update`` is recorded, not applied: the
    test process must stay without a persistent cache."""
    import jax

    from akka_allreduce_tpu.utils import compile_cache

    updates = []
    monkeypatch.setattr(
        jax.config, "update", lambda name, value: updates.append((name, value))
    )
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV_VAR, env_dir)
    got = compile_cache.enable_compile_cache()
    if env_dir is not None:
        assert got == env_dir and updates == []
        return
    fixed = os.path.join(ROOT, ".jax_cache")
    assert got == fixed == compile_cache.DEFAULT_DIR
    assert updates == [("jax_compilation_cache_dir", fixed)]
    assert not fixed.startswith(tempfile.gettempdir())
    assert compile_cache.enable_compile_cache() == fixed  # same path again


@pytest.mark.parametrize(
    "argv,placed",
    [
        (["train-lm"], True),
        (["train-moe"], True),
        (["elastic-demo"], True),
        (["cluster-node"], False),  # cluster roles and drill children:
        (["chaos-train-node"], False),  # no persistent cache (Design 1)
        ([], False),
    ],
)
def test_only_the_process_entry_places_the_cache(argv, placed, monkeypatch):
    import akka_allreduce_tpu.__main__ as cli
    from akka_allreduce_tpu import utils

    calls = []
    monkeypatch.setattr(
        utils, "enable_compile_cache", lambda: calls.append("placed")
    )
    monkeypatch.setattr(cli, "main", lambda: 0)
    monkeypatch.setattr(sys, "argv", ["akka_allreduce_tpu", *argv])
    assert cli._COMPILE_CACHED <= set(cli.COMMANDS)
    assert cli._process_main() == 0
    assert calls == (["placed"] if placed else [])


def test_in_process_main_never_places_the_cache(monkeypatch):
    """``main([...])`` — how the tests and chip_smoke call the CLI — leaves
    the cache alone: only ``python -m akka_allreduce_tpu`` places it."""
    import akka_allreduce_tpu.__main__ as cli
    from akka_allreduce_tpu import utils

    calls = []
    monkeypatch.setattr(
        utils, "enable_compile_cache", lambda: calls.append("placed")
    )
    monkeypatch.setitem(cli.COMMANDS, "train-lm", lambda argv: 0)
    assert cli.main(["train-lm", "--steps", "1"]) == 0
    assert calls == []


class _Device:
    def __init__(self, platform: str, device_kind: str) -> None:
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize(
    "device,want",
    [
        (_Device("tpu", "TPU v5 lite"), 197e12),
        (_Device("cpu", "cpu"), None),
        (_Device("tpu", "TPU v9 not in the table"), ValueError),
    ],
)
def test_peak_flops_has_no_silent_none_on_a_tpu(device, want):
    from akka_allreduce_tpu.utils.benchmarking import device_peak_flops, mfu

    if want is ValueError:
        with pytest.raises(ValueError, match="TPU v9 not in the table"):
            device_peak_flops(device)
        return
    assert device_peak_flops(device) == want
    got = mfu(1e12, 1.0, device_peak_flops(device))
    assert (got is None) if want is None else (got == 1e12 / want)


# 16 tokens; QK^T and AV are 2 x 2 B T^2 d = 2,048 a layer forward, 6,144
# over three layers, half of it under a causal mask
_LM = dict(n_params=1000, batch=2, seq=8, d_model=4, n_layers=3)
_MOE_TREE = {
    "embed": np.zeros((10, 4)),  # 40
    "layer_0": {
        "router": np.zeros((4, 4)),  # 16
        "moe_experts": {
            "w_in": np.zeros((4, 4, 8)),  # 128
            "w_out": np.zeros((4, 8, 4)),  # 128
        },
    },
}


@pytest.mark.parametrize(
    "fn,kwargs,by_hand",
    [
        # 6 x 1,000 x 16, and three forwards' worth of 3,072
        pytest.param(
            "transformer_train_flops", _LM, 96_000 + 9_216, id="lm_causal"
        ),
        pytest.param(
            "transformer_train_flops", dict(_LM, causal=False),
            96_000 + 18_432, id="lm_not_causal",
        ),
        # a fourth forward of both terms: 8 x 16,000 + 4 x 3,072
        pytest.param(
            "transformer_train_flops", dict(_LM, remat=True),
            128_000 + 12_288, id="lm_causal_remat",
        ),
        pytest.param(
            "dense_train_flops", dict(n_params=1000, tokens=16), 96_000,
            id="dense",
        ),
        # 40 + 16 whole, the 256 under ``moe_`` at 2 of 4
        pytest.param(
            "moe_active_params", dict(params=_MOE_TREE, topk=2, n_experts=4),
            56 + 128, id="moe_active_top2_of_4",
        ),
    ],
)
def test_flop_counts_against_counts_by_hand(fn, kwargs, by_hand):
    """The counts behind the train CLIs' MFU print and ``soak.py``."""
    from akka_allreduce_tpu.utils import benchmarking

    assert getattr(benchmarking, fn)(**kwargs) == by_hand


def test_second_node_process_on_one_chip_is_refused(monkeypatch):
    """``train-cluster-node``'s guard: a second process that would need the
    same chip fails with a message, before touching JAX; on the CPU
    platform there is nothing to claim."""
    from akka_allreduce_tpu.__main__ import _claim_accelerator

    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert _claim_accelerator("train-cluster-node") is None
    monkeypatch.delenv("JAX_PLATFORMS")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", f"test-{os.getpid()}")
    first = _claim_accelerator("train-cluster-node")
    try:
        with pytest.raises(SystemExit, match="ONE node process per chip"):
            _claim_accelerator("train-cluster-node")
    finally:
        first.close()
        os.remove(first.name)
    # released with its holder: the chip can be claimed again
    again = _claim_accelerator("train-cluster-node")
    again.close()
    os.remove(again.name)

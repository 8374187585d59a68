"""The per-test limit of tests/conftest.py: a test that blocks forever is one
named failure, the tests after it still run, and nothing is left armed.

The blocking cases run ``python -m pytest`` in a subprocess on a two-test file
under a COPY of the repo's conftest.py, with the repo's pyproject.toml as the
ini-file and ``--strict-markers`` (so the ``limit`` marker must be registered
there), serially and under ``-n 2 --dist loadfile`` as tier-1 runs.
"""

import os
import shutil
import signal
import subprocess
import sys
import time
import xml.etree.ElementTree as ET

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.skipif(
    not hasattr(signal, "SIGALRM"), reason="the limit is SIGALRM-based"
)

BLOCK_IN_ASYNCIO = "asyncio.run(asyncio.Event().wait())"
# a duration no other process on the machine sleeps for: the test looks for
# it in /proc to prove the child died with the test
SLEEP_ARG = "60.0625"
BLOCK_IN_SUBPROCESS = f'subprocess.run(["sleep", "{SLEEP_ARG}"])'

TWO_TESTS = """\
import asyncio
import subprocess

import pytest


@pytest.mark.limit(2)
def test_blocks_forever():
    {block}


def test_runs_after_the_hang():
    assert True
"""


def _run_pytest_on(tmp_path, body, xdist):
    shutil.copy(os.path.join(ROOT, "tests", "conftest.py"), tmp_path)
    (tmp_path / "test_two.py").write_text(body)
    junit = tmp_path / "junit.xml"
    env = {
        k: v for k, v in os.environ.items() if not k.startswith("PYTEST_")
    }
    t0 = time.monotonic()
    proc = subprocess.run(
        [
            sys.executable, "-m", "pytest", str(tmp_path / "test_two.py"),
            "-q", "-c", os.path.join(ROOT, "pyproject.toml"),
            "--rootdir", str(tmp_path), "--strict-markers",
            "-p", "no:cacheprovider", "-p", "no:randomly",
            f"--junitxml={junit}",
            *(["-p", "xdist", "-n", "2", "--dist", "loadfile"] if xdist else []),
        ],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    seconds = time.monotonic() - t0
    cases = {
        c.get("name"): c for c in ET.parse(junit).getroot().iter("testcase")
    }
    return proc, cases, seconds


def _sleepers():
    found = []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                argv = f.read().split(b"\0")
        except OSError:
            continue  # gone between listdir and open
        if SLEEP_ARG.encode() in argv:
            found.append(int(pid))
    return found


@pytest.mark.parametrize("xdist", [False, True], ids=["serial", "n2_loadfile"])
@pytest.mark.parametrize(
    "block", [BLOCK_IN_ASYNCIO, BLOCK_IN_SUBPROCESS], ids=["asyncio", "subprocess"]
)
def test_blocked_test_is_one_named_failure(tmp_path, block, xdist):
    proc, cases, seconds = _run_pytest_on(
        tmp_path, TWO_TESTS.format(block=block), xdist
    )
    tail = proc.stdout[-3000:] + proc.stderr[-2000:]
    assert proc.returncode == 1, tail  # tests failed; not a crash, not a hang
    assert sorted(cases) == ["test_blocks_forever", "test_runs_after_the_hang"]
    failure = cases["test_blocks_forever"].find("failure")
    assert failure is not None, tail
    report = failure.get("message", "") + (failure.text or "")
    assert "TimeoutError" in report
    assert "test_two.py::test_blocks_forever ran over its 2 s limit" in report
    after = cases["test_runs_after_the_hang"]
    assert after.find("failure") is None and after.find("error") is None, tail
    assert "1 failed, 1 passed" in proc.stdout, tail
    # the limit (2 s) ended it, not the 60 s sleep nor this run's timeout=120
    assert seconds < 45, f"took {seconds:.1f} s\n{tail}"
    if block == BLOCK_IN_SUBPROCESS and os.path.isdir("/proc"):
        assert _sleepers() == [], "the blocked test's child outlived the run"


@pytest.fixture
def sigalrm_sentinel():
    """Installs a handler BEFORE the call phase (so the hook must put it
    back) and checks, AFTER it, that the hook left nothing behind."""

    def sentinel(signum, frame):  # pragma: no cover - never delivered
        raise AssertionError("sentinel SIGALRM handler was called")

    before = signal.signal(signal.SIGALRM, sentinel)
    seen = {}
    try:
        yield seen
        assert signal.getsignal(signal.SIGALRM) is sentinel, (
            "the limit hook did not restore the previous SIGALRM handler"
        )
        assert signal.alarm(0) == 0, "an alarm was still pending after the test"
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, before)
    # and inside the test the hook's own handler was armed with this limit
    assert seen["handler"] is not sentinel and callable(seen["handler"])
    assert 0 < seen["remaining_s"] <= 7


@pytest.mark.limit(7)
def test_limit_disarms_and_restores_the_handler(sigalrm_sentinel):
    seen = sigalrm_sentinel
    seen["handler"] = signal.getsignal(signal.SIGALRM)
    seen["remaining_s"] = signal.getitimer(signal.ITIMER_REAL)[0]


def test_default_limit_applies_without_a_marker(request):
    from conftest import DEFAULT_LIMIT_S

    assert request.node.get_closest_marker("limit") is None
    remaining = signal.getitimer(signal.ITIMER_REAL)[0]
    assert DEFAULT_LIMIT_S - 5 < remaining <= DEFAULT_LIMIT_S

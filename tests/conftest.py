"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax initializes.

This mirrors the reference's test philosophy (SURVEY.md §5): multi-node behavior is
tested without any real cluster. Here "multi-node" data-plane tests run on one host
via ``xla_force_host_platform_device_count=8``; control-plane tests use in-process
fake peers. Numeric oracle throughout: numpy masked-sum / count.

This is the CPU recipe: ``JAX_PLATFORMS=cpu``, 8 virtual devices, Pallas kernels in
interpret mode (ops/_platform.py), and no persistent compile cache.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

# Force, don't setdefault: the suite runs on the CPU whatever the caller's
# environment says, and the subprocesses tests spawn inherit this.
os.environ["JAX_PLATFORMS"] = "cpu"
# tier-1 runs WITHOUT a persistent compile cache (ROADMAP Design 1 ties a
# hang/abort to it); JAX would read this variable at import
os.environ.pop("JAX_COMPILATION_CACHE_DIR", None)
if "--xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

# One limit for every test: a hang costs its worker this long and shows as
# one named failure, never the whole run's clock. The slowest test takes 85 s
# with seven files running beside it (ISSUE 25's reading).
DEFAULT_LIMIT_S = 300


@pytest.hookimpl(wrapper=True)
def pytest_runtest_call(item):
    """Arm SIGALRM around the test body; ``@pytest.mark.limit(seconds)``
    overrides the default. Tests run in the main thread of each xdist
    worker, so the handler's TimeoutError lands in the test and unwinds
    asyncio.run / subprocess.run / time.sleep alike. (Python runs the
    handler between bytecodes: a thread stuck inside native code that
    never returns is still the run's own clock's to end.)"""
    if not hasattr(signal, "SIGALRM"):
        return (yield)
    mark = item.get_closest_marker("limit")
    n = int(mark.args[0]) if mark else DEFAULT_LIMIT_S

    def on_alarm(signum, frame):
        raise TimeoutError(f"{item.nodeid} ran over its {n} s limit")

    old = signal.signal(signal.SIGALRM, on_alarm)
    signal.alarm(n)
    try:
        return (yield)
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


#: top-level packages that only the Orbax checkpointer needs (PERF.md
#: section 5, set-up): seconds of a start, so no way into the package loads
#: them; ``TrainerCheckpointer``'s construction does
DEFERRED_IMPORTS = ("orbax", "tensorstore", "google.cloud")

_FRESH_REPORT = textwrap.dedent(
    """
    import json, sys
    from akka_allreduce_tpu.obs import metrics, trace
    print(json.dumps({
        "deferred": sorted(
            m for m in set(sys.modules) - at_start
            if any(m == p or m.startswith(p + ".") for p in %r)
        ),
        "import_s": metrics.REGISTRY.snapshot().get(
            "checkpoint.orbax_import_s"
        ),
        "spans": sum(
            r["name"] == "checkpoint.import_orbax" for r in trace.snapshot()
        ),
        "extra": extra,
    }))
    """
    % (DEFERRED_IMPORTS,)
)


@pytest.fixture
def run_fresh():
    """``run_fresh(body, *argv)``: run ``body`` in a NEW interpreter on the
    CPU (this one has long since imported everything) and return what it
    left behind: ``deferred``, the modules of ``DEFERRED_IMPORTS`` that
    ``body`` loaded (``site`` leaves the bare namespace packages ``google``
    and ``google.cloud`` in ``sys.modules`` before any import, so only what
    was added counts); ``import_s``, the gauge
    ``checkpoint.orbax_import_s`` or None; ``spans``, how many
    ``checkpoint.import_orbax`` spans ended; ``extra``, whatever JSON-ready
    value ``body`` bound to that name."""

    def run(body: str, *argv: str) -> dict:
        script = (
            "import sys\nat_start = set(sys.modules)\nextra = None\n"
            + textwrap.dedent(body)
            + _FRESH_REPORT
        )
        out = subprocess.run(
            [sys.executable, "-c", script, *argv],
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            capture_output=True,
            text=True,
            timeout=240,
        )
        assert out.returncode == 0, out.stderr[-4000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    return run

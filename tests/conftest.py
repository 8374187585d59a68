"""Test bootstrap: force an 8-device virtual CPU mesh BEFORE jax initializes.

This mirrors the reference's test philosophy (SURVEY.md §5): multi-node behavior is
tested without any real cluster. Here "multi-node" data-plane tests run on one host
via ``xla_force_host_platform_device_count=8``; control-plane tests use in-process
fake peers. Numeric oracle throughout: numpy masked-sum / count.

This is the CPU recipe: ``JAX_PLATFORMS=cpu``, 8 virtual devices, Pallas kernels in
interpret mode (ops/_platform.py), and no persistent compile cache.
"""

import os
import sys

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

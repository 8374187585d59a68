"""Where JAX's persistent compilation cache lives: one rule, no options.

- ``JAX_COMPILATION_CACHE_DIR`` set in the environment: JAX already reads
  it at import, so this module sets NO directory — whoever placed the cache
  (the chip tool, an operator) owns the path.
- unset: the cache goes to :data:`DEFAULT_DIR`, ``<checkout>/.jax_cache``
  (listed in ``.gitignore``). The path is fixed — never a temp dir, a pid
  or a timestamp — because the directory is part of what an entry is
  keyed on: a cache that moves never hits.

JAX's two entry thresholds (minimum compile time / entry size) stay at
their defaults: the programs worth keeping between processes are the
multi-second trainer steps, which clear them, and a cache-everything
override is what ROADMAP Design 1's abort was tied to.

Called by the PROCESS entry points only (``python -m akka_allreduce_tpu``
for the training commands, ``chip_smoke.py``) before
their first compile — never at package import and never by the tests'
conftest: tier-1 runs without a persistent cache.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache`` — beside the package, inside the checkout
DEFAULT_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Apply the rule above; returns the cache directory in force."""
    from_env = os.environ.get(ENV_VAR)
    if from_env:
        return from_env
    import jax

    os.makedirs(DEFAULT_DIR, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

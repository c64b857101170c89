"""Where jax's persistent compilation cache lives — the one rule.

Entry points call :func:`enable` once, before their first compile
(``python -m veles_tpu``, ``bench.py`` workers, ``tools/convergence.py``,
``tools/lm_bench.py``, ``chip_smoke.py``); nothing arms a cache at import.

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and this module
  sets nothing.
- otherwise, on an accelerator: ``<checkout>/.jax_cache`` (git-ignored).
  The path is part of the cache key, so it is fixed — never built from a
  temporary name, a pid or a time.
- otherwise, on the CPU — pinned there, or because jax found no
  accelerator: no cache.  CPU compiles take seconds, and this jaxlib's
  CPU executable deserialization segfaulted when a warm cache was reused
  across processes (tests/conftest.py).
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _on_cpu(before_distributed_init):
    """Pinned to the CPU by the config, or jax's default backend is the
    CPU.  Asking for the backend starts it, and
    ``jax.distributed.initialize`` must come first: a caller that has it
    still ahead (the launcher under ``--distributed``) is judged by the
    config alone."""
    import jax
    if (jax.config.jax_platforms or "").split(",")[0] == "cpu":
        return True
    return not before_distributed_init and jax.default_backend() == "cpu"


def enable(before_distributed_init=False):
    """Arm the cache by the rule above; returns the directory in use, or
    None when there is none."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    if _on_cpu(before_distributed_init):
        return None
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)
    return path

"""Where XLA's persistent compilation cache lives — one rule for every
launcher that compiles (``train_dist``, ``serve``, ``generate``,
``profiler``, ``search_dist``'s elastic re-plan) and for ``chip_smoke.py``.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; this module sets
  no path in code, so whoever runs the program decides where compiled
  programs are kept (and finds them again on the next run).
* unset: ``<checkout>/.jax_cache`` (git-ignored). The directory is part of
  the cache key, so it is ONE fixed path — never a temp dir, pid or
  timestamp, which could never hit.

Lives under ``cli/`` because it reads the process environment
(``analysis/lint.py`` GAL006).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Point JAX's persistent compilation cache at the directory described
    above and return it. Call before the first compile; idempotent."""
    env_dir = os.environ.get(ENV_VAR)
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR

"""Where JAX's persistent compilation cache lives — the one place that
decides it.

Every ``pio`` process that compiles (train, eval, deploy, batchpredict)
and ``chip_smoke.py``'s children call :func:`configure`
before their first compile. The bucketed training program takes tens of
seconds to compile at the ML-20M shape and the serving ladder is dozens
of programs, so a second process with the same shapes should find them
on disk.

- ``JAX_COMPILATION_CACHE_DIR`` set: jax reads it itself and no cache
  path is set in code.
- unset: one fixed directory inside the checkout (``<repo>/.jax_cache``,
  git-ignored). The directory is part of how two processes find each
  other's entries, so it is never derived from ``tempfile``, a pid or
  the clock.

Either way the minimum compile time for an entry to be stored drops from
jax's 1 s default to 0: the ladder's programs compile in well under a
second each and would otherwise never be cached.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def cache_dir() -> str:
    """The directory the cache resolves to in this environment."""
    return os.environ.get(ENV_VAR, "").strip() or DEFAULT_DIR


def configure() -> str:
    """Point jax at the persistent cache (see module docstring) and
    return the directory. Idempotent; call before the first compile."""
    import jax

    if not os.environ.get(ENV_VAR, "").strip():
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return cache_dir()


def entry_names(directory: str) -> set:
    """The executables stored under ``directory`` (empty when it does
    not exist yet). jax writes one ``<name>-<key>-cache`` file per
    executable next to ``*-atime`` bookkeeping files; only the former
    are entries."""
    try:
        names = os.listdir(directory)
    except FileNotFoundError:
        return set()
    return {n for n in names if not n.endswith("-atime")}

"""Persistent JAX compilation cache enablement (DESIGN.md §11).

The last layer of the replan-cost cache stack (eager → allocate memo →
plan bucket → THIS): ``jax.experimental.compilation_cache`` persists
compiled XLA executables to disk, so a cold process (a relaunched
benchmark, a CI job restoring the cache directory via ``actions/cache``)
pays dictionary-lookup + deserialization instead of a recompile for
every program shape it has ever seen — including every bucket branch.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR`` set — JAX's own variable wins; this
  module sets no other directory.
* otherwise — one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored). The directory is part of the cache key, so it must not
  move between runs.
* ``REPRO_NO_COMPILE_CACHE`` set non-empty — the cache stays off.

``enable_persistent_cache`` is wired into ``benchmarks/common.py``, both
launchers and ``chip_smoke.py``. Idempotent and safe to call multiple
times.
"""
from __future__ import annotations

import os

#: the fixed in-checkout cache directory used when JAX's variable is unset
_DEFAULT_DIR = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", "..", ".jax_cache"
))
_enabled_dir: str | None = None


def cache_dir() -> str | None:
    """Directory the persistent cache was enabled at (None = not enabled)."""
    return _enabled_dir


def enable_persistent_cache() -> str | None:
    """Enable JAX's on-disk compilation cache; returns the directory.

    Thresholds are dropped to zero so even the small allocation cores
    and sub-second bucket branches persist (the default only caches
    programs that took >= 1 s to compile). Returns None when opted out.
    """
    global _enabled_dir
    if os.environ.get("REPRO_NO_COMPILE_CACHE"):
        return None
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or _DEFAULT_DIR
    if _enabled_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        _enabled_dir = path
    return path

"""Persistent XLA compilation cache wiring.

Cold compile of a trainer or a serving engine is tens of seconds to
minutes; JAX's persistent compilation cache (``jax_compilation_cache_dir``)
lets the next process deserialize instead.  This module turns it on for
paddle_tpu trainers, engines, the benchmark and the test suite:

- ``JAX_COMPILATION_CACHE_DIR`` set (or ``jax_compilation_cache_dir``
  already configured): the cache is there and nothing here sets another;
- unset: one fixed path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  The path is part of the cache key, so it never depends
  on ``~``, a pid, a time or a temporary name;
- ``PADDLE_TPU_COMPILE_CACHE=0`` (or ``off``) disables it.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["ensure_compile_cache", "compile_cache_dir",
           "compile_cache_enabled"]

_STATE: dict = {"resolved": False, "dir": None}
_OFF_VALUES = ("0", "off", "false", "none", "disabled")
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def ensure_compile_cache() -> Optional[str]:
    """Enable the persistent XLA compile cache (idempotent); returns the
    active cache directory, or None when disabled."""
    if _STATE["resolved"]:
        return _STATE["dir"]
    _STATE["resolved"] = True
    env = os.environ.get("PADDLE_TPU_COMPILE_CACHE", "").strip()
    if env.lower() in _OFF_VALUES:
        return None
    import jax
    path = jax.config.jax_compilation_cache_dir
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    # trainer/engine executables are exactly the entries worth
    # persisting; the default 1s/min-size thresholds would also skip the
    # small eval/update programs, so disable them
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _STATE["dir"] = path
    return path


def compile_cache_dir() -> Optional[str]:
    """The active persistent cache dir (after ensure_compile_cache)."""
    return _STATE["dir"]


def compile_cache_enabled() -> bool:
    return _STATE["dir"] is not None

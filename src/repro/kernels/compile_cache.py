"""Persistent XLA compilation cache for the limb kernels.

Each process pays the batched-path compiles once, since the jit caches
live in process memory; JAX's persistent compilation cache lets the next
process deserialize those executables instead of lowering them again.

Where the cache lives:

* ``JAX_COMPILATION_CACHE_DIR``, when set, is used as it is; no other
  directory is set in code;
* otherwise one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  The path is part of what makes a later run hit, so it
  never carries a temporary name, a process id or a time.

:func:`enable` is idempotent.  The entry points call it at start-up
(``chip_smoke.py``, ``repro.launch.edge_sim``, ``repro.launch.serve_sim``),
and so do :func:`repro.core.paillier_batch.warmup` and
``repro.runtime.dispatch.calibrate``.
"""
from __future__ import annotations

import os

ENV_DIR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")

_state: dict = {"enabled": None}


def cache_dir() -> str:
    """The directory :func:`enable` uses: ``$JAX_COMPILATION_CACHE_DIR``,
    else one a host application already gave JAX, else the checkout's."""
    import jax
    return (os.environ.get(ENV_DIR) or jax.config.jax_compilation_cache_dir
            or DEFAULT_DIR)


def enable() -> str | None:
    """Point JAX's persistent compilation cache at :func:`cache_dir`.

    Returns the directory in use, or ``None`` when it cannot be created
    (a read-only checkout then runs uncached).  Safe to call repeatedly.
    """
    import jax
    path = cache_dir()
    if _state["enabled"] == path:
        return path
    try:
        os.makedirs(path, exist_ok=True)
    except OSError:
        return None
    jax.config.update("jax_compilation_cache_dir", path)
    # cache every kernel regardless of size/compile time: the batched
    # CRT executables are individually small but numerous
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _state["enabled"] = path
    return path


def stats() -> dict:
    """Cache-state snapshot for the RunReport profile section.

    Counts on-disk executables in the persistent cache directory — an
    approximation of hits (warm entries deserialized instead of lowered):
    entries present before a run's compiles are hits-in-waiting, entries
    added during it were misses.  Returns ``{"enabled", "dir", "entries"}``.
    """
    path = _state["enabled"]
    entries = 0
    if path and os.path.isdir(path):
        try:
            entries = sum(1 for name in os.listdir(path)
                          if not name.startswith("."))
        except OSError:
            entries = 0
    return {"enabled": path is not None, "dir": path, "entries": entries}

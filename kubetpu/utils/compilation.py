"""Persistent XLA compilation cache.

The scheduling programs are large (the sequential scan and the gang auction
compile in tens of seconds at serving shapes) but their shapes are bucketed
(utils/intern.py pow2_bucket), so a process restart recompiles byte-identical
programs.  Enabling JAX's persistent compilation cache makes warm restarts
skip XLA entirely — the serving analog of the reference reusing a running
process (there is no compile step to amortize in Go; here there is, and this
bounds it).

Where the cache lives is decided OUTSIDE the program: jax itself reads
``JAX_COMPILATION_CACHE_DIR`` into ``jax_compilation_cache_dir``, and when
that (or an embedding application's own config) names a directory this
module sets no other.  Unset, the cache goes to one fixed path inside the
checkout — the path is part of the cache key's environment, so a directory
built from a temp name, pid or time would never hit.
"""

from __future__ import annotations

import contextlib
import os
import shutil
from typing import Iterator

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".xla_cache")


def enable_persistent_cache() -> str:
    """Idempotently enable the JAX persistent compilation cache.  Returns
    the cache directory in use.  Safe to call before or after jax init."""
    import jax
    cache_dir = jax.config.jax_compilation_cache_dir
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # cache every program: even sub-second kernels add up across restarts
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir


@contextlib.contextmanager
def _cache_option(option: str, value, restore) -> Iterator[None]:
    """Hold one of jax's persistent-cache options at ``value`` for the
    duration.  jax builds its cache object (and memoizes whether caching
    is on) once per process, so a change after the first compile takes
    effect only after ``reset_cache()``."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update(option, value)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update(option, restore)
        compilation_cache.reset_cache()


@contextlib.contextmanager
def cache_subdir(name: str) -> Iterator[str]:
    """Point the persistent cache at ``<active cache root>/<name>``,
    emptied first, for the duration — a PRIVATE cold cache at a fixed,
    placeable path (a cold-vs-cache-warm restart is measured against
    it)."""
    root = enable_persistent_cache()
    sub = os.path.join(root, name)
    shutil.rmtree(sub, ignore_errors=True)
    os.makedirs(sub)
    with _cache_option("jax_compilation_cache_dir", sub, root):
        yield sub


def cache_disabled():
    """Context manager: no persistent-cache reads or writes for the
    duration, so every compile inside is a true backend compile
    (tools/kubeaot serializes executables, and one that came back as a
    cache hit re-serializes to a blob that cannot be loaded)."""
    return _cache_option("jax_enable_compilation_cache", False, True)

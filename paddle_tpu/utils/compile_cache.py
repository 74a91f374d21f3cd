"""Where JAX's persistent compilation cache lives.

Reference parity: the reference caches compiled programs in-process per
``ProgramDesc``; on TPU the expensive artifact is the XLA executable, and
jax ships a content-addressed on-disk compilation cache for exactly the
relaunch/restart case (a supervised restart otherwise re-compiles every
jitted step — tens of seconds for the BERT-base config).

The cache is placed from outside.  ``JAX_COMPILATION_CACHE_DIR`` in the
environment is read by JAX itself and this module then sets no directory
at all.  Without it the cache goes to one fixed path inside the checkout,
``<repo>/.jax_cache`` — the path is part of the cache key, so it is
derived from the package location and never from a temp dir, a pid or
the clock.  ``configure()`` runs once at package import, before the first
compile.  The thresholds jax gates persistence on (min compile seconds /
min entry bytes) are zeroed so every executable lands in the cache — a
restarted trainer wants ALL of its programs back, not just the slow ones.
"""
from __future__ import annotations

import os
from typing import Optional

__all__ = ["configure", "cache_dir", "entry_count", "DEFAULT_DIR"]

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> None:
    import jax
    # persistence thresholds: cache everything, not just slow compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)


def cache_dir() -> Optional[str]:
    """The directory JAX's persistent cache is using (None: disabled)."""
    import jax
    return jax.config.jax_compilation_cache_dir


def entry_count(d: Optional[str] = None) -> int:
    """Number of cached executables on disk (0 when the directory does
    not exist yet).  A second run that compiles nothing new leaves this
    unchanged."""
    d = d or cache_dir()
    if not d or not os.path.isdir(d):
        return 0
    n = 0
    for _root, _dirs, files in os.walk(d):
        n += sum(1 for f in files if f.endswith("-cache"))
    return n

"""Where JAX keeps its persistent compilation cache.

Entry points call ``use_compile_cache()`` at the start of ``main()``; it
never runs at import. If ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
reads it and nothing else is set. Otherwise the cache goes to the fixed
``<repo>/.jax_cache`` (gitignored): the directory is part of what a later
process must find, so it never depends on a temporary name, a pid or the
time. The variable is exported too, so child processes share the cache.
"""
from __future__ import annotations

import os
import sys

ENV = "JAX_COMPILATION_CACHE_DIR"
REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))
DEFAULT_DIR = os.path.join(REPO_ROOT, ".jax_cache")


def use_compile_cache() -> str:
    """Point JAX (this process and its children) at the cache; return it."""
    path = os.environ.get(ENV)
    if path:
        return path
    os.environ[ENV] = DEFAULT_DIR
    jax = sys.modules.get("jax")
    if jax is not None:         # imported already: it read its env back then
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR


class CompileStats:
    """Backend compile seconds and persistent-cache hits of this process,
    counted from JAX's monitoring events. A cache hit's program still
    counts, with its retrieval time in place of a compile."""

    def __init__(self):
        import jax
        self.programs = 0
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.programs += 1
            self.seconds += secs

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __str__(self):
        return (f"{self.programs} programs, {self.seconds:.1f}s backend "
                f"compile, {self.cache_hits} persistent-cache hits")

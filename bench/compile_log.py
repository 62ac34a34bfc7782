"""JAX's persistent compilation cache and a count of compiles.

``CompileLog`` is a copy of the one in ``chip_smoke.py``; ``use_compile_cache``
follows ``benchmarks/_util.py``, with the cache always at a fixed path inside
the checkout (the path is part of what the cache is keyed on) and every
program cached, however short its compile.
"""
from __future__ import annotations

import os


def use_compile_cache(root: str) -> str:
    """Turn on the persistent compilation cache at ``<root>/bench/_cache/jax``."""
    import jax

    path = os.path.join(root, "bench", "_cache", "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileLog:
    """Counts compiles and sums their seconds through ``jax.monitoring``."""

    def __init__(self):
        import jax

        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.seconds, self.compiles, self.cache_hits

"""Backend compile seconds (persistent-cache retrievals included) and cache hits.

Copied from the chip smoke test.  The harness reads it for set-up, and counts
compiles inside the measured window, where there must be none.
"""

from __future__ import annotations

import jax


class CompileLog:
    def __init__(self):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def report(self) -> str:
        return (f"compile_s={self.seconds:.3f} compiles={self.compiles} "
                f"persistent_cache_hits={self.hits} persistent_cache_misses={self.misses}")

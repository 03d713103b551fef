"""What jax itself reports about compilation while the clock is active.

Copied from ``chip_smoke.CompileClock`` (sound, PR 21) and extended by a
count of backend compile requests: a program that is first needed inside
the measured window shows there whether the persistent cache had it or
not, so ``programs == 0`` over the window is the proof that nothing
compiled or loaded inside it.
"""

import jax


class CompileClock:
    """Seconds in backend compilation (persistent-cache loads included),
    the number of such requests, and how many hit or missed the
    persistent cache.  Tracing and lowering are host Python time."""

    _COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.seconds = 0.0
        self.programs = 0
        self.cache_hits = 0
        self.cache_misses = 0

    def _on_duration(self, event, secs, **_):
        if event == self._COMPILE:
            self.seconds += secs
            self.programs += 1

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "programs": self.programs,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration)
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_duration)
        jax.monitoring.unregister_event_listener(self._on_event)

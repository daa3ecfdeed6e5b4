"""Backend compiles and persistent-cache hits, counted from
``jax.monitoring``: the source of ``compile_s`` and of "nothing compiled
inside the window"."""

from __future__ import annotations

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    def __init__(self):
        from jax import monitoring

        self.total = 0
        self.total_seconds = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, seconds: float, **_kw) -> None:
        if event == COMPILE_EVENT:
            self.total += 1
            self.total_seconds += float(seconds)

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def mark(self) -> tuple:
        return (self.total, self.total_seconds, self.cache_hits,
                self.cache_misses)

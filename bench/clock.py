"""Programs compiled or loaded, counted from JAX's monitoring events.

JAX records ``backend_compile_duration`` around every program it builds
for a backend, whether it compiles it or loads it from the persistent
cache (which also records a ``cache_hits`` event).  Either inside a
measured window means a shape was not warmed up.
"""

from __future__ import annotations

import jax

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Backend compile seconds and programs, and persistent-cache hits."""

    def __init__(self):
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        self.names: list[str] = []

        def on_duration(event, duration, **kw):
            if event == BACKEND_COMPILE:
                self.seconds += duration
                self.compiles += 1
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **_):
            if event == CACHE_HIT:
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def snapshot(self) -> tuple[int, int]:
        return self.compiles, self.cache_hits

    def since(self, snap: tuple[int, int]) -> dict:
        """Programs built (``compiles``, loads included) and loaded from the
        persistent cache since ``snap``, with the built programs' names."""
        return {"compiles": self.compiles - snap[0],
                "cache_hits": self.cache_hits - snap[1],
                "names": self.names[snap[0]:]}

    def summary(self) -> str:
        return (f"{self.seconds:.1f} s building {self.compiles} programs, "
                f"{self.cache_hits} of them loaded from the persistent cache")

"""Count what JAX compiles, so a run can show that its window compiles
nothing. A copy of the listener in the program's
``launch/compile_cache.count_compiles``, kept here so that the program
cannot change what the benchmark counts."""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass
class Compiles:
    """Backend compiles, persistent-cache hits and misses, and backend
    compile seconds, summed since :func:`listen`."""
    backend: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    seconds: float = 0.0

    @property
    def programs(self) -> int:
        """Executables made: compiled by the backend or read from the
        persistent cache."""
        return self.backend + self.cache_hits


def listen() -> Compiles:
    """Register JAX monitoring listeners that add into a new
    :class:`Compiles`; they stay registered for the process."""
    from jax import monitoring
    c = Compiles()

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            c.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            c.cache_misses += 1

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            c.backend += 1
            c.seconds += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return c

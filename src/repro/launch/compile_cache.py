"""JAX's persistent compilation cache, and a count of what compiling cost.

:func:`enable_compile_cache` is called by the launchers, a measuring
``python -m repro tune`` and ``chip_smoke.py`` (never at import). Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this module
sets no other directory; otherwise the cache lives at the fixed path
``<checkout>/.jax_cache``, so every run from one checkout finds it again
(the path is part of what a cache entry is keyed on)."""
from __future__ import annotations

import dataclasses
import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)


@dataclasses.dataclass
class CompileStats:
    """Backend compile seconds (cache reads included) and persistent-cache
    hits and misses, summed since :func:`count_compiles`."""
    seconds: float = 0.0
    hits: int = 0
    misses: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)


def count_compiles() -> CompileStats:
    """Register JAX monitoring listeners that add into a new
    :class:`CompileStats`; they stay registered for the process."""
    from jax import monitoring
    stats = CompileStats()

    def on_event(event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            stats.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            stats.misses += 1

    def on_duration(event: str, secs: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            stats.seconds += secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return stats

"""Persistent compilation cache for the entry points.

`chip_smoke.py`, the launchers (`train`, `serve`, `shardckpt`) and the
benchmark modules call `use_compile_cache()` first; library modules never
touch the cache. Where `JAX_COMPILATION_CACHE_DIR` is set, JAX reads it
itself and nothing here overrides it. Otherwise the cache lives at the
fixed path `<checkout>/.jax_cache/` (gitignored): the path is part of the
cache key, so a second identical run from the same checkout hits.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT = Path(__file__).resolve().parents[3]
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_counts = {_HIT: 0, _MISS: 0}
_listening = False


def _count(event: str, **_) -> None:
    if event in _counts:
        _counts[event] += 1


def use_compile_cache() -> str:
    """Point JAX's persistent compilation cache at its directory (see the
    module docstring) and start counting hits; returns the directory."""
    global _listening
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    if not _listening:
        jax.monitoring.register_event_listener(_count)
        _listening = True
    return path


def cache_stats() -> dict:
    """{'dir', 'hits', 'misses'} of the persistent cache in this process."""
    return {
        "dir": jax.config.jax_compilation_cache_dir,
        "hits": _counts[_HIT],
        "misses": _counts[_MISS],
    }

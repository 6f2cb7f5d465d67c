"""Where JAX's persistent compilation cache lives, and what it did.

`enable_compile_cache()` is called by every entry point that compiles at
full size (`launch/serve.py`, `chip_smoke.py`) before its first compile:

  * `JAX_COMPILATION_CACHE_DIR` set -> JAX already reads it; nothing is
    set in code, so whoever places the cache keeps it where they put it.
  * unset -> `<checkout>/.jax_cache` (git-ignored). The path is part of
    what a later process must find again, so it is fixed: never built from
    a temporary name, a PID or the time.

`compile_stats()` counts, from JAX's own monitoring events, the compiles
that asked the cache, the hits, the entries written, and the seconds
spent in backend compiles (cache retrieval included on a hit).
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Dict, Optional

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"

_EVENTS = {
    "/jax/compilation_cache/compile_requests_use_cache": "requests",
    "/jax/compilation_cache/cache_hits": "hits",
    "/jax/compilation_cache/cache_misses": "writes",
}
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_stats: Dict[str, float] = {"requests": 0, "hits": 0, "writes": 0,
                            "compile_s": 0.0}
_dir: Optional[str] = None


def _on_event(event: str, **_) -> None:
    key = _EVENTS.get(event)
    if key is not None:
        _stats[key] += 1


def _on_duration(event: str, duration: float, **_) -> None:
    if event == _COMPILE_EVENT:
        _stats["compile_s"] += duration


def enable_compile_cache() -> str:
    """Point the persistent cache at its directory (see module doc) and
    start counting; idempotent. Returns the directory in use."""
    global _dir
    if _dir is None:
        import jax
        path = os.environ.get(ENV_VAR)
        if not path:
            path = str(DEFAULT_DIR)
            jax.config.update("jax_compilation_cache_dir", path)
        jax.monitoring.register_event_listener(_on_event)
        jax.monitoring.register_event_duration_secs_listener(_on_duration)
        _dir = path
    return _dir


def compile_stats() -> Dict[str, float]:
    """Counts since enable_compile_cache(): requests, hits, writes, and
    compile_s (seconds in backend compiles)."""
    return dict(_stats)

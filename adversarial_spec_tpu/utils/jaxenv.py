"""One-time jax process configuration (platform mirroring + compile cache)
and the process's device report.

Called lazily from the first jax-touching entry point (engine dispatch,
device introspection) so mock-only CLI flows never pay the jax import.

1. Mirror JAX_PLATFORMS into jax.config before first backend use, so an
   interpreter whose start-up hooks imported jax early still honours the
   caller's platform choice. ``JAX_PLATFORMS=cpu`` is the one way to ask
   for the CPU; nothing here falls back to it.
2. Enable the persistent compilation cache. The L5 debate protocol invokes
   the CLI once per round as a fresh process; without the cache every
   round re-pays the full XLA compile of prefill + decode. The cache keys
   on program + topology + ITS OWN PATH, so the directory must not move:
   ``JAX_COMPILATION_CACHE_DIR`` when the caller set it (jax reads that
   variable itself — nothing is configured here), else one fixed
   directory inside the checkout, resolved from this package's location.
3. Count what the compiler did (``jax.monitoring``): seconds spent in
   backend compiles and persistent-cache hits/misses, so a report can
   say whether a process compiled or reused (``device_report``).
"""

from __future__ import annotations

import os
from pathlib import Path

# <checkout>/.jax_cache — never the home directory, the working
# directory, a temp name, a pid or the time.
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"

_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS_EVENT = "/jax/compilation_cache/cache_misses"

_configured = False
_compile_stats = {
    "backend_compiles": 0,
    "backend_compile_s": 0.0,
    "persistent_cache_hits": 0,
    "persistent_cache_misses": 0,
}


def _on_duration(event: str, duration_s: float, **_kw) -> None:
    if event == _BACKEND_COMPILE_EVENT:
        _compile_stats["backend_compiles"] += 1
        _compile_stats["backend_compile_s"] += duration_s


def _on_event(event: str, **_kw) -> None:
    if event == _CACHE_HIT_EVENT:
        _compile_stats["persistent_cache_hits"] += 1
    elif event == _CACHE_MISS_EVENT:
        _compile_stats["persistent_cache_misses"] += 1


def compile_cache_dir() -> str:
    """The directory this process's persistent compile cache lives in."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        DEFAULT_CACHE_DIR
    )


def configure_jax() -> None:
    global _configured
    if _configured:
        return
    _configured = True
    import jax
    from jax import monitoring

    plat = os.environ.get("JAX_PLATFORMS")
    if plat:
        jax.config.update("jax_platforms", plat)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    # jax's own floors stand: programs that compiled in under a second
    # are not worth a cache entry (JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS
    # moves the floor from outside; the test suite and the smoke's CPU
    # rehearsal set it to 0 for their tiny programs).
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_listener(_on_event)


def device_report() -> dict | None:
    """What this process's jax runs on and what its compiler did:
    platform, device kind and count as jax reports them, device 0's
    memory statistics where the backend keeps them, and the compile
    counters. None in a process that never configured jax (mock-only
    flows stay off the jax import)."""
    if not _configured:
        return None
    import jax

    devs = jax.devices()
    stats = devs[0].memory_stats() or {}
    return {
        "platform": devs[0].platform,
        "kind": devs[0].device_kind,
        "count": len(devs),
        "memory": {
            k: int(stats[k])
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats
        },
        "compile": {
            **_compile_stats,
            "backend_compile_s": round(_compile_stats["backend_compile_s"], 3),
            "cache_dir": compile_cache_dir(),
        },
    }

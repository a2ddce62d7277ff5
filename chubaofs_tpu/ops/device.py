"""Process-level device setup for the codec: which platform this process asked
for, where its compiled programs are kept, and what it resolved to.

An accelerator belongs to ONE process at a time: the first process that
initialises the backend holds the chip, and a second one fails or hangs. So
only processes that do device work call into this module — the blobstore
daemon (the one role that owns a CodecService), bench.py and chip_smoke.py's
in-process children — and each does so before its first jitted call.
"""

from __future__ import annotations

import functools
import os

import jax

# fixed, inside the checkout, git-ignored: the directory is part of JAX's
# cache key, so a path that moves (temp name, pid, timestamp) never hits
CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")


def request_platform(plat: str | None) -> None:
    """Pin JAX to ``plat`` ("cpu", "tpu") before any backend initialises;
    None keeps JAX's own default. A requested platform that cannot
    initialise raises at the first device call — it never degrades."""
    if plat:
        jax.config.update("jax_platforms", plat)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` places the cache from outside: when it is
    set, JAX reads it itself and no directory is set in code. Otherwise the
    cache lives at CACHE_DIR. Every program is kept: the served path compiles
    one program per (matrix shape, batch/g, bucket), most of which take under
    JAX's default 1 s write threshold yet are paid inside user requests by
    every cold daemon."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _install_compile_counters()
    return path


@functools.cache  # once per process: JAX listeners cannot be taken back
def _install_compile_counters() -> None:
    """Mirror JAX's compile events into the codec registry (cfs_codec_compile_*)
    so a daemon's /metrics — and the smoke reading it — can tell a cold
    process (compiles, cache writes) from a warm one (cache hits)."""
    from chubaofs_tpu.utils.exporter import registry

    reg = registry("codec")
    events = {
        "/jax/compilation_cache/cache_hits": reg.counter("compile_cache_hits_total"),
        "/jax/compilation_cache/cache_misses": reg.counter("compile_cache_writes_total"),
    }
    total = reg.counter("compile_total")
    seconds = reg.counter("compile_seconds_total")

    def on_event(event: str, **_):
        c = events.get(event)
        if c is not None:
            c.add()

    def on_duration(event: str, secs: float, **_):
        # fires once per XLA program built OR fetched from the cache
        if event == "/jax/core/compile/backend_compile_duration":
            total.add()
            seconds.add(secs)

    jax.monitoring.register_event_listener(on_event)
    jax.monitoring.register_event_duration_secs_listener(on_duration)


def compile_stats() -> dict:
    """Snapshot of the cfs_codec_compile_* counters (zeros before
    enable_compile_cache installed them)."""
    from chubaofs_tpu.utils.exporter import registry

    reg = registry("codec")
    return {
        "compiles": int(reg.counter("compile_total").value),
        "compile_seconds": reg.counter("compile_seconds_total").value,
        "cache_hits": int(reg.counter("compile_cache_hits_total").value),
        "cache_writes": int(reg.counter("compile_cache_writes_total").value),
    }


def describe() -> dict:
    """What this process resolved to — initialises the backend. The blobstore
    boot line, /admin/stat, bench.py and the smoke all print exactly this, so
    a daemon on the TPU and one on the CPU never look the same from outside."""
    from chubaofs_tpu.ops import rs

    devs = jax.devices()
    return {
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "device_count": len(devs),
        "lowering": rs.lowering(),
    }

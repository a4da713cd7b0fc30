"""Where the compile caches live, plus JAX runtime telemetry.

One cache root holds everything this program compiles: the XLA
persistent compilation cache at the root itself and the
serialized-executable store (utils/exec_cache) under ``<root>/exec``.
The root is ``JAX_COMPILATION_CACHE_DIR`` when that is set — JAX reads
the variable itself, so this module then sets no directory in code —
and otherwise ``<checkout>/.jax_cache`` (git-ignored), computed from
this file's location so every process of a checkout agrees on it: the
path is part of a cache entry's key, and a directory that moves never
hits.

The chip tool copies the checkout as it stands on disk, so a
``.jax_cache`` filled here on the CPU would travel to the machine with
the chip.  Decision: it does not travel — ``.chiprunignore`` lists
``.jax_cache/`` (and ``.gitignore`` does, so the driver's checkout never
has one).  XLA's cache key carries the platform, so a CPU entry would
never be offered to the TPU anyway; what is not safe is a CPU entry read
back on a *different* CPU host (XLA:CPU AOT results embed machine
features and have crashed when loaded elsewhere), and the copy has a
size limit the test suite's cache would break.

Telemetry: jax.monitoring listeners count backend compiles (with
durations) and persistent-cache hits/misses; runtime_telemetry() adds
per-device memory stats and live-array counts for the flight recorder,
and update_metrics_gauges() mirrors them into the Metrics registry.
Every telemetry path is exception-guarded — a backend without
memory_stats() degrades to empty data, never an error in the prover
path.
"""

from __future__ import annotations

import os
import threading

_LOCK = threading.Lock()
_MONITORING_INSTALLED = False
_CHECKOUT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
# What the older keys really count (their names stay: the benchmark's
# `window_compiles` reads `compiles`): `compiles` and `compile_seconds`
# are JAX's backend_compile events, which a persistent-cache HIT fires
# too (its seconds are then the read and deserialisation);
# `cache_misses` counts cache WRITES, so a program under the caching
# threshold is neither a hit nor a miss.  The last three split a
# program's way to the device before the backend: tracing to a jaxpr,
# lowering to MLIR, and reading an executable back from the cache.
STATS = {"compiles": 0, "compile_seconds": 0.0,
         "cache_hits": 0, "cache_misses": 0,
         "trace_seconds": 0.0, "lower_seconds": 0.0,
         "cache_retrieval_seconds": 0.0}
# JAX's duration events that are summed, by what ends their name
_DURATION_KEYS = {"jaxpr_trace_duration": "trace_seconds",
                  "jaxpr_to_mlir_module_duration": "lower_seconds",
                  "cache_retrieval_time_sec": "cache_retrieval_seconds"}


def cache_dir() -> str:
    """The one cache root: JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_CHECKOUT, ".jax_cache"))


def _on_duration(event: str, duration: float, **kw) -> None:
    try:
        if "backend_compile" in event:
            with _LOCK:
                STATS["compiles"] += 1
                STATS["compile_seconds"] += duration
            from .metrics import record_jax_compile

            record_jax_compile(duration)
        else:
            key = _DURATION_KEYS.get(event.rsplit("/", 1)[-1])
            if key is not None:
                with _LOCK:
                    STATS[key] += duration
    except Exception:
        pass


def _on_event(event: str, **kw) -> None:
    try:
        if "cache_hit" in event:
            with _LOCK:
                STATS["cache_hits"] += 1
            from .metrics import record_jax_cache_event

            record_jax_cache_event(True)
        elif "cache_miss" in event:
            with _LOCK:
                STATS["cache_misses"] += 1
            from .metrics import record_jax_cache_event

            record_jax_cache_event(False)
    except Exception:
        pass


def install_monitoring() -> bool:
    """Attach jax.monitoring listeners (idempotent).  Returns whether
    listeners are installed."""
    global _MONITORING_INSTALLED
    from jax import monitoring

    with _LOCK:
        if not _MONITORING_INSTALLED:
            monitoring.register_event_duration_secs_listener(_on_duration)
            monitoring.register_event_listener(_on_event)
            _MONITORING_INSTALLED = True
    return True


def enable_persistent_cache(min_compile_secs: float = 1.0) -> None:
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_dir())
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      min_compile_secs)
    install_monitoring()


def runtime_telemetry() -> dict:
    """JAX runtime facts for the flight recorder.  Never raises."""
    with _LOCK:
        out = {"cache": dict(STATS), "cacheDir": cache_dir(),
               "monitoring": _MONITORING_INSTALLED}
    try:
        import jax

        out["backend"] = jax.default_backend()
        devices = []
        for d in jax.local_devices():
            entry = {"id": d.id, "platform": d.platform,
                     "kind": getattr(d, "device_kind", None)}
            try:
                ms = d.memory_stats()
                entry["memory"] = (
                    {k: ms[k] for k in ("bytes_in_use", "peak_bytes_in_use",
                                        "bytes_limit") if k in ms}
                    if ms else None)
            except Exception:
                entry["memory"] = None
            devices.append(entry)
        out["devices"] = devices
        try:
            out["liveArrays"] = len(jax.live_arrays())
        except Exception:
            out["liveArrays"] = None
    except Exception as exc:
        out["error"] = f"{type(exc).__name__}: {exc}"
    return out


def update_metrics_gauges() -> None:
    """Mirror device memory / live-array stats into gauges.  Called
    after each backend prove; never raises."""
    try:
        from .metrics import (record_jax_device_memory,
                              record_jax_live_arrays)

        tel = runtime_telemetry()
        in_use = peak = 0.0
        seen = False
        for d in tel.get("devices", ()):
            mem = d.get("memory")
            if not mem:
                continue
            seen = True
            in_use += mem.get("bytes_in_use", 0) or 0
            peak += mem.get("peak_bytes_in_use", 0) or 0
        if seen:
            record_jax_device_memory(in_use, peak)
        if tel.get("liveArrays") is not None:
            record_jax_live_arrays(tel["liveArrays"])
    except Exception:
        pass

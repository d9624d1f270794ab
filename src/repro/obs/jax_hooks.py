"""jax runtime observability: retrace counters, compile/execute split,
device gauges.

Everything here degrades gracefully: jax is imported lazily, every
runtime probe is wrapped so API drift (the reason two seed tests broke)
turns a metric into an absence, never an exception on the solve path.

Three surfaces:

- :func:`install` — registers a ``jax.monitoring`` event-duration
  listener feeding ``jax_compile_events_total`` /
  ``jax_compile_seconds_total`` counters (and a histogram), giving the
  compile side of the compile-vs-execute split; execute time is what the
  planner/solver spans already measure, so
  ``execute ≈ span_time - compile_delta`` per window.
- :func:`jit_cache_entries` — sizes of the repro engine's jit caches
  (the fan-out ``grid``/``fanout`` launchers, the blocked twins, and the
  local-search climb), without forcing compilation of anything not
  already built. The per-bucket cache-miss *deltas* are recorded at the
  launch site in ``core/portfolio.py`` (``jax_jit_cache_misses_total``);
  this probe is the absolute snapshot.
- :func:`update_device_gauges` — best-effort ``memory_stats()`` and
  live-array gauges per device.
"""
from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from .metrics import MetricsRegistry

__all__ = ["install", "installed", "jit_cache_entries",
           "update_device_gauges", "snapshot"]

_install_lock = threading.Lock()
_installed_registry: Optional[MetricsRegistry] = None


def installed() -> bool:
    return _installed_registry is not None


def install(registry: MetricsRegistry) -> bool:
    """Register jax.monitoring listeners feeding ``registry``.

    Idempotent; only the first registry wins (jax offers no listener
    deregistration). Returns True when the hooks are (already) live.
    """
    global _installed_registry
    with _install_lock:
        if _installed_registry is not None:
            return True
        try:
            import jax
            events = registry.counter(
                "jax_compile_events_total",
                "jax.monitoring duration events seen, by event key",
                labels=("event",))
            seconds = registry.counter(
                "jax_compile_seconds_total",
                "cumulative seconds attributed to jax compilation events",
                labels=("event",))
            hist = registry.histogram(
                "jax_compile_seconds",
                "distribution of per-event jax compilation durations",
                labels=("event",))

            def _on_duration(event: str, duration: float, **kw: Any) -> None:
                try:
                    key = event.strip("/").split("/")[-1] or event
                    events.inc(event=key)
                    seconds.inc(duration, event=key)
                    hist.observe(duration, event=key)
                except Exception:
                    pass

            jax.monitoring.register_event_duration_secs_listener(
                _on_duration)
        except Exception:
            return False
        _installed_registry = registry
        return True


def jit_cache_entries() -> Dict[str, int]:
    """Compiled-signature counts for the engine's jit launchers.

    Keys: ``greedy.grid`` (the single-device grid launch),
    ``greedy.grid_sharded.<ndev>`` per sharded grid built, and
    ``blocked.chunk`` (the blocked form's chunk program), each once built,
    plus ``climb.variants`` for the local-search climb lru (distinct
    padded signatures). Probes that would *trigger* compilation are
    skipped.
    """
    out: Dict[str, int] = {}
    try:
        from repro.core import greedy_jax
        launchers = {}
        if greedy_jax._impl.cache_info().currsize:
            launchers["greedy.grid"] = greedy_jax._impl()
        for ndev, fn in list(greedy_jax._sharded_grids.items()):
            launchers[f"greedy.grid_sharded.{ndev}"] = fn
        if greedy_jax._blocked_impl.cache_info().currsize:
            launchers["blocked.chunk"] = greedy_jax._blocked_impl()
        for name, fn in launchers.items():
            try:
                out[name] = int(fn._cache_size())
            except Exception:
                pass
    except Exception:
        pass
    try:
        from repro.core import local_search_jax
        out["climb.variants"] = int(
            local_search_jax._climb_impl.cache_info().currsize)
    except Exception:
        pass
    return out


def update_device_gauges(registry: MetricsRegistry) -> Dict[str, float]:
    """Refresh best-effort device gauges; returns what was recorded."""
    recorded: Dict[str, float] = {}
    try:
        import jax
    except Exception:
        return recorded
    mem = registry.gauge("jax_device_memory_bytes",
                         "device.memory_stats() values",
                         labels=("device", "stat"))
    try:
        for dev in jax.devices():
            stats = dev.memory_stats() or {}
            for key in ("bytes_in_use", "peak_bytes_in_use",
                        "bytes_limit", "largest_alloc_size"):
                if key in stats:
                    mem.set(float(stats[key]), device=str(dev.id), stat=key)
                    recorded[f"{dev.id}.{key}"] = float(stats[key])
    except Exception:
        pass
    try:
        live = len(jax.live_arrays())
        registry.gauge("jax_live_arrays",
                       "arrays currently alive on any device").set(live)
        recorded["live_arrays"] = float(live)
    except Exception:
        pass
    cache = registry.gauge("jax_jit_cache_entries",
                           "compiled signatures per engine jit launcher",
                           labels=("fn",))
    for name, size in jit_cache_entries().items():
        cache.set(float(size), fn=name)
        recorded[f"jit.{name}"] = float(size)
    return recorded


def snapshot(registry: MetricsRegistry) -> Dict[str, Any]:
    """One-call summary used by the bench's ``obs`` section."""
    update_device_gauges(registry)
    compile_events = 0.0
    compile_seconds = 0.0
    m = registry.get("jax_compile_events_total")
    if m is not None:
        compile_events = m.total()
    m = registry.get("jax_compile_seconds_total")
    if m is not None:
        compile_seconds = m.total()
    return {
        "hooks_installed": installed(),
        "compile_events": compile_events,
        "compile_seconds": round(compile_seconds, 6),
        "jit_cache_entries": jit_cache_entries(),
        "live_arrays": int(registry.value("jax_live_arrays")),
    }

"""What the harness counts around calls: XLA compilations (from
``jax.monitoring``), persistent-cache hits, and the program's
resilience counters, any move of which means a call did not run as
itself (a twin served it, it was retried, or it was split)."""

from __future__ import annotations

import threading

BAD_COUNTERS = ("srj_tpu_breaker_fallbacks_total", "srj_tpu_retry_total",
                "srj_tpu_oom_splits_total",
                "srj_tpu_mem_proactive_splits_total",
                "srj_tpu_fatal_recoveries_total")

_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"


class CompileClock:
    """Counts the programs XLA hands back (JAX times each one as a
    backend compile, also when the persistent cache serves it) and their
    seconds, and the persistent cache hits among them, from the moment
    it is installed."""

    def __init__(self):
        self.programs = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        self._lock = threading.Lock()

    def install(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def _duration(self, event, duration, **_):
        if event == _COMPILE:
            with self._lock:
                self.programs += 1
                self.compile_s += duration

    def _event(self, event, **_):
        if event == _CACHE_HIT:
            with self._lock:
                self.cache_hits += 1


def bad_counter_total() -> float:
    """Sum of the resilience counters that must not move."""
    from spark_rapids_jni_tpu.obs import metrics
    snap = metrics.registry().snapshot()
    total = 0
    for name in BAD_COUNTERS:
        for v in snap.get(name, {}).get("values", {}).values():
            if isinstance(v, (int, float)):
                total += v
    return total

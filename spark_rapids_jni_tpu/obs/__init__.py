"""Structured span/event observability — the NVTX + nvml telemetry tier.

The reference instruments every footer API and kernel hot spot with NVTX
ranges (``CUDF_FUNC_RANGE()``, ``NativeParquetJni.cpp:136,392``) and ships
a fault-observation tool (``faultinj.cu``); profile-guided rounds hang off
that substrate.  This package is the TPU-native equivalent, extending
``utils/tracing.py`` (named scopes):

- :func:`span` / :func:`span_fn` — timed spans on every hot entry point:
  host wall-clock, device-completion time (``block_until_ready`` fence),
  nesting, thread identity, rows/bytes attributes, exception capture.
  Under any ``jax.profiler`` capture every span is also an ``srj/<name>``
  annotation on the capture's host plane; :func:`launch` spans sit at
  each program launch of the hash and row paths.
- :mod:`~spark_rapids_jni_tpu.obs.compilemon` — ``jax.monitoring``
  subscription counting XLA backend compiles (and compile-seconds) per
  span, so shape-churn recompiles are a visible counter, not a mystery
  slowdown.
- Device-memory snapshots at span boundaries from the PJRT allocator
  counters (``memory.device_memory_stats``).
- A bounded in-process ring buffer (:func:`events`) plus an optional JSONL
  sink: ``SRJ_TPU_EVENTS=<path>`` writes one event per line.  Ring
  evictions and sink write failures are counted (:func:`dropped`) and
  surfaced in the report, so truncated telemetry is distinguishable from
  a quiet run.
- :mod:`~spark_rapids_jni_tpu.obs.metrics` — live thread-safe registry of
  counters/gauges/histograms, fed automatically from span completion
  (same family names as ``report --prom``).
- :mod:`~spark_rapids_jni_tpu.obs.exporter` — opt-in stdlib HTTP daemon
  thread serving the live registry: Prometheus text at ``/metrics`` and a
  JSON liveness snapshot at ``/healthz``.  ``SRJ_TPU_METRICS_PORT=<port>``
  starts it at import; off by default (no thread, no socket).
- :mod:`~spark_rapids_jni_tpu.obs.trace` — span log -> Chrome/Perfetto
  ``trace_event`` JSON (per-thread lanes, nested durations, compile and
  transfer counter tracks, request->batch flow arrows, per-host process
  lanes for merged multihost logs).
- :mod:`~spark_rapids_jni_tpu.obs.context` — request-scoped trace
  context (``trace_id``/``span_id``/tenant) with an explicit
  ``capture()``/``activate()`` handoff for thread pools; spans stamp it
  into every event automatically.
- :mod:`~spark_rapids_jni_tpu.obs.recorder` — failure flight recorder:
  on a failed span or a :class:`~spark_rapids_jni_tpu.obs.recorder.Watchdog`
  stall, dump the last-K ring events + the failing program's lowered
  StableHLO + memory/env snapshots as a bundle under
  ``SRJ_TPU_DIAG_DIR``.
- ``python -m spark_rapids_jni_tpu.obs <events.jsonl>`` — per-op summary
  table (calls, p50/p95 wall, device ms, volume, compiles, failures), a
  ``--prom`` Prometheus text exposition, ``--trace out.json`` for the
  Perfetto export, ``--merge host*.jsonl`` to combine per-host logs, and
  ``--bundle <dir>`` to render a flight-recorder bundle.

Enable with ``SRJ_TPU_EVENTS=<path>``, ``SRJ_TPU_OBS=1``, or
:func:`enable`; off by default and free when off (no fences, no locks).
"""

import os as _os

from spark_rapids_jni_tpu.obs.spans import (  # noqa: F401
    Span, clear, configure_sink, current_span, disable, dropped, emit,
    enable, enabled, events, flush, launch, recording, sink_path, span,
    span_fn,
)
from spark_rapids_jni_tpu.obs import compilemon as _compilemon
from spark_rapids_jni_tpu.obs import context  # noqa: F401
from spark_rapids_jni_tpu.obs import metrics  # noqa: F401
from spark_rapids_jni_tpu.obs import recorder  # noqa: F401
from spark_rapids_jni_tpu.obs import report  # noqa: F401

compile_totals = _compilemon.totals

_compilemon.install()


def _maybe_start_exporter() -> None:
    """Env-driven exporter bring-up.  Must never break importing the
    package: a malformed port or a bind conflict is reported on stderr by
    the exporter and otherwise ignored."""
    raw = _os.environ.get("SRJ_TPU_METRICS_PORT")
    if not raw:
        return
    try:
        port = int(raw)
    except ValueError:
        import sys
        print(f"[obs] ignoring non-integer SRJ_TPU_METRICS_PORT={raw!r}",
              file=sys.stderr)
        return
    from spark_rapids_jni_tpu.obs import exporter
    exporter.start(port)


_maybe_start_exporter()

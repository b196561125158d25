"""Timed spans: the structured core of the observability layer.

A span measures one operator invocation end to end: host wall-clock,
device-completion time (an explicit ``block_until_ready`` fence, the
``cudaEventSynchronize``-bracketing every CUDA profiler leans on), the XLA
compiles that happened inside it (attributed by
:mod:`~spark_rapids_jni_tpu.obs.compilemon`), device-memory deltas from the
PJRT allocator counters, and — when the body raises — the exception type,
message, and device health instead of letting the failure vanish into a log
tail.

Spans nest (thread-local stack; events carry ``depth`` and ``parent``) and
are thread-safe.  Finished spans land in a bounded in-process ring buffer
(:func:`events`) and, when a sink is configured, as one JSON object per
line in a JSONL file — the format :mod:`~spark_rapids_jni_tpu.obs.report`
consumes.

Off by default and **free when off**: the disabled path is one attribute
read, inserts no device fences, and takes no locks — the same contract as
``tracing`` (and the acceptance guard in
``tests/test_obs.py::test_disabled_spans_insert_no_fences``).  Enable with
``SRJ_TPU_EVENTS=<path>`` (JSONL sink), ``SRJ_TPU_OBS=1`` (ring only), or
:func:`enable`.  Spans also stand down inside a jit trace (recording there
would fire once per compile, not per call, and a tracer cannot be fenced).

Whenever a ``jax.profiler`` capture is running, every span — recorded or
not — also opens a ``jax.profiler.TraceAnnotation`` named
``srj/<span name>`` carrying its attributes as metadata, so the program's
spans lie on the capture's host plane on the same clock as the device's
"XLA Ops" and "XLA Modules" lines.  That path never fences and never
reads memory.  :func:`launch` is the span around one call that hands one
program to the runtime; its ``program`` attribute is the XLA module name
the launch runs as.
"""

from __future__ import annotations

import collections
import contextlib
import functools
import json
import os
import threading
import time
from typing import Dict, List, Optional

import jax
from jax.profiler import TraceAnnotation as _TraceAnnotation

from spark_rapids_jni_tpu.utils import metrics as _metrics
from spark_rapids_jni_tpu.obs import context as _context
from spark_rapids_jni_tpu.obs.metrics import observe_event as _observe_event

_RING_CAP = int(os.environ.get("SRJ_TPU_OBS_RING", "4096"))

#: prefix of every span's name in a profiler capture
TRACE_PREFIX = "srj/"
_capture_on = _TraceAnnotation.is_enabled


class _State:
    def __init__(self):
        self.lock = threading.Lock()
        self.enabled = False
        self.sink_path: Optional[str] = None
        self.sink = None
        self.ring = collections.deque(maxlen=_RING_CAP)
        # truncation accounting: ring evictions and sink write failures.
        # Silently-partial telemetry reads as complete telemetry, so every
        # drop is counted, scrapeable, and stamped into the JSONL log
        # (kind="obs_meta") at flush/disable time.
        self.events_dropped = 0
        self.sink_errors = 0


_STATE = _State()
_tls = threading.local()


def _stack() -> list:
    st = getattr(_tls, "stack", None)
    if st is None:
        st = _tls.stack = []
    return st


# ---------------------------------------------------------------------------
# Enablement + sink
# ---------------------------------------------------------------------------

def enable(sink: Optional[str] = None) -> None:
    """Turn span recording on.  ``sink``: optional JSONL path (append, one
    event per line); omitted, the current sink configuration (typically
    from ``SRJ_TPU_EVENTS``) is kept."""
    with _STATE.lock:
        _STATE.enabled = True
        if sink is not None:
            _set_sink_locked(sink)


def disable() -> None:
    """Turn span recording off and flush/close the sink.  The sink *path*
    stays configured; :func:`enable` re-opens it on the next event."""
    with _STATE.lock:
        _STATE.enabled = False
        _write_meta_locked()
        _close_sink_locked()


def enabled() -> bool:
    return _STATE.enabled


def recording() -> bool:
    """True when spans should record here and now: enabled AND executing
    eagerly (inside a jit trace a span body runs once per compile, not per
    invocation, and tracers cannot be fenced)."""
    return _STATE.enabled and _metrics.eager()


def capturing() -> bool:
    """True when spans should annotate a running profiler capture here
    and now: a capture is on AND executing eagerly."""
    return _capture_on() and _metrics.eager()


def configure_sink(path: Optional[str]) -> None:
    """Point the JSONL sink at ``path`` (``None`` detaches it)."""
    with _STATE.lock:
        if path is None:
            _close_sink_locked()
            _STATE.sink_path = None
        else:
            _set_sink_locked(path)


def sink_path() -> Optional[str]:
    return _STATE.sink_path


def _set_sink_locked(path: str) -> None:
    if path != _STATE.sink_path:
        _close_sink_locked()
    _STATE.sink_path = path


def _close_sink_locked() -> None:
    if _STATE.sink is not None:
        try:
            _STATE.sink.close()
        except Exception:
            pass
        _STATE.sink = None


def flush() -> None:
    with _STATE.lock:
        if _STATE.sink is not None:
            try:
                _write_meta_locked()
                _STATE.sink.flush()
            except Exception:
                pass


def dropped() -> Dict[str, int]:
    """Truncation counters: ``events_dropped`` (ring evictions — the
    in-process :func:`events` snapshot is missing at least that many) and
    ``sink_errors`` (JSONL write/open failures — the log on disk is
    missing events)."""
    with _STATE.lock:
        return {"events_dropped": _STATE.events_dropped,
                "sink_errors": _STATE.sink_errors}


def _write_meta_locked() -> None:
    """Stamp a ``kind="obs_meta"`` truncation record into the sink (only
    when something was actually dropped), so the offline report can warn
    that the log is incomplete."""
    if _STATE.sink is None:
        return
    if not (_STATE.events_dropped or _STATE.sink_errors):
        return
    meta = {"kind": "obs_meta", "ts": time.time(),
            "events_dropped": _STATE.events_dropped,
            "sink_errors": _STATE.sink_errors,
            "ring_cap": _RING_CAP}
    try:
        _STATE.sink.write(json.dumps(meta) + "\n")
    except Exception:
        pass


# ---------------------------------------------------------------------------
# Event stream
# ---------------------------------------------------------------------------

def emit(event: Dict) -> None:
    """Record one event (no-op unless enabled): append to the ring buffer
    (counting the eviction when the ring is full), write one JSON line
    when a sink is configured (counting write/open failures), and fold
    the event into the live metrics registry
    (:func:`~spark_rapids_jni_tpu.obs.metrics.observe_event`).  Never
    raises — observability must not take down the operation it
    observes."""
    if not _STATE.enabled:
        return
    ev = dict(event)
    ev.setdefault("ts", time.time())
    # host lane id: lets per-host JSONL logs from a multihost run merge
    # into one trace (report --merge) with one process lane per host
    ev.setdefault("host", _context.host_id())
    # replica lane id: same-host fleet replica processes share a host id,
    # so lanes key on (host, replica) — absent outside a fleet
    rep = _context.replica_id()
    if rep is not None:
        ev.setdefault("replica", rep)
    try:
        with _STATE.lock:
            if len(_STATE.ring) == _STATE.ring.maxlen:
                # the deque evicts silently; the count is what tells a
                # ring consumer its snapshot is partial
                _STATE.events_dropped += 1
                _count_drop("ring")
            _STATE.ring.append(ev)
            if _STATE.sink is None and _STATE.sink_path:
                try:
                    _STATE.sink = open(_STATE.sink_path, "a")
                except OSError:
                    _STATE.sink_path = None  # bad path: drop, keep the ring
                    _STATE.sink_errors += 1
                    _count_drop("sink")
            if _STATE.sink is not None:
                try:
                    _STATE.sink.write(json.dumps(ev, default=str) + "\n")
                    _STATE.sink.flush()
                except Exception:
                    _close_sink_locked()
                    _STATE.sink_errors += 1
                    _count_drop("sink")
        _observe_event(ev)
    except Exception:
        pass


def _count_drop(reason: str) -> None:
    try:
        from spark_rapids_jni_tpu.obs import metrics as _m
        _m.counter("srj_tpu_obs_events_dropped_total",
                   "Obs events lost to ring eviction or sink failure.",
                   ("reason",)).inc(reason=reason)
    except Exception:
        pass


def events(kind: Optional[str] = None) -> List[Dict]:
    """Snapshot of the in-process ring buffer, optionally filtered."""
    with _STATE.lock:
        evs = list(_STATE.ring)
    if kind is not None:
        evs = [e for e in evs if e.get("kind") == kind]
    return evs


def clear() -> None:
    with _STATE.lock:
        _STATE.ring.clear()


# ---------------------------------------------------------------------------
# Spans
# ---------------------------------------------------------------------------

def _mem_snapshot() -> Dict[str, int]:
    try:
        from spark_rapids_jni_tpu.memory import device_memory_stats
        return device_memory_stats()
    except Exception:
        return {}


def _reset_peak() -> None:
    """Reset the allocator's peak counter at span start where the PJRT
    backend exposes a reset, so ``peak_bytes_in_use`` at span end is the
    span-local peak rather than a process-lifetime one.  No-op (and
    harmless) on backends without the hook."""
    try:
        from spark_rapids_jni_tpu.memory import reset_peak_memory_stats
        reset_peak_memory_stats()
    except Exception:
        pass


def _device_dead() -> bool:
    try:
        from spark_rapids_jni_tpu import faultinj
        return bool(faultinj.state().device_dead)
    except Exception:
        return False


class Span:
    """An active span.  ``set(**attrs)`` attaches attributes (``rows``,
    ``bytes``, …); ``fence(value)`` blocks until ``value``'s arrays are
    device-complete and stamps the device time."""

    __slots__ = ("name", "attrs", "depth", "parent", "t0", "_fence_t",
                 "compiles", "compile_s", "_mem0", "span_id", "trace_id",
                 "parent_span_id", "tenant", "_tm")

    def __init__(self, name: str, attrs: Dict, depth: int,
                 parent: Optional[str]):
        self.name = name
        self.attrs = dict(attrs)
        self.depth = depth
        self.parent = parent
        self.t0 = 0.0
        self._fence_t = None
        self.compiles = 0
        self.compile_s = 0.0
        self._mem0 = None
        self.span_id = None
        self.trace_id = None
        self.parent_span_id = None
        self.tenant = None
        self._tm = None

    def set(self, **attrs) -> None:
        self.attrs.update(attrs)
        if self._tm is not None:
            self._tm.set_metadata(**attrs)

    def fence(self, value):
        """Block until every array in ``value`` is device-complete and
        record the span's device-completion time; returns ``value``."""
        # looked up via the module attribute so tests (and users) can
        # interpose jax.block_until_ready and see exactly our fences
        jax.block_until_ready(value)
        self._fence_t = time.perf_counter()
        return value


class _NullSpan:
    """The disabled stand-in: every method is a no-op (``fence`` does NOT
    block — disabled instrumentation must insert no device fences)."""

    __slots__ = ()

    def set(self, **attrs) -> None:
        pass

    def fence(self, value):
        return value


_NULL_SPAN = _NullSpan()
_OFF = contextlib.nullcontext(_NULL_SPAN)


class _Annotation:
    """A span that is not recorded but annotates a running profiler
    capture: ``set`` adds metadata to the annotation, ``fence`` does
    not block."""

    __slots__ = ("_tm",)

    def __init__(self, name: str, attrs: Dict):
        self._tm = _TraceAnnotation(TRACE_PREFIX + name, **attrs)

    def __enter__(self):
        self._tm.__enter__()
        return self

    def __exit__(self, *exc):
        self._tm.__exit__(*exc)
        return False

    def set(self, **attrs) -> None:
        self._tm.set_metadata(**attrs)

    def fence(self, value):
        return value


def span(name: str, **attrs):
    """Context manager timing a block as one span event.

    Yields the active :class:`Span` (or a stand-in when not recording:
    one that annotates a running profiler capture, else a no-op).  On
    exception the event records ``status="error"`` with the exception
    type/message and device health, then re-raises."""
    if recording():
        return _recorded(name, attrs)
    if capturing():
        return _Annotation(name, attrs)
    return _OFF


def launch(name: str, fn, **attrs):
    """Span around one call that hands one program to the runtime.
    ``fn`` is the jitted callable the call launches (or the function it
    jits); the span's ``program`` attribute is the XLA module name that
    launch runs as, :func:`program_name` of ``fn``."""
    if not (_STATE.enabled or _capture_on()):
        return _OFF
    return span(name, program=program_name(fn), **attrs)


def program_name(fn) -> str:
    """The XLA module a jitted callable runs as: ``jit_<function name>``."""
    return "jit_" + fn.__name__


@contextlib.contextmanager
def _recorded(name: str, attrs: Dict):
    stack = _stack()
    sp = Span(name, attrs, depth=len(stack),
              parent=stack[-1].name if stack else None)
    if not stack:
        # only an outermost span reads memory and resets the peak: a
        # child's reset would erase the peak its parent reports
        sp._mem0 = _mem_snapshot()
        if sp._mem0:
            _reset_peak()
    if _capture_on():
        sp._tm = _TraceAnnotation(TRACE_PREFIX + name, **attrs)
        sp._tm.__enter__()
    # request-scoped causality: under an active TraceContext the span
    # joins that request's trace and becomes the parent of whatever its
    # body starts — including work handed to other threads via
    # context.capture()/run_with()
    ctx = _context.current()
    ctx_token = None
    if ctx is not None:
        sp.span_id = _context.new_id()
        sp.trace_id = ctx.trace_id
        sp.parent_span_id = ctx.span_id
        sp.tenant = ctx.tenant
        ctx_token = _context._set(ctx.child(sp.span_id))
    stack.append(sp)
    sp.t0 = time.perf_counter()
    try:
        yield sp
    except Exception as e:
        _finish(sp, "error", err=e)
        raise
    else:
        _finish(sp, "ok")
    finally:
        stack.pop()
        if ctx_token is not None:
            _context._reset(ctx_token)
        if sp._tm is not None:
            sp._tm.__exit__(None, None, None)


def _finish(sp: Span, status: str, err: Optional[BaseException] = None
            ) -> None:
    wall = time.perf_counter() - sp.t0
    ev: Dict = {"kind": "span", "name": sp.name, "status": status,
                "wall_s": wall, "depth": sp.depth,
                "thread": threading.current_thread().name}
    if sp.parent is not None:
        ev["parent"] = sp.parent
    if sp._fence_t is not None:
        ev["device_s"] = sp._fence_t - sp.t0
    if sp.compiles:
        ev["compiles"] = sp.compiles
        ev["compile_s"] = sp.compile_s
    ev.update(sp.attrs)
    mem1 = _mem_snapshot() if sp.depth == 0 else None
    if mem1:
        mem = {"bytes_in_use": mem1.get("bytes_in_use"),
               "peak_bytes_in_use": mem1.get("peak_bytes_in_use")}
        if sp._mem0:
            mem["delta_bytes"] = (mem1.get("bytes_in_use", 0)
                                  - sp._mem0.get("bytes_in_use", 0))
            # true span peak over the start baseline: what the footprint
            # model trains on when the backend reports peaks (after the
            # span-start reset this is span-local, not process-lifetime)
            p1 = mem1.get("peak_bytes_in_use")
            b0 = sp._mem0.get("bytes_in_use")
            if isinstance(p1, (int, float)) and isinstance(b0, (int, float)):
                mem["peak_delta_bytes"] = max(0, int(p1) - int(b0))
        ev["mem"] = mem
    if sp.trace_id is not None:
        ev["trace_id"] = sp.trace_id
        ev["span_id"] = sp.span_id
        ev["parent_span_id"] = sp.parent_span_id
        if sp.tenant is not None:
            ev.setdefault("tenant", sp.tenant)
    if err is not None:
        ev["error_type"] = type(err).__name__
        ev["error"] = str(err)[:300]
        ev["device_dead"] = _device_dead()
    emit(ev)
    if err is not None:
        # flight recorder: errors are rare, so the import + armed check
        # live entirely on this branch (after emit — the error event must
        # already be in the ring the bundle snapshots)
        try:
            from spark_rapids_jni_tpu.obs import recorder as _recorder
            if _recorder.armed():
                _recorder.on_error(ev, err)
        except Exception:
            pass


def span_fn(name: Optional[str] = None, attrs=None, fence: bool = True):
    """Decorator form of :func:`span` for operator entry points.

    ``attrs``: optional ``(*args, **kwargs) -> dict`` extracting event
    attributes (``rows``, ``bytes``, …) from the call; extraction errors
    are swallowed — attributes are best-effort, timing is not.
    ``fence=False`` for host-only functions (no arrays to wait on).

    When not recording (disabled, or inside a jit trace) and no profiler
    capture is running, the wrapper is two predicate checks and a tail
    call — no fence, no lock.  Under a capture alone it annotates the
    call and does not fence."""

    def deco(fn):
        sname = name or fn.__name__

        def extract(args, kwargs):
            if attrs is None:
                return {}
            try:
                return attrs(*args, **kwargs) or {}
            except Exception:
                return {}

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not recording():
                if not capturing():
                    return fn(*args, **kwargs)
                with _Annotation(sname, extract(args, kwargs)):
                    return fn(*args, **kwargs)
            with span(sname, **extract(args, kwargs)) as sp:
                out = fn(*args, **kwargs)
                if fence:
                    sp.fence(out)
                return out

        return wrapper

    return deco


def current_span() -> Optional[Span]:
    """The innermost active span on this thread, if any."""
    stack = getattr(_tls, "stack", None)
    return stack[-1] if stack else None


# env-driven bring-up (the SRJ_TPU_TRACE pattern):
# SRJ_TPU_EVENTS=<path> enables recording with a JSONL sink;
# SRJ_TPU_OBS=1 enables the ring buffer alone.
_env_sink = os.environ.get("SRJ_TPU_EVENTS")
if _env_sink:
    enable(_env_sink)
elif os.environ.get("SRJ_TPU_OBS", "0") == "1":
    enable()
del _env_sink

"""Tracing/profiling utilities — the NVTX-range analogue.

The reference annotates every footer API and kernel hot spot with NVTX
ranges (``CUDF_FUNC_RANGE()``, ``NativeParquetJni.cpp:136,392,...``) and
exposes a Java-side toggle (``pom.xml:86,488-491``).  The TPU equivalents
(SURVEY.md §5): ``jax.named_scope`` annotations that show up in XLA/HLO and
in ``jax.profiler`` traces, plus a trace context manager writing a
TensorBoard-loadable profile.

Toggle: ``SRJ_TPU_TRACE=0`` (the ``ai.rapids.cudf.nvtx.enabled`` analogue)
or :func:`disable` / :func:`enable` — the decision is read per call, so a
process can turn scoping on around one suspect region and back off.
Structured timing/failure telemetry lives one layer up in
:mod:`spark_rapids_jni_tpu.obs`.
"""

from __future__ import annotations

import contextlib
import functools
import os

import jax

_enabled = os.environ.get("SRJ_TPU_TRACE", "1") != "0"


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def enabled() -> bool:
    return _enabled


def func_range(name: str | None = None):
    """Decorator: wrap a function body in a named scope (the
    ``CUDF_FUNC_RANGE`` analogue).  Scope names appear in HLO op metadata
    and profiler timelines.  The enable check happens per call — decorated
    functions honor :func:`enable`/:func:`disable` at runtime instead of
    baking in the import-time setting."""

    def deco(fn):
        scope = name or f"srj::{fn.__name__}"

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            if not _enabled:
                return fn(*args, **kwargs)
            with jax.named_scope(scope):
                return fn(*args, **kwargs)

        return wrapper

    return deco


def op_scope(op: str, bucket=None):
    """Named scope carrying the shape-bucket identity: ``srj::op[b<N>]``.

    Wrap the *jitted call site* of a bucketed dispatch with this so the
    HLO op-metadata lines up with the flight-recorder bundle key — the
    same ``(op, bucket)`` pair names the lowered ``program-*.txt`` in a
    diagnostics bundle (:mod:`spark_rapids_jni_tpu.obs.recorder`), a
    profiler scope, and the span attrs.  ``bucket=None`` (unbucketed
    dispatch) drops the suffix; disabled tracing costs one predicate."""
    if not _enabled:
        return contextlib.nullcontext()
    scope = f"srj::{op}" if bucket is None else f"srj::{op}[b{bucket}]"
    return jax.named_scope(scope)


@contextlib.contextmanager
def trace(log_dir: str = "/tmp/srj_tpu_trace"):
    """Capture a ``jax.profiler`` trace around a block (TensorBoard/XProf
    loadable — the nsight-capture analogue used to tune the reference's
    kernel constants, ``row_conversion.cu:66-70``).

    Routed through the :mod:`spark_rapids_jni_tpu.obs.profiler` session
    manager: only one capture session exists per process, so entering
    while another capture runs raises a clean
    :class:`~spark_rapids_jni_tpu.obs.profiler.SessionBusy` instead of
    an unhandled ``jax.profiler`` error."""
    from spark_rapids_jni_tpu.obs import profiler as _profiler
    with _profiler.session(log_dir):
        yield log_dir


@contextlib.contextmanager
def annotate(name: str):
    """Host-side trace annotation (``nvtxRangePush``/``Pop`` analogue)."""
    with jax.profiler.TraceAnnotation(name):
        yield

"""The eager-execution probe that every recording call site checks.

Spans (``obs/spans.py``), the shape policy, resilience and the plan
executor record only when executing eagerly: inside a jit trace a call
site's Python runs once per compile, not once per invocation, so
recording there would under-count (and cached traces record nothing at
all).  Operator counters live in the obs registry
(``srj_tpu_span_calls_total``, ``srj_tpu_span_rows_total``,
``srj_tpu_span_bytes_total``), fed by span completion.
"""

from __future__ import annotations

# trace-state probe, resolved once: ``jax.core.trace_state_clean`` has
# churned across jax releases (moved under jax._src.core; re-exported via
# a deprecation shim that newer versions drop), so try the private home
# first, then the public alias.  False == no usable probe.
_trace_probe = None


def _resolve_trace_probe():
    global _trace_probe
    if _trace_probe is None:
        probe = None
        try:
            from jax._src.core import trace_state_clean as probe
        except Exception:
            try:
                from jax.core import trace_state_clean as probe
            except Exception:
                probe = None
        _trace_probe = probe if probe is not None else False
    return _trace_probe


def eager() -> bool:
    """True when executing eagerly (outside any jit trace).  When the
    probe is unavailable or raises, report NOT eager: recording inside a
    trace fires once per compile, not per invocation — exactly the
    under/over-count this guard exists to prevent — so an unknown trace
    state must fail toward not recording."""
    probe = _resolve_trace_probe()
    if not probe:
        return False
    try:
        return bool(probe())
    except Exception:
        return False

"""Aux subsystem tests: tracing annotations and build provenance
(SURVEY.md §5 tracing/observability rows)."""

import jax
import jax.numpy as jnp
import numpy as np

from spark_rapids_jni_tpu.utils import build_info, func_range
from spark_rapids_jni_tpu.utils import tracing
from spark_rapids_jni_tpu.utils.tracing import annotate


def test_func_range_preserves_behavior():
    @func_range("srj::test_scope")
    def f(x):
        return x + 1

    assert int(f(jnp.int32(1))) == 2
    # and inside jit: the scope must appear in the lowered HLO metadata
    lowered = jax.jit(f).lower(jnp.int32(1))
    try:
        txt = lowered.as_text(debug_info=True)
    except TypeError:
        # jax 0.4.x: as_text has no debug_info kwarg, and plain as_text
        # drops location metadata — ask the MLIR module for it directly
        txt = lowered.compiler_ir(dialect="stablehlo") \
            .operation.get_asm(enable_debug_info=True)
    assert "test_scope" in txt


def test_func_range_toggle_is_dynamic(monkeypatch):
    """The enable check happens per CALL, not at decoration/import time:
    a function decorated while tracing is on must stop opening scopes
    after disable() and start again after enable()."""
    opened = []

    class _FakeScope:
        def __init__(self, name):
            opened.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax, "named_scope", _FakeScope)

    @func_range("srj::dynamic_scope")
    def f(x):
        return x + 1

    assert tracing.enabled()
    try:
        assert f(1) == 2
        assert opened == ["srj::dynamic_scope"]
        tracing.disable()
        assert f(2) == 3
        assert opened == ["srj::dynamic_scope"]  # no new scope while off
        tracing.enable()
        assert f(3) == 4
        assert opened == ["srj::dynamic_scope"] * 2
    finally:
        tracing.enable()


def test_annotate_context():
    with annotate("srj::host_section"):
        x = np.arange(4).sum()
    assert x == 6


def test_build_info_has_core_keys():
    info = build_info()
    assert "version" in info and "revision" in info
    assert info["version"] == "0.1.0"


def test_metrics_registry_counts_operators():
    """Operator calls, rows and bytes land in the obs registry's span
    families while spans record, and nothing lands there while off."""
    import numpy as np
    from spark_rapids_jni_tpu import Column, Table, INT32, obs
    from spark_rapids_jni_tpu.ops import convert_to_rows, convert_from_rows
    from spark_rapids_jni_tpu.ops.hashing import murmur3_hash

    def counts():
        snap = obs.metrics.registry().snapshot()
        return {fam: dict(snap.get(fam, {}).get("values", {}))
                for fam in ("srj_tpu_span_calls_total",
                            "srj_tpu_span_rows_total",
                            "srj_tpu_span_bytes_total")}

    def delta(a, b, fam, op):
        key = f"op={op}"
        return b[fam].get(key, 0) - a[fam].get(key, 0)

    before = counts()
    obs.enable()
    try:
        t = Table((Column.from_numpy(np.arange(64, dtype=np.int32), INT32),))
        [rows] = convert_to_rows(t)
        convert_from_rows(rows, t.dtypes)
        murmur3_hash(t)
    finally:
        obs.disable()
        obs.clear()
    after = counts()
    assert delta(before, after, "srj_tpu_span_calls_total",
                 "convert_to_rows") == 1
    assert delta(before, after, "srj_tpu_span_rows_total",
                 "convert_to_rows") == 64
    assert delta(before, after, "srj_tpu_span_bytes_total",
                 "convert_from_rows") == int(np.asarray(rows.offsets)[-1])
    assert delta(before, after, "srj_tpu_span_rows_total",
                 "murmur3_hash") == 64
    # disabled: the zero-overhead path records nothing
    murmur3_hash(Table((Column.from_numpy(np.arange(4, dtype=np.int32),
                                          INT32),)))
    assert counts() == after

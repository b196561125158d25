"""Observability-layer tests: span semantics (nesting, threading, failure
capture), the JSONL sink -> report CLI round trip, compile telemetry,
faultinj event integration, and the free-when-off fence guard the layer's
acceptance contract names (disabled instrumentation must not change device
synchronization)."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from spark_rapids_jni_tpu import Column, INT32, Table, faultinj, obs
from spark_rapids_jni_tpu.obs import report
from spark_rapids_jni_tpu.ops import convert_from_rows, convert_to_rows
from spark_rapids_jni_tpu.obs import spans
from spark_rapids_jni_tpu.ops.hashing import murmur3_hash, pmod
from spark_rapids_jni_tpu.utils import metrics


@pytest.fixture
def obs_on():
    """Enabled obs with a clean ring and no sink; everything off after."""
    obs.configure_sink(None)
    obs.clear()
    obs.enable()
    yield
    obs.disable()
    obs.configure_sink(None)
    obs.clear()


def _int_table(n=16):
    return Table((Column(INT32, jnp.arange(n, dtype=jnp.int32)),))


# ---------------------------------------------------------------------------
# Span semantics
# ---------------------------------------------------------------------------

def test_span_nesting_depth_and_parent(obs_on):
    with obs.span("outer"):
        with obs.span("inner") as sp:
            sp.set(rows=7)
    evs = obs.events(kind="span")
    by_name = {e["name"]: e for e in evs}
    assert by_name["inner"]["depth"] == 1
    assert by_name["inner"]["parent"] == "outer"
    assert by_name["inner"]["rows"] == 7
    assert by_name["outer"]["depth"] == 0
    assert "parent" not in by_name["outer"]
    # inner finishes (and is emitted) before outer
    assert evs.index(by_name["inner"]) < evs.index(by_name["outer"])


def test_span_records_wall_and_fenced_device_time(obs_on):
    t = _int_table()
    out = convert_to_rows(t)
    jax.block_until_ready([b.data for b in out])
    evs = obs.events(kind="span")
    ev = next(e for e in evs if e["name"] == "convert_to_rows")
    assert ev["status"] == "ok"
    assert ev["wall_s"] > 0
    assert 0 < ev["device_s"] <= ev["wall_s"] * 1.001
    assert ev["rows"] == t.num_rows


def test_span_failure_capture(obs_on):
    with pytest.raises(ValueError, match="boom"):
        with obs.span("doomed"):
            raise ValueError("boom")
    ev = next(e for e in obs.events(kind="span") if e["name"] == "doomed")
    assert ev["status"] == "error"
    assert ev["error_type"] == "ValueError"
    assert "boom" in ev["error"]
    assert ev["device_dead"] is False


def test_spans_thread_safe(obs_on):
    def work(i):
        for j in range(50):
            with obs.span(f"t{i}"):
                with obs.span(f"t{i}.child"):
                    pass

    threads = [threading.Thread(target=work, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    evs = obs.events(kind="span")
    assert len(evs) == 8 * 50 * 2
    for i in range(8):
        children = [e for e in evs if e["name"] == f"t{i}.child"]
        assert len(children) == 50
        # the thread-local stack keeps parentage per thread, not global
        assert all(e["parent"] == f"t{i}" and e["depth"] == 1
                   for e in children)


def test_span_inside_jit_trace_not_recorded(obs_on):
    @jax.jit
    def f(x):
        with obs.span("traced"):
            return x + 1

    f(jnp.int32(1))
    f(jnp.int32(2))  # cached call: span body doesn't even run
    assert not [e for e in obs.events(kind="span")
                if e["name"] == "traced"]


# ---------------------------------------------------------------------------
# Compile telemetry
# ---------------------------------------------------------------------------

def test_compile_telemetry_attributed_to_span(obs_on):
    before = obs.compile_totals()["compiles"]
    with obs.span("compiling"):
        # a fresh lambda gets a fresh jit cache entry, and conftest's
        # persistent-cache threshold (2s) keeps tiny compiles uncached —
        # so the backend compile really runs, inside the span
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(jnp.arange(8)))
    ev = next(e for e in obs.events(kind="span")
              if e["name"] == "compiling")
    assert ev["compiles"] >= 1
    assert ev["compile_s"] > 0
    assert obs.compile_totals()["compiles"] > before
    comp = [e for e in obs.events(kind="compile")
            if e.get("span") == "compiling"]
    assert len(comp) >= 1


# ---------------------------------------------------------------------------
# JSONL sink -> report CLI round trip
# ---------------------------------------------------------------------------

def test_jsonl_roundtrip_through_report_cli(obs_on, tmp_path, capsys):
    path = str(tmp_path / "events.jsonl")
    obs.configure_sink(path)
    t = _int_table()
    convert_from_rows(convert_to_rows(t)[0], [INT32])
    murmur3_hash(t)
    with pytest.raises(RuntimeError):
        with obs.span("exploding_leg"):
            raise RuntimeError("device busy")
    obs.flush()

    evs = list(report.load_events(path))
    assert evs and all(isinstance(e, dict) for e in evs)
    summ = report.summarize(evs)
    assert summ["ops"]["convert_from_rows"]["calls"] == 1
    assert summ["ops"]["convert_from_rows"]["rows"] == t.num_rows
    assert summ["ops"]["exploding_leg"]["failures"] == 1
    assert summ["ops"]["exploding_leg"]["error_types"] == {
        "RuntimeError": 1}

    assert report.main([path]) == 0
    out = capsys.readouterr().out
    assert "convert_from_rows" in out and "exploding_leg" in out
    assert "RuntimeError" in out

    assert report.main([path, "--prom"]) == 0
    prom = capsys.readouterr().out
    assert 'srj_tpu_span_calls_total{op="murmur3_hash"} 1' in prom
    assert 'srj_tpu_span_failures_total{op="exploding_leg"} 1' in prom

    assert report.main([path, "--json"]) == 0
    assert json.loads(capsys.readouterr().out)["ops"]


def test_report_cli_corrupt_lines_and_missing_file(tmp_path, capsys):
    p = tmp_path / "partial.jsonl"
    p.write_text('{"kind": "span", "name": "op", "status": "ok", '
                 '"wall_s": 0.5}\nnot json at all\n\n')
    assert report.main([str(p)]) == 0
    assert "op" in capsys.readouterr().out
    assert report.main([str(tmp_path / "absent.jsonl")]) == 2
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    assert report.main([str(empty)]) == 1


# ---------------------------------------------------------------------------
# faultinj -> event integration
# ---------------------------------------------------------------------------

def test_faultinj_trap_produces_fault_and_span_events(obs_on):
    faultinj.install(config={})
    try:
        x = jax.block_until_ready(jnp.arange(8))
        faultinj.state().apply_config({"pjrtExecuteFaults": {
            "*": {"percent": 100, "injectionType": 0,
                  "interceptionCount": 1}}})
        with pytest.raises(faultinj.FatalDeviceError):
            with obs.span("dying_op"):
                jax.block_until_ready(jax.jit(lambda a: a * 2)(x))
        fault = [e for e in obs.events(kind="fault")
                 if not e.get("rejected")]
        assert fault and fault[-1]["domain"] == "pjrtExecuteFaults"
        assert fault[-1]["injection_type"] == 0
        sp = next(e for e in obs.events(kind="span")
                  if e["name"] == "dying_op")
        assert sp["status"] == "error"
        assert sp["error_type"] == "FatalDeviceError"
        assert sp["device_dead"] is True
        # the dead device rejects the NEXT call too, as a rejected event
        with pytest.raises(faultinj.FatalDeviceError):
            faultinj.state().maybe_inject("pjrtExecuteFaults", "next")
        assert any(e.get("rejected") for e in obs.events(kind="fault"))
    finally:
        faultinj.reset_device()
        faultinj.uninstall()


# ---------------------------------------------------------------------------
# The free-when-off contract
# ---------------------------------------------------------------------------

def test_disabled_spans_insert_no_fences(monkeypatch):
    """With obs off and no profiler capture running, instrumented
    operators must add ZERO ``jax.block_until_ready`` fences and open no
    profiler annotation — disabled observability cannot change
    dispatch/synchronization behavior (acceptance criterion)."""
    obs.disable()
    t = _int_table()
    # warm everything first so the instrumented calls below do no lazy
    # init that might legitimately fence
    convert_from_rows(convert_to_rows(t)[0], [INT32])
    murmur3_hash(t)

    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda v: (calls.append(1), real(v))[1])
    opened = []
    real_annotation = spans._TraceAnnotation

    class CountingAnnotation(real_annotation):
        def __init__(self, name, **kw):
            opened.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(spans, "_TraceAnnotation", CountingAnnotation)
    assert not spans.capturing()
    convert_from_rows(convert_to_rows(t)[0], [INT32])
    murmur3_hash(t)
    pmod(murmur3_hash(t), 7)
    assert calls == []
    assert opened == []

    # and the SAME call sites do fence once recording is on
    obs.enable()
    try:
        convert_to_rows(t)
        assert len(calls) >= 1
        assert opened == []   # still no capture: no annotation
    finally:
        obs.disable()
        obs.clear()


# ---------------------------------------------------------------------------
# eager probe hardening (satellite: version-robust probe, fail-closed)
# ---------------------------------------------------------------------------

def _span_calls(op):
    fam = obs.metrics.registry().snapshot().get("srj_tpu_span_calls_total")
    return (fam or {}).get("values", {}).get(f"op={op}", 0)


def test_metrics_probe_failure_fails_toward_not_recording(monkeypatch,
                                                          obs_on):
    def broken_probe():
        raise RuntimeError("probe exploded")

    monkeypatch.setattr(metrics, "_trace_probe", broken_probe)
    assert metrics.eager() is False
    assert spans.recording() is False
    with obs.span("should_not_record"):
        pass
    assert obs.events(kind="span") == []
    assert _span_calls("should_not_record") == 0


def test_metrics_probe_missing_fails_toward_not_recording(monkeypatch,
                                                          obs_on):
    monkeypatch.setattr(metrics, "_trace_probe", False)
    assert metrics.eager() is False

    @obs.span_fn(attrs=lambda n: {"rows": n})
    def ghost(n):
        return n

    assert ghost(10) == 10
    assert obs.events(kind="span") == []
    assert _span_calls("ghost") == 0


def test_metrics_enable_disable_race():
    """Span writers racing an enable/disable toggler must neither raise
    nor corrupt the registry or the ring (the lock covers both; the flag
    is a benign boolean read)."""
    obs.configure_sink(None)
    obs.clear()
    before = _span_calls("raced")
    stop = threading.Event()

    def toggler():
        while not stop.is_set():
            obs.enable()
            obs.disable()

    def writer():
        for _ in range(500):
            with obs.span("raced", rows=1):
                pass

    tg = threading.Thread(target=toggler)
    ws = [threading.Thread(target=writer) for _ in range(4)]
    tg.start()
    for w in ws:
        w.start()
    for w in ws:
        w.join()
    stop.set()
    tg.join()
    obs.disable()
    evs = obs.events(kind="span")
    assert {e["name"] for e in evs} <= {"raced"}
    recorded = _span_calls("raced") - before
    assert recorded == len(evs) <= 4 * 500
    obs.clear()

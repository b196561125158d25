"""The reduction from a trace's plain form to metrics: busy union, idle
gaps, attribution by annotation, and the readers on top of them."""

import json
import os

import pytest

from benchmarks.harness import runner, spec, trace

DATA = os.path.join(os.path.dirname(__file__), "data")

HAND = {"devices": {"0": [["fusion.1", "m", 100, 50],
                          ["fusion.2", "m", 120, 60],
                          ["all-to-all.3", "m", 300, 20]]},
        "host": [["stage", 90, 150], ["stage", 280, 100]]}


def _ctx(form, dispatch=(0.001, 0.003, 0.002), work=None):
    return runner.Context(work or {}, {"hbm_bytes_per_s": 819e9},
                          trace.TraceView(form), list(dispatch), 12, 16)


def _read(name, ctx):
    return spec.load_module("metrics", name).read(ctx)


def test_union_and_clipping():
    assert trace.union([(5, 9), (0, 2), (1, 3), (9, 10)]) == [(0, 3), (5, 10)]
    assert trace.clipped_ns([(0, 3), (5, 10)], 2, 6) == 2


def test_hand_trace_reduction():
    v = trace.TraceView(HAND)
    assert v.chips == [0]
    assert v.span() == (90, 380) and v.window_ns() == 290
    assert v.busy[0] == [(100, 180), (300, 320)]
    assert v.busy_ns(0) == 100
    assert v.label_busy_ns("stage", 0) == 100
    assert v.idle_gaps() == [["between_calls", 120e-9], ["stage", 60e-9],
                             ["stage", 10e-9]]
    assert v.top_ops(2) == [["m/fusion.2", 60e-9], ["m/fusion.1", 50e-9]]


def test_readers_on_the_hand_trace():
    ctx = _ctx(HAND)
    assert _read("device.idle_pct", ctx) == pytest.approx(100 * 190 / 290)
    assert _read("host.dispatch_ms", ctx) == pytest.approx(2.0)
    assert _read("device.peak_hbm_pct", ctx) == pytest.approx(75.0)
    assert _read("to_rows_roofline", ctx) is None  # no such annotation


def test_roofline_charges_each_entry_its_own_bytes():
    """Two to_rows entries of different sizes, charged 819 B and 2,457 B:
    3,276 B at 819 GB/s is 4 ns least over the 100 ns busy inside them."""
    rows = {"devices": HAND["devices"],
            "host": [["to_rows", 90, 150], ["to_rows", 280, 100]]}
    ctx = _ctx(rows, work={"to_rows": 819 + 3 * 819})
    assert _read("to_rows_roofline", ctx) == pytest.approx(4.0)
    # no bytes charged: nothing to read, never a zero share
    assert _read("to_rows_roofline", _ctx(rows)) is None
    assert _read("murmur3_roofline", ctx) is None


def test_readers_find_nothing_in_an_empty_trace():
    ctx = _ctx({"devices": {}, "host": []}, dispatch=(),
               work={"murmur3": 1e9})
    for name in ("device.idle_pct", "murmur3_roofline", "host.dispatch_ms"):
        assert _read(name, ctx) is None


def test_recorded_chip_trace():
    """Two calls of the q6 stage traced on a v5e: every device op falls
    inside a stage annotation, to within the millisecond by which the
    trace's device and host clocks can disagree (here a call's first
    ops show about 0.5 ms before its annotation opens), and the
    reduction agrees with a plain recount of the same events."""
    with open(os.path.join(DATA, "q6_stage_trace.json")) as f:
        form = json.load(f)
    v = trace.TraceView(form)
    assert v.chips == [0] and len(v.intervals("stage")) == 2
    ops = form["devices"]["0"]
    spans = v.intervals("stage")
    ms = 1_000_000
    assert all(any(s - ms <= e[2] and e[2] + e[3] <= t + ms
                   for s, t in spans) for e in ops)
    # recount busy time in the traced stretch with a sweep over the
    # clipped ops' start and end edges
    a, b = v.span()
    clipped = [(max(e[2], a), min(e[2] + e[3], b)) for e in ops]
    edges = sorted([(s, 1) for s, t in clipped if t > s]
                   + [(t, -1) for s, t in clipped if t > s])
    busy, depth, since = 0, 0, None
    for t, d in edges:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    assert v.busy_ns(0) == busy
    assert v.label_busy_ns("stage", 0) <= busy
    assert 0 < busy <= v.window_ns()
    idle = _read("device.idle_pct", _ctx(form))
    assert idle == pytest.approx(100 * (1 - busy / v.window_ns()))
    gaps = v.idle_gaps(10)
    assert gaps and all(g[1] > 0 for g in gaps)
    assert sum(g[1] for g in v.idle_gaps(10 ** 6)) == pytest.approx(
        (v.window_ns() - busy) / 1e9)
    top = v.top_ops(3)
    assert top[0][0].startswith("jit_plan_") and top[0][1] > 1.0

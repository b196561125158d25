"""One run of one cell: set-up and warm-up, the measured window (or the
traced stretch), then the comparison with the plain reference.

Every cell is a closed loop with one caller, as a Spark task is: issue
one call, wait for all of its outputs, issue the next.  The inputs are
made once, on the device, from the seed, and stay resident.
"""

from __future__ import annotations

import dataclasses
import json
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from benchmarks.harness import spec
from benchmarks.harness import trace as _trace
from benchmarks.harness.clock import CompileClock, bad_counter_total

MAX_WARM_CALLS = 64


class Entry:
    """How a call module calls into the program: ``entry(label, nbytes,
    fn, *args)``, where ``nbytes`` is what this call's algorithm must
    move (``harness/work.py``), or None.  It times the host until ``fn``
    returns (the dispatch) and adds ``nbytes`` to the label's work; when
    traced it also wraps the call in a profiler annotation named
    ``label`` and waits for its outputs before returning, so device work
    falls inside the annotation of the entry that issued it."""

    def __init__(self, traced: bool = False):
        self.traced = traced
        self.dispatch_s = 0.0
        self.work_bytes: Dict[str, float] = {}

    def __call__(self, label: str, nbytes: Optional[float], fn: Callable,
                 *args, **kwargs):
        import jax
        if nbytes:
            self.work_bytes[label] = self.work_bytes.get(label, 0) + nbytes
        if not self.traced:
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self.dispatch_s += time.perf_counter() - t0
            return out
        with jax.profiler.TraceAnnotation(label):
            t0 = time.perf_counter()
            out = fn(*args, **kwargs)
            self.dispatch_s += time.perf_counter() - t0
            jax.block_until_ready(out)
        return out


@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader may read: ``work_bytes`` sums,
    per label, the bytes every traced entry call was charged."""
    work_bytes: Dict[str, float]
    peaks: Dict
    trace: Optional[_trace.TraceView]
    dispatch_s: List[float]
    memory_peak_bytes: Optional[int]
    memory_limit_bytes: Optional[int]


def log(rec: Dict) -> None:
    """An earlier line of standard output: a phase record."""
    print(json.dumps(rec), flush=True)


def _memory(devices):
    peak = limit = None
    for d in devices:
        st = d.memory_stats() or {}
        if "peak_bytes_in_use" in st:
            if peak is None or st["peak_bytes_in_use"] > peak:
                peak = int(st["peak_bytes_in_use"])
                limit = st.get("bytes_limit")
    return peak, (int(limit) if limit else None)


def _one_call(wl, entry, sync):
    out = wl.call(entry)
    sync(out)
    return out


def _device_hidden(events) -> List[str]:
    """Spans that ran the XLA twin where a kernel was eligible, or ran a
    kernel in interpret mode: on the chip both hide the device path."""
    return sorted({ev["name"] for ev in events if ev.get("kind") == "span"
                   and ((ev.get("impl") == "xla"
                         and ev.get("reason") != "ineligible")
                        or ev.get("interpret"))})


def warm_up(wl, mix: Dict, clock: CompileClock, platform: str) -> int:
    """Run calls until one whole call needs no new program (compiled or
    loaded from the cache), and then on
    until the mix's ``warm_calls`` have run or ``warm_seconds`` have
    passed (a program that re-plans after some calls compiles here, not
    in the window).  The first call runs with the program's spans on,
    to see which engine served each entry."""
    import jax
    from spark_rapids_jni_tpu import obs
    t0 = time.perf_counter()
    c0 = clock.programs
    obs.clear()
    obs.enable()
    try:
        out = _one_call(wl, Entry(), jax.block_until_ready)
    finally:
        obs.disable()
    events = obs.events()
    obs.clear()
    engines = {}
    for ev in events:
        if ev.get("kind") == "span" and ev.get("impl"):
            engines.setdefault(ev["name"], set()).add(
                ev.get("engine") or ev["impl"])
    hidden = _device_hidden(events) if platform == "tpu" else []
    log({"phase": "engines",
         "engines": {k: sorted(v) for k, v in sorted(engines.items())},
         "device_hidden": hidden})
    if hidden:
        raise RuntimeError(f"the XLA twin or interpret mode served {hidden} "
                           "where the TPU kernel is eligible")
    calls, settled = 1, clock.programs == c0
    while calls < MAX_WARM_CALLS:
        done = (calls >= int(mix.get("warm_calls", 1))
                or time.perf_counter() - t0 >= float(
                    mix.get("warm_seconds", 0)))
        if settled and done:
            break
        c0 = clock.programs
        out = None
        out = _one_call(wl, Entry(), jax.block_until_ready)
        calls += 1
        settled = clock.programs == c0
    return calls


def measure(wl, seconds: float, clock: CompileClock):
    """The window: calls back to back for ``seconds``; every call is
    timed from its start to all of its outputs being ready."""
    import jax
    lat: List[float] = []
    failed = 0
    entry = Entry()
    c0 = clock.programs
    out = None
    t_start = time.perf_counter()
    deadline = t_start + seconds
    while True:
        out = None
        bad0 = bad_counter_total()
        t1 = time.perf_counter()
        out = _one_call(wl, entry, jax.block_until_ready)
        t2 = time.perf_counter()
        lat.append(t2 - t1)
        if bad_counter_total() != bad0:
            failed += 1
        if t2 >= deadline:
            break
    return out, {"calls": len(lat), "window_s": t2 - t_start,
                 "latency_s": lat, "failed": failed,
                 "programs_in_window": clock.programs - c0,
                 "dispatch_s": entry.dispatch_s}


def measure_traced(wl, seconds: float, mix: Dict, clock: CompileClock):
    """The traced stretch: a few calls under the profiler, each entry in
    its own annotation."""
    import jax
    held = {"out": None, "dispatch": [], "failed": 0, "work": {}}
    c0 = clock.programs

    def calls():
        t_end = time.perf_counter() + seconds
        for _ in range(int(mix.get("trace_calls", 3))):
            held["out"] = None
            entry = Entry(traced=True)
            bad0 = bad_counter_total()
            held["out"] = _one_call(wl, entry, jax.block_until_ready)
            held["dispatch"].append(entry.dispatch_s)
            for label, nbytes in entry.work_bytes.items():
                held["work"][label] = held["work"].get(label, 0) + nbytes
            if bad_counter_total() != bad0:
                held["failed"] += 1
            if time.perf_counter() >= t_end:
                break

    form = _trace.capture(calls, wl.labels)
    return held["out"], form, {
        "calls": len(held["dispatch"]), "failed": held["failed"],
        "programs_in_window": clock.programs - c0,
        "dispatch_s": held["dispatch"], "work_bytes": held["work"]}


def _percentile(values, q):
    return float(np.percentile(np.asarray(values), q))


def end_to_end(cell, wl, setup_s: float, win: Dict) -> Dict:
    calls = win["calls"]
    value = {"setup_s": setup_s,
             "rows_per_s": calls * wl.rows_per_call / win["window_s"],
             "call_p95_ms": 1e3 * _percentile(win["latency_s"], 95)}
    return {m["name"]: {"value": value[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end}


def per_layer(cell, ctx: Context) -> Dict:
    out = {}
    for m in cell.per_layer:
        v = spec.load_module("metrics", m["name"], cell.root).read(ctx)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def check(cell, wl, observed):
    """Compare what the last call produced, as ``wl.observe`` brought it
    to the host, with the plain reference."""
    values = wl.compare(observed)
    limits = cell.mix["limits"]
    checks = {name: {"value": v, "limit": limits[name]}
              for name, v in values.items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, checks


def run(cell: spec.Cell, seed: int, seconds: float, traced: bool,
        devices, peaks: Dict, t0: float) -> Dict:
    """The whole run; returns the result line's object (``checks``
    last)."""
    import jax
    clock = CompileClock().install()
    calls_mod = spec.load_module("calls", cell.mix["call"], cell.root)
    wl = calls_mod.build(cell, seed, devices)
    warm = warm_up(wl, cell.mix, clock, devices[0].platform)
    setup_s = time.perf_counter() - t0
    log({"phase": "setup", "setup_s": setup_s, "warm_calls": warm,
         "programs": clock.programs, "from_cache": clock.cache_hits,
         "compile_s": clock.compile_s})
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind,
              "count": len(jax.devices())}
    if traced:
        out, form, win = measure_traced(wl, seconds, cell.mix, clock)
        view = _trace.TraceView(form)
        peak, limit = _memory(devices)
        chips = view.chips
        device["busy_s"] = (sum(view.busy_ns(c) for c in chips)
                            / max(len(chips), 1) / 1e9)
        device["window_s"] = view.window_ns() / 1e9
        ctx = Context(win["work_bytes"], peaks, view, win["dispatch_s"],
                      peak, limit)
        metrics = per_layer(cell, ctx)
        breakdown = {"device_ops": view.top_ops(10),
                     "idle_gaps": view.idle_gaps(10)}
    else:
        out, win = measure(wl, seconds, clock)
        peak, limit = _memory(devices)
        metrics = end_to_end(cell, wl, setup_s, win)
        breakdown = None
    log({"phase": "window", "traced": traced,
         **{k: v for k, v in win.items()
            if k not in ("latency_s", "work_bytes")},
         "latency_ms": ({"p50": 1e3 * _percentile(win["latency_s"], 50),
                         "max": 1e3 * max(win["latency_s"])}
                        if "latency_s" in win else None)})
    device["memory_peak_bytes"] = peak
    # the reference runs once the program's device state is released
    observed = wl.observe(out, np.random.default_rng(
        np.random.SeedSequence([int(seed), 7])))
    out = None
    wl.release()
    correct, checks = check(cell, wl, observed)
    result = {"correct": correct, "attempted": win["calls"],
              "failed": win["failed"], "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    return result


def report(result: Dict) -> None:
    """Each number compared, beside its limit, as the last lines of
    standard error; then the result as the last line of standard
    output."""
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)

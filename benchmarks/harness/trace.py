"""Capture a profiler trace of a few calls, bring it to a small plain
form, and reduce it.

The plain form is what every reader sees, and what the tests check the
reduction on::

    {"devices": {"0": [[op, module, start_ns, dur_ns], ...], ...},
     "host": [[label, start_ns, dur_ns], ...]}

``devices`` holds each TPU's "XLA Ops" line (one event per operation
run on the device), each op named by its HLO instruction and the
program ("XLA Modules" line) it ran in; ``host`` holds the harness's
own annotations, one per entry call, named by the traffic's labels.
Device events are attributed to a layer by the annotation interval
they fall in, never by the program's internal names.
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from typing import Dict, List, Optional, Sequence, Tuple

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_MODULES_LINE = "XLA Modules"


def capture(fn, labels: Sequence[str]) -> Dict:
    """Run ``fn()`` under the JAX profiler and return the plain form of
    what it recorded.  The raw trace goes to a temporary directory,
    which is removed."""
    import jax
    tmp = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        jax.profiler.start_trace(tmp)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        paths = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return {"devices": {}, "host": []}
        return plain_form(paths[0], labels)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _op_name(full: str) -> str:
    """``%fusion.3 = u8[...] fusion(...)`` -> ``fusion.3``."""
    return full.split(" = ", 1)[0].lstrip("%")


def _module_name(full: str) -> str:
    """``jit_plan_dc26dbfb_seg1(1538...)`` -> ``jit_plan_dc26dbfb_seg1``."""
    return full.split("(", 1)[0]


def _with_modules(ops, modules):
    """Name each op's program: the module execution it starts in."""
    out, j = [], 0
    for full, s, d in ops:
        while j < len(modules) and modules[j][1] + modules[j][2] <= s:
            j += 1
        mod = modules[j][0] if j < len(modules) and modules[j][1] <= s \
            else ""
        out.append([_op_name(full), mod, s, d])
    return out


def plain_form(xplane_path: str, labels: Sequence[str]) -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(xplane_path)
    labels = set(labels)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: sorted(
                ([ev.name, int(ev.start_ns), int(ev.duration_ns)]
                 for ev in line.events), key=lambda e: e[1])
                for line in plane.lines
                if line.name in (_OPS_LINE, _MODULES_LINE)}
            modules = [[_module_name(n), s, d]
                       for n, s, d in lines.get(_MODULES_LINE, [])]
            devices[m.group(1)] = _with_modules(lines.get(_OPS_LINE, []),
                                                modules)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in labels:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.duration_ns)])
    host.sort(key=lambda e: e[1])
    return {"devices": devices, "host": host}


# ---------------------------------------------------------------------------
# Reduction
# ---------------------------------------------------------------------------

Interval = Tuple[int, int]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clipped_ns(merged: Sequence[Interval], a: int, b: int) -> int:
    """Length of the part of disjoint ``merged`` inside ``[a, b)``."""
    return sum(max(0, min(e, b) - max(s, a)) for s, e in merged)


class TraceView:
    """Reductions over one plain-form trace."""

    def __init__(self, form: Dict):
        self.form = form
        self.host = [(l, s, s + d) for l, s, d in form["host"]]
        self.ops = {int(k): v for k, v in form["devices"].items() if v}
        self.busy = {c: union([(e[2], e[2] + e[3]) for e in evs])
                     for c, evs in self.ops.items()}

    @property
    def chips(self) -> List[int]:
        return sorted(self.ops)

    def span(self) -> Optional[Interval]:
        """The traced stretch: first annotation start to last end."""
        if not self.host:
            return None
        return (min(s for _, s, _ in self.host),
                max(e for _, _, e in self.host))

    def window_ns(self) -> int:
        sp = self.span()
        return sp[1] - sp[0] if sp else 0

    def busy_ns(self, chip: int) -> int:
        """Device busy time of ``chip`` inside the traced stretch."""
        sp = self.span()
        if sp is None or chip not in self.busy:
            return 0
        return clipped_ns(self.busy[chip], *sp)

    def intervals(self, label: str) -> List[Interval]:
        return [(s, e) for l, s, e in self.host if l == label]

    def label_busy_ns(self, label: str, chip: int) -> int:
        """Device busy time of ``chip`` inside the ``label`` annotations."""
        merged = self.busy.get(chip, [])
        return sum(clipped_ns(merged, s, e) for s, e in self.intervals(label))

    def label_at(self, t: int) -> str:
        for l, s, e in self.host:
            if s <= t < e:
                return l
        return "between_calls"

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` device operations that took most time inside the
        traced stretch, summed over chips and runs, in seconds."""
        sp = self.span()
        tot: Dict[str, int] = {}
        for evs in self.ops.values():
            for name, module, s, d in evs:
                if sp and (s + d <= sp[0] or s >= sp[1]):
                    continue
                key = f"{module}/{name}" if module else name
                tot[key] = tot.get(key, 0) + d
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns / 1e9] for name, ns in top]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest idle gaps inside the traced stretch over
        all chips, each named by the annotation open at its midpoint
        (``between_calls`` where none is), in seconds."""
        sp = self.span()
        if sp is None:
            return []
        gaps = []
        for merged in self.busy.values():
            edges = [(sp[0], sp[0])] + [
                (max(s, sp[0]), min(e, sp[1])) for s, e in merged
                if e > sp[0] and s < sp[1]] + [(sp[1], sp[1])]
            for (_, e0), (s1, _) in zip(edges, edges[1:]):
                if s1 > e0:
                    gaps.append((s1 - e0, self.label_at((e0 + s1) // 2)))
        gaps.sort(key=lambda g: -g[0])
        return [[label, ns / 1e9] for ns, label in gaps[:k]]

"""``BENCHMARK.json`` keeps to its schema, and every name in it has the
file the harness looks for."""

import json
import os
import re

from benchmarks.harness import spec

ROOT = spec.ROOT
BENCH = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_keys_names_and_units():
    assert set(BENCH) == KEYS["top"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in BENCH[kind]]
        assert len(set(names)) == len(names)
        for e in BENCH[kind]:
            extra = set(e) - KEYS[kind]
            assert extra <= ({"workloads"} if kind in ("end_to_end",
                                                       "per_layer")
                             else set()), (kind, extra)
            assert NAME.match(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.match(e["unit"]) and e["better"] in (
                    "lower", "higher")
            for k in ("why", "layer", "source"):
                if k in e and kind != "end_to_end" and kind != "per_layer":
                    assert _text(e[k]), (e["name"], k)
    for m in BENCH["per_layer"]:
        assert _text(m["layer"])
    assert all(_text(w) for w in BENCH["command"])


def test_every_name_has_its_file():
    cfgs = {c["name"]: c for c in BENCH["configs"]}
    for c in cfgs.values():
        doc = json.load(open(os.path.join(ROOT, c["file"])))
        assert doc["name"] == c["name"]
        assert all(k in doc for k in c["reduced"])
        assert c["file"].startswith("benchmarks/")
    for w in BENCH["workloads"]:
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        mix = json.load(open(os.path.join(
            ROOT, "benchmarks", "traffic", w["traffic"] + ".json")))
        assert os.path.isfile(os.path.join(
            ROOT, "benchmarks", "calls", mix["call"] + ".py"))
        cell = spec.load_cell(w["name"])
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        assert len(cell.end_to_end) >= 2 and cell.per_layer
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
        spec.load_module("metrics", m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)

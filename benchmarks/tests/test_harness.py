"""The harness end to end on the CPU at tiny sizes: every cell's build,
call and check, the refusal of a CPU device, and the lookup by name
that lets a later change add a cell with files alone."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

import cells
from benchmarks.harness import spec

ROOT = spec.ROOT
WORKLOADS = cells.WORKLOADS


@pytest.mark.parametrize("traced", [False, True], ids=["window", "traced"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_checks(workload, traced):
    cell = cells.tiny_cell(workload)
    res = cells.run(cell, traced=traced)
    assert res["correct"] is True, res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert all(c["value"] <= c["limit"] for c in res["checks"].values())
    if traced:
        # the CPU trace has no TPU device lines: readers of the device
        # trace find nothing and leave their metric out
        assert "host.dispatch_ms" in res["metrics"]
        assert not any(k.endswith("_roofline") for k in res["metrics"])
        assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    else:
        assert set(res["metrics"]) == {m["name"] for m in cell.end_to_end}
        assert all(m["value"] > 0 for m in res["metrics"].values())


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "jcudf_fixed212.murmur3_partition", "--seed",
         "12345678901",
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc):
    return not any(line.startswith("{") and '"correct"' in line
                   for line in proc.stdout.splitlines())


def test_run_refuses_a_cpu_device():
    proc = _run_py(ROOT)
    assert proc.returncode != 0 and _no_result(proc)
    assert "no TPU" in proc.stderr


def test_run_fails_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(tmp_path)
    assert proc.returncode != 0 and _no_result(proc)


def _run_module():
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    try:
        import run
    finally:
        sys.path.pop(0)
    return run


def test_unknown_device_kind_is_an_error():
    run = _run_module()
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(run.NoDevice):
        run.load_peaks("TPU v99")


def test_one_compile_cache_in_the_checkout():
    import jax
    run = _run_module()
    try:
        run.configure_jax()
        assert jax.config.jax_compilation_cache_dir == run.CACHE_DIR
        assert run.CACHE_DIR == os.path.join(ROOT, ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", None)


def test_decode_entries_are_charged_their_own_rows(monkeypatch):
    """A table that leaves to_rows in two batches: each from_rows entry
    is charged the bytes of its own batch, so the two together move what
    one whole-table pass does, not twice that."""
    from spark_rapids_jni_tpu import ops
    from spark_rapids_jni_tpu.table import slice_table
    from benchmarks.harness import runner, work
    orig = ops.convert_to_rows
    monkeypatch.setattr(ops, "convert_to_rows", lambda t: [
        orig(slice_table(t, 0, 512))[0],
        orig(slice_table(t, 512, t.num_rows))[0]])
    cell = cells.tiny_cell("jcudf_fixed155.to_rows", decode=True)
    wl = cells.build(cell)
    entry = runner.Entry()
    batches, decoded = wl.call(entry)
    assert [b.num_rows for b in batches] == [512, 1536] and len(decoded) == 2
    whole = work.rows_convert_bytes(wl.itemsizes, 2048)
    assert entry.work_bytes == {"to_rows": whole, "from_rows": whole}
    obs = wl.observe((batches, decoded), np.random.default_rng(1))
    assert all(v == 0 for v in wl.compare(obs).values())


NEW_CALL = '''
import jax.numpy as jnp
import numpy as np


class Sum:
    labels = ("sum",)
    rows_per_call = 1000

    def __init__(self, seed):
        self.x = jnp.arange(1000, dtype=jnp.int32) + seed % 7

    def call(self, entry):
        return entry("sum", 4000, jnp.sum, self.x)

    def observe(self, out, rng):
        return {"got": int(out), "x": np.asarray(self.x)}

    def release(self):
        self.x = None

    def compare(self, obs):
        return {"sum_wrong": abs(obs["got"] - int(obs["x"].sum()))}


def build(cell, seed, devices):
    return Sum(seed)
'''

NEW_METRIC = '''
def read(ctx):
    return 100.0 * ctx.work_bytes["sum"] / (4000 * len(ctx.dispatch_s))
'''


def test_a_cell_added_as_files_is_found_by_name(tmp_path):
    """A new call kind, traffic mix and per-layer metric, each a new
    file, and a new workload entry: no file already there changes."""
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    bench["workloads"].append({"name": "jcudf_fixed212.tiny_sum",
                               "config": "jcudf_fixed212",
                               "traffic": "tiny_sum", "chips": 1,
                               "why": "a test cell"})
    bench["per_layer"].append({"name": "tiny.sum_pct", "unit": "%",
                               "better": "higher", "source": "device_trace",
                               "layer": "device", "moves": "rows_per_s",
                               "workloads": ["jcudf_fixed212.tiny_sum"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "benchmarks" / "traffic" / "tiny_sum.json").write_text(
        json.dumps({"call": "tiny_sum", "limits": {"sum_wrong": 0}}))
    (tmp_path / "benchmarks" / "calls" / "tiny_sum.py").write_text(NEW_CALL)
    (tmp_path / "benchmarks" / "metrics" / "tiny.sum_pct.py").write_text(
        NEW_METRIC)
    cell = spec.load_cell("jcudf_fixed212.tiny_sum", root=str(tmp_path))
    assert [m["name"] for m in cell.per_layer] == [
        "host.dispatch_ms", "device.idle_pct", "device.peak_hbm_pct",
        "tiny.sum_pct"]
    res = cells.run(cell)
    assert res["correct"] and res["checks"]["sum_wrong"]["value"] == 0
    res = cells.run(cell, traced=True)
    assert res["metrics"]["tiny.sum_pct"]["value"] == 100.0


def test_missing_files_are_spec_errors(tmp_path):
    with pytest.raises(spec.SpecError):
        spec.load_cell("no.such_cell")
    with pytest.raises(spec.SpecError):
        spec.load_module("metrics", "no.such_metric")

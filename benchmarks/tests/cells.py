"""Tiny versions of the benchmark's cells for CPU tests: the same call
kinds, traffic and checks, at sizes a test run holds."""

from __future__ import annotations

import dataclasses
import time

TINY = {
    "jcudf_fixed212": {"num_columns": 14, "num_rows": 2048},
    "jcudf_fixed155": {"num_columns": 20, "num_rows": 2048},
}
PEAKS = {"hbm_bytes_per_s": 819e9}
WORKLOADS = ["jcudf_fixed155.to_rows", "jcudf_fixed212.murmur3_partition"]


def tiny_cell(workload: str, root=None, **mix):
    """The cell at a tiny size; ``mix`` overrides keys of its traffic."""
    from benchmarks.harness import spec
    cell = spec.load_cell(workload, **({"root": root} if root else {}))
    cfg = dict(cell.config)
    cfg.update(TINY.get(cfg["name"], {}))
    return dataclasses.replace(cell, config=cfg, mix=dict(cell.mix, **mix))


def devices(cell):
    import jax
    return jax.devices()[:cell.chips]


def run(cell, seed=2**31 + 5, traced=False, seconds=0.2):
    """A whole run past the look for a chip."""
    from benchmarks.harness import runner
    return runner.run(cell, seed, seconds, traced, devices(cell), PEAKS,
                      time.perf_counter())


def build(cell, seed=2**31 + 5):
    from benchmarks.harness import spec
    mod = spec.load_module("calls", cell.mix["call"], cell.root)
    return mod.build(cell, seed, devices(cell))

#!/usr/bin/env python3
"""Run one benchmark cell on the TPU it is started on.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

``<cell>`` is a ``workloads`` entry of ``BENCHMARK.json``.  With
``--trace 0`` the run measures the cell's end-to-end metrics over a
window of ``--seconds``; with ``--trace 1`` it traces a few calls and
reports the per-layer metrics read from the trace.  Earlier lines of
standard output are phase records; the last line is one JSON object
(``correct``, ``attempted``, ``failed``, ``metrics``, ``device``,
``breakdown`` when traced, and ``checks``, each number compared with
its limit).  Without a TPU, or with fewer chips than the cell asks for,
it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

# the compile cache lives at a fixed path inside the checkout, settled
# before JAX is imported, and the program is handed the same directory
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


class NoDevice(RuntimeError):
    """No TPU, or fewer chips than the cell asks for."""


def load_peaks(kind: str) -> dict:
    """The peak table's row for ``kind``; an unknown kind is an error."""
    with open(os.path.join(ROOT, "benchmarks", "peaks.json")) as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise NoDevice(f"device kind {kind!r} is not in benchmarks/peaks.json "
                       f"(have {sorted(table['devices'])})")
    return table["devices"][kind]


def check_devices(chips: int):
    """The first ``chips`` TPU devices and their peaks."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoDevice(f"no TPU: the platform is {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    return devs[:chips], load_peaks(devs[0].device_kind)


def configure_jax():
    """The checkout's compile cache, for every cell."""
    import jax
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the cache is this checkout's own: keep every program, evict nothing
    jax.config.update("jax_compilation_cache_max_size", -1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    from benchmarks.harness import runner, spec
    try:
        cell = spec.load_cell(args.workload)
        configure_jax()
        devices, peaks = check_devices(cell.chips)
    except (spec.SpecError, NoDevice) as e:
        print(f"benchmark: {e}", file=sys.stderr, flush=True)
        return 2
    runner.log({"phase": "device", "kind": devices[0].device_kind,
                "chips": len(devices), "cache_dir": CACHE_DIR,
                "workload": cell.name, "seed": args.seed})
    result = runner.run(cell, args.seed, args.seconds, bool(args.trace),
                        devices, peaks, T0)
    runner.report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

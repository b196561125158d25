#!/usr/bin/env python3
"""Read each cell's control beside the program, seed by seed.

    python3 benchmarks/control.py --workload <cell> --seeds 1 2 3

For every seed: build the cell's inputs at its own size, run one call
of the program, and compare, as a run does; then put the control in the
program's place -- the plain reference with one guarantee broken, or
computed one precision lower -- and compare that the same way.  Prints
one JSON line a seed with both readings and the limits.  The control
has to come out not correct; the benchmark's own runs never run it.
Like ``run.py`` it needs the cell's chips.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def readings(cell, seed: int, devices):
    """``(program values, control values)`` for one seed."""
    import jax
    import numpy as np
    from benchmarks.harness import runner, spec
    wl = spec.load_module("calls", cell.mix["call"], cell.root).build(
        cell, seed, devices)
    out = wl.call(runner.Entry())
    jax.block_until_ready(out)
    rng = np.random.default_rng(np.random.SeedSequence([int(seed), 7]))
    obs = wl.observe(out, rng)
    del out
    wl.release()
    return wl.compare(obs), wl.compare(wl.control(obs))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
    import run
    os.environ["JAX_COMPILATION_CACHE_DIR"] = run.CACHE_DIR
    from benchmarks.harness import spec
    try:
        cell = spec.load_cell(args.workload)
        run.configure_jax()
        devices, _ = run.check_devices(cell.chips)
    except (spec.SpecError, run.NoDevice) as e:
        print(f"control: {e}", file=sys.stderr, flush=True)
        return 2
    limits = cell.mix["limits"]
    for seed in args.seeds:
        program, control = readings(cell, seed, devices)
        print(json.dumps({
            "workload": cell.name, "seed": seed, "program": program,
            "control": control, "limits": limits,
            "control_fails": any(v > limits[k] for k, v in control.items()),
            "program_passes": all(v <= limits[k]
                                  for k, v in program.items())}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

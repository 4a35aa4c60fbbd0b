"""Readings the limit of a cell's check is set from, on the chip: for each
seed, the program's reading (a short window at the cell's own load, checked
as a run checks it) and the fp8 control's reading of the same sample, all in
one process.  The benchmark's own runs never run the control.

    python chipbench/calibrate.py CELL SECONDS SEED [SEED ...]

Prints one JSON line per seed and a summary (largest program reading,
smallest control reading).
"""
from __future__ import annotations

import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list) -> int:
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench import harness

    cell, seconds, seeds = argv[0], float(argv[1]), [int(s) for s in argv[2:]]
    bench = harness.load_benchmark()
    w, config, traffic, limits = harness.load_cell(bench, cell)
    devices = harness.require_chips(int(w["chips"]))
    harness.setup_compile_cache()
    drv = harness.driver(traffic["driver"])
    program, control = {}, {}
    for seed in seeds:
        ctx = harness.Context(
            cell=cell, config=config, traffic=traffic, seed=seed,
            seconds=seconds, trace=False,
            t_start=time.perf_counter(), limits=limits, control=True,
            log=lambda s: print(f"  {s}", flush=True),
            device_kind=devices[0].device_kind)
        with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
            run = drv.run(ctx, tmp)
        for k, (v, _) in run.checks.items():
            program.setdefault(k, []).append(v)
            control.setdefault(k, []).append(run.control[k])
        print(json.dumps({"seed": seed, "attempted": run.attempted,
                          "failed": run.failed,
                          "program": {k: v for k, (v, _) in run.checks.items()},
                          "control": run.control}), flush=True)
    for k in program:
        print(json.dumps({"check": k, "program_max": max(program[k]),
                          "control_min": min(control[k]),
                          "ratio": min(control[k]) / max(program[k]),
                          "program": program[k], "control": control[k]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

"""Run several runs of the benchmark one after another, each in its own
process, and keep what each printed.

    python chipbench/sweep.py OUT_DIR CELL:SEED:SECONDS:TRACE [...]

Each run is ``python3 chipbench/run.py`` as the benchmark's command gives
it; its standard output and error go to ``OUT_DIR/<n>.out`` and
``<n>.err``, and one line per run (exit code, wall time, the result
line) to ``OUT_DIR/summary.jsonl``.  This process never imports JAX, so
each run has the chip to itself.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv: list) -> int:
    out_dir, specs = argv[0], argv[1:]
    os.makedirs(out_dir, exist_ok=True)
    bad = 0
    for n, spec in enumerate(specs):
        cell, seed, seconds, trace = spec.split(":")
        cmd = [sys.executable, os.path.join(ROOT, "chipbench", "run.py"),
               "--workload", cell, "--seed", seed, "--seconds", seconds,
               "--trace", trace]
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                               timeout=900)
            rc, stdout, stderr = p.returncode, p.stdout, p.stderr
        except subprocess.TimeoutExpired as e:
            rc, stdout, stderr = 124, e.stdout or "", e.stderr or ""
            stdout = stdout.decode() if isinstance(stdout, bytes) else stdout
            stderr = stderr.decode() if isinstance(stderr, bytes) else stderr
        wall = time.perf_counter() - t0
        with open(os.path.join(out_dir, f"{n}.out"), "w") as f:
            f.write(stdout)
        with open(os.path.join(out_dir, f"{n}.err"), "w") as f:
            f.write(stderr)
        lines = stdout.strip().splitlines()
        try:
            res = json.loads(lines[-1]) if lines else None
        except json.JSONDecodeError:
            res = None
        bad += rc != 0 or not (res or {}).get("correct")
        rec = {"n": n, "cell": cell, "seed": int(seed), "trace": int(trace),
               "rc": rc, "wall_s": wall, "result": res}
        with open(os.path.join(out_dir, "summary.jsonl"), "a") as f:
            f.write(json.dumps(rec) + "\n")
        m = {k: v["value"] for k, v in ((res or {}).get("metrics") or {}).items()}
        print(f"run {n} {cell} seed={seed} trace={trace} rc={rc} "
              f"wall={wall:.1f}s correct={(res or {}).get('correct')} "
              f"checks={(res or {}).get('checks')} metrics={m}", flush=True)
        if rc != 0 or res is None:
            print("  stderr tail: " + stderr[-1500:].replace("\n", "\n  "),
                  flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))

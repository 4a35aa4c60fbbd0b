"""Smoke run of the offloader on one TPU chip: plan -> store -> serve.

    python chip_smoke.py [--seed N]        # one chip: model, jaxpr, python_ast
    python chip_smoke.py --four-chips      # four chips: mesh destinations and
                                           # the sharded offload plan only

Each phase drives the system through its normal entry points
(``Offloader.plan`` -> ``PlanStore`` -> ``Server.from_store`` for the model;
the jaxpr and python_ast frontends for traced and source programs) and
checks its outputs against a plain float32 reference; see
``src/repro/launch/smoke.py``.  Weights and inputs are random, made from
``--seed``.  Any timing printed is a smoke figure, not a benchmark.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
printed only when every phase passed.  Without a TPU, or outside a checkout
of the repository, the script exits non-zero and prints no such line.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import traceback

ROOT = os.path.dirname(os.path.abspath(__file__))
SRC = os.path.join(ROOT, "src")


def _fail(msg: str, code: int = 1) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(code)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(SRC, "repro")):
        _fail(f"no repro package under {SRC}: run this script from a "
              f"checkout of the repository")
    sys.path.insert(0, SRC)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        _fail(f"no TPU found: JAX's first device is {dev.platform!r} "
              f"({dev.device_kind}); this smoke run needs a TPU chip")
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        _fail(f"--four-chips needs 4 TPU chips, found {len(devices)}")

    from repro.configs import get_config
    from repro.launch import smoke
    from repro.launch.compile_cache import setup_compile_cache

    def log(msg: str) -> None:
        print(f"[smoke] {msg}", flush=True)

    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)} "
        f"jax {jax.__version__}")
    log(f"compile cache: {setup_compile_cache()}")

    if args.four_chips:
        phases = [("mesh", lambda: smoke.mesh_phase(
            smoke.moe_mesh_config(get_config("olmoe_1b_7b")), n=4,
            seed=args.seed, log=log))]
    else:
        phases = [
            ("model", lambda: smoke.model_phase(
                get_config("qwen3_0_6b"), device_kind=dev.device_kind,
                seed=args.seed, log=log)),
            ("jaxpr", lambda: smoke.jaxpr_phase(seed=args.seed, log=log)),
            ("python_ast", lambda: smoke.python_phase(seed=args.seed,
                                                      log=log)),
        ]

    failed = []
    for name, run in phases:
        log(f"--- phase {name}")
        try:
            run()
        except Exception as e:  # noqa: BLE001 — report every phase, then exit
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name} FAILED: {type(e).__name__}: {e}")
        else:
            log(f"phase {name} ok")
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            log(f"device 0 peak_bytes_in_use={stats['peak_bytes_in_use']} "
                f"after phase {name}")
    if failed:
        _fail(f"phases failed: {failed}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()

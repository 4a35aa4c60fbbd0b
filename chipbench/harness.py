"""The benchmark harness: finds a cell's configuration, traffic and metrics
by the names ``BENCHMARK.json`` gives them, runs the cell's driver, reads
each metric with its own reader and prints the result line.

Layout, all found by name:

* ``configs/<config>.json`` — the configuration as it is run;
* ``traffic/<traffic>.json`` — the traffic mix; its ``driver`` key names
  the general driver (``drivers/<driver>.py``) that runs every mix of its
  kind;
* ``metrics/<metric>.py`` — one reader per metric: ``read(run)`` returns a
  number, or ``None`` where the run holds nothing to read;
* ``limits/<cell>.json`` — the limit of each number the cell's check
  compares, with the readings it was set from.
"""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import sys
import tempfile
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


@dataclass
class Context:
    """What a driver gets: the cell, its data and the run's options."""

    cell: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                    # host clock when the process began
    limits: dict = field(default_factory=dict)   # check name -> limit
    log: Callable[[str], None] = print
    device_kind: str = ""
    control: bool = False             # also read the fp8 control (limits)


@dataclass
class Run:
    """What a driver returns; the metric readers read it."""

    config: dict
    traffic: dict
    device_kind: str = ""
    setup_s: float = float("nan")
    window: tuple = (0.0, 0.0)        # host clock: first start, last end
    requests: list = field(default_factory=list)   # serving: one per call
    plans: list = field(default_factory=list)      # planning: one per plan
    attempted: int = 0
    failed: int = 0
    checks: dict = field(default_factory=dict)     # name -> (value, limit)
    memory_peak_bytes: int = 0
    compiles_in_window: int = 0
    trace: Any = None                 # trace.Trace of the traced part
    spans: list = field(default_factory=list)      # the program's own spans
    control: dict = field(default_factory=dict)    # check name -> control's

    @property
    def correct(self) -> bool:
        return (self.attempted > 0 and self.failed == 0 and bool(self.checks)
                and all(v <= lim for v, lim in self.checks.values()))


# ---------------------------------------------------------------------------
# finding things by name
# ---------------------------------------------------------------------------


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(bench: dict, name: str, root: str = ROOT) -> tuple:
    """(workload entry, configuration, traffic, limits) of cell ``name``."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    limits = {k: float(v["limit"]) for k, v in
              _json(root, "chipbench", "limits", f"{name}.json").items()}
    return (w, _json(root, conf["file"]),
            _json(root, "chipbench", "traffic", f"{w['traffic']}.json"),
            limits)


def metrics_of(bench: dict, cell: str, trace: bool) -> list:
    """The metric entries a run of ``cell`` reports: end-to-end ones
    untraced, per-layer ones traced."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}

    def applies(m: dict) -> bool:
        if "workloads" in m:
            return cell in m["workloads"]
        return m["moves"] in moved

    return [m for m in bench["per_layer"] if applies(m)]


def reader(name: str, root: str = ROOT) -> Callable[[Run], Optional[float]]:
    path = os.path.join(root, "chipbench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def driver(name: str):
    return importlib.import_module(f"chipbench.drivers.{name}")


# ---------------------------------------------------------------------------
# the device
# ---------------------------------------------------------------------------


def require_chips(n: int) -> list:
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX's first device is {devices[0].platform!r} "
                     f"({devices[0].device_kind}); this benchmark measures "
                     f"on a TPU only")
    if len(devices) < n:
        raise NoChip(f"the cell needs {n} TPU chips, JAX found {len(devices)}")
    return devices


def setup_compile_cache() -> str:
    """JAX's persistent compilation cache: ``JAX_COMPILATION_CACHE_DIR``
    where set, else ``.jax_cache`` at the checkout's root (a fixed path, so
    later runs in this checkout find every program)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes() -> int:
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.local_devices()]
    return int(max(peaks or [0]))


# ---------------------------------------------------------------------------
# the result
# ---------------------------------------------------------------------------


def result(run: Run, metrics: list, devices: list, root: str = ROOT) -> dict:
    values = {}
    for m in metrics:
        v = reader(m["name"], root)(run)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": values, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        out["breakdown"] = {"device_ops": run.trace.top_ops(10),
                            "idle_gaps": run.trace.named_gaps(10)}
    out["checks"] = {k: {"value": float(v), "limit": float(lim)}
                     for k, (v, lim) in run.checks.items()}
    return out


def run_cell(cell: str, *, seed: int, seconds: float, trace: bool,
             devices: list, t_start: float, device_kind: str = "",
             root: str = ROOT, log: Callable[[str], None] = print) -> tuple:
    """Run ``cell`` on ``devices`` (already checked); returns the driver's
    :class:`Run` and the result line's object."""
    bench = load_benchmark(root)
    w, config, traffic, limits = load_cell(bench, cell, root)
    ctx = Context(cell=cell, config=config, traffic=traffic, seed=int(seed),
                  seconds=float(seconds), trace=bool(trace),
                  t_start=t_start, limits=limits,
                  log=log, device_kind=device_kind or devices[0].device_kind)
    with tempfile.TemporaryDirectory(prefix="chipbench_") as tmp:
        run = driver(traffic["driver"]).run(ctx, tmp)
    return run, result(run, metrics_of(bench, cell, ctx.trace), devices, root)


def main(args, t_start: float) -> int:
    """Run one cell as the command line asks; returns the exit code."""
    bench = load_benchmark()
    w = {x["name"]: x for x in bench["workloads"]}.get(args.workload)
    if w is None:
        print(f"chipbench: no workload {args.workload!r}", file=sys.stderr)
        return 2

    def log(msg: str) -> None:
        print(f"[chipbench] {msg}", flush=True)

    try:
        devices = require_chips(int(w["chips"]))
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 3
    import jax

    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)} jax {jax.__version__}")
    log(f"compile cache: {setup_compile_cache()}")
    _, out = run_cell(args.workload, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), devices=devices,
                      t_start=t_start, log=log)
    for name, c in out["checks"].items():
        print(f"check {name} = {c['value']!r} limit {c['limit']!r} "
              f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0

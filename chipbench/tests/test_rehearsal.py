"""The harness and its drivers end to end on the CPU, at tiny sizes,
through internal functions (the command itself refuses without a TPU)."""
import json
import os
import shutil
import subprocess
import sys
import tempfile

import jax
import pytest

import tiny
from chipbench import harness
from chipbench.drivers import plan, serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


@pytest.mark.parametrize("config", ["dense", "moe"])
def test_serve_driver_end_to_end(config):
    cfg = {"dense": tiny.DENSE, "moe": tiny.MOE}[config]
    ctx = tiny.context(cfg, tiny.SERVE, {"widest_logit_gap": 0.05})
    with tempfile.TemporaryDirectory() as d:
        run = serve.run(ctx, d)
    assert run.correct, run.checks
    assert run.attempted == len(run.requests) > 2 and run.failed == 0
    assert run.compiles_in_window == 0
    assert {r["prompt"] for r in run.requests} == {8, 16}
    assert run.window[0] < run.window[1] and run.setup_s > 0


def test_plan_driver_end_to_end():
    ctx = tiny.context(tiny.ATTN, tiny.PLAN, {"output_rel_l2": 0.02},
                       seconds=0.05)
    with tempfile.TemporaryDirectory() as d:
        run = plan.run(ctx, d)
    assert run.correct, run.checks
    assert len(run.plans) == run.attempted >= 1
    assert all(p["compiles"] > 0 and p["evaluations"] > 0 for p in run.plans)
    assert all(p["call_s"] > 0 for p in run.plans)


def test_every_seed_asks_for_the_same_work():
    a = serve.schedule(1, [128, 512], 40)
    b = serve.schedule(2**33 + 5, [128, 512], 40)
    assert a != b
    for s in (a, b):
        for i in range(0, 40, 2):
            assert sorted(s[i:i + 2]) == [128, 512]


def test_sample_holds_the_longest_request():
    served = [(i, None) for i in range(10)]
    lengths = [8, 16] * 5
    for seed in range(20):
        picked = serve.sample(seed, served, lengths, 2)
        assert len(picked) == 2 and any(lengths[i] == 16 for i, _ in picked)


def _command(cwd, *extra, env=None):
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen3-0.6b.decode", "--seed", "3", "--seconds", "1", *extra],
        cwd=cwd, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu", **(env or {})))


def test_command_refuses_without_a_tpu():
    p = _command(ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip().endswith("}")


def test_command_refuses_without_the_program():
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), d)
        shutil.copytree(os.path.join(ROOT, "chipbench"),
                        os.path.join(d, "chipbench"))
        p = _command(d)
    assert p.returncode != 0
    assert not p.stdout.strip()


def test_a_new_cell_is_added_as_files_only():
    """A configuration, a traffic mix, a per-layer metric and the cell's
    limits are new files; BENCHMARK.json gains entries; no file that was
    there changes, and the harness runs the new cell."""
    with tempfile.TemporaryDirectory() as d:
        shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), d)
        shutil.copytree(os.path.join(ROOT, "chipbench"),
                        os.path.join(d, "chipbench"),
                        ignore=shutil.ignore_patterns("__pycache__"))
        before = {p: open(p, "rb").read() for p in _files(d)}
        cb = os.path.join(d, "chipbench")
        _write(os.path.join(cb, "configs", "tiny-moe.json"), tiny.MOE)
        _write(os.path.join(cb, "traffic", "tiny-chat.json"), tiny.SERVE)
        # a loose limit: what is compared, and against what, is
        # test_faults.py's and test_control.py's to show
        _write(os.path.join(cb, "limits", "tiny-moe.tiny-chat.json"),
               {"widest_logit_gap": {"limit": 1.0}})
        with open(os.path.join(cb, "metrics", "requests_done.py"), "w") as f:
            f.write("def read(run):\n    return len(run.requests)\n")
        bench = json.load(open(os.path.join(d, "BENCHMARK.json")))
        bench["configs"].append({"name": "tiny-moe", "source": "tiny",
                                 "file": "chipbench/configs/tiny-moe.json",
                                 "reduced": [], "why": "test"})
        bench["workloads"].append({"name": "tiny-moe.tiny-chat",
                                   "config": "tiny-moe",
                                   "traffic": "tiny-chat", "chips": 1,
                                   "why": "test"})
        for m in bench["end_to_end"]:
            if m["name"] == "output_tokens_per_s":
                m["workloads"].append("tiny-moe.tiny-chat")
        bench["per_layer"].append({
            "name": "requests_done", "unit": "count", "better": "higher",
            "source": "host_clock", "layer": "serve loop",
            "moves": "output_tokens_per_s",
            "workloads": ["tiny-moe.tiny-chat"]})
        _write(os.path.join(d, "BENCHMARK.json"), bench)
        for p, data in before.items():
            if not p.endswith("BENCHMARK.json"):
                assert open(p, "rb").read() == data, p
        import time
        for trace in (False, True):
            if trace:
                # a traced run needs the chip's profiler planes; read the
                # per-layer metric list and the new reader without them
                names = [m["name"] for m in harness.metrics_of(
                    bench, "tiny-moe.tiny-chat", True)]
                assert "requests_done" in names
                continue
            run, out = harness.run_cell(
                "tiny-moe.tiny-chat", seed=5, seconds=0.5, trace=False,
                devices=jax.devices(), t_start=time.perf_counter(),
                device_kind="TPU v5 lite", root=d, log=lambda s: None)
            assert out["correct"], out
            assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
            assert list(out)[-1] == "checks"
            assert harness.reader("requests_done", d)(run) == \
                len(run.requests) > 0


def _files(d):
    return [os.path.join(b, f) for b, _, fs in os.walk(d) for f in fs]


def _write(path, obj):
    with open(path, "w") as f:
        json.dump(obj, f)

"""The chip smoke run at CPU sizes: its phases, its refusals, and the
compile-cache rule of its entry points."""
import os
import shutil
import subprocess
import sys

import jax
import pytest

from repro.configs import get_config
from repro.launch import smoke

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_TINY_ATTN = dict(batch=1, seq=16, n_heads=4, n_kv_heads=2, head_dim=8,
                  d_model=32)


def _run_script(cwd, env_extra=None):
    env = dict(os.environ)
    env.pop("PYTHONPATH", None)
    env.update(env_extra or {})
    return subprocess.run([sys.executable, "chip_smoke.py"], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=120)


def test_chip_smoke_refuses_without_a_tpu():
    res = _run_script(ROOT, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert "no TPU found" in res.stderr, res.stderr[-2000:]
    assert '"ok"' not in res.stdout


def test_chip_smoke_refuses_outside_a_checkout(tmp_path):
    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run_script(tmp_path, {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert "no repro package" in res.stderr, res.stderr[-2000:]
    assert '"ok"' not in res.stdout


def test_jaxpr_phase_substitutes_both_kernels():
    out = smoke.jaxpr_phase(**_TINY_ATTN, population=2, generations=1,
                            repeats=1, log=lambda s: None)
    assert set(out["forced_substituted"].values()) == {"pallas"}
    assert len(out["forced_substituted"]) == 2
    assert out["max_rel_l2"] <= smoke.BF16_REL_L2


def test_refused_pallas_kernel_fails_the_phase(monkeypatch):
    # compiling the kernels for the device rather than interpreting them is
    # refused on the CPU backend: the phase must fail, not fall back
    from repro.kernels import ops

    monkeypatch.setattr(ops, "_interpret", lambda: False)
    jax.clear_caches()          # no trace made in interpret mode is reused
    try:
        with pytest.raises(smoke.SmokeFailure, match="failed to compile"):
            smoke.jaxpr_phase(**_TINY_ATTN, population=2, generations=1,
                              repeats=1, log=lambda s: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()


def test_model_phase_serves_from_the_store():
    out = smoke.model_phase(get_config("qwen3_0_6b").reduced(),
                            device_kind="TPU v5 lite", batch=2, prompt=8,
                            new_tokens=4, requests=2, population=2,
                            generations=1, log=lambda s: None)
    assert out["max_rel_l2"] <= smoke.BF16_REL_L2
    assert out["plan_compiles"] > 0


def test_python_phase_runs_a_variant():
    out = smoke.python_phase(rows=16, cols=8, population=2, generations=1,
                             log=lambda s: None)
    assert set(out["chosen"].values()) <= {"fused_jnp", "pallas"}


_MESH_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
from repro.configs import get_config
from repro.launch import smoke
out = smoke.mesh_phase(
    smoke.moe_mesh_config(get_config("olmoe_1b_7b").reduced()), n=4,
    batch=4, seq=16, n_heads=4, n_kv_heads=2, head_dim=8, d_model=32,
    loss_batch=8, loss_seq=32, population=2, generations=1,
    log=lambda s: None)
assert out["mesh"] == {"data": 2, "model": 2}, out
print("MESH_PHASE_OK", out["jaxpr_max_rel_l2"], out["loss_abs_diff"])
"""


def test_mesh_phase_on_four_virtual_devices():
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], cwd=ROOT,
                         env=env, capture_output=True, text=True,
                         timeout=300)
    assert "MESH_PHASE_OK" in res.stdout, (res.stdout[-2000:],
                                           res.stderr[-3000:])


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_directory(monkeypatch, tmp_path, env_dir):
    from repro.launch.compile_cache import setup_compile_cache

    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        want = str(tmp_path)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    try:
        assert setup_compile_cache() == want
        assert jax.config.jax_compilation_cache_dir == want
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

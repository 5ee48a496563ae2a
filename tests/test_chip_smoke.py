"""`chip_smoke.py` guarded on CPU: its phases at smoke size, its refusal to
report success without a TPU, and the compile-cache placement it shares
with `launch/train.py`.
"""
import importlib.util
import os
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.configs import get_smoke_config
from repro.launch import compile_cache

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "chip_smoke.py")


def _chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", _SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_phase_fred_smoke_size():
    """Phase A end to end: the kernel body (interpret mode) trains the MLP,
    agrees with its reference, and the asgd arm takes the cotangent path."""
    out = _chip_smoke().phase_fred(lam=16, K=8, events=64, asgd_events=32,
                                   n_train=512, n_valid=256,
                                   kernel_interpret=True)
    assert out["cost_after"] < out["cost_before"]
    assert out["counters"]["kernel_events"] == 64
    assert not out["kernel_in_step"]          # interpret mode: no Mosaic


def test_phase_lm_smoke_size():
    """Phase B through `launch.train.run_round_trainer` on the smoke
    tinyllama: one finite loss per round, server = W + n/b/v."""
    cfg = get_smoke_config("tinyllama-1.1b")
    out = _chip_smoke().phase_lm(cfg, seq=32, clients=2, batch=2, rounds=3,
                                 kernel_interpret=True)
    assert len(out["losses"]) == 3
    assert out["server_bytes"] == 4 * out["params"] * 4     # f32 W, n, b, v
    assert out["fleet_bytes"] == 2 * out["params"] * 4      # 2 client copies


_FOUR_DEVICES = textwrap.dedent("""
    import importlib.util, sys
    spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
    cs = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cs)
    cs.phase_four_chips(lam=16, K=8, events=16, n_train=512, n_valid=256,
                        kernel_interpret=True)
    print("FOUR_DEVICE_PHASE_OK")
""")


def test_phase_four_chips_on_forced_cpu_devices():
    """The ``--chips 4`` phase on four forced CPU devices (a child process:
    the device count is fixed when jax starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", _FOUR_DEVICES, _SCRIPT],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "FOUR_DEVICE_PHASE_OK" in r.stdout
    assert "spans 4 devices" in r.stdout


@pytest.mark.parametrize("env", [
    {},
    {"REPRO_KERNEL_INTERPRET": "1"},
], ids=["no-tpu", "interpret-env"])
def test_script_fails_without_tpu(env):
    """On CPU, or with the kernel diverted, the script exits non-zero and
    never prints the success line."""
    full = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    r = subprocess.run([sys.executable, _SCRIPT], capture_output=True,
                       text=True, env=full, timeout=300, cwd=_ROOT)
    assert r.returncode != 0
    assert '"ok": true' not in r.stdout


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir, tmp_path):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None   # left to JAX


def test_compile_cache_defaults_to_repo(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want

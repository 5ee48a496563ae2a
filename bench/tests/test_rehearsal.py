"""The drivers' window loops at smoke size on the CPU (Pallas in interpret
mode), through `run.measure`, which the chip-only entry calls; and the
entry's refusals without a chip."""
import json
import os
import subprocess
import sys

import pytest

import smoke
from bench import run

WORKLOADS = [w["name"] for w in smoke.bench()["workloads"]]
SEED = 2**33 + 11                        # past 32 bits, as the driver's are


@pytest.mark.parametrize("workload", WORKLOADS)
def test_cell_runs_and_is_correct_at_smoke_size(workload):
    config, traffic = smoke.spec(workload)
    out = run.measure(smoke.bench(), workload, config, traffic, seed=SEED,
                      seconds=0.2, trace=0, interpret=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    rate = "events_per_s" if traffic["driver"] == "fred" else "tokens_per_s"
    assert set(out["metrics"]) == {rate, "setup_s", "peak_hbm_gib"}
    assert out["metrics"][rate]["value"] > 0


def test_seeds_past_32_bits_give_different_inputs():
    from bench.drivers.common import seed_key
    import jax
    a, b = (jax.random.key_data(seed_key(s)) for s in (5, 5 + 2**32))
    assert (a != b).any()


@pytest.mark.parametrize("env", [{}, {"REPRO_KERNEL_INTERPRET": "1"}])
def test_no_tpu_means_no_result(env):
    e = dict(os.environ, JAX_PLATFORMS="cpu", **env)
    p = subprocess.run(
        [sys.executable, os.path.join(smoke.ROOT, "bench", "run.py"),
         "--workload", WORKLOADS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], env=e, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    with pytest.raises(json.JSONDecodeError):
        json.loads(p.stdout or "x")

"""A run with the timed path broken underneath has to come out not correct:
once for a step that returns its state unchanged, once for half of the
batch left out with the mean taken over the rest, once for the fetch left
out of the step.  The run goes through `run.measure` at smoke size on the
CPU, with the cell's committed limits; the faults are `control.planted`'s,
which `bench/control.py` reads at the cells' own sizes on the chip."""
import pytest

import smoke
from bench import control, run

WORKLOADS = [w["name"] for w in smoke.bench()["workloads"]]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "fetch_skipped"])
@pytest.mark.parametrize("workload", WORKLOADS)
def test_broken_path_is_not_correct(workload, fault):
    config, traffic = smoke.spec(workload)
    with control.planted(fault):
        out = run.measure(smoke.bench(), workload, config, traffic, seed=7,
                          seconds=0.2, trace=0, interpret=True)
    assert not out["correct"], out["checks"]

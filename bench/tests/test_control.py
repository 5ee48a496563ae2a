"""The control at a size a test run holds: the reference one precision
below the configuration's fails the cell's committed limits, and so does
the reference with half of each step's events left out, while the
program passes them; the program with its fetch left out fails them too.
The same readings at the cells' own sizes are made
on the chip by `bench/control.py`."""
import importlib

import pytest

import smoke
from bench import check, control

WORKLOADS = [w["name"] for w in smoke.bench()["workloads"]]


@pytest.mark.parametrize("workload", WORKLOADS)
def test_control_and_half_batch_fail_where_the_program_passes(workload):
    config, traffic = smoke.spec(workload)
    driver = importlib.import_module("bench.drivers." + traffic["driver"])
    got = control.readings_for(
        lambda: driver.Cell(config, traffic, 3, interpret=True), traffic)
    limits = traffic["limits"]
    ok = {kind: check.judge({k: r[k] for k in limits}, limits)[0]
          for kind, r in got.items()}
    assert ok == {"program": True, "control": False, "half_batch": False,
                  "fetch_skipped": False}, got

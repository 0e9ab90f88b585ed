"""Each cell's check: sound runs pass; the control, each planted fault
and one perturbed decision fail."""
import contextlib
import time

import numpy as np
import pytest

from diana_bench import grids
from diana_bench.harness import Context, Suite

from diana_bench.control import (
    FAULTS, SIM_FAULTS, ControlScheduler, SimControl, faulty_scheduler, faulty_sim,
)

PLACING = ["bulk_10k.wlcg_cms", "bulk_10k_hier.wlcg_cms", "arrivals_poisson.wlcg_cms"]
CELLS = PLACING + ["sim_stream.wlcg_cms"]


def _values(line):
    return {k: v["value"] for k, v in line["checks"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(run_toy, cell):
    line = run_toy(cell)
    assert line["correct"], _values(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert set(line["metrics"]) == {m["name"] for m in Suite().end_to_end(cell)}


@pytest.mark.parametrize("cell", CELLS)
def test_control_one_precision_below_is_not_correct(run_toy, cell):
    control = SimControl if cell.startswith("sim_") else ControlScheduler
    line = run_toy(cell, factory=control)
    assert not line["correct"], _values(line)


@pytest.mark.parametrize("cell,fault", [(c, f) for c in PLACING for f in FAULTS])
def test_planted_fault_is_not_correct(run_toy, cell, fault):
    line = run_toy(cell, factory=faulty_scheduler(fault))
    assert not line["correct"], _values(line)


@pytest.mark.parametrize("fault", list(SIM_FAULTS))
def test_planted_sim_fault_is_not_correct(run_toy, fault):
    line = run_toy("sim_stream.wlcg_cms", factory=faulty_sim(fault))
    assert not line["correct"], _values(line)


def _perturb_one(cell, record):
    """Move one decision of the window's record to another site."""
    if cell.startswith("bulk_"):
        sites = np.array(record[0][1], copy=True)
        sites[0] += 1
        record[0] = (record[0][0], sites) + tuple(record[0][2:])
    elif cell.startswith("arrivals_"):
        log, _ = record
        log[0][3][0] += 1
    else:
        record[0][1][0] += 1


@pytest.mark.parametrize("cell", CELLS)
def test_one_perturbed_decision_fails_the_check(toy_suite, cell):
    w = toy_suite.cell(cell)
    config, traffic = toy_suite.config(w["config"]), toy_suite.traffic(w["traffic"])
    driver = toy_suite.driver(traffic["driver"])
    ctx = Context(cell=w, config=config, traffic=traffic, seed=3, seconds=0.2,
                  grid=grids.make_grid(config, 3), annotate=lambda name: contextlib.nullcontext())
    state = driver.setup(ctx)
    result = driver.window(state, ctx, time.perf_counter())
    assert all(v <= lim for v, lim in driver.check(state, result).values())
    _perturb_one(cell, result.record)
    checks = driver.check(state, result)
    assert any(v > lim for v, lim in checks.values()), checks

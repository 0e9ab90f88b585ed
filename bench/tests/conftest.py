"""The benchmark's CPU tests: make ``diana_bench`` and the program
importable, and give the tests a way to run a cell at toy sizes."""
import sys
import time
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

# Toy sizes of each configuration and traffic mix: the same code paths,
# small enough for a few seconds on the CPU.
TOY_CONFIG: dict = {}
TOY_TRAFFIC = {
    "bulk_10k": {"group_jobs": 300, "distinct_groups": 2},
    "bulk_10k_hier": {"group_jobs": 300, "distinct_groups": 2},
    "arrivals_poisson": {"rate_per_s": 3000.0},
    "sim_stream": {"trace_jobs": 400, "distinct_traces": 2, "prebuilt_jobs_per_s": 2000.0},
}


@pytest.fixture
def toy_suite():
    """The benchmark's suite with each file's sizes cut to toy scale."""
    from diana_bench.harness import Suite

    suite = Suite()
    config, traffic = suite.config, suite.traffic
    suite.config = lambda name: dict(config(name), **TOY_CONFIG.get(name, {}))
    suite.traffic = lambda name: dict(traffic(name), **TOY_TRAFFIC.get(name, {}))
    return suite


@pytest.fixture
def run_toy(toy_suite):
    """Run one cell at toy size on the CPU, untraced and with no look
    for a chip; ``factory`` puts another scheduler in the program's
    place."""
    from diana_bench.harness import run_cell

    def run(workload, *, seed=5, seconds=0.3, factory=None, suite=None):
        return run_cell(suite or toy_suite, workload, seed, seconds, False,
                        t_start=time.perf_counter(), require_tpu=False,
                        scheduler_factory=factory)

    return run

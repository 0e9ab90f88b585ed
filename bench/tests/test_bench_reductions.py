"""The reductions from samples and traces to per-layer numbers."""
import json
import time
from pathlib import Path

import pytest

from diana_bench import tracing
from diana_bench.harness import Observations, Suite
from diana_bench.sampler import StackSampler

FIXTURES = Path(__file__).parent / "fixtures"
HERE = "bench/tests/test_bench_reductions.py"


def _shares(samples, maps):
    obs = Observations(samples=samples, trace=None, series={}, layer_maps=maps)
    reducer = Suite().reducer("host_share")
    return {name: reducer.read({"name": name}, obs) for name in maps}


def test_time_goes_to_the_innermost_listed_frame():
    f = lambda q: ("/x/repro/core/batch.py", q)                     # noqa: E731
    g = lambda q: ("/x/repro/sim/grid.py", q)                        # noqa: E731
    samples = [
        (1.0, (f("class_total"), f("replay_on_pack"), f("replay_place"))),
        (2.0, (("/np/core.py", "argmin"), f("replay_on_pack"))),
        (3.0, (f("cost_components"), f("replay_on_pack"))),
        (4.0, (g("GridSim._migrate_site_batched"), g("GridSim._run_horizon"))),
        (10.0, (("/x/other.py", "main"),)),
    ]
    maps = {
        "replay": ["repro/core/batch.py:replay_on_pack", "repro/core/batch.py:class_total"],
        "plane": ["repro/core/batch.py:cost_components"],
        "migration": ["repro/sim/grid.py:GridSim._migrate_site_batched"],
        "loop": ["repro/sim/grid.py:*"],
    }
    got = _shares(samples, maps)
    assert got == pytest.approx({"replay": 15.0, "plane": 15.0, "migration": 20.0, "loop": 0.0})


def _inner(t_end):
    while time.perf_counter() < t_end:
        pass


def _outer(inner_s, own_s):
    _inner(time.perf_counter() + inner_s)
    t_end = time.perf_counter() + own_s
    while time.perf_counter() < t_end:
        pass


def test_sampler_gives_time_to_the_innermost_listed_function():
    with StackSampler(interval_s=0.0005) as s:
        _outer(0.45, 0.15)
    maps = {"inner": [f"{HERE}:_inner"], "outer": [f"{HERE}:_outer"]}
    got = _shares(s.samples, maps)
    assert got["inner"] > got["outer"] > 5.0
    assert 50.0 < got["inner"] < 95.0
    assert sum(w for w, _ in s.samples) == pytest.approx(0.6, rel=0.35)


def test_trace_reduction_on_a_small_trace():
    # window [0, 1000) ns; one chip busy [100, 300) and [250, 400), so
    # 300 ns busy; host: place_batch [0, 600) with complete [500, 550)
    # inside it, sleep [600, 1000).
    trace = {
        "window": [0, 1000],
        "device": {"/device:TPU:0": [[100, 200, "fusion"], [250, 150, "custom-call"]]},
        "host": [["place_batch", 0, 600], ["complete", 500, 50], ["sleep", 600, 400]],
    }
    r = tracing.reduce(trace)
    assert r["busy_s"] == pytest.approx(300e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert dict(r["device_ops"]) == pytest.approx({"fusion": 200e-9, "custom-call": 150e-9})
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"place_batch": 250e-9, "complete": 50e-9, "sleep": 400e-9})


def test_describe_names_where_the_device_ops_fell():
    trace = {"window": [1000, 2000],
             "device": {"/device:TPU:0": [[100, 50, "fusion"], [300, 20, "copy"]]},
             "host": []}
    assert tracing.reduce(trace)["busy_s"] == 0.0
    text = tracing.describe(trace)
    assert "window [1000, 2000] ns" in text
    assert "/device:TPU:0: 2 ops in [100, 320] ns" in text
    assert "no 'XLA Ops' line" in tracing.describe(dict(trace, device={}))
    assert "no trace" in tracing.describe(None)


def test_trace_reduction_on_a_recorded_chip_trace():
    """A window of bulk groups over 256 sites traced on one TPU v5e, cut to
    its first events; ``expected`` was computed by hand from them."""
    rec = json.loads((FIXTURES / "trace_tpu_v5e.json").read_text())
    r = tracing.reduce(rec["trace"])
    want = rec["expected"]
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert 0.0 < r["busy_s"] < r["window_s"]
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)


def test_device_idle_reader():
    obs = Observations(samples=[], trace={"busy_s": 0.25, "window_s": 1.0},
                       series={}, layer_maps={})
    assert Suite().reducer("device_idle").read({}, obs) == pytest.approx(75.0)
    assert Suite().reducer("device_idle").read(
        {}, Observations(samples=[], trace=None, series={}, layer_maps={})) is None


def test_quantile_reader():
    import numpy as np

    read = Suite().reducer("quantile").read
    spec = {"series": "gen_late_s", "q": 0.5, "scale": 1000.0}
    obs = Observations(samples=[], trace=None, layer_maps={},
                       series={"gen_late_s": np.asarray([0.001, 0.002, 0.003])})
    assert read(spec, obs) == pytest.approx(2.0)
    empty = Observations(samples=[], trace=None, layer_maps={}, series={"gen_late_s": []})
    assert read(spec, empty) is None

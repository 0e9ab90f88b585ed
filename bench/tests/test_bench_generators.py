"""Every generator the benchmark copied is a function of the seed."""
import numpy as np
import pytest

from diana_bench import grids, traces
from diana_bench.harness import Suite

SEED = 2**31 + 17   # the driver's seeds are this large


def _arrays(obj):
    return {k: np.asarray(v) for k, v in vars(obj).items() if v is not None and k != "names"}


def _same(a: dict, b: dict) -> bool:
    return a.keys() == b.keys() and all(np.array_equal(a[k], b[k]) for k in a)


@pytest.mark.parametrize("config", ["wlcg_cms"])
def test_grid_is_a_function_of_the_seed(config):
    cfg = Suite().config(config)
    one, two = grids.make_grid(cfg, SEED), grids.make_grid(cfg, SEED)
    other = grids.make_grid(cfg, SEED + 1)
    assert len(one) == sum(t["sites"] for t in cfg["tiers"]) and one.alive.all()
    assert _same(_arrays(one), _arrays(two))
    assert not np.array_equal(one.cap, other.cap)


def test_wlcg_tiers_and_regions():
    cfg = Suite().config("wlcg_cms")
    g = grids.make_grid(cfg, SEED)
    t0, t1, t2 = (g.sites_of(t["name"], cfg) for t in cfg["tiers"])
    assert (len(t0), len(t1), len(t2)) == (1, 13, 160)
    assert g.cap[t0].sum() / g.cap.sum() == pytest.approx(0.2, abs=0.03)
    assert np.all(g.loss[t0] == 0.0) and np.all(g.loss[t1] == 0.0)
    assert g.tier[t0[0]] == 0 and sorted(g.tier[t1]) == list(range(1, 14))
    assert np.array_equal(np.bincount(g.tier[t2]), [0] + [len(t2) // 13 + (k < len(t2) % 13)
                                                          for k in range(13)])


def test_demands_are_a_function_of_the_seed():
    cfg = Suite().config("wlcg_cms")
    one = grids.demands(cfg, 20_000, grids.rng_for(SEED, 1, 0))
    two = grids.demands(cfg, 20_000, grids.rng_for(SEED, 1, 0))
    other = grids.demands(cfg, 20_000, grids.rng_for(SEED, 1, 1))
    assert _same(_arrays(one), _arrays(two))
    assert not np.array_equal(one.work, other.work)
    # cms_case_study's medians: about 55 s of work and a 12 GB dataset.
    assert np.median(one.work) == pytest.approx(np.exp(4.0), rel=0.05)
    assert np.median(one.input_bytes) == pytest.approx(np.exp(2.5) * 1e9, rel=0.05)
    assert np.allclose(one.output_bytes, one.input_bytes * 0.01)
    assert set(np.unique(one.user)) == set(range(100))


def test_pair_links_are_a_function_of_the_seed():
    cfg = Suite().config("wlcg_cms")
    grid = grids.make_grid(cfg, SEED)
    one = grids.pair_links(grid)
    two = grids.pair_links(grids.make_grid(cfg, SEED))
    assert _same(one, two)
    assert np.all(np.diag(one["loss"]) == 0.0)
    assert one["rtt"][1, 2] == grid.rtt[1] + grid.rtt[2]
    assert one["bw"][0, 20] == min(grid.bw[0], grid.bw[20])


def test_sim_trace_is_a_function_of_the_seed():
    tr = Suite().traffic("sim_stream")
    nodes = np.arange(1, 33)
    data = np.arange(1, 14)
    one = traces.sim_trace(tr, nodes, 0, data, grids.rng_for(SEED, 6, 0))
    two = traces.sim_trace(tr, nodes, 0, data, grids.rng_for(SEED, 6, 0))
    other = traces.sim_trace(tr, nodes, 0, data, grids.rng_for(SEED, 6, 1))
    assert _same(one, two)
    assert not np.array_equal(one["data_site"], other["data_site"])
    # The paper's spacing: one job every 1.5 s per 24 nodes.
    assert np.allclose(np.diff(one["arrival"]), 1.5 * 24 / nodes.sum())
    assert len(one["arrival"]) == tr["trace_jobs"]
    assert set(np.unique(one["data_site"])) == set(data)


def test_poisson_arrivals_are_a_function_of_the_seed():
    arrivals = Suite().driver("open_arrivals").arrivals
    one = arrivals(5000.0, 2.0, grids.rng_for(SEED, 2))
    two = arrivals(5000.0, 2.0, grids.rng_for(SEED, 2))
    assert np.array_equal(one, two)
    assert one.max() < 2.0 and abs(len(one) - 10_000) < 500

"""The P2P cell (``p2p_stream.wlcg_cms_p2p``) at toy size on the CPU: the
program passes its check against the plain P2P reference; the
omniscient simulator, the reference in float32 and a planted fault in
the merge each come out not correct."""
import contextlib
import time

import numpy as np
import pytest

from diana_bench import grids
from diana_bench.control_p2p import P2P_FAULTS, P2PControl
from diana_bench.harness import Context, Suite, run_cell

CELL = "p2p_stream.wlcg_cms_p2p"


def _toy_tiers(tiers):
    """Three Tier-1 regions of four Tier-2s each, widths as stated."""
    sites = {"tier0": 1, "tier1": 3, "tier2": 12}
    return [dict(t, sites=sites[t["name"]]) for t in tiers]


@pytest.fixture
def p2p_suite():
    suite = Suite()
    config, traffic = suite.config, suite.traffic
    suite.config = lambda name: dict(config(name), tiers=_toy_tiers(config(name)["tiers"]))
    suite.traffic = lambda name: dict(traffic(name), trace_jobs=500, distinct_traces=2,
                                      prebuilt_jobs_per_s=2000.0)
    return suite


def _run(suite, factory=None, seed=2147480104):
    return run_cell(suite, CELL, seed, 0.3, False, t_start=time.perf_counter(),
                    require_tpu=False, scheduler_factory=factory)


def _values(line):
    return {k: v["value"] for k, v in line["checks"].items()}


def test_sound_run_is_correct(p2p_suite):
    line = _run(p2p_suite)
    assert line["correct"], _values(line)
    assert line["attempted"] > 0 and line["failed"] == 0
    assert set(line["metrics"]) == {"sim_jobs_per_s", "setup_s"}


def test_each_region_is_one_peer_homed_at_its_head(p2p_suite):
    config = p2p_suite.config("wlcg_cms_p2p")
    drv = p2p_suite.driver("p2p_stream")
    g = grids.make_grid(config, 9)
    peers = drv.peer_sites(g, config)
    assert len(peers) == 4
    assert sorted(s for p in peers for s in p) == list(range(len(g)))
    for p in peers:
        assert len({int(g.tier[s]) for s in p}) == 1
        assert g.role[p[0]] == min(g.role[s] for s in p) <= 1


def test_window_records_gossip_and_migrations(p2p_suite):
    w = p2p_suite.cell(CELL)
    config, traffic = p2p_suite.config(w["config"]), p2p_suite.traffic(w["traffic"])
    drv = p2p_suite.driver("p2p_stream")
    ctx = Context(cell=w, config=config, traffic=traffic, seed=4, seconds=0.2,
                  grid=grids.make_grid(config, 4), annotate=lambda n: contextlib.nullcontext())
    state = drv.setup(ctx)
    result = drv.window(state, ctx, time.perf_counter())
    assert all(v <= lim for v, lim in drv.check(state, result).values())
    series = result.series
    assert len(series["bytes_per_job"]) == len(result.record)
    assert all(b > 0 for b in series["bytes_per_job"])
    # one decision moved: the check sees it
    result.record[0][1][0] = (result.record[0][1][0] + 1) % len(state["names"])
    assert any(v > lim for v, lim in drv.check(state, result).values())


def test_control_one_precision_below_is_not_correct(p2p_suite):
    line = _run(p2p_suite, factory=P2PControl)
    assert not line["correct"], _values(line)


@pytest.mark.parametrize("fault", list(P2P_FAULTS))
def test_planted_fault_is_not_correct(p2p_suite, fault):
    line = _run(p2p_suite, factory=P2P_FAULTS[fault]())
    assert not line["correct"], _values(line)


def test_sound_control_in_float64_is_correct(p2p_suite):
    """The control's machinery is sound: at the stated precision it
    agrees with the program."""
    line = _run(p2p_suite, factory=lambda *a, **kw: P2PControl(*a, dtype=np.float64, **kw))
    assert line["correct"], _values(line)

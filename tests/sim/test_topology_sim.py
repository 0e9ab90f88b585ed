"""Simulators carrying a ``GridTopology``.

``GridSim`` places over every site whatever topology it is given;
``P2PGridSim`` uses the topology for its hierarchy-aware gossip fan-out.
Both run loops must produce identical event streams — exec sites,
finish times, migration counts — with topologies, dead sites, dense
bursts and a swapped link table in play. Options that once chose
other paths are refused.
"""
import copy

import numpy as np
import pytest

from repro.core import GridTopology, Job, NetworkLink, Node
from repro.sim import GridSim, P2PGridSim, SimConfig
from repro.sim.faults import FaultPlan
from repro.sim.workloads import SimJob


def _grid(rng, n_sites):
    names = [f"s{i:02d}" for i in range(n_sites)]
    spec = {n: int(rng.integers(1, 5)) for n in names}
    links = {}
    for a in names:
        for b in names:
            links[(a, b)] = NetworkLink(
                bandwidth_Bps=float(rng.uniform(1e6, 1e8)),
                loss_rate=0.0 if a == b else float(rng.uniform(0.0, 0.02)),
                rtt_s=float(rng.uniform(0.01, 0.3)),
            )
    return names, spec, links


def _topology(names, n_tiers):
    topo = GridTopology()
    for i, n in enumerate(names):
        topo.join(f"root{i % n_tiers}", Node(name=n))
    return topo


def _workload(rng, names, n=300):
    S = len(names)
    return [
        SimJob(
            user=("hog" if i % 5 == 0 else f"u{i % 7}"),
            arrival=float(i // 8) * 5.0,
            work=float(rng.integers(10, 600)),
            input_bytes=float(rng.choice([0.0, 1e6, 5e9])),
            output_bytes=float(rng.choice([0.0, 2e8])),
            data_site=(names[i % S] if i % 3 else None),
            origin_site=names[(i * 7) % S],
        )
        for i in range(n)
    ]


def _trace(result):
    return [
        (j.user, j.arrival, j.exec_site, j.start, j.finish,
         j.migrated, j.requeues)
        for j in result.jobs
    ]


def _run(cls, spec, links, jobs, topo, horizon, **kw):
    cfg = SimConfig(
        policy="diana", topology=topo,
        migration_interval_s=30.0, congestion_window_s=120.0,
        horizon=horizon, **kw,
    )
    sim = cls(dict(spec), links=dict(links), config=cfg)
    return sim.run(copy.deepcopy(jobs))


@pytest.mark.parametrize("horizon", [True, False])
def test_gridsim_topology_matches_none(horizon):
    rng = np.random.default_rng(7)
    names, spec, links = _grid(rng, 24)
    jobs = _workload(rng, names)
    plain = _run(GridSim, spec, links, jobs, None, horizon)
    tiered = _run(GridSim, spec, links, jobs, _topology(names, 4), horizon)
    assert _trace(plain) == _trace(tiered)
    assert plain.migrations() == tiered.migrations()
    assert tiered.migrations() > 0           # the §IX path actually ran


@pytest.mark.parametrize("latency", [0.0, 5.0])
def test_p2p_topology_horizon_matches_per_event(latency):
    rng = np.random.default_rng(9)
    names, spec, links = _grid(rng, 20)
    topo = _topology(names, 4)
    jobs = _workload(rng, names)
    kw = dict(num_peers=5, exchange_interval_s=60.0, exchange_latency_s=latency)
    per_event = _run(P2PGridSim, spec, links, jobs, topo, False, **kw)
    horizon = _run(P2PGridSim, spec, links, jobs, topo, True, **kw)
    assert _trace(per_event) == _trace(horizon)
    assert per_event.migrations() == horizon.migrations()


def test_topology_with_site_faults_horizon_matches_per_event():
    """Dead columns are poisoned alike in the batched arrival path and
    the per-event reference."""
    rng = np.random.default_rng(11)
    names, spec, links = _grid(rng, 16)
    topo = _topology(names, 3)
    jobs = _workload(rng, names, n=250)
    plan = FaultPlan()
    plan.site_down(20.0, names[3]); plan.site_up(120.0, names[3])
    plan.site_down(50.0, names[7]); plan.site_up(300.0, names[7])
    per_event = _run(GridSim, spec, links, jobs, topo, False,
                     fault_plan=copy.deepcopy(plan))
    horizon = _run(GridSim, spec, links, jobs, topo, True,
                   fault_plan=copy.deepcopy(plan))
    assert _trace(per_event) == _trace(horizon)
    assert any(j.requeues for j in horizon.jobs)   # the faults bit


def test_new_link_table_matches_fresh_sim():
    """Swapping the link table drops every plane derived from the old
    one: the sim then decides as a fresh sim over the new table."""
    rng = np.random.default_rng(17)
    names, spec, links = _grid(rng, 12)
    topo = _topology(names, 3)
    jobs = _workload(rng, names, n=120)
    cfg = SimConfig(policy="diana", topology=topo)
    sim = GridSim(dict(spec), links=dict(links), config=cfg)
    sim.choose_sites_batch(copy.deepcopy(jobs))   # builds the old planes
    _, _, links2 = _grid(rng, 12)
    sim.links = links2                            # setter → invalidate_links
    swapped = sim.run(copy.deepcopy(jobs))
    fresh = GridSim(dict(spec), links=dict(links2), config=cfg.replace())
    assert _trace(swapped) == _trace(fresh.run(copy.deepcopy(jobs)))


def _config_placement():
    SimConfig(placement="hier")


def _config_gossip_summaries():
    SimConfig(gossip_summaries=True)


def _legacy_placement():
    GridSim({"a": 1, "b": 1}, placement="hier")


def _peer_place_batch_mode():
    sim = P2PGridSim({"a": 1, "b": 1}, config=SimConfig(num_peers=2))
    sim.peers[0].place_batch([Job(user="u")], mode="hier")


@pytest.mark.parametrize("call", [
    _config_placement, _config_gossip_summaries, _legacy_placement,
    _peer_place_batch_mode,
])
def test_removed_option_raises_typeerror(call):
    """A caller of a removed option is told so, not silently given
    another path."""
    with pytest.raises(TypeError):
        call()

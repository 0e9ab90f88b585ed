"""P2P ownership as the deployment states it (``SimConfig.peer_sites``):
one peer per region, homed at the region's Tier-0 or Tier-1; bad
partitions are refused; without one, sites are dealt round-robin."""
import copy

import numpy as np
import pytest

from repro.core import NetworkLink
from repro.sim import P2PGridSim, SimConfig, SimJob

# A toy tiered grid whose regional heads do NOT sort first in their
# region: the home is stated, not read off name order.
REGIONS = {
    "t0-cern": ["t0-cern"],
    "t1-fnal": ["a2-fnal", "b2-fnal", "t1-fnal"],
    "t1-ral": ["a2-ral", "t1-ral", "z2-ral"],
    "t1-in2p3": ["c2-in2p3", "t1-in2p3"],
}
NODES = {"t0-cern": 6, "t1-fnal": 4, "t1-ral": 4, "t1-in2p3": 3,
         "a2-fnal": 1, "b2-fnal": 2, "a2-ral": 2, "z2-ral": 1, "c2-in2p3": 2}


def _regional():
    return [[head] + [n for n in members if n != head] for head, members in REGIONS.items()]


def _links(seed=0):
    rng = np.random.default_rng(seed)
    names = sorted(NODES)
    loss = {n: 0.0 if n.startswith("t") else float(rng.uniform(1e-4, 1e-2)) for n in names}
    return {
        (a, b): NetworkLink(bandwidth_Bps=1.25e8 if "2-" in a + b else 1.25e9,
                            loss_rate=0.0 if a == b else max(loss[a], loss[b]),
                            rtt_s=0.02 + 0.01 * (a != b))
        for a in names for b in names
    }


def _jobs(n=150, seed=1):
    rng = np.random.default_rng(seed)
    heads = [h for h in REGIONS if h.startswith("t1")]
    return [
        SimJob(user=f"u{k % 5}", arrival=0.4 * k, work=30.0, input_bytes=4e8,
               output_bytes=2e7, data_site=heads[int(rng.integers(len(heads)))],
               origin_site=heads[int(rng.integers(len(heads)))])
        for k in range(n)
    ]


def _config(**kw):
    kw.setdefault("exchange_interval_s", 30.0)
    kw.setdefault("exchange_latency_s", 2.0)
    return SimConfig(policy="diana", migration_interval_s=20.0,
                     congestion_window_s=60.0, **kw)


def _decisions(res):
    return ([j.exec_site for j in res.jobs], [j.start for j in res.jobs],
            [j.finish for j in res.jobs], [j.migrated for j in res.jobs])


def test_each_peer_owns_exactly_its_region_homed_at_its_head():
    sim = P2PGridSim(dict(NODES), links=_links(), config=_config(peer_sites=_regional()))
    assert sim.num_peers == len(REGIONS)
    for peer, (head, members) in zip(sim.peers, REGIONS.items()):
        assert peer.home == head
        assert sorted(peer.home_names) == sorted(members)
        assert all(sim._peer_by_site[n] is peer for n in members)
        assert peer.links[head] is sim.links[(head, head)]


@pytest.mark.parametrize("num_peers", [None, 4])
def test_num_peers_follows_the_partition(num_peers):
    sim = P2PGridSim(dict(NODES), links=_links(),
                     config=_config(peer_sites=_regional(), num_peers=num_peers))
    assert len(sim.peers) == sim.num_peers == 4


def test_num_peers_disagreeing_with_the_partition_is_refused():
    with pytest.raises(ValueError, match="num_peers=2 disagrees"):
        P2PGridSim(dict(NODES), links=_links(),
                   config=_config(peer_sites=_regional(), num_peers=2))


def test_jobs_enter_through_their_region_peer():
    sim = P2PGridSim(dict(NODES), links=_links(), config=_config(peer_sites=_regional()))
    for sj in _jobs(12):
        assert sim._submit_peer(sj).home == sj.origin_site


@pytest.mark.parametrize("partition,why", [
    ([["t0-cern"], ["t1-fnal", "a2-fnal", "b2-fnal", "t0-cern"], ["t1-ral", "a2-ral", "z2-ral"],
      ["t1-in2p3", "c2-in2p3"]], "twice"),
    ([["t0-cern"], ["t1-fnal", "a2-fnal"], ["t1-ral", "a2-ral", "z2-ral"],
      ["t1-in2p3", "c2-in2p3"]], "missing"),
    (_regional() + [[]], "own no site"),
    (_regional()[:-1] + [["t1-in2p3", "c2-in2p3", "x2-nowhere"]], "not in the grid"),
])
def test_bad_partitions_are_refused(partition, why):
    with pytest.raises(ValueError, match=why):
        P2PGridSim(dict(NODES), links=_links(), config=_config(peer_sites=partition))


def test_no_partition_is_the_round_robin_deal():
    names = sorted(NODES)
    sim = P2PGridSim(dict(NODES), links=_links(), config=_config(num_peers=3))
    assert [p.home for p in sim.peers] == names[:3]
    assert [p.home_names for p in sim.peers] == [names[i::3] for i in range(3)]
    stated = P2PGridSim(dict(NODES), links=_links(),
                        config=_config(peer_sites=[names[i::3] for i in range(3)]))
    jobs = _jobs()
    a, b = sim.run(copy.deepcopy(jobs)), stated.run(copy.deepcopy(jobs))
    assert _decisions(a) == _decisions(b)
    assert sim.exchange.stats.as_dict() == stated.exchange.stats.as_dict()


def test_regional_ownership_decides_from_each_regions_view():
    """Ownership is a fact of the deployment: the regional split and the
    round-robin deal of the same grid see different stale views."""
    jobs = _jobs()
    regional = P2PGridSim(dict(NODES), links=_links(), config=_config(peer_sites=_regional()))
    dealt = P2PGridSim(dict(NODES), links=_links(), config=_config(num_peers=4))
    a, b = regional.run(copy.deepcopy(jobs)), dealt.run(copy.deepcopy(jobs))
    assert all(f >= 0 for f in _decisions(a)[2])
    assert _decisions(a) != _decisions(b)

"""The reliable delta wire simulated a round at a time
(``GossipExchange._round_batched``) against the per-packet path it
stands for: every job, every peer's view, every pair's wire state and
every counter come out the same."""
import copy

import numpy as np
import pytest

from repro.core import GossipExchange, GridTopology, NetworkLink, Node, PeerScheduler, SiteState
from repro.sim import GridSim, P2PGridSim, SimConfig, SimJob
from repro.sim.faults import FaultPlan

NAMES = [f"s{i:02d}" for i in range(12)]
NODES = {n: 1 + i % 3 for i, n in enumerate(NAMES)}


def _links(seed=0):
    rng = np.random.default_rng(seed)
    loss = {n: 0.0 if i < 4 else float(rng.choice([1e-4, 1e-2])) for i, n in enumerate(NAMES)}
    return {
        (a, b): NetworkLink(bandwidth_Bps=float(rng.uniform(1e7, 1e9)),
                            loss_rate=0.0 if a == b else max(loss[a], loss[b]),
                            rtt_s=0.01 if a == b else float(rng.uniform(0.02, 0.2)))
        for a in NAMES for b in NAMES
    }


def _jobs(n=160, seed=1):
    """Bursts from four origins: queues build, §IX moves work, and
    jobs moved behind lossy paths leave a long quiet tail."""
    rng = np.random.default_rng(seed)
    return [
        SimJob(user=f"u{k % 5}", arrival=0.3 * k, work=float(rng.uniform(20, 90)),
               input_bytes=4e8, output_bytes=2e7,
               data_site=NAMES[int(rng.integers(4))], origin_site=NAMES[int(rng.integers(4))])
        for k in range(n)
    ]


def _topology():
    topo = GridTopology()
    for n in NAMES[:5]:
        topo.join("east", Node(name=n))
    for n in NAMES[5:]:
        topo.join("west", Node(name=n))
    return topo


CASES = {
    "mesh": dict(num_peers=4),
    "stated": dict(peer_sites=[NAMES[0:3], NAMES[3:4], NAMES[4:9], NAMES[9:]]),
    "late": dict(num_peers=4, exchange_latency_s=25.0),
    "fanout": dict(num_peers=5, gossip_fanout=2),
    "hier": dict(num_peers=4, topology=_topology()),
    "sync1": dict(num_peers=3, gossip_full_sync_every=1),
    "sync3_f16": dict(num_peers=4, gossip_full_sync_every=3, gossip_quant="f16"),
    # rounds at 10, 20, ...: each churn lands while a round is in flight
    "churn": dict(num_peers=4, exchange_latency_s=5.0,
                  fault_plan=FaultPlan().peer_leave(12.0, 1).peer_join(53.0, 1)),
    "events": dict(num_peers=4, horizon=False),
}


def _run(case, batched):
    kw = dict(CASES[case])
    kw.setdefault("exchange_interval_s", 10.0)
    kw.setdefault("exchange_latency_s", 2.0)
    cfg = SimConfig(policy="diana", migration_interval_s=20.0, congestion_window_s=60.0, **kw)
    sim = P2PGridSim(dict(NODES), links=_links(), config=cfg)
    assert sim.exchange._batched
    sim.exchange._batched = batched
    return sim, sim.run(copy.deepcopy(_jobs()))


def _state(sim, res):
    ex = sim.exchange
    out = {
        "jobs": [(j.exec_site, j.start, j.finish, j.migrated) for j in res.jobs],
        "stats": ex.stats.as_dict(),
        "in_flight": ex.in_flight,
    }
    for k, p in enumerate(sim.peers):
        for name in ("version", "stamp", "_dirty", "free", "home_cols"):
            out[f"{k}.{name}"] = getattr(p, name).tolist()
        for name in ("queue", "work", "load", "alive"):
            out[f"{k}.view.{name}"] = getattr(p.view, name).tolist()
    for key in sorted(ex._pairs):
        st = ex._pairs[key]
        out[f"pair{key}"] = (st.acked.tolist(), st.hb_stamp.tolist(), st.sync_round,
                             st.send_seq, st.recv_max, st.recv_window, st.table is None)
    return out


@pytest.mark.parametrize("case", sorted(CASES))
def test_batched_rounds_match_per_packet(case):
    per_packet = _state(*_run(case, batched=False))
    batched = _state(*_run(case, batched=True))
    assert batched["stats"]["rounds"] > 20
    assert batched == per_packet


def test_fast_paths_are_taken():
    """The quiet tail replays heartbeat rounds from the plan, and every
    all-pairs full sync is applied at once."""
    cfg = SimConfig(policy="diana", migration_interval_s=20.0, congestion_window_s=60.0,
                    num_peers=4, exchange_interval_s=10.0, exchange_latency_s=2.0)
    sim = P2PGridSim(dict(NODES), links=_links(), config=cfg)
    ex = sim.exchange
    seen = {"steady": 0, "full": 0}
    refresh, apply_full = ex._refresh_steady, ex._apply_full

    def count_steady(b):
        seen["steady"] += 1
        return refresh(b)

    def count_full(b):
        got = apply_full(b)
        seen["full"] += got is not None
        return got

    ex._refresh_steady, ex._apply_full = count_steady, count_full
    res = sim.run(copy.deepcopy(_jobs()))
    assert seen["steady"] > ex.stats.rounds // 4
    assert seen["full"] >= 2
    assert res.finished == len(res.jobs)


def test_queued_anywhere_answers_as_a_scan():
    """The periodic events' stop test asks the last queued site first;
    it answers exactly as a scan of every site."""
    sim = GridSim(dict(NODES), links=_links(), config=SimConfig(policy="diana"))
    asked = []
    scan = sim._queued_anywhere

    def checked():
        got = scan()
        assert got == any(s.queue_len() for s in sim.sites.values())
        asked.append(got)
        return got

    sim._queued_anywhere = checked
    sim.run(copy.deepcopy(_jobs()))
    assert True in asked and False in asked


def _exchange(seed, batched, **kw):
    """Four peers dealt ten sites round-robin, over one exchange."""
    rng = np.random.default_rng(seed)
    names = [f"x{i}" for i in range(10)]
    sites = {n: SiteState(name=n, capacity=float(rng.integers(10, 200)),
                          queue_length=float(rng.integers(0, 20)),
                          waiting_work=float(rng.uniform(0, 500)),
                          load=float(rng.uniform(0, 1)))
             for n in names}
    links = {n: NetworkLink(bandwidth_Bps=1e9, rtt_s=0.05) for n in names}
    peers = [PeerScheduler(home=names[i], sites=copy.deepcopy(sites), links=dict(links),
                           home_sites=names[i::4], order=names) for i in range(4)]
    ex = GossipExchange(peers, **kw)
    assert ex._batched
    ex._batched = batched
    return names, peers, ex


@pytest.mark.parametrize("kw", [
    dict(latency_s=2.0),
    dict(latency_s=2.0, full_sync_every=3),
    dict(latency_s=15.0, fanout=2),
    dict(latency_s=25.0, quant="f16"),
], ids=["mesh", "sync3", "fanout", "late_f16"])
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_exchange_walk_matches_per_packet(seed, kw):
    """Rounds and deliveries interleaved with owners' changes, remote
    speculation and peers leaving and rejoining: the batched exchange
    and the per-packet one stay equal after every step."""
    runs = [_exchange(seed, batched, **kw) for batched in (False, True)]
    ops = np.random.default_rng(seed + 100)
    now = 0.0
    for _ in range(80):
        now += 10.0
        op, k, c, w = ops.integers(6), ops.integers(4), ops.integers(10), ops.uniform(1, 50)
        for names, peers, ex in runs:
            p = peers[k]
            if op == 0:
                n = p.home_names[c % len(p.home_names)]
                p.authoritative[n].queue_length += 1.0
            elif op == 1 and names[c] not in p.home_sites:
                p.note_remote_placement(names[c], w)
            elif op == 2 and k:
                ex.set_active(int(k), not ex._active[k])
            ex.deliver_due(now)
            ex.round(now)
            ex.deliver_due(now + 4.0)
        states = [_exchange_state(peers, ex) for _, peers, ex in runs]
        assert states[1] == states[0]
    for _, _, ex in runs:
        ex.deliver_due(now + 1e3)
    assert _exchange_state(*runs[1][1:]) == _exchange_state(*runs[0][1:])


def _exchange_state(peers, ex):
    out = {"stats": ex.stats.as_dict(), "in_flight": ex.in_flight}
    for k, p in enumerate(peers):
        for name in ("version", "stamp", "_dirty", "free"):
            out[f"{k}.{name}"] = getattr(p, name).tolist()
        for name in ("queue", "work", "load", "alive"):
            out[f"{k}.view.{name}"] = getattr(p.view, name).tolist()
    for key in sorted(ex._pairs):
        st = ex._pairs[key]
        out[f"pair{key}"] = (st.acked.tolist(), st.hb_stamp.tolist(), st.sync_round,
                             st.send_seq, st.recv_max, st.recv_window, st.table is None)
    return out

"""Back-to-back traces through ``P2PGridSim.run``: DIANA as peers.

The configuration's ``p2p`` block states the deployment: a peer
meta-scheduler per region of the grid (``peers: "regions"``, each peer
homed at its region's first site, the Tier-0 or the Tier-1), the gossip
interval and latency, the wire and its precision. The simulated grid is
``sim_stream``'s: ``ceil(capacity / capacity_per_node)`` nodes a site,
each pair of sites on the link of its two endpoints. Set-up draws
``distinct_traces`` traces of ``trace_jobs`` jobs of the paper's §XI
mix from the run's seed, as ``sim_stream`` does, with each job's origin
drawn from the seed over the sites of ``origin_tier``: each job enters
through the peer of its origin, which places it from its own view.

The arrivals are ``sim_stream``'s: one job per
``node_seconds_between_arrivals`` over all of the grid's nodes, spaced
by ``sim_trace``. Placement by §IV keeps these jobs on the sites with
lossless paths, and the peers' stale views congest some of them; §IX,
which takes the site with the fewest jobs ahead and the cost only on a
tie, then moves queued jobs, some to idle Tier-2s behind lossy paths,
where a 4 GB input takes hours of simulated time. The simulator gossips
every interval while any job is queued, so a trace's cost follows how
long its last migrated jobs wait.

The window runs the traces in turn, each through a fresh
``P2PGridSim``, cycle after cycle, and closes at the end of the cycle
running when ``seconds`` have passed. ``sim_jobs_per_s`` is every job
completed over that whole time. Per trace it records the gossip's bytes
(``ExchangeStats.bytes_sent``) over the trace's jobs, as the series
``bytes_per_job``.

The check runs each distinct trace through the plain per-event P2P
reference (``diana_bench.reference_p2p``) and compares, for every job
of every trace of the window, its execution site, its finish time and
whether it migrated.
"""
from __future__ import annotations

import math
import time

import numpy as np

from diana_bench import grids
from diana_bench.harness import WindowResult
from diana_bench.traces import sim_trace
from drivers.sim_stream import _sim_jobs


def peer_sites(grid: grids.Grid, config: dict) -> list[list[int]]:
    """One list of site indices per region, the region's first site of
    the earliest tier (its Tier-0 or Tier-1) first."""
    if config["p2p"]["peers"] != "regions":
        raise ValueError("p2p.peers must be 'regions'")
    return [sorted(np.flatnonzero(grid.tier == r).tolist(), key=lambda s: (grid.role[s], s))
            for r in np.unique(grid.tier)]


def p2p_trace(tr: dict, nodes: np.ndarray, origins: np.ndarray, data_sites: np.ndarray,
              rng: np.random.Generator) -> dict:
    """``sim_trace``'s jobs over the grid's ``nodes``, each from an
    origin drawn over ``origins``."""
    t = sim_trace(tr, nodes, int(origins[0]), data_sites, rng)
    t["origin_site"] = rng.choice(origins, size=len(t["arrival"]))
    return t


def setup(ctx):
    from repro.core import NetworkLink
    from repro.sim import P2PGridSim, SimConfig

    tr, g, p2p = ctx.traffic, ctx.grid, ctx.config["p2p"]
    per = ctx.config["sim"]["capacity_per_node"]
    nodes = np.asarray([max(1, math.ceil(c / per)) for c in g.cap], np.int64)
    planes = grids.pair_links(g)
    names = g.names
    links = {
        (a, b): NetworkLink(bandwidth_Bps=float(planes["bw"][i, k]),
                            loss_rate=float(planes["loss"][i, k]),
                            rtt_s=float(planes["rtt"][i, k]),
                            mss_bytes=float(planes["mss"][i, k]))
        for i, a in enumerate(names) for k, b in enumerate(names)
    }
    if p2p["transport"] != "lossless":
        raise ValueError("p2p.transport must be 'lossless'")
    peers = peer_sites(g, ctx.config)
    origins = g.sites_of(tr["origin_tier"], ctx.config)
    data_sites = g.sites_of(tr["data_tier"], ctx.config)
    traces = [
        p2p_trace(tr, nodes, origins, data_sites, grids.rng_for(ctx.seed, 6, k))
        for k in range(tr["distinct_traces"])
    ]
    config = SimConfig(
        policy="diana", migration_interval_s=tr["migration_interval_s"],
        congestion_window_s=tr["congestion_window_s"],
        peer_sites=[[names[s] for s in p] for p in peers],
        exchange_interval_s=p2p["exchange_interval_s"],
        exchange_latency_s=p2p["exchange_latency_s"],
        gossip_wire=p2p["wire"], gossip_quant=p2p["quant"],
        gossip_full_sync_every=p2p["full_sync_every"], gossip_fanout=p2p["fanout"],
    )
    site_nodes = {n: int(c) for n, c in zip(names, nodes)}
    runs = math.ceil(ctx.seconds * tr["prebuilt_jobs_per_s"] / tr["trace_jobs"])
    runs = len(traces) * math.ceil(runs / len(traces))
    prebuilt = [_sim_jobs(traces[k % len(traces)], names) for k in range(runs)]
    # Warm-up: a short trace through the same path.
    warm = p2p_trace(dict(tr, trace_jobs=200), nodes, origins, data_sites,
                     grids.rng_for(ctx.seed, 7))
    P2PGridSim(dict(site_nodes), links=links, config=config).run(_sim_jobs(warm, names))
    return {"traces": traces, "names": names, "site_nodes": site_nodes, "links": links,
            "config": config, "planes": planes, "nodes": nodes, "prebuilt": prebuilt,
            "peers": peers, "p2p": p2p}


def window(state, ctx, t0) -> WindowResult:
    from repro.sim import P2PGridSim

    traces, names = state["traces"], state["names"]
    make = ctx.scheduler_factory or P2PGridSim
    index = {n: i for i, n in enumerate(names)}
    ann = ctx.annotate
    records, done, attempted = [], 0, 0
    k = 0
    prebuilt = state["prebuilt"]
    while True:
        t = k % len(traces)
        if k < len(prebuilt):
            jobs = prebuilt[k]
        else:
            with ann("trace_start"):
                jobs = _sim_jobs(traces[t], names)
        sim = make(dict(state["site_nodes"]), links=state["links"], config=state["config"])
        with ann("sim_run"):
            res = sim.run(jobs)
        finished = sum(1 for j in res.jobs if j.finish >= 0)
        done += finished
        attempted += len(jobs)
        exchange = getattr(sim, "exchange", None)
        records.append((t, np.asarray([index.get(j.exec_site, -1) for j in res.jobs]),
                        np.asarray([j.finish for j in res.jobs]),
                        np.asarray([j.migrated for j in res.jobs]),
                        exchange.stats.bytes_sent if exchange is not None else None,
                        len(jobs)))
        k += 1
        if k % len(traces) == 0 and time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    return WindowResult(
        end_to_end={"sim_jobs_per_s": done / elapsed},
        attempted=attempted,
        failed=attempted - done,
        series={"traces": [len(records)],
                "bytes_per_job": [r[4] / r[5] for r in records if r[4] is not None]},
        record=records,
    )


def reference(state, dtype=np.float64):
    """The plain P2P reference over the run's grid and deployment."""
    from diana_bench.reference_p2p import P2PReference

    p, cfg, p2p = state["planes"], state["config"], state["p2p"]
    return P2PReference(
        state["nodes"], p["loss"], p["bw"], p["rtt"], p["mss"],
        peer_sites=state["peers"], exchange_interval_s=p2p["exchange_interval_s"],
        exchange_latency_s=p2p["exchange_latency_s"],
        full_sync_every=p2p["full_sync_every"], wire=p2p["wire"], quant=p2p["quant"],
        fanout=p2p["fanout"], transport=p2p["transport"],
        migration_interval_s=cfg.migration_interval_s,
        congestion_window_s=cfg.congestion_window_s, dtype=dtype)


def check(state, result) -> dict:
    tr = state["traces"]
    ref = reference(state)
    expect = {t: ref.run(tr[t]) for t in sorted({r[0] for r in result.record})}
    wrong_sites = wrong_finish = wrong_migrated = unfinished = 0
    for t, site, finish, migrated, *_ in result.record:
        e = expect[t]
        n = min(len(site), len(e["exec_site"]))
        unfinished += int(np.sum(finish[:n] < 0)) + len(e["exec_site"]) - n
        wrong_sites += int(np.sum(site[:n] != e["exec_site"][:n]))
        wrong_finish += int(np.sum(finish[:n] != e["finish"][:n]))
        wrong_migrated += int(np.sum(migrated[:n] != e["migrated"][:n]))
    return {
        "wrong_exec_sites": (wrong_sites, 0),
        "wrong_finish_times": (wrong_finish, 0),
        "wrong_migrated_flags": (wrong_migrated, 0),
        "unfinished_jobs": (unfinished, 0),
    }

"""Back-to-back traces through ``GridSim.run`` with the DIANA policy.

The configuration's grid becomes a simulated grid: a site has
``ceil(capacity / capacity_per_node)`` nodes, and each ordered pair of
sites has the link of its two endpoints (``grids.pair_links``). Set-up
draws ``distinct_traces`` traces of ``trace_jobs`` jobs of the paper's
§XI mix from the run's seed (``diana_bench.traces``); the arrivals
outpace the grid's service once the input fetch is counted, so queues
deepen through every trace, §X reprioritizes on each arrival and §IX
checks congestion on each tick.

The window runs the traces in turn, each through a fresh
``GridSim(config=SimConfig(policy="diana", ...))``, cycle after cycle,
and closes at the end of the cycle running when ``seconds`` have
passed. ``sim_jobs_per_s`` is every job completed over that whole
time; a trace's size is fixed, so a faster program meets the same
queue depths. The simulator fills in the jobs it is given, so set-up
builds a fresh job list for every trace the window may run, enough for
``prebuilt_jobs_per_s`` over the window; past that the window builds
them itself, under the ``trace_start`` annotation.

The check runs each distinct trace through the plain per-event
reference and compares, for every job of every trace of the window,
its execution site, its finish time and whether it migrated.
"""
from __future__ import annotations

import math
import time

import numpy as np

from diana_bench import grids
from diana_bench.harness import WindowResult
from diana_bench.traces import sim_trace


def setup(ctx):
    from repro.core import NetworkLink
    from repro.sim import GridSim, SimConfig

    tr, g = ctx.traffic, ctx.grid
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
    origin = int(g.sites_of(tr["origin_tier"], ctx.config)[0])
    data_sites = g.sites_of(tr["data_tier"], ctx.config)
    traces = [
        sim_trace(tr, nodes, origin, data_sites, grids.rng_for(ctx.seed, 6, k))
        for k in range(tr["distinct_traces"])
    ]
    config = SimConfig(policy="diana", migration_interval_s=tr["migration_interval_s"],
                       congestion_window_s=tr["congestion_window_s"])
    site_nodes = {n: int(c) for n, c in zip(names, nodes)}
    runs = math.ceil(ctx.seconds * tr["prebuilt_jobs_per_s"] / tr["trace_jobs"])
    runs = len(traces) * math.ceil(runs / len(traces))
    prebuilt = [_sim_jobs(traces[k % len(traces)], names) for k in range(runs)]
    # Warm-up: a short trace through the same path.
    warm = sim_trace(dict(tr, trace_jobs=200), nodes, origin, data_sites,
                     grids.rng_for(ctx.seed, 7))
    GridSim(dict(site_nodes), links=links, config=config).run(_sim_jobs(warm, names))
    return {"traces": traces, "names": names, "site_nodes": site_nodes, "links": links,
            "config": config, "planes": planes, "nodes": nodes, "prebuilt": prebuilt}


def _sim_jobs(trace: dict, names: list[str]):
    from repro.sim.workloads import SimJob

    return [
        SimJob(user=f"user{int(u):02d}", arrival=float(a), work=float(w),
               input_bytes=float(i), output_bytes=float(o),
               data_site=names[d], origin_site=names[og])
        for u, a, w, i, o, d, og in zip(
            trace["user"], trace["arrival"], trace["work"], trace["input_bytes"],
            trace["output_bytes"], trace["data_site"], trace["origin_site"])
    ]


def window(state, ctx, t0) -> WindowResult:
    from repro.sim import GridSim

    traces, names = state["traces"], state["names"]
    make = ctx.scheduler_factory or GridSim
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
        records.append((t, np.asarray([index.get(j.exec_site, -1) for j in res.jobs]),
                        np.asarray([j.finish for j in res.jobs]),
                        np.asarray([j.migrated for j in res.jobs]),
                        res.migrations()))
        k += 1
        if k % len(traces) == 0 and time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    return WindowResult(
        end_to_end={"sim_jobs_per_s": done / elapsed},
        attempted=attempted,
        failed=attempted - done,
        series={"traces": [len(records)], "migrations": [r[4] for r in records]},
        record=records,
    )


def check(state, result) -> dict:
    from diana_bench.reference_sim import SimReference

    p, tr = state["planes"], state["traces"]
    ref = SimReference(state["nodes"], p["loss"], p["bw"], p["rtt"], p["mss"],
                       migration_interval_s=state["config"].migration_interval_s,
                       congestion_window_s=state["config"].congestion_window_s)
    expect = {t: ref.run(tr[t]) for t in sorted({r[0] for r in result.record})}
    wrong_sites = wrong_finish = wrong_migrated = unfinished = 0
    for t, site, finish, migrated, _ in result.record:
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

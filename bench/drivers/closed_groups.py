"""Closed loop of bulk groups through ``DianaScheduler.place_batch``.

Set-up draws ``distinct_groups`` groups of ``group_jobs`` jobs from the
seed. The window places them one after another, cycling, through the
scheduler's ``place_batch`` with the traffic file's keyword arguments
(its ``place_batch``, e.g. ``mode: "hier"``).
After each group its jobs are released with ``complete``, and the site
queues are set back to the grid's generated state, so every group
starts from the same state, one group is in flight at a time, and the
work per group stays the same through the window. The window closes at
the end of the group that is running when ``seconds`` have passed; the
rate, every job placed over that whole time, is reported under the
traffic file's ``rate_metric`` (``bulk_decisions_per_s`` if it names
none).

The check replays each distinct group through the plain reference from
the generated state and compares every placement of the window with
it: the site, the cost, and the queue length and waiting work that
``place_batch`` committed at every site.
"""
from __future__ import annotations

import time

import numpy as np

from diana_bench import grids
from diana_bench.harness import WindowResult, make_scheduler
from diana_bench.reference import SchedulerReference


def setup(ctx):
    tr = ctx.traffic
    groups = [
        grids.demands(ctx.config, tr["group_jobs"], grids.rng_for(ctx.seed, 1, g))
        for g in range(tr["distinct_groups"])
    ]
    sched = make_scheduler(ctx)
    site_objs = list(sched.sites.values())
    start = [(float(q), float(w)) for q, w in zip(ctx.grid.queue, ctx.grid.work)]
    kw = ctx.traffic.get("place_batch", {})
    # Warm-up: one short group through the same call, then back to the
    # generated state.
    warm = groups[0].jobs(0, 64)
    sched.place_batch(warm, **kw)
    for job in warm:
        sched.complete(job)
    for s, (q, w) in zip(site_objs, start):
        s.queue_length, s.waiting_work = q, w
    return {
        "groups": groups, "jobs": [g.jobs() for g in groups], "sched": sched,
        "site_objs": site_objs, "start": start, "kw": kw, "grid": ctx.grid,
    }


def window(state, ctx, t0) -> WindowResult:
    sched, jobs, kw = state["sched"], state["jobs"], state["kw"]
    site_objs, start = state["site_objs"], state["start"]
    ann = ctx.annotate
    records = []
    k = 0
    while True:
        g = k % len(jobs)
        with ann("place_batch"):
            p = sched.place_batch(jobs[g], **kw)
        with ann("snapshot"):
            committed = (
                [s.queue_length for s in site_objs],
                [s.waiting_work for s in site_objs],
            )
        with ann("complete"):
            for job in jobs[g]:
                sched.complete(job)
        with ann("reset"):
            for s, (q, w) in zip(site_objs, start):
                s.queue_length, s.waiting_work = q, w
        records.append((g, p.site_indices, p.costs, committed))
        k += 1
        if time.perf_counter() - t0 >= ctx.seconds:
            break
    elapsed = time.perf_counter() - t0
    placed = sum(len(r[1]) for r in records)
    attempted = sum(len(jobs[r[0]]) for r in records)
    return WindowResult(
        end_to_end={ctx.traffic.get("rate_metric", "bulk_decisions_per_s"): placed / elapsed},
        attempted=attempted,
        failed=attempted - placed,
        series={"groups": [len(records)]},
        record=records,
    )


def check(state, result) -> dict:
    return compare_groups(state["grid"], state["groups"], result.record)


def compare_groups(grid, groups, records) -> dict:
    ref = SchedulerReference(grid)
    expect = {}
    for g in sorted({r[0] for r in records}):
        ref.reset()
        d = groups[g]
        placed = ref.place(d.work, d.input_bytes, d.output_bytes)
        expect[g] = (placed, ref.q.copy(), ref.w.copy())
    wrong_sites = wrong_costs = wrong_state = unplaced = 0
    for g, sites, costs, (q, w) in records:
        placed, rq, rw = expect[g]
        n = min(len(sites), len(placed.site))
        unplaced += len(placed.site) - n
        wrong_sites += int(np.sum(np.asarray(sites[:n]) != placed.site[:n]))
        wrong_costs += int(np.sum(np.asarray(costs[:n], np.float64) != placed.cost[:n]))
        wrong_state += int(np.sum((np.asarray(q) != rq) | (np.asarray(w) != rw)))
    return {
        "wrong_sites": (wrong_sites, 0),
        "wrong_costs": (wrong_costs, 0),
        "wrong_site_state": (wrong_state, 0),
        "unplaced_jobs": (unplaced, 0),
    }

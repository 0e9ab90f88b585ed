"""Open loop of single jobs arriving as a Poisson process.

Set-up draws the arrival times (rate ``rate_per_s`` over the window)
and one demand per job from the seed. The window runs on its own
clock, which starts at the window's start. At each turn the driver
first releases, with ``complete``, every placed job whose service has
ended by now, then places every job that is due by now with one
``place_batch`` call; when no job is due it sleeps until the next one
is. A job's service ends at its due time plus ``service_scale`` times
its work over its site's capacity, so completions follow the
schedule's clock and not the program's speed.

``arrival_p50_ms`` is the median, over every job due in the window, of
the time from its due time to the return of the call that placed it;
the series keep every job's latency, so the 95th percentile is a
per-layer metric (a stall of the host's for a fraction of a second
moves the tail of one run by tens of percent). Jobs due before the
window closes are all placed, however late. The series give each call's wall time and, for every job, how
late the generator issued it: from its due time to the start of the
call that placed it (a late wake-up from a sleep counts there too).

The check replays the logged sequence (the releases, then the jobs of
each call, in order) through the plain reference and compares every
placement, its cost, the committed queue length and waiting work of
each site the call touched, and every site's state at the end.
"""
from __future__ import annotations

import heapq
import time

import numpy as np

from diana_bench import grids
from diana_bench.harness import WindowResult, make_scheduler
from diana_bench.reference import SchedulerReference


def arrivals(rate: float, seconds: float, rng: np.random.Generator) -> np.ndarray:
    """Poisson due times in [0, seconds)."""
    n = int(rate * seconds * 1.2 + 10 * np.sqrt(rate * seconds) + 100)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    while t[-1] < seconds:
        t = np.concatenate([t, t[-1] + np.cumsum(rng.exponential(1.0 / rate, size=n))])
    return t[t < seconds]


def setup(ctx):
    tr = ctx.traffic
    due = arrivals(tr["rate_per_s"], ctx.seconds, grids.rng_for(ctx.seed, 2))
    d = grids.demands(ctx.config, len(due), grids.rng_for(ctx.seed, 3))
    sched = make_scheduler(ctx)
    site_objs = list(sched.sites.values())
    kw = ctx.traffic.get("place_batch", {})
    # Warm-up: a few calls of the sizes the window makes, then back to
    # the generated state.
    warm = grids.demands(ctx.config, 64, grids.rng_for(ctx.seed, 4)).jobs()
    for lo, hi in ((0, 1), (1, 3), (3, 10), (10, 64)):
        sched.place_batch(warm[lo:hi], **kw)
    for s, q, w in zip(site_objs, ctx.grid.queue, ctx.grid.work):
        s.queue_length, s.waiting_work = float(q), float(w)
    return {
        "due": due, "demands": d, "jobs": d.jobs(), "sched": sched,
        "site_objs": site_objs, "kw": kw, "grid": ctx.grid,
        "scale": float(tr["service_scale"]),
    }


def window(state, ctx, t0) -> WindowResult:
    sched, jobs, kw = state["sched"], state["jobs"], state["kw"]
    due, site_objs, scale = state["due"], state["site_objs"], state["scale"]
    work, cap = state["demands"].work, state["grid"].cap
    ann = ctx.annotate
    clock = time.perf_counter
    n = len(due)
    latency = np.empty(n)
    issued = np.empty(n)
    pending: list[tuple[float, int]] = []
    log, call_s = [], []
    i = 0
    while i < n:
        now = clock() - t0
        if due[i] > now:
            with ann("sleep"):
                time.sleep(due[i] - now)
            continue
        released = []
        with ann("complete"):
            while pending and pending[0][0] <= now:
                k = heapq.heappop(pending)[1]
                sched.complete(jobs[k])
                released.append(k)
        j = int(np.searchsorted(due, now, side="right"))
        c0 = clock()
        with ann("place_batch"):
            p = sched.place_batch(jobs[i:j], **kw)
        c1 = clock()
        issued[i:j] = (c0 - t0) - due[i:j]
        latency[i:j] = (c1 - t0) - due[i:j]
        call_s.append(c1 - c0)
        sites = np.asarray(p.site_indices)
        with ann("snapshot"):
            touched = np.unique(sites)
            committed = [(site_objs[s].queue_length, site_objs[s].waiting_work)
                         for s in touched]
        log.append((released, i, j, sites, np.asarray(p.costs, np.float64),
                    touched, committed))
        ends = due[i:i + len(sites)] + scale * work[i:i + len(sites)] / cap[sites]
        for k, e in enumerate(ends.tolist(), start=i):
            heapq.heappush(pending, (e, k))
        i = j
    elapsed = clock() - t0
    final = [(s.queue_length, s.waiting_work) for s in site_objs]
    placed = sum(len(e[3]) for e in log)
    return WindowResult(
        end_to_end={"arrival_p50_ms": float(np.percentile(latency, 50)) * 1e3},
        attempted=n,
        failed=n - placed,
        series={"call_s": call_s, "gen_late_s": issued, "window_s": [elapsed],
                "latency_s": latency, "call_due_s": [due[e[1]] for e in log],
                "batch_jobs": [e[2] - e[1] for e in log]},
        record=(log, final),
    )


def check(state, result) -> dict:
    log, final = result.record
    d, grid = state["demands"], state["grid"]
    ref = SchedulerReference(grid)
    ref_site = np.full(len(d), -1, np.int64)
    wrong_sites = wrong_costs = wrong_state = unplaced = 0
    for released, i, j, sites, costs, touched, committed in log:
        for k in released:
            ref.complete(int(ref_site[k]), d.work[k])
        placed = ref.place(d.work[i:j], d.input_bytes[i:j], d.output_bytes[i:j])
        ref_site[i:j] = placed.site
        m = min(len(sites), j - i)
        unplaced += (j - i) - m
        wrong_sites += int(np.sum(sites[:m] != placed.site[:m]))
        wrong_costs += int(np.sum(costs[:m] != placed.cost[:m]))
        got = np.asarray(committed, np.float64).reshape(-1, 2)
        want = np.stack([ref.q[touched], ref.w[touched]], axis=1).astype(np.float64)
        wrong_state += int(np.sum(np.any(got != want, axis=1)))
    got = np.asarray(final, np.float64)
    want = np.stack([ref.q, ref.w], axis=1).astype(np.float64)
    wrong_state += int(np.sum(np.any(got != want, axis=1)))
    unplaced += len(d) - sum(j - i for _, i, j, *_ in log)
    return {
        "wrong_sites": (wrong_sites, 0),
        "wrong_costs": (wrong_costs, 0),
        "wrong_site_state": (wrong_state, 0),
        "unplaced_jobs": (unplaced, 0),
    }

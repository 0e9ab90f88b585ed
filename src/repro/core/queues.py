"""Multilevel feedback queue management (paper §VI, §VII, §X).

Four queues Q1..Q4 partition the priority interval (−1, 1). On each
arrival every queued job is re-prioritized (priority.reprioritize) and
re-bucketed — jobs migrate between queues in both directions, which is
the paper's anti-starvation mechanism. Within equal priority the order
is FCFS by arrival timestamp; batches are SJF-arranged (fewer required
processors ⇒ shorter ⇒ first) before enqueue. Scheduling is
non-preemptive: dispatch never recalls a running job.

A job's §X priority depends only on its user's job count and quota, its
own t and the totals (Q, T), so the queued jobs of one (user, t) class
share it. The queues keep one class per (user, t): a shared cell with
the priority and band, and a heap of the class's jobs in FCFS order. An
arrival recomputes each class once and a dispatch compares the class
heads, instead of touching every queued job.

Congestion (§X): (arrival_rate − service_rate)/arrival_rate > Thrs
triggers migration of low-priority jobs to peers (see migration.py).
"""
from __future__ import annotations

import itertools
from bisect import bisect_left
from dataclasses import dataclass, field
from heapq import heapify, heappop, heappush
from typing import Iterable, Optional

import numpy as np

from . import priority as prio
from . import trace

__all__ = ["Job", "MultilevelFeedbackQueues", "is_congested"]

_seq = itertools.count()

# Sums of non-negative integers below this are exact in float64 in any
# order, so running totals then equal the in-order sums bit for bit.
_EXACT = 2**53
# Counts below this are exact in float32.
_F32_EXACT = 2**24
_SCALAR = (int, float)


@dataclass
class Job:
    """One schedulable unit — a subjob, or a whole group treated as one
    job by the meta-scheduler (§VIII).

    While the job waits in a ``MultilevelFeedbackQueues``, ``priority``
    and ``queue`` read its (user, t) class's shared cell. Writing either
    one then gives the job its own value until the queue's next arrival
    recomputes it, as that arrival recomputes every queued job. The
    queue reads ``t`` and ``compute_work`` into its running totals as
    the job enters and leaves, so neither may change while it waits.
    """

    user: str
    t: float = 1.0                   # processors required (SJF key, §VII)
    submit_time: float = 0.0
    compute_work: float = 1.0        # processor·hours or FLOPs
    input_bytes: float = 0.0
    output_bytes: float = 0.0
    executable_bytes: float = 0.0
    group_id: Optional[str] = None
    job_id: int = field(default_factory=_seq.__next__)
    priority: float = 0.0
    queue: int = 1
    migrated: bool = False           # §IX: pinned after one migration
    site: Optional[str] = None

    _cell = None                     # the job's _Class while queued

    @property
    def data_intensive(self) -> bool:
        return self.total_bytes > self.compute_work

    @property
    def total_bytes(self) -> float:
        return self.input_bytes + self.output_bytes + self.executable_bytes


def _get_priority(job: Job) -> float:
    cell = job._cell
    return job._priority if cell is None else cell.priority


def _set_priority(job: Job, value: float) -> None:
    if job._cell is not None:
        job._cell.owner._detach(job)
    job._priority = value


def _get_queue(job: Job) -> int:
    cell = job._cell
    return job._queue if cell is None else cell.queue


def _set_queue(job: Job, value: int) -> None:
    if job._cell is not None:
        job._cell.owner._detach(job)
    job._queue = value


Job.priority = property(_get_priority, _set_priority)
Job.queue = property(_get_queue, _set_queue)


def _whole(x) -> Optional[int]:
    """``x`` as an int where it is a non-negative whole int or float,
    whose running sums are exact (below ``_EXACT``); else None."""
    if type(x) in _SCALAR and x >= 0 and x.is_integer():
        return int(x)
    return None


class _Class:
    """The queued jobs of one (user, t): one §X priority and band for
    all, and a heap of ``(submit_time, job_id, seq, job)`` entries.
    Removed jobs leave dead entries behind, which ``n`` (the live count)
    and ``MultilevelFeedbackQueues._entry`` tell apart."""

    __slots__ = ("owner", "key", "user", "t32", "tw", "heap", "n", "priority", "queue", "q", "q32")

    def __init__(self, owner: "MultilevelFeedbackQueues", user: str, t: float):
        self.owner = owner
        self.key = (user, t)
        self.user = user
        self.t32 = float(np.float32(t))
        self.tw = _whole(t)
        self.heap: list[tuple] = []
        self.n = 0
        self.priority = 0.0
        self.queue = 1
        self.q = None                # the quota q32 was rounded from
        self.q32 = 0.0


def is_congested(arrival_rate: float, service_rate: float, thrs: float) -> bool:
    """Paper §X: (Arrival − Service)/Arrival > Thrs, Thrs ∈ (0, 1)."""
    if arrival_rate <= 0:
        return False
    return (arrival_rate - service_rate) / arrival_rate > thrs


class MultilevelFeedbackQueues:
    """The per-site DIANA queue manager.

    Maintains the four priority-band queues plus the per-user quota
    table needed for §X re-prioritization.
    """

    def __init__(self, quotas: dict[str, float], congestion_thrs: float = 0.5):
        self.quotas = dict(quotas)
        self.congestion_thrs = congestion_thrs
        # Every queued (not running) job, by id(), in arrival order;
        # ``jobs`` is a live view of them.
        self._jobs: dict[int, Job] = {}
        self.jobs = self._jobs.values()
        self._classes: dict[tuple, _Class] = {}     # the non-empty ones
        self._spare: dict[tuple, _Class] = {}       # the emptied ones
        # id(job) → the job's live heap entry, for jobs in a class.
        self._entry: dict[int, tuple] = {}
        # id(job) → entry, for queued jobs holding a priority of their
        # own (written, or requeued) until the next arrival.
        self._loose: dict[int, tuple] = {}
        self._users: dict[str, int] = {}   # queued jobs per user
        self._t_sum = 0        # Σ t over queued jobs whose t is a non-negative integer
        self._t_inexact = 0    # queued jobs whose t is not
        self._work_sum = 0     # Σ compute_work over queued jobs whose work is a non-negative integer
        self._work_inexact = 0  # queued jobs whose work is not
        self._order = itertools.count()
        self._arrivals = 0
        self._services = 0
        self._arrival_times: list[float] = []
        self._service_times: list[float] = []
        # Rate-sample pruning bookkeeping: simulation timestamps arrive
        # in non-decreasing order, so samples older than the widest
        # window ever queried can be discarded (rates() does this) —
        # without pruning a million-job stream retains every timestamp
        # forever and every congestion check rescans them all.
        self._rate_monotone = True         # appends seen so far are sorted
        self._max_window = 0.0
        self._prune_floor = -float("inf")

    # -- §X quota aggregates ------------------------------------------------
    def _totals(self) -> tuple[float, float]:
        users = {j.user for j in self.jobs}
        Q = sum(self.quotas.get(u, 1.0) for u in users)
        T = sum(j.t for j in self.jobs)
        return Q, T

    # -- membership ---------------------------------------------------------
    def _enter(self, job: Job, loose: bool) -> None:
        """Queue ``job`` at the end of ``jobs``, in the counts and T, and
        in its class, or loose with the priority and band it holds."""
        key = id(job)
        if key in self._jobs:
            raise ValueError("job is already queued")
        entry = (job.submit_time, job.job_id, next(self._order), job)
        self._jobs[key] = job
        users = self._users
        users[job.user] = users.get(job.user, 0) + 1
        if loose:
            self._loose[key] = entry
            tw = _whole(job.t)
        else:
            tw = self._attach(entry).tw
        if tw is None:
            self._t_inexact += 1
        else:
            self._t_sum += tw
        ww = _whole(job.compute_work)
        if ww is None:
            self._work_inexact += 1
        else:
            self._work_sum += ww

    def _leave(self, job: Job) -> None:
        """Take a queued job out, its priority and band frozen into it."""
        key = id(job)
        if job._cell is None:
            del self._loose[key]
            tw = _whole(job.t)
        else:
            tw = self._unclass(job).tw
        del self._jobs[key]
        users = self._users
        n = users[job.user] - 1
        if n:
            users[job.user] = n
        else:
            del users[job.user]
        if tw is None:
            self._t_inexact -= 1
        else:
            self._t_sum -= tw
        ww = _whole(job.compute_work)
        if ww is None:
            self._work_inexact -= 1
        else:
            self._work_sum -= ww

    def _attach(self, entry: tuple) -> _Class:
        job = entry[3]
        ck = (job.user, job.t)
        cls = self._classes.get(ck)
        if cls is None:
            cls = self._spare.pop(ck, None) or _Class(self, job.user, job.t)
            self._classes[ck] = cls
        heappush(cls.heap, entry)
        cls.n += 1
        self._entry[id(job)] = entry
        job._cell = cls
        return cls

    def _unclass(self, job: Job) -> _Class:
        """Freeze a classed job's priority and band into the job itself
        and take it out of its class; its heap entry is dead after. An
        emptied class waits in ``_spare`` for the key's next job."""
        cls = job._cell
        job._priority, job._queue, job._cell = cls.priority, cls.queue, None
        del self._entry[id(job)]
        cls.n -= 1
        if not cls.n:
            cls.heap.clear()
            self._spare[cls.key] = self._classes.pop(cls.key)
        return cls

    def _detach(self, job: Job) -> None:
        """A write to a classed job's priority or band: the job keeps
        its own value, as loose, until the next arrival."""
        entry = self._entry.get(id(job))
        if entry is None:              # a copy of a queued job
            cell = job._cell
            job._priority, job._queue, job._cell = cell.priority, cell.queue, None
        else:
            self._unclass(job)
            self._loose[id(job)] = entry

    # -- arrivals -----------------------------------------------------------
    def submit(self, job: Job, now: Optional[float] = None) -> Job:
        """Enqueue one job and §X-reprioritize everything."""
        if job.user not in self.quotas:
            self.quotas[job.user] = 1.0
        self._enter(job, loose=False)
        self._arrivals += 1
        t = job.submit_time if now is None else now
        if self._arrival_times and t < self._arrival_times[-1]:
            self._rate_monotone = False
        self._arrival_times.append(t)
        self.reprioritize_all()
        return job

    def submit_batch(self, jobs: Iterable[Job], now: Optional[float] = None) -> list[Job]:
        """SJF-arrange (§VII: fewer processors first) then enqueue."""
        batch = sorted(jobs, key=lambda j: (j.t, j.submit_time, j.job_id))
        return [self.submit(j, now) for j in batch]

    def requeue(self, job: Job) -> None:
        """Put a dispatched job back at the end of the queue with the
        priority and band it holds; nothing is reprioritized (§X: only
        arrivals do that), and the next arrival recomputes it too."""
        self._enter(job, loose=True)

    def reprioritize_all(self) -> None:
        """Recompute Pr for every queued job with current (Q, T) (§X):
        once per (user, t) class, whose jobs share it."""
        if not self._jobs:
            return
        with trace.span("diana.mlfq.reprioritize"):
            if self._loose:
                for e in self._loose.values():
                    # a fresh tuple: the old one may lie dead in its heap
                    self._attach((e[0], e[1], e[2], e[3]))
                self._loose.clear()
            users, quotas = self._users, self.quotas
            # Q and T from running sums where every term is whole, so
            # that any order of summing gives _totals' bits; else _totals.
            Q, exact = 0, not self._t_inexact and self._t_sum < _EXACT
            for u in users:
                q = quotas.get(u, 1.0)
                if type(q) not in _SCALAR or not (q >= 0 and q.is_integer()):
                    exact = False
                    break
                Q += q
            if exact and Q < _EXACT:
                Q, T = float(Q), float(self._t_sum)
            else:
                exact = False
                Q, T = map(float, self._totals())
            row = prio.reprioritize_row
            for cls in self._classes.values():
                n = users[cls.user]
                if n >= _F32_EXACT:
                    n = float(np.float32(n))
                q = quotas[cls.user]
                if q != cls.q:
                    cls.q, cls.q32 = q, float(np.float32(q))
                cls.priority, cls.queue = row(n, cls.q32, cls.t32, Q, T)
        if trace.on:
            trace.count("diana.mlfq.submits")
            trace.count("diana.mlfq.reprioritized", len(self._jobs))
            trace.count("diana.mlfq.classes", len(self._classes))
            if not exact:
                trace.count("diana.mlfq.exact_fallback")

    # -- service ------------------------------------------------------------
    def pop_next(self, now: Optional[float] = None) -> Optional[Job]:
        """Dispatch the head job: highest priority; FCFS on ties (§X).

        Per §X, service does NOT trigger re-prioritization.
        """
        if not self._jobs:
            return None
        with trace.span("diana.mlfq.pop"):
            # the least (-priority, submit_time, job_id, seq) over the
            # class heads and the loose jobs
            best = best_cls = None
            live = self._entry
            for cls in self._classes.values():
                heap = cls.heap
                if len(heap) != cls.n:         # dead entries: clear the head
                    while live.get(id(heap[0][3])) is not heap[0]:
                        heappop(heap)
                p, e = cls.priority, heap[0]
                if best is None or p > bp or (p == bp and e < best):
                    best, bp, best_cls = e, p, cls
            for e in self._loose.values():
                p = e[3]._priority
                if best is None or p > bp or (p == bp and e < best):
                    best, bp, best_cls = e, p, None
            if best_cls is not None:
                heappop(best_cls.heap)
            job = best[3]
            self._leave(job)
            self._services += 1
            if now is not None:
                if self._service_times and now < self._service_times[-1]:
                    self._rate_monotone = False
                self._service_times.append(now)
        return job

    def remove(self, job: Job) -> None:
        if self._jobs.get(id(job)) is not job:
            raise ValueError("job is not queued")
        cls = job._cell
        self._leave(job)
        if cls is not None and cls.n and len(cls.heap) > 2 * cls.n + 8:
            live = self._entry
            cls.heap = [e for e in cls.heap if live.get(id(e[3])) is e]
            heapify(cls.heap)

    # -- introspection --------------------------------------------------------
    def queue_contents(self) -> list[list[Job]]:
        """Jobs per band, each band sorted (priority desc, FCFS ties)."""
        bands: list[list[Job]] = [[] for _ in range(prio.NUM_QUEUES)]
        for j in self.jobs:
            bands[j.queue].append(j)
        for band in bands:
            band.sort(key=lambda j: (-j.priority, j.submit_time, j.job_id))
        return bands

    def __len__(self) -> int:
        return len(self._jobs)

    def queued_work(self) -> float:
        """Σ ``compute_work`` over the queued jobs, with the bits of the
        in-order ``sum``: from the running total while every queued work
        is whole and the total below ``_EXACT``, else that sum, O(L)."""
        if not self._work_inexact and self._work_sum < _EXACT:
            return float(self._work_sum)
        if trace.on:
            trace.count("diana.mlfq.work_fallback")
        return sum(j.compute_work for j in self.jobs)

    def jobs_ahead(self, p: float) -> int:
        """§IX: number of queued jobs with priority ≥ p."""
        n = sum(cls.n for cls in self._classes.values() if cls.priority >= p)
        return n + sum(1 for e in self._loose.values() if e[3]._priority >= p)

    def low_priority_jobs(self) -> list[Job]:
        """§X: only low-priority (Q4) jobs are migration candidates."""
        return [j for j in self.jobs if j.queue == prio.NUM_QUEUES - 1]

    # -- copying --------------------------------------------------------------
    def __getstate__(self) -> dict:
        """The id()-keyed maps travel as lists, so that a copy keys them
        by the ids of its own jobs; ``jobs`` is rebuilt as a view, and
        the entry counter travels as the next number it gives."""
        state = dict(self.__dict__)
        del state["jobs"]
        for k in ("_jobs", "_entry", "_loose"):
            state[k] = list(state[k].values())
        state["_order"] = next(self._order)
        return state

    def __setstate__(self, state: dict) -> None:
        self.__dict__.update(state)
        self._jobs = {id(j): j for j in state["_jobs"]}
        self._entry = {id(e[3]): e for e in state["_entry"]}
        self._loose = {id(e[3]): e for e in state["_loose"]}
        self._order = itertools.count(state["_order"])
        self.jobs = self._jobs.values()

    # -- rates / congestion ---------------------------------------------------
    def prune_rate_samples(self, cutoff: float) -> None:
        """Discard rate samples strictly older than ``cutoff``. Only
        safe (and only applied) while the recorded timestamps are
        non-decreasing — ``rates`` calls this with ``now`` minus the
        widest window it has ever been asked about, which keeps memory
        bounded by window × rate instead of total jobs ever queued."""
        if not self._rate_monotone or cutoff <= self._prune_floor:
            return
        self._prune_floor = cutoff
        for lst in (self._arrival_times, self._service_times):
            i = bisect_left(lst, cutoff)
            if i:
                del lst[:i]

    def rates(self, window: float, now: float) -> tuple[float, float]:
        """(arrival_rate, service_rate) over the trailing window.

        Assumes ``now`` is non-decreasing across calls (the simulator's
        clock): samples older than the widest window ever queried are
        pruned and no longer countable by a later call that jumps
        backwards in time. Out-of-order *sample appends* are detected
        and disable pruning (the count then falls back to a full scan).
        """
        lo = now - window
        if self._rate_monotone:
            if window > self._max_window:
                self._max_window = window
            self.prune_rate_samples(now - self._max_window)
            at, st = self._arrival_times, self._service_times
            arr = len(at) - bisect_left(at, lo)
            srv = len(st) - bisect_left(st, lo)
        else:
            arr = sum(1 for ts in self._arrival_times if ts >= lo)
            srv = sum(1 for ts in self._service_times if ts >= lo)
        return arr / window, srv / window

    def congested(self, window: float, now: float) -> bool:
        a, s = self.rates(window, now)
        return is_congested(a, s, self.congestion_thrs)

    def littles_law_estimate(self, window: float, now: float, avg_wait: float) -> float:
        """N = R·W (§VII)."""
        a, _ = self.rates(window, now)
        return prio.littles_law_queue_length(a, avg_wait)

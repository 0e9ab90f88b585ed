"""Plain reference of the grid simulator's DIANA policy.

Written from the paper's §IV–§X and the simulator's documented
semantics, importing nothing of the program. One event at a time, from
a heap ordered by (time, push order):

* arrival: the job goes to the site of least §IV cost (network from its
  origin, computation on the site's live state, input fetch from its
  data site and output return to its origin, over the pair's Mathis
  effective bandwidth; the first site in name order on a tie); it joins
  that site's queue and the site dispatches.
* queue (§X): on every arrival at a site each queued job's priority is
  recomputed from the quota economy (N = q·T / (Q·t), Pr = (N − n)/N
  when n ≤ N, else (N − n)/n; every quota 1, every t 1) and banded into
  Q1–Q4 at 0.5, 0 and −0.5; dispatch takes the highest priority, the
  earliest admitted on a tie, and never reprioritizes.
* service: a node runs one job for its work plus the input fetch (when
  the data site is another) plus the output return (when the origin is
  another).
* migration (§IX), every ``migration_interval_s`` while work remains:
  each site whose arrivals outpace its services by more than half over
  the last ``congestion_window_s`` offers its Q4 jobs, in queue order;
  a job that has not moved yet goes to the peer with the fewest queued
  jobs of at least its priority (the cheapest, then the first, on a
  tie) when that peer has fewer than its own site, with its priority
  raised by 0.1, and the peer reprioritizes and dispatches.

The site's waiting work is the builtin ``sum`` of its queued jobs' work,
in queue order, plus the work of its running jobs kept as a running
total, so the floats are those of the same sequence of operations.
``dtype=np.float32`` computes the costs, service times and clock one
precision below (the control).
"""
from __future__ import annotations

import bisect
import heapq
from dataclasses import dataclass, field

import numpy as np

__all__ = ["SimReference"]


@dataclass
class _Job:
    idx: int
    user: int
    arrival: float
    work: float
    inb: float
    outb: float
    data: int          # -1: no dataset
    origin: int
    seq: int = 0       # admission order (the tie-break after submit time)
    submit: float = 0.0
    priority: float = 0.0
    band: int = 1
    migrated: bool = False


@dataclass
class _Site:
    nodes: int
    queue: list = field(default_factory=list)
    busy: int = 0
    running_work: float = 0.0
    arrivals: list = field(default_factory=list)
    services: list = field(default_factory=list)


class SimReference:
    def __init__(self, nodes, loss, bw, rtt, mss, *, migration_interval_s,
                 congestion_window_s, w_queue=0.0, w_work=1.0, w_load=0.0,
                 congestion_thrs=0.5, priority_bump=0.1, dtype=np.float64):
        f = self.f = np.dtype(dtype).type
        loss, bw, rtt, mss = (np.asarray(a, f) for a in (loss, bw, rtt, mss))
        self.nodes = [int(n) for n in nodes]
        self.net = (loss / bw) * f(1.0e6)
        with np.errstate(divide="ignore", invalid="ignore"):
            mathis = mss / (rtt * np.sqrt(loss))
        self.eff = np.where(loss <= 0.0, bw, np.minimum(bw, mathis)).astype(f)
        self.cap = np.asarray(self.nodes, f)
        self.interval = migration_interval_s
        self.window = congestion_window_s
        self.w = (w_queue, w_work, w_load)
        self.thrs = congestion_thrs
        self.bump = priority_bump

    # -- §IV ------------------------------------------------------------
    def _comp(self, s: int) -> float:
        site = self.sites[s]
        wq, ww, wl = self.w
        cap = float(site.nodes)
        waiting = sum(j.work for j in site.queue) + site.running_work
        return wq * float(len(site.queue)) / cap + ww * waiting / cap + wl * (site.busy / site.nodes)

    def _comp_all(self) -> np.ndarray:
        for s in self.dirty:
            self.comp[s] = self._comp(s)
        self.dirty.clear()
        return self.comp

    def _static(self, j: _Job) -> tuple[np.ndarray, np.ndarray]:
        f, S = self.f, len(self.nodes)
        cols = np.arange(S)
        dtc = np.zeros(S, f)
        if j.data >= 0:
            dtc = np.where(cols != j.data, f(j.inb) / self.eff[j.data, :], f(0.0))
        out = np.where(cols != j.origin, f(j.outb) / self.eff[:, j.origin], f(0.0))
        return self.net[j.origin, :], dtc + out

    def _costs(self, j: _Job) -> np.ndarray:
        net, dtc = self._static(j)
        comp = self._comp_all().astype(self.f) + self.f(j.work) / self.cap
        return (net + comp) + dtc

    def _service(self, j: _Job, s: int) -> float:
        f = self.f
        dur = f(j.work)
        if j.data >= 0 and j.data != s:
            dur += f(j.inb) / self.eff[j.data, s]
        if j.origin != s:
            dur += f(j.outb) / self.eff[s, j.origin]
        return dur

    # -- §X -------------------------------------------------------------
    def _submit(self, s: int, j: _Job, now: float) -> None:
        site = self.sites[s]
        site.queue.append(j)
        site.arrivals.append(now)
        users: dict[int, int] = {}
        for q in site.queue:
            users[q.user] = users.get(q.user, 0) + 1
        Q, T = float(len(users)), float(len(site.queue))
        n = np.asarray([users[q.user] for q in site.queue], np.float64)
        N = (1.0 * T) / (Q * 1.0)
        pr = np.where(n <= N, (N - n) / N, (N - n) / n)
        band = (pr < 0.5).astype(np.int64) + (pr < 0.0) + (pr < -0.5)
        for q, p, b in zip(site.queue, pr.tolist(), band.tolist()):
            q.priority, q.band = p, b
        self.dirty.add(s)
        self.sorted_pr.pop(s, None)

    def _dispatch(self, s: int, now: float) -> None:
        site = self.sites[s]
        while site.busy < site.nodes and site.queue:
            best = min(site.queue, key=lambda q: (-q.priority, q.submit, q.seq))
            site.queue.remove(best)
            site.services.append(now)
            self.sorted_pr.pop(s, None)
            self.start[best.idx] = now
            self.finish[best.idx] = float(self.f(now) + self._service(best, s))
            site.busy += 1
            site.running_work += best.work
            self.dirty.add(s)
            self._push(self.finish[best.idx], "finish", (s, best))

    # -- §IX ------------------------------------------------------------
    def _congested(self, s: int, now: float) -> bool:
        site, lo = self.sites[s], now - self.window
        arr = sum(1 for t in site.arrivals if t >= lo) / self.window
        srv = sum(1 for t in site.services if t >= lo) / self.window
        return arr > 0 and (arr - srv) / arr > self.thrs

    def _jobs_ahead(self, s: int, p: float) -> int:
        pr = self.sorted_pr.get(s)
        if pr is None:
            pr = self.sorted_pr[s] = sorted(q.priority for q in self.sites[s].queue)
        return len(pr) - bisect.bisect_left(pr, p)

    def _migrate(self, now: float) -> None:
        S = len(self.sites)
        for s in range(S):
            if not self._congested(s, now):
                continue
            for j in [q for q in self.sites[s].queue if q.band == 3]:
                if j.migrated:
                    continue
                local_ja = self._jobs_ahead(s, j.priority)
                costs = self._costs(j)
                best, best_key = -1, None
                for p in range(S):
                    if p == s:
                        continue
                    key = (self._jobs_ahead(p, j.priority), float(costs[p]))
                    if best_key is None or key < best_key:
                        best, best_key = p, key
                if best < 0 or not best_key[0] < local_ja:
                    continue
                if not (best_key[1] <= float(costs[s]) or best_key[1] < float("inf")):
                    continue
                self.sites[s].queue.remove(j)
                self.dirty.add(s)
                self.sorted_pr.pop(s, None)
                j.priority = min(1.0, j.priority + self.bump)
                j.migrated = True
                self.exec_site[j.idx] = best
                self._submit(best, j, now)
                self._dispatch(best, now)

    # -- the loop ---------------------------------------------------------
    def _push(self, t: float, kind: str, payload) -> None:
        heapq.heappush(self.events, (t, self.seq, kind, payload))
        self.seq += 1

    def run(self, trace: dict) -> dict:
        """``trace``: arrays ``user, arrival, work, input_bytes,
        output_bytes, data_site`` (−1 for none) and ``origin_site``."""
        J = len(trace["arrival"])
        self.sites = [_Site(n) for n in self.nodes]
        self.comp = np.empty(len(self.nodes))
        self.dirty = set(range(len(self.nodes)))
        self.sorted_pr: dict[int, list] = {}
        self.events, self.seq, admitted = [], 0, 0
        self.exec_site = np.full(J, -1, np.int64)
        self.start = np.full(J, -1.0)
        self.finish = np.full(J, -1.0)
        jobs = [
            _Job(i, int(trace["user"][i]), float(trace["arrival"][i]),
                 float(trace["work"][i]), float(trace["input_bytes"][i]),
                 float(trace["output_bytes"][i]), int(trace["data_site"][i]),
                 int(trace["origin_site"][i]))
            for i in range(J)
        ]
        for j in jobs:
            self._push(j.arrival, "arrive", j)
        pending = J
        if J:
            self._push(min(j.arrival for j in jobs) + self.interval, "migrate", None)
        while self.events:
            now, _, kind, payload = heapq.heappop(self.events)
            if kind == "arrive":
                pending -= 1
                j = payload
                s = int(np.argmin(self._costs(j)))
                self.exec_site[j.idx] = s
                j.submit, j.seq = now, admitted
                admitted += 1
                self._submit(s, j, now)
                self._dispatch(s, now)
            elif kind == "finish":
                s, j = payload
                site = self.sites[s]
                site.busy -= 1
                site.running_work -= j.work
                self.dirty.add(s)
                self._dispatch(s, now)
            else:
                self._migrate(now)
                if pending or any(site.queue for site in self.sites):
                    self._push(now + self.interval, "migrate", None)
        return {
            "exec_site": self.exec_site, "start": self.start, "finish": self.finish,
            "migrated": np.asarray([j.migrated for j in jobs]),
        }

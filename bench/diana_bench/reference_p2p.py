"""Plain reference of the grid simulator's peer-to-peer DIANA deployment.

Written from the documented semantics of ``P2PGridSim``, the gossip
exchange and ``PeerScheduler``, importing nothing of the program. It is
``reference_sim.SimReference`` (the per-event DIANA loop: §IV placement,
§X queues, §IX migration) with the grid split among peers:

* each peer owns a list of sites (its region) and keeps a view of every
  site's row: queue length, waiting work and load. Its own sites' rows
  are read live at each placement; every other row is as last
  delivered to it, rounded to float32 as the wire carries them;
* a job is placed by the peer owning its origin site, by §IV over that
  peer's view (the first site in name order on a tie). When the chosen
  site is not the peer's own, the peer adds the job to its view of that
  site (one more queued job, its work more waiting) and marks the row
  as its own guess;
* every ``exchange_interval_s`` from the first arrival each owner
  re-measures its rows, stamped with that time. A row whose content
  (queue length, waiting work, busy nodes) differs from the owner's
  previous measurement opens a new epoch. ``exchange_latency_s`` later
  every other peer receives the round: a row of a new epoch replaces
  the peer's row and stamp; a row of the same epoch refreshes the stamp
  alone, and is not applied where the peer holds a guess, which stays
  with its old stamp. Every ``full_sync_every``-th round, the first
  included, is a full sync: every row is sent whole, and replaces the
  peer's row and stamp, guess or not;
* §IX runs as in ``SimReference``, except that a congested site's peer
  polls only the sites whose rows in its view are at most two exchange
  intervals plus the latency old (its own sites are always fresh);
* stamps start at the first arrival, the views at the sites' initial
  (empty) state.

Departures from the program's docstrings, and why they give the same
decisions here:

* The wire is not modelled (packets, interned ids, acknowledgements,
  per-receiver acked versions, heartbeats as such). On a full mesh
  without loss, each owner's rows reach every peer directly each round,
  and an acknowledgement is back before the next round when the latency
  is under half the interval; so a round's delta is exactly the rows
  whose epoch opened in it, and its heartbeats the rest.
* Relayed rows (a peer forwarding what it heard of a third peer's
  sites) are left out. The program sends them only in full syncs, on a
  full mesh, and they carry the epoch the receiver already holds from
  the owner, at the same content; the owner's own row, in the same
  delivery, sets the same row and the newest stamp.
* The reference takes only what the configuration states: the delta
  wire, float32 rows, a full mesh, a lossless transport, and a latency
  above zero and under half the interval. Anything else is refused.

``dtype=np.float32`` computes the costs, service times and clock one
precision below (the control).
"""
from __future__ import annotations

import heapq

import numpy as np

from .reference_sim import SimReference, _Job, _Site

__all__ = ["P2PReference"]


def _wire(a: np.ndarray) -> np.ndarray:
    """Values as a row carries them: rounded to float32."""
    return a.astype(np.float32).astype(np.float64)


class _Peer:
    """One peer's view: rows of every site, the owner's stamp of each,
    and which rows hold the peer's own guesses."""

    def __init__(self, sites: list[int], S: int, t0: float):
        self.home = np.zeros(S, bool)
        self.home[sites] = True
        self.queue = np.zeros(S)
        self.work = np.zeros(S)
        self.load = np.zeros(S)
        self.stamp = np.full(S, float(t0))
        self.guess = np.zeros(S, bool)


class P2PReference(SimReference):
    def __init__(self, nodes, loss, bw, rtt, mss, *, peer_sites, exchange_interval_s,
                 exchange_latency_s, full_sync_every, migration_interval_s,
                 congestion_window_s, wire="delta", quant="f32", fanout=None,
                 transport="lossless", **kw):
        super().__init__(nodes, loss, bw, rtt, mss, migration_interval_s=migration_interval_s,
                         congestion_window_s=congestion_window_s, **kw)
        if (wire, quant, fanout, transport) != ("delta", "f32", None, "lossless"):
            raise ValueError("the P2P reference models the delta wire with float32 rows "
                             "over a lossless full mesh only")
        if not 0.0 < 2.0 * exchange_latency_s < exchange_interval_s:
            raise ValueError("the P2P reference needs 0 < latency < interval / 2")
        S = len(self.nodes)
        owner = np.full(S, -1, np.int64)
        for k, sites in enumerate(peer_sites):
            if not len(sites) or (owner[list(sites)] >= 0).any():
                raise ValueError("peer_sites must hold every site exactly once")
            owner[list(sites)] = k
        if (owner < 0).any():
            raise ValueError("peer_sites must hold every site exactly once")
        self.owner = owner
        self.peer_sites = [list(s) for s in peer_sites]
        self.exchange = float(exchange_interval_s)
        self.latency = float(exchange_latency_s)
        self.full_sync_every = int(full_sync_every)
        self.max_staleness = 2.0 * self.exchange + self.latency

    # -- a peer's view -----------------------------------------------------
    def _view_costs(self, j: _Job) -> np.ndarray:
        """§IV costs of ``j`` over the view of the peer owning its origin."""
        p = self.peers[self.owner[j.origin]]
        wq, ww, wl = self.w
        comp = wq * p.queue / self.cap64 + ww * p.work / self.cap64 + wl * p.load
        live = self._comp_all()
        comp[p.home] = live[p.home]
        net, dtc = self._static(j)
        return (net + (comp.astype(self.f) + self.f(j.work) / self.cap)) + dtc

    def _guess(self, j: _Job, s: int) -> None:
        p = self.peers[self.owner[j.origin]]
        if p.home[s]:
            return
        p.queue[s] += 1.0
        p.work[s] += j.work
        p.guess[s] = True

    # -- gossip --------------------------------------------------------------
    def _measure(self) -> tuple:
        """Every site's row now, and which rows differ from the last
        measurement."""
        rows = [(float(len(s.queue)), sum(q.work for q in s.queue) + s.running_work, s.busy)
                for s in self.sites]
        changed = np.asarray([r != m for r, m in zip(rows, self.measured)])
        self.measured = rows
        q, w, b = (np.asarray(c, np.float64) for c in zip(*rows))
        load = b / np.asarray(self.nodes, np.float64)
        return _wire(q), _wire(w), _wire(load), changed

    def _deliver(self, t: float, rows: tuple, full_sync: bool) -> None:
        q, w, load, changed = rows
        for p in self.peers:
            take = ~p.home & (changed | full_sync | ~p.guess)
            p.queue[take], p.work[take], p.load[take] = q[take], w[take], load[take]
            p.stamp[take] = t
            p.guess[take] = False

    def _trusted(self, s: int, now: float) -> np.ndarray:
        p = self.peers[self.owner[s]]
        stale = np.maximum(0.0, now - p.stamp)
        stale[p.home] = 0.0
        return stale <= self.max_staleness

    # -- §IX, polling trusted peers only ------------------------------------
    def _migrate(self, now: float) -> None:
        S = len(self.sites)
        for s in range(S):
            if not self._congested(s, now):
                continue
            trusted = self._trusted(s, now)
            for j in [q for q in self.sites[s].queue if q.band == 3]:
                if j.migrated:
                    continue
                local_ja = self._jobs_ahead(s, j.priority)
                costs = self._costs(j)
                best, best_key = -1, None
                for p in range(S):
                    if p == s or not trusted[p]:
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

    # -- the loop ---------------------------------------------------------------
    def run(self, trace: dict) -> dict:
        """``trace`` as for ``SimReference.run``; every origin site must
        be one of the grid's."""
        J = len(trace["arrival"])
        S = len(self.nodes)
        self.cap64 = np.asarray(self.nodes, np.float64)
        self.sites = [_Site(n) for n in self.nodes]
        self.comp = np.empty(S)
        self.dirty = set(range(S))
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
        t0 = min((j.arrival for j in jobs), default=0.0)
        self.peers = [_Peer(sites, S, t0) for sites in self.peer_sites]
        self.measured = [(0.0, 0.0, 0)] * S
        rounds = 0
        for j in jobs:
            self._push(j.arrival, "arrive", j)
        pending = J
        if J:
            self._push(t0 + self.interval, "migrate", None)
            self._push(t0 + self.exchange, "exchange", None)
        while self.events:
            now, _, kind, payload = heapq.heappop(self.events)
            if kind == "arrive":
                pending -= 1
                j = payload
                s = int(np.argmin(self._view_costs(j)))
                self.exec_site[j.idx] = s
                j.submit, j.seq = now, admitted
                admitted += 1
                self._submit(s, j, now)
                self._dispatch(s, now)
                self._guess(j, s)
            elif kind == "finish":
                s, j = payload
                site = self.sites[s]
                site.busy -= 1
                site.running_work -= j.work
                self.dirty.add(s)
                self._dispatch(s, now)
            elif kind == "deliver":
                t, rows, full_sync = payload
                self._deliver(t, rows, full_sync)
            else:
                if kind == "migrate":
                    self._migrate(now)
                else:
                    rounds += 1
                    full_sync = (rounds - 1) % self.full_sync_every == 0
                    self._push(now + self.latency, "deliver", (now, self._measure(), full_sync))
                if pending or any(site.queue for site in self.sites):
                    step = self.interval if kind == "migrate" else self.exchange
                    self._push(now + step, kind, None)
        return {
            "exec_site": self.exec_site, "start": self.start, "finish": self.finish,
            "migrated": np.asarray([j.migrated for j in jobs]),
        }

"""MONARC-style discrete-event grid simulator (paper §XI test-bed).

Five policies are simulated over the same event stream:

  'diana'   — §IV/§V cost-based placement + §X multilevel feedback
              queues + §IX congestion-driven migration
  'greedy'  — submit to the resource with most free slots, no global
              cost view (the strawman in §I)
  'local'   — always run at the submission site, move data to the job
              (MyGrid-style, §III)
  'fcfs'    — one central FCFS queue over all sites (EGEE-WMS-like
              baseline used for comparison in §XI)

Each site has N single-job nodes (§II: a subjob uses one CPU). A job's
wall time on a node = pure work + input fetch (if the dataset is
remote) + output return (if the user is remote) — exactly the cost
structure DIANA optimizes and the baselines ignore.
"""
from __future__ import annotations

import heapq
import itertools
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import Optional

import numpy as np

from repro.core import (
    CostWeights,
    Job,
    JobPack,
    MultilevelFeedbackQueues,
    NetworkLink,
    PeerView,
    SitePack,
    SiteState,
    computation_cost,
    network_cost,
    select_peer,
)
from repro.core import trace
from repro.core.batch import comp_site_column
from repro.core.bulk import stable_user_peer
from repro.core.costs import computation_cost_of
from repro.core.migration import (
    MigrationDecision,
    apply_migration,
    select_peer_targets,
)
from repro.core.p2p import GossipExchange, PeerScheduler
from repro.core.topology import GridTopology
from .config import _ALL_FIELDS, _BASE_FIELDS, SimConfig, resolve_config
from .streaming import StreamStats, _ArrivalCursor, as_arrival_source
from .workloads import SimJob

__all__ = ["GridSim", "P2PGridSim", "SimConfig", "SimResult", "uniform_links"]


def uniform_links(
    sites: list[str],
    bandwidth_Bps: float = 1e9,
    loss_rate: float = 0.001,
    local_bandwidth_Bps: float = 10e9,
) -> dict[tuple[str, str], NetworkLink]:
    links: dict[tuple[str, str], NetworkLink] = {}
    for a in sites:
        for b in sites:
            if a == b:
                links[(a, b)] = NetworkLink(bandwidth_Bps=local_bandwidth_Bps, loss_rate=0.0)
            else:
                links[(a, b)] = NetworkLink(bandwidth_Bps=bandwidth_Bps, loss_rate=loss_rate)
    return links


@dataclass
class SimResult:
    """One simulation run's outcome — the same type for every entry
    point. ``jobs`` is the caller's list for ``run(list)`` and the
    (usually empty, see ``SimConfig.retain_jobs``) collected list for
    streaming ``ArrivalSource`` runs; ``stats`` is always populated
    with the bounded streaming accumulators, so averages, percentiles
    and makespan survive even when no per-job records are retained."""

    jobs: list[SimJob]
    # site → time-bucket → counters (Fig 9/10/11 series)
    timeline: dict[str, dict[str, list[int]]]
    bucket_s: float
    policy: str
    stats: Optional[StreamStats] = None

    @property
    def avg_queue_time(self) -> float:
        done = [j for j in self.jobs if j.finish >= 0]
        if done:
            return float(np.mean([j.queue_time for j in done]))
        return self.stats.queue_times.mean if self.stats else 0.0

    @property
    def avg_exec_time(self) -> float:
        done = [j for j in self.jobs if j.finish >= 0]
        if done:
            return float(np.mean([j.exec_time for j in done]))
        return self.stats.exec_times.mean if self.stats else 0.0

    @property
    def avg_turnaround(self) -> float:
        done = [j for j in self.jobs if j.finish >= 0]
        if done:
            return float(np.mean([j.turnaround for j in done]))
        return self.stats.turnarounds.mean if self.stats else 0.0

    @property
    def makespan(self) -> float:
        done = [j.finish for j in self.jobs if j.finish >= 0]
        if done:
            return max(done)
        return self.stats.last_finish if self.stats else 0.0

    @property
    def finished(self) -> int:
        n = sum(1 for j in self.jobs if j.finish >= 0)
        if n == 0 and self.stats is not None:
            return self.stats.finished
        return n

    @property
    def throughput(self) -> float:
        m = self.makespan
        return self.finished / m if m > 0 else 0.0

    def migrations(self) -> int:
        n = sum(1 for j in self.jobs if j.migrated)
        if n == 0 and self.stats is not None:
            return self.stats.migrated
        return n

    # -- streaming-safe percentiles (satellite: bounded accumulators) -----
    def queue_time_percentiles(self, qs=(0.5, 0.95, 0.99)) -> list[float]:
        """p50/p95/p99 (by default) queue time from the bounded
        histogram accumulators — available even for million-job
        streaming runs that retained no per-job records."""
        if self.stats is not None and self.stats.finished:
            return [self.stats.queue_times.quantile(q) for q in qs]
        done = [j.queue_time for j in self.jobs if j.finish >= 0]
        return [float(np.quantile(done, q)) for q in qs] if done else [0.0] * len(qs)

    def turnaround_percentiles(self, qs=(0.5, 0.95, 0.99)) -> list[float]:
        if self.stats is not None and self.stats.finished:
            return [self.stats.turnarounds.quantile(q) for q in qs]
        done = [j.turnaround for j in self.jobs if j.finish >= 0]
        return [float(np.quantile(done, q)) for q in qs] if done else [0.0] * len(qs)


class _Site:
    def __init__(self, name: str, nodes: int, quotas: dict[str, float], use_mlfq: bool):
        self.name = name
        self.nodes = nodes
        self.busy = 0
        self.use_mlfq = use_mlfq
        self.mlfq = MultilevelFeedbackQueues(quotas=dict(quotas))
        self.fifo: list[Job] = []
        self.running_work = 0.0
        self.alive = True
        # job_id → Job for every job currently executing here, in
        # dispatch order — a site_down fault kills exactly these.
        self.running: dict[int, Job] = {}

    # queue ops ------------------------------------------------------------
    def enqueue(self, cj: Job, now: float) -> None:
        if self.use_mlfq:
            self.mlfq.submit(cj, now=now)
        else:
            self.fifo.append(cj)

    def pop(self, now: float) -> Optional[Job]:
        if self.use_mlfq:
            return self.mlfq.pop_next(now=now)
        return self.fifo.pop(0) if self.fifo else None

    def queue_len(self) -> int:
        return len(self.mlfq) if self.use_mlfq else len(self.fifo)

    def queued_work(self) -> float:
        if self.use_mlfq:
            return self.mlfq.queued_work()
        return sum(j.compute_work for j in self.fifo)

    def state(self) -> SiteState:
        return SiteState(
            name=self.name,
            capacity=float(self.nodes),
            queue_length=float(self.queue_len()),
            waiting_work=self.queued_work() + self.running_work,
            load=self.busy / self.nodes,
            alive=self.alive,
            free_slots=float(self.nodes - self.busy),
        )


class GridSim:
    """Deterministic event-driven simulation of one policy over a grid."""

    # LRU bound on the memoized static cost rows (~4 KB/entry at S=256):
    # arrival batches insert once-used rows; only queued migration
    # candidates re-hit, and evicted rows rebuild vectorized next tick.
    # Per-instance the bound adapts to the site count (rows are O(S)
    # each) so a 1k-site streaming run caps the cache near 128 MB.
    _STATIC_CACHE_MAX = 16_384

    #: SimConfig fields this class accepts as legacy keyword arguments.
    _LEGACY_FIELDS = _BASE_FIELDS

    def __init__(
        self,
        site_nodes: dict[str, int],
        links: Optional[dict[tuple[str, str], NetworkLink]] = None,
        config: Optional[SimConfig] = None,
        **kw,
    ):
        cfg = resolve_config(config, kw, self._LEGACY_FIELDS, type(self).__name__)
        assert cfg.policy in ("diana", "greedy", "local", "fcfs")
        self.config = cfg
        policy = self.policy = cfg.policy
        self._loss: Optional[np.ndarray] = None  # built on first batch
        self._dense_failed = False               # partial table: don't retry
        # job-signature → (net, dtc) static cost rows (see _static_cost_rows)
        self._static_row_cache: dict[tuple, tuple[np.ndarray, np.ndarray]] = {}
        S = max(1, len(site_nodes))
        self._static_cache_max = min(
            self._STATIC_CACHE_MAX, max(256, int(128e6 / (16 * S)))
        )
        self.links = links or uniform_links(list(site_nodes))
        self.quotas = cfg.quotas or {}
        self.weights = cfg.weights
        self.migration_interval_s = cfg.migration_interval_s
        self.congestion_window_s = cfg.congestion_window_s
        self.bucket_s = cfg.bucket_s
        self.batch_arrivals = cfg.batch_arrivals
        self._batch_arrivals_auto_disabled = False
        self.batch_migration = cfg.batch_migration
        self.sites = {
            name: _Site(name, n, self.quotas, use_mlfq=(policy == "diana"))
            for name, n in site_nodes.items()
        }
        self._queued_hint: Optional[_Site] = None  # see _queued_anywhere
        self.central_fifo: deque[Job] = deque()  # fcfs policy only
        self._cj2sj: dict[int, SimJob] = {}
        self._seq = itertools.count()
        self.timeline: dict[str, dict[str, list[int]]] = {
            s: {"submitted": [], "executed": [], "exported": [],
                "imported": [], "requeued": []}
            for s in self.sites
        }
        # Columns in sorted-name order: np.argmin's first-index tie-break
        # then matches choose_site's (cost, name) tuple sort exactly.
        self._names_sorted = sorted(self.sites)
        self._site_idx = {n: i for i, n in enumerate(self._names_sorted)}
        # Migration evaluates peers in sites-dict order (the sequential
        # PeerView list order), not sorted order: _dict_perm maps dict
        # position → sorted column so the (J, S) planes can be permuted
        # into the order select_peer's stable min walks.
        self._dict_names = list(self.sites)
        self._dict_perm = np.asarray(
            [self._site_idx[n] for n in self._dict_names], np.int64
        )
        self._dict_pos = {n: i for i, n in enumerate(self._dict_names)}
        self._sp: Optional[SitePack] = None        # reused migration SitePack
        self._sp_dirty: Optional[set[str]] = None  # cols to re-read next tick
        self._mig_prio_cache: dict[str, np.ndarray] = {}
        # Per-site computation-cost value cache (see _comp_base_vec):
        # recomputed from the site's scalars on demand for dirtied
        # columns only, bit-identical to full recomputation.
        self._cap_vec = np.asarray(
            [float(self.sites[n].nodes) for n in self._names_sorted]
        )
        # Fault-injection state (SimConfig.fault_plan). _alive_vec
        # mirrors the per-site alive bits in sorted-column order;
        # _dead counts down sites so the zero-fault fast paths stay
        # exactly the pre-fault code. _run_token invalidates pending
        # completion events of killed jobs without heap surgery: each
        # dispatch stamps a fresh token into the finish payload and a
        # popped finish whose token is stale is simply dropped.
        self._alive_vec = np.ones(len(self._names_sorted), bool)
        self._dead = 0
        self._run_token: dict[int, int] = {}
        self._token_seq = itertools.count()
        self._comp_base: Optional[np.ndarray] = None
        self._comp_dirty: Optional[set[int]] = None  # columns to recompute
        self._stats: Optional[StreamStats] = None   # active run's accumulators
        self._collect: Optional[list[SimJob]] = None

    # -- link-table lifecycle -------------------------------------------------
    @property
    def links(self) -> dict[tuple[str, str], NetworkLink]:
        return self._links

    @links.setter
    def links(self, value: dict[tuple[str, str], NetworkLink]) -> None:
        self._links = value
        # A new table is its own pristine state: link faults snapshot
        # lazily on first degradation (see _apply_link_fault).
        self._pristine_links = None
        self.invalidate_links()

    def invalidate_links(self) -> None:
        """Drop every plane derived from the link table (the dense WAN
        matrices and the memoized static cost rows). Call after mutating
        ``links`` in place; assigning a new table does it automatically.
        A fast path disabled by an earlier partial table gets another
        chance against the new one."""
        self._loss = None
        self._bw = self._eff = None
        self._static_row_cache.clear()
        self._dense_failed = False
        # Re-enable the arrival fast path only if the old table's
        # partialness disabled it (never override a user's own setting).
        if getattr(self, "_batch_arrivals_auto_disabled", False):
            self._batch_arrivals_auto_disabled = False
            self.batch_arrivals = True

    def _link_matrices_ready(self) -> bool:
        """Build the dense WAN-link matrices for the arrival-batch fast
        path on first use. A partial link table (only the pairs the
        sequential path happens to traverse) can't be densified — then
        the fast path is disabled and arrivals fall back to the
        sequential handler instead of crashing previously-valid setups."""
        if self._loss is not None:
            return True
        if self._dense_failed:          # known-partial: don't rescan S²
            return False
        S = len(self._names_sorted)
        loss = np.empty((S, S))
        bw = np.empty((S, S))
        eff = np.empty((S, S))
        try:
            for a, na in enumerate(self._names_sorted):
                for b, nb in enumerate(self._names_sorted):
                    link = self.links[(na, nb)]
                    loss[a, b] = link.loss_rate
                    bw[a, b] = link.bandwidth_Bps
                    eff[a, b] = link.effective_bandwidth()
        except KeyError:
            if self.batch_arrivals:
                self.batch_arrivals = False
                self._batch_arrivals_auto_disabled = True
            self._dense_failed = True
            return False
        self._loss, self._bw, self._eff = loss, bw, eff
        return True

    # -- cost model (§IV on simulator state) --------------------------------
    def _eff_bw(self, a: str, b: str) -> float:
        return self.links[(a, b)].effective_bandwidth()

    def _static_terms(self, sj: SimJob, site: str) -> tuple[float, float]:
        """The job-constant §IV terms (net, dtc) of ``placement_cost``
        — the single scalar source of the formula (P2P placement swaps
        only the computation term, so it must share these)."""
        net = network_cost(self.links[(sj.origin_site, site)])
        dtc = 0.0
        if sj.data_site is not None and sj.data_site != site:
            dtc += sj.input_bytes / self._eff_bw(sj.data_site, site)
        if sj.origin_site != site:
            dtc += sj.output_bytes / self._eff_bw(site, sj.origin_site)
        return net, dtc

    def placement_cost(self, sj: SimJob, site: str) -> float:
        st = self.sites[site].state()
        net, dtc = self._static_terms(sj, site)
        comp = computation_cost(st, self.weights) + sj.work / st.capacity
        return net + comp + dtc

    def _service_seconds(self, sj: SimJob, site: str) -> float:
        dur = sj.work
        if sj.data_site is not None and sj.data_site != site:
            dur += sj.input_bytes / self._eff_bw(sj.data_site, site)
        if sj.origin_site != site:
            dur += sj.output_bytes / self._eff_bw(site, sj.origin_site)
        return dur

    # -- placement policies --------------------------------------------------
    def choose_site(self, sj: SimJob) -> str:
        if self.policy == "local":
            # Dead origin sites bounce in _admit (the job is redirected
            # through the §IX failover path, not silently re-homed).
            return sj.origin_site
        if self.policy == "greedy":
            pool = (
                [s for s in self.sites.values() if s.alive]
                if self._dead else self.sites.values()
            )
            if not pool:
                raise RuntimeError("no alive site available")
            return max(
                pool,
                key=lambda s: (s.nodes - s.busy - s.queue_len(), s.nodes),
            ).name
        # diana — §V: ascending total cost, first alive site.
        costs = sorted(
            (self.placement_cost(sj, name), name)
            for name in self.sites
            if not self._dead or self.sites[name].alive
        )
        if not costs:
            raise RuntimeError("no alive site available")
        return costs[0][1]

    # -- batched §IV evaluation (arrival-batch fast path) ---------------------
    def _batch_eligible(self, batch: list[SimJob]) -> bool:
        """The dense fast path needs a full link table AND every job
        endpoint to be a grid site; jobs whose data/origin lives on a
        link-table-only node (e.g. a storage element) go through the
        sequential handler, which indexes links by tuple directly."""
        if self.policy != "diana" or not self._link_matrices_ready():
            return False
        idx = self._site_idx
        return all(
            sj.origin_site in idx
            and (sj.data_site is None or sj.data_site in idx)
            for sj in batch
        )

    @staticmethod
    def _static_sig(sj: SimJob) -> tuple:
        """Memoization key for the per-job-constant (net, dtc) rows:
        everything ``placement_cost`` reads besides live site state."""
        return (sj.origin_site, sj.data_site, sj.input_bytes, sj.output_bytes)

    def _static_cost_rows(self, batch: list[SimJob]) -> tuple[np.ndarray, np.ndarray]:
        """(net, dtc) rows of ``placement_cost`` over sorted-site columns
        for a batch of jobs — the per-job-constant terms, memoized by job
        signature. Each row depends only on its own job (the vectorized
        evaluation is elementwise per row), so rows cached from earlier
        batches are bit-identical to recomputing them; the migration
        pass re-evaluates the same congested jobs every tick and hits
        the cache. ``invalidate_links`` clears it."""
        if not self._link_matrices_ready():
            raise KeyError("link table is partial; dense matrices unavailable")
        S = len(self._names_sorted)
        net = np.empty((len(batch), S))
        dtc = np.empty((len(batch), S))
        miss: list[SimJob] = []
        miss_rows: list[list[int]] = []
        pending: dict[tuple, int] = {}  # bulk bursts share one signature
        cache = self._static_row_cache
        for i, sj in enumerate(batch):
            sig = self._static_sig(sj)
            hit = cache.pop(sig, None)
            if hit is not None:
                cache[sig] = hit        # re-insert: LRU order via dict
                net[i], dtc[i] = hit
                continue
            k = pending.get(sig)
            if k is None:
                pending[sig] = len(miss)
                miss.append(sj)
                miss_rows.append([i])
            else:
                miss_rows[k].append(i)
        if miss:
            mnet, mdtc = self._compute_static_rows(miss)
            for k, rows in enumerate(miss_rows):
                row = (mnet[k].copy(), mdtc[k].copy())
                cache[self._static_sig(miss[k])] = row
                for i in rows:
                    net[i], dtc[i] = row
            while len(cache) > self._static_cache_max:
                cache.pop(next(iter(cache)))
        return net, dtc

    def _compute_static_rows(self, batch: list[SimJob]) -> tuple[np.ndarray, np.ndarray]:
        """Uncached (net, dtc) rows, vectorized over the dense WAN-link
        matrices."""
        S = len(self._names_sorted)
        o = np.asarray([self._site_idx[sj.origin_site] for sj in batch])
        net = (self._loss[o, :] / self._bw[o, :]) * 1.0e6
        cols = np.arange(S)[None, :]
        inb = np.asarray([sj.input_bytes for sj in batch])
        outb = np.asarray([sj.output_bytes for sj in batch])
        has_data = np.asarray([sj.data_site is not None for sj in batch])
        d = np.asarray(
            [self._site_idx[sj.data_site] if sj.data_site is not None else 0
             for sj in batch]
        )
        in_term = np.where(
            has_data[:, None] & (d[:, None] != cols),
            inb[:, None] / self._eff[d, :], 0.0,
        )
        out_term = np.where(
            o[:, None] != cols, outb[:, None] / self._eff[:, o].T, 0.0
        )
        return net, in_term + out_term

    def _dirty_site(self, name: str) -> None:
        """Invalidate the cached per-site derived values after any
        mutation of that site's queue/busy/running state. Every mutation
        path (_admit enqueue, _start, _on_finish, migration moves) calls
        this; the batch-vs-sequential equivalence suites double as
        invalidation-completeness tests."""
        dirty = self._comp_dirty
        if dirty is not None:
            dirty.add(self._site_idx[name])
        sd = self._sp_dirty
        if sd is not None:
            sd.add(name)

    def _comp_base_vec(self) -> np.ndarray:
        """Per-site ``computation_cost(state())`` column over sorted-name
        order, value-cached with dirty invalidation.

        A touched site's entry is recomputed from its scalars
        (``computation_cost_of``), so each value is the exact float the
        sequential path's ``placement_cost`` computes. The queued work
        among them is a running total
        (``MultilevelFeedbackQueues.queued_work``), exact while every
        queued work is a non-negative whole number and the total below
        2**53: every partial sum in any order is then an exact integer.
        Otherwise it is the in-order sum over the queue."""
        base, dirty = self._comp_base, self._comp_dirty
        if base is None:
            S = len(self._names_sorted)
            base = self._comp_base = np.empty(S)
            dirty = self._comp_dirty = set(range(S))
        if dirty:
            sites, names = self.sites, self._names_sorted
            for i in dirty:
                s = sites[names[i]]
                base[i] = computation_cost_of(
                    float(s.queue_len()), s.queued_work() + s.running_work,
                    s.busy / s.nodes, float(s.nodes), self.weights,
                )
            dirty.clear()
        return base

    def _comp_vec(self, sj: SimJob) -> np.ndarray:
        """Live computation-cost column (the only term arrivals mutate):
        the dirty-cached per-site base plus this job's work/capacity
        row — elementwise the same two-term addition as the sequential
        path's ``placement_cost`` (bit-identical)."""
        out = self._comp_base_vec() + sj.work / self._cap_vec
        if self._dead:
            # Poison dead columns: +inf propagates through the cost
            # sum, so argmin lands on the cheapest alive site — the
            # same site the filtered sequential sort selects.
            out = np.where(self._alive_vec, out, np.inf)
        return out

    def choose_sites_batch(self, batch: list[SimJob]) -> list[str]:
        """Vectorized ``choose_site`` over a batch against the current
        state snapshot (no admissions in between) — equivalent to
        ``[self.choose_site(sj) for sj in batch]`` with untouched state.
        The event loop's fast path (``_on_arrive_batch``) interleaves
        the same evaluation with admissions instead."""
        if not self._batch_eligible(batch):
            return [self.choose_site(sj) for sj in batch]
        net, dtc = self._static_cost_rows(batch)
        # State is frozen here, so the job-independent computation base
        # is computed once; adding sj.work/cap per row keeps the same
        # two-term addition as placement_cost (bit-identical).
        base = np.asarray(
            [computation_cost(self.sites[n].state(), self.weights)
             for n in self._names_sorted]
        )
        if self._dead:
            base = np.where(self._alive_vec, base, np.inf)
        cap = np.asarray([float(self.sites[n].nodes) for n in self._names_sorted])
        return [
            self._names_sorted[int(np.argmin((net[i] + (base + sj.work / cap)) + dtc[i]))]
            for i, sj in enumerate(batch)
        ]

    # -- simulation ------------------------------------------------------------
    def run(self, jobs, until: Optional[float] = None) -> SimResult:
        """Simulate one workload to completion (or ``until``).

        ``jobs`` is either a materialized ``list[SimJob]`` (the classic
        entry point — the returned ``SimResult.jobs`` is that same
        list) or any lazy ``ArrivalSource`` (an object with
        ``chunks()``), in which case jobs are generated, placed and
        retired incrementally with bounded in-flight state and the
        result carries only the streaming accumulators (unless
        ``SimConfig.retain_jobs``). Both entry points and both loop
        implementations (``horizon`` on/off) produce bit-identical
        results on the same workload.
        """
        with trace.span("diana.sim.run"):
            source = as_arrival_source(jobs)
            input_list = jobs if isinstance(jobs, list) else None
            horizon_t = until if until is not None else float("inf")
            plan = self.config.fault_plan
            if plan is not None:
                plan.validate(
                    sites=set(self.sites),
                    num_peers=getattr(self, "num_peers", None),
                )
            # Every run replays its fault plan from a clean slate (and a
            # previous truncated run must not leak liveness/link damage
            # into a plain re-run either).
            self._reset_faults()
            self._stats = StreamStats()
            # Derived-value caches never survive into a run: the caller may
            # have mutated site state between runs.
            self._comp_base = self._comp_dirty = None
            self._sp = None
            self._sp_dirty = None
            self._collect = [] if input_list is None and self.config.retain_jobs else None
            cursor = _ArrivalCursor(source.chunks())
            self._on_stream_start(cursor.peek_time())
            if self.config.horizon:
                self._run_horizon(cursor, horizon_t)
                out_jobs = input_list if input_list is not None else (self._collect or [])
            else:
                materialized = input_list if input_list is not None else cursor.drain()
                self._run_events(materialized, horizon_t)
                out_jobs = materialized if (
                    input_list is not None or self.config.retain_jobs
                ) else []
            stats, self._stats, self._collect = self._stats, None, None
        return SimResult(
            jobs=out_jobs, timeline=self.timeline, bucket_s=self.bucket_s,
            policy=self.policy, stats=stats,
        )

    def _on_stream_start(self, t0: float) -> None:
        """Hook invoked once per run with the first arrival timestamp
        (``inf`` for an empty workload) — P2PGridSim seeds its peers'
        bootstrap stamps here."""

    def _run_events(self, jobs: list[SimJob], horizon: float) -> None:
        """The per-event reference loop: one heap pop per event, exactly
        the pre-horizon semantics. Arrivals are heap-seeded up front
        (their seqs are the lowest, so at equal timestamps arrivals
        always precede completions/migration/exchange)."""
        events: list[tuple[float, int, str, object]] = []
        for sj in jobs:
            heapq.heappush(events, (sj.arrival, next(self._seq), "arrive", sj))
        self._seed_faults(events)
        if self.policy == "diana" and jobs:
            t0 = min(j.arrival for j in jobs)
            heapq.heappush(
                events,
                (t0 + self.migration_interval_s, next(self._seq), "migrate", None),
            )
            if getattr(self, "exchange_interval_s", None):
                heapq.heappush(
                    events,
                    (t0 + self.exchange_interval_s, next(self._seq), "exchange", None),
                )

        while events:
            now, _, kind, payload = heapq.heappop(events)
            if now > horizon:
                break
            if kind == "arrive":
                # Same-instant arrivals pop consecutively (their seqs are
                # the lowest at that timestamp), so draining them here is
                # order-identical to one-at-a-time processing.
                with trace.span("diana.sim.arrive"):
                    if self.batch_arrivals and self.policy == "diana":
                        batch = [payload]
                        while events and events[0][0] == now and events[0][2] == "arrive":
                            batch.append(heapq.heappop(events)[3])
                        if len(batch) > 1 and self._batch_eligible(batch):
                            self._on_arrive_batch(batch, now, events)
                        else:
                            for sj in batch:
                                self._on_arrive(sj, now, events)
                    else:
                        self._on_arrive(payload, now, events)
            elif kind == "finish":
                site_name, cj, tok = payload
                self._on_finish(site_name, cj, tok, now, events)
            elif kind == "fault":
                self._on_fault(payload, now, events)
            elif kind == "migrate":
                self._on_migrate_check(now, events)
                if self._work_remaining(events):
                    heapq.heappush(
                        events,
                        (now + self.migration_interval_s, next(self._seq), "migrate", None),
                    )
            elif kind == "exchange":
                # Multi-scheduler mode only (P2PGridSim): a peer
                # advertisement round, rescheduled while work remains
                # (in-flight adverts drain via "deliver" events, so they
                # must NOT keep the exchange alive — each round sends
                # new ones and the sim would never terminate).
                self._on_exchange(now, events)
                if self._work_remaining(events):
                    heapq.heappush(
                        events,
                        (now + self.exchange_interval_s, next(self._seq), "exchange", None),
                    )
            elif kind == "deliver":
                self._on_deliver(now, events)

    def _run_horizon(self, cursor: _ArrivalCursor, horizon: float) -> None:
        """The batched event-horizon loop.

        Arrivals live in the lazy ``cursor`` (never in the heap — a 1M
        job stream costs no heap memory); the heap holds only
        completions and the periodic migrate/exchange/deliver events.
        Each iteration advances to ``min(next arrival, heap top)``:

        * arrivals first at equal timestamps (in the per-event loop
          every arrival's seq is lower than any later-pushed event's),
          draining the whole same-instant run — or, with
          ``horizon_eps_s``, the whole epsilon window — into one
          ``_on_arrive_batch`` (J, S) pass;
        * consecutive same-instant completions drain in one heap pass
          (strictly in seq order — each finish still applies its own
          bookkeeping + dispatch so float op order matches the
          reference loop bit-for-bit);
        * migrate/exchange/deliver behave exactly as in the per-event
          loop, with "arrivals still to come" read from the cursor.

        With ``horizon_eps_s == 0`` the schedule is bit-identical to
        ``_run_events`` (equivalence-tested for GridSim and P2PGridSim).
        """
        inf = float("inf")
        eps = float(self.config.horizon_eps_s)
        events: list[tuple[float, int, str, object]] = []
        # Fault events are seeded up front in both loops, so their seqs
        # are below every runtime-pushed finish: at equal timestamps a
        # fault pops before the finishes it is about to invalidate —
        # identically here and in the reference loop (the same-instant
        # finish drain below stops when a fault reaches the heap top).
        self._seed_faults(events)
        t0 = cursor.peek_time()
        if self.policy == "diana" and t0 != inf:
            heapq.heappush(
                events,
                (t0 + self.migration_interval_s, next(self._seq), "migrate", None),
            )
            if getattr(self, "exchange_interval_s", None):
                heapq.heappush(
                    events,
                    (t0 + self.exchange_interval_s, next(self._seq), "exchange", None),
                )

        while True:
            ta = cursor.peek_time()
            te = events[0][0] if events else inf
            now = min(ta, te)
            if now == inf or now > horizon:
                break
            if ta <= te:
                hi = min(ta + eps, horizon) if eps > 0.0 else ta
                self._process_arrivals(cursor.pop_until(hi), ta, events)
                continue
            now, _, kind, payload = heapq.heappop(events)
            if kind == "finish":
                site_name, cj, tok = payload
                self._on_finish(site_name, cj, tok, now, events)
                # Drain the consecutive same-instant completion run
                # (bulk bursts finish together) without bouncing through
                # the cursor comparison per event. Strictly in heap
                # order: a zero-duration dispatch can push a new finish
                # at `now`, and an interleaved migrate/exchange/fault
                # event ends the run exactly as it would end the pop
                # sequence.
                while events and events[0][0] == now and events[0][2] == "finish":
                    _, _, _, (sn, fcj, ftok) = heapq.heappop(events)
                    self._on_finish(sn, fcj, ftok, now, events)
            elif kind == "fault":
                self._on_fault(payload, now, events)
            elif kind == "migrate":
                self._on_migrate_check(now, events)
                if self._stream_work_remaining(cursor):
                    heapq.heappush(
                        events,
                        (now + self.migration_interval_s, next(self._seq), "migrate", None),
                    )
            elif kind == "exchange":
                self._on_exchange(now, events)
                if self._stream_work_remaining(cursor):
                    heapq.heappush(
                        events,
                        (now + self.exchange_interval_s, next(self._seq), "exchange", None),
                    )
            elif kind == "deliver":
                self._on_deliver(now, events)

    def _process_arrivals(self, batch: list[SimJob], now: float, events: list) -> None:
        """Admit one drained arrival batch (same-instant, or one eps
        window). Unlike the per-event loop, eligible single-job batches
        also take the vectorized path — it is bit-identical to
        ``choose_site`` per row, and open-loop Poisson streams are
        almost entirely single arrivals."""
        if not batch:
            return
        with trace.span("diana.sim.arrive"):
            if (
                self.batch_arrivals
                and self.policy == "diana"
                and self._batch_eligible(batch)
            ):
                self._on_arrive_batch(batch, now, events)
            else:
                for sj in batch:
                    self._on_arrive(sj, now, events)

    def _work_remaining(self, events: list) -> bool:
        """Whether the periodic events (migrate/exchange) should keep
        rescheduling: queued jobs anywhere, or arrivals still to come.
        One predicate for both so they always stop together."""
        return self._queued_anywhere() or any(e[2] == "arrive" for e in events)

    def _stream_work_remaining(self, cursor: _ArrivalCursor) -> bool:
        """``_work_remaining`` for the horizon loop: pending arrivals
        live in the cursor, not the heap. Equivalent predicate — in
        both loops an arrival pending at decision time is strictly in
        the future."""
        return self._queued_anywhere() or cursor.peek_time() != float("inf")

    def _queued_anywhere(self) -> bool:
        """Whether any site has a queued job, asking first the site that
        had one last time (a long queue answers for many periods)."""
        site = self._queued_hint
        if site is not None and site.queue_len():
            return True
        for site in self.sites.values():
            if site.queue_len():
                self._queued_hint = site
                return True
        return False

    # -- multi-scheduler hooks (no-ops in the omniscient base sim) -----------
    #: §IX trust horizon: peers whose advertised rows are older than this
    #: are not polled for migration (P2PGridSim overrides the staleness).
    migration_max_staleness_s = float("inf")

    def _on_exchange(self, now: float, events: list) -> None:
        """Peer advertisement round (P2PGridSim)."""

    def _on_deliver(self, now: float, events: list) -> None:
        """Latency-delayed advert delivery (P2PGridSim)."""

    def _migration_staleness(self, name: str, now: float) -> Optional[np.ndarray]:
        """Per-column (sorted-name order) age of the deciding
        scheduler's world view; None = omniscient (zero staleness)."""
        return None

    # -- handlers ------------------------------------------------------------
    def _bucket(self, site: str, key: str, now: float) -> None:
        series = self.timeline[site][key]
        idx = int(now / self.bucket_s)
        while len(series) <= idx:
            series.append(0)
        series[idx] += 1

    def _on_arrive(self, sj: SimJob, now: float, events: list) -> None:
        self._admit(sj, self.choose_site(sj), now, events)

    def _on_arrive_batch(self, batch: list[SimJob], now: float, events: list) -> None:
        """Arrival-batch fast path (§VIII bulk bursts): the static
        network + data-transfer planes are evaluated once for the whole
        same-instant batch; per job only the computation term is
        re-read from live site state, so placements are bit-identical
        to sequential ``_on_arrive`` calls."""
        net, dtc = self._static_cost_rows(batch)
        for i, sj in enumerate(batch):
            row = (net[i] + self._comp_vec(sj)) + dtc[i]
            self._admit(sj, self._names_sorted[int(np.argmin(row))], now, events)

    def _admit(self, sj: SimJob, target: str, now: float, events: list) -> str:
        if self.policy != "fcfs" and not self.sites[target].alive:
            # A stale-view submission (P2P) or dead-origin local job
            # aimed at a down site: the authoritative grid bounces it
            # to the cheapest alive site. Returns the final target so
            # the caller's optimistic bookkeeping follows the job.
            target = self._failover_target(sj)
            sj.requeues += 1
            if self._stats is not None:
                self._stats.on_redirect()
        sj.exec_site = target
        sj.queue_enter = now
        cj = Job(
            user=sj.user, t=sj.t, submit_time=now, compute_work=sj.work,
            input_bytes=sj.input_bytes, output_bytes=sj.output_bytes,
            group_id=sj.group_id,
        )
        self._cj2sj[cj.job_id] = sj
        if self._stats is not None:
            self._stats.on_admit(sj, len(self._cj2sj))
        if self._collect is not None:
            self._collect.append(sj)
        self._bucket(target, "submitted", now)
        if self.policy == "fcfs":
            self.central_fifo.append(cj)
            self._dispatch_central(now, events)
        else:
            self.sites[target].enqueue(cj, now)
            self._dirty_site(target)
            self._dispatch(target, now, events)
        return target

    def _start(self, site: _Site, cj: Job, now: float, events: list) -> None:
        sj = self._cj2sj[cj.job_id]
        sj.start = now
        dur = self._service_seconds(sj, site.name)
        sj.finish = now + dur
        site.busy += 1
        site.running_work += sj.work
        site.running[cj.job_id] = cj
        tok = next(self._token_seq)
        self._run_token[cj.job_id] = tok
        self._dirty_site(site.name)
        heapq.heappush(
            events, (sj.finish, next(self._seq), "finish", (site.name, cj, tok))
        )

    def _dispatch(self, site_name: str, now: float, events: list) -> None:
        site = self.sites[site_name]
        if not site.alive:
            return
        while site.busy < site.nodes:
            cj = site.pop(now)
            if cj is None:
                return
            self._start(site, cj, now, events)

    def _dispatch_central(self, now: float, events: list) -> None:
        while self.central_fifo:
            free = [s for s in self.sites.values() if s.alive and s.busy < s.nodes]
            if not free:
                return
            cj = self.central_fifo.popleft()
            site = free[0]
            self._cj2sj[cj.job_id].exec_site = site.name
            self._start(site, cj, now, events)

    def _on_finish(
        self, site_name: str, cj: Job, tok: int, now: float, events: list
    ) -> None:
        if self._run_token.get(cj.job_id) != tok:
            # Stale completion: the job's site died and the job was
            # requeued (and possibly redispatched with a fresh token)
            # after this event was scheduled. Drop it.
            return
        del self._run_token[cj.job_id]
        site = self.sites[site_name]
        if not site.alive:
            raise AssertionError(
                f"job {cj.job_id} completed on dead site {site_name!r} — "
                f"fault bookkeeping failed to invalidate its finish event"
            )
        site.busy -= 1
        site.running_work -= cj.compute_work
        site.running.pop(cj.job_id, None)
        self._dirty_site(site_name)
        self._bucket(site_name, "executed", now)
        self._finalize(cj)
        if self.policy == "fcfs":
            self._dispatch_central(now, events)
        else:
            self._dispatch(site_name, now, events)

    def _finalize(self, cj: Job) -> None:
        """Retire one completed job: feed the streaming accumulators
        and drop its in-flight mapping (bounded state — no reference
        to a finished job's Job/SimJob pair survives unless the caller
        holds the list)."""
        sj = self._cj2sj.pop(cj.job_id, None)
        if sj is not None and self._stats is not None:
            self._stats.on_finish(sj)

    # -- fault injection (SimConfig.fault_plan) -------------------------------
    def _seed_faults(self, events: list) -> None:
        """Push the plan's events into the heap before any runtime
        event allocates a seq: at equal timestamps faults then order
        after arrivals (whose seqs are lower still) and before every
        finish/migrate/exchange — identically in both run loops."""
        plan = self.config.fault_plan
        if plan is None:
            return
        for ev in plan.sorted_events():
            heapq.heappush(events, (ev.time, next(self._seq), "fault", ev))

    def _on_fault(self, ev, now: float, events: list) -> None:
        if ev.kind == "site_down":
            self._fail_site(ev.site, now, events)
        elif ev.kind == "site_up":
            self._recover_site(ev.site, now, events)
        elif ev.kind in ("link_degrade", "link_restore"):
            self._apply_link_fault(ev)
        else:
            # peer_leave/peer_join — P2PGridSim overrides; run() has
            # already validated plans, so this is a defensive backstop.
            raise ValueError(
                f"fault kind {ev.kind!r} requires the multi-scheduler "
                f"P2PGridSim"
            )

    def _failover_target(self, sj: SimJob) -> str:
        """Re-place one displaced/redirected job over the alive sites:
        greedy keeps its free-slot rule; every other policy takes the
        §IX route — cheapest alive site by the full §IV cost."""
        alive = [n for n in self.sites if self.sites[n].alive]
        if not alive:
            raise RuntimeError("no alive site available")
        if self.policy == "greedy":
            return max(
                (self.sites[n] for n in alive),
                key=lambda s: (s.nodes - s.busy - s.queue_len(), s.nodes),
            ).name
        return min((self.placement_cost(sj, n), n) for n in alive)[1]

    def _fail_site(self, name: str, now: float, events: list) -> None:
        site = self.sites[name]
        if not site.alive:
            return
        site.alive = False
        self._alive_vec[self._site_idx[name]] = False
        self._dead += 1
        # Kill running jobs (their pending finish events go stale via
        # the run-token check), then drain the queue; displaced jobs
        # re-enter placement in dispatch order then queue order.
        displaced: list[Job] = []
        for jid, cj in list(site.running.items()):
            del site.running[jid]
            self._run_token.pop(jid, None)
            site.busy -= 1
            site.running_work -= cj.compute_work
            sj = self._cj2sj[cj.job_id]
            sj.start = sj.finish = -1.0
            displaced.append(cj)
        if site.use_mlfq:
            for cj in list(site.mlfq.jobs):
                site.mlfq.remove(cj)
                displaced.append(cj)
        else:
            drained, site.fifo = site.fifo, []
            displaced.extend(drained)
        self._dirty_site(name)
        for cj in displaced:
            self._requeue(cj, name, now, events)

    def _requeue(self, cj: Job, from_site: str, now: float, events: list) -> None:
        """Re-place one job displaced by a site death — the §IX
        migration path over the alive sites (fcfs jobs simply rejoin
        the central queue). The job is NOT pinned: a genuine §IX
        migration later may still move it once."""
        sj = self._cj2sj[cj.job_id]
        sj.requeues += 1
        if self._stats is not None:
            self._stats.on_requeue()
        self._bucket(from_site, "requeued", now)
        if self.policy == "fcfs":
            self.central_fifo.append(cj)
            self._dispatch_central(now, events)
            return
        target = self._failover_target(sj)
        sj.exec_site = target
        self.sites[target].enqueue(cj, now)
        self._dirty_site(target)
        self._dispatch(target, now, events)

    def _recover_site(self, name: str, now: float, events: list) -> None:
        site = self.sites[name]
        if site.alive:
            return
        site.alive = True
        self._alive_vec[self._site_idx[name]] = True
        self._dead -= 1
        self._dirty_site(name)
        if self.policy == "fcfs":
            # The revived capacity may unblock the central queue; other
            # policies re-route at the next arrival/migration tick (the
            # site comes back with an empty queue).
            self._dispatch_central(now, events)

    def _apply_link_fault(self, ev) -> None:
        """Degrade (multiply bandwidth / add loss) or restore the
        matching directed links, then drop every derived cost plane.
        Degradations compose; restore returns to the pre-fault table."""
        if self._pristine_links is None:
            self._pristine_links = dict(self._links)
        if ev.pairs is not None:
            wanted = set(ev.pairs)
            match = wanted.__contains__
        else:
            match = lambda pair: ev.site in pair and pair[0] != pair[1]
        changed = False
        for pair, link in list(self._links.items()):
            if not match(pair):
                continue
            if ev.kind == "link_degrade":
                self._links[pair] = NetworkLink(
                    bandwidth_Bps=link.bandwidth_Bps * ev.bandwidth_factor,
                    loss_rate=min(0.999, link.loss_rate + ev.loss_add),
                    rtt_s=link.rtt_s,
                    mss_bytes=link.mss_bytes,
                )
            else:
                self._links[pair] = self._pristine_links.get(pair, link)
            changed = True
        if changed:
            self.invalidate_links()

    def _reset_faults(self) -> None:
        """Restore construction-time liveness and link state so every
        ``run()`` replays its plan from a clean slate."""
        if getattr(self, "_pristine_links", None) is not None:
            self.links = dict(self._pristine_links)  # setter invalidates
        for site in self.sites.values():
            site.alive = True
            site.running.clear()
        self._alive_vec[:] = True
        self._dead = 0
        self._run_token.clear()

    def _on_migrate_check(self, now: float, events: list) -> None:
        """§IX/§X: congested sites push Q4 jobs to cheaper peers.

        The batched engine evaluates each congested site's whole Q4
        candidate set as one (J, S) matrix pass; sites are still visited
        in sequence (an import mutates the target's queue, congestion
        window and Q4 membership, so a later site's candidate set
        genuinely depends on earlier sites' moves — a global upfront
        collection could not stay bit-identical)."""
        with trace.span("diana.sim.migrate"):
            batched = self.batch_migration and self.policy == "diana"
            if batched and not any(s.mlfq.jobs for s in self.sites.values()):
                return  # no queued job: no candidate anywhere
            batched = batched and self._link_matrices_ready()
            if not batched:
                for name, site in self.sites.items():
                    if (
                        site.use_mlfq
                        and site.alive
                        and site.mlfq.congested(self.congestion_window_s, now)
                    ):
                        self._migrate_site_sequential(name, site, now, events)
                return
            self._mig_prio_cache.clear()
            sp: Optional[SitePack] = None
            idx = self._site_idx
            for name, site in self.sites.items():
                # An empty queue has no Q4 candidate; skipping its rate
                # check changes nothing (the samples it would prune are
                # older than any window a later check counts).
                if not site.mlfq.jobs or not site.use_mlfq or not site.alive:
                    continue
                if not site.mlfq.congested(self.congestion_window_s, now):
                    continue
                cands = list(site.mlfq.low_priority_jobs())
                if not cands:
                    continue
                sjs = [self._cj2sj[cj.job_id] for cj in cands]
                if sp is None:
                    sp = self._site_pack()
                if not all(
                    sj.origin_site in idx
                    and (sj.data_site is None or sj.data_site in idx)
                    for sj in sjs
                ):
                    # Off-grid endpoints (e.g. a storage element) can't use
                    # the dense planes — fall back per job for this site and
                    # resync the packed state it mutated.
                    touched = self._migrate_site_sequential(name, site, now, events)
                    self._resync_pack(sp, touched)
                    continue
                self._migrate_site_batched(name, site, cands, sjs, sp, now, events)

    def _migrate_site_sequential(
        self, name: str, site: _Site, now: float, events: list
    ) -> set[str]:
        """The per-job §IX reference loop for one congested site.
        Returns the sites whose queues it mutated."""
        touched: set[str] = set()
        stale = self._migration_staleness(name, now)
        trusted = None
        if stale is not None:
            trusted = {
                n for n in self.sites
                if stale[self._site_idx[n]] <= self.migration_max_staleness_s
            }
        for cj in list(site.mlfq.low_priority_jobs()):
            sj = self._cj2sj[cj.job_id]
            peers = [
                PeerView(
                    name=p,
                    queue_length=self.sites[p].queue_len(),
                    jobs_ahead=self.sites[p].mlfq.jobs_ahead(cj.priority),
                    total_cost=self.placement_cost(sj, p),
                )
                for p in self.sites
                if p != name
                and self.sites[p].alive
                and (trusted is None or p in trusted)
            ]
            decision = select_peer(
                cj, name,
                site.mlfq.jobs_ahead(cj.priority),
                self.placement_cost(sj, name),
                peers,
            )
            if decision.migrate and decision.target:
                self._apply_migration_decision(name, site, cj, sj, decision, now, events)
                touched.update((name, decision.target))
        return touched

    def _apply_migration_decision(
        self,
        name: str,
        site: _Site,
        cj: Job,
        sj: SimJob,
        decision,
        now: float,
        events: list,
    ) -> None:
        """Commit one §IX move: export bookkeeping, enqueue at the
        target (which §X-reprioritizes it), dispatch."""
        site.mlfq.remove(cj)
        apply_migration(cj, decision)
        sj.migrated = True
        sj.exec_site = decision.target
        self._dirty_site(name)
        self._bucket(name, "exported", now)
        self._bucket(decision.target, "imported", now)
        self.sites[decision.target].enqueue(cj, now)
        self._dirty_site(decision.target)
        self._dispatch(decision.target, now, events)

    # -- batched §IX machinery ------------------------------------------------
    def _site_pack(self) -> SitePack:
        """Reused dense site-state pack (sorted-name columns). Built
        once; across event horizons only the columns dirtied since the
        last refresh are re-read (``_dirty_site`` marks them), so a
        mostly-idle 1k-site grid refreshes a handful of columns per
        migration tick instead of all S. Re-reading a column yields the
        identical floats a full refresh would, so the narrowing is
        bit-identical."""
        if self._sp is None:
            states = {n: self.sites[n].state() for n in self._names_sorted}
            links = {n: NetworkLink(bandwidth_Bps=1.0) for n in self._names_sorted}
            self._sp = SitePack.from_scheduler(states, links, order=self._names_sorted)
            self._sp_dirty = set()
        elif self._sp_dirty:
            names = sorted(self._sp_dirty)
            self._sp.refresh_from(
                lambda n: self.sites[n].state(), only=names
            )
            self._sp_dirty.clear()
        return self._sp

    def _resync_pack(self, sp: SitePack, touched: set[str]) -> None:
        """Re-read the packed dynamic columns (and drop cached priority
        arrays) for sites whose queues just changed."""
        if not touched:
            return
        for tn in touched:
            self._mig_prio_cache.pop(tn, None)
        sp.refresh_dynamic(
            {tn: self.sites[tn].state() for tn in touched}, only=list(touched)
        )
        if self._sp_dirty is not None:
            self._sp_dirty -= touched

    def _sorted_priorities(self, name: str) -> np.ndarray:
        """Ascending priority array of one site's queued jobs, cached
        per migration tick (invalidated for sites a move touches)."""
        arr = self._mig_prio_cache.get(name)
        if arr is None:
            arr = np.sort(
                np.asarray(
                    [j.priority for j in self.sites[name].mlfq.jobs], np.float64
                )
            )
            self._mig_prio_cache[name] = arr
        return arr

    def _jobs_ahead_column(self, name: str, cand_p: np.ndarray) -> np.ndarray:
        """Vectorized ``mlfq.jobs_ahead``: count of queued jobs at
        ``name`` with priority ≥ each candidate's priority."""
        spr = self._sorted_priorities(name)
        return len(spr) - np.searchsorted(spr, cand_p, side="left")

    def _migrate_site_batched(
        self,
        name: str,
        site: _Site,
        cands: list[Job],
        sjs: list[SimJob],
        sp: SitePack,
        now: float,
        events: list,
    ) -> None:
        """One congested site's §IX pass as a matrix program.

        All candidate × peer placement costs come from the memoized
        static (net, dtc) planes plus one dynamic computation column
        read from the reused SitePack; jobsAhead is a searchsorted per
        peer column. Decisions are taken by ``select_peers_batch`` and
        applied in candidate order; an applied move mutates exactly two
        sites (source and target), so only those two columns are
        re-read and the remaining rows re-decided — every decision is
        bit-identical to the sequential per-job loop."""
        R = len(cands)
        perm = self._dict_perm
        names = self._dict_names
        local_col = self._dict_pos[name]
        jp = JobPack.from_jobs(cands)
        work = jp.work                      # == [sj.work for sj in sjs]
        cand_p = np.asarray([cj.priority for cj in cands], np.float64)
        net, dtc = self._static_cost_rows(sjs)
        net_d, dtc_d = net[:, perm], dtc[:, perm]
        cap_d = sp.cap[perm]
        comp_d = comp_site_column(sp, self.weights)[perm]
        # placement_cost's exact op order: (net + (comp_site + w/cap)) + dtc
        cost = (net_d + (comp_d[None, :] + work[:, None] / cap_d[None, :])) + dtc_d
        ja = np.empty((R, len(names)))
        for s, pname in enumerate(names):
            ja[:, s] = self._jobs_ahead_column(pname, cand_p)
        pinned = np.asarray([cj.migrated for cj in cands], bool)
        excluded = np.asarray(
            [n == name or not self.sites[n].alive for n in names]
        )
        # P2P mode: only poll peers whose advertised rows are fresh
        # enough (sorted-order staleness permuted into dict order).
        stale = self._migration_staleness(name, now)
        stale_d = None if stale is None else stale[perm]
        migrate, best = select_peer_targets(
            pinned, ja[:, local_col], cost[:, local_col], excluded, ja, cost,
            staleness=stale_d, max_staleness=self.migration_max_staleness_s,
        )
        i = 0
        while i < R:
            rel = np.flatnonzero(migrate[i:])
            if rel.size == 0:
                break
            i += int(rel[0])
            c = int(best[i])
            target = names[c]
            d = MigrationDecision(
                True, target=target,
                reason="peer has fewer jobs ahead at lower cost"
                if cost[i, c] <= cost[i, local_col]
                else "peer has fewer jobs ahead",
            )
            self._apply_migration_decision(name, site, cands[i], sjs[i], d, now, events)
            # The move touched exactly {source, target}: re-read those
            # two columns and re-decide the remaining candidates.
            self._resync_pack(sp, {name, target})
            i += 1
            if i >= R:
                break
            comp = comp_site_column(sp, self.weights)
            for tn in (name, target):
                c = self._dict_pos[tn]
                sc = self._site_idx[tn]
                cost[:, c] = (net[:, sc] + (comp[sc] + work / sp.cap[sc])) + dtc[:, sc]
                ja[:, c] = self._jobs_ahead_column(tn, cand_p)
            rest = slice(i, R)
            migrate[rest], best[rest] = select_peer_targets(
                pinned[rest], ja[rest, local_col], cost[rest, local_col],
                excluded, ja[rest], cost[rest],
                staleness=stale_d, max_staleness=self.migration_max_staleness_s,
            )


def _stated_partition(peer_sites, names: list[str]) -> list[list[str]]:
    """The deployment's ownership, checked: one non-empty list of site
    names per peer, every site of the grid in exactly one list."""
    partition = [list(p) for p in peer_sites]
    empty = [k for k, p in enumerate(partition) if not p]
    if empty:
        raise ValueError(f"peer_sites: peer(s) {empty} own no site")
    seen = Counter(n for p in partition for n in p)
    twice = sorted(n for n, c in seen.items() if c > 1)
    unknown = sorted(set(seen) - set(names))
    missing = sorted(set(names) - set(seen))
    if twice or unknown or missing:
        raise ValueError(
            "peer_sites must hold every site exactly once: "
            f"twice {twice}, not in the grid {unknown}, missing {missing}"
        )
    return partition


class P2PGridSim(GridSim):
    """Multi-scheduler mode: the paper's decentralized deployment
    (§III/§IX) over the same event stream.

    Each ``PeerScheduler`` owns the sites the deployment states for it
    (``SimConfig.peer_sites``: one list per peer, its first site the
    peer's home, every site exactly once; e.g. one peer per RootGrid
    region, homed at the region's Tier-0 or Tier-1). Without a stated
    partition the sites are dealt round-robin in sorted-name order
    over ``num_peers`` peers. Each peer owns its partition's
    authoritative state and sees every other site only through the
    gossip exchange: every ``exchange_interval_s`` each peer
    re-measures its home rows and advertises its whole world view to
    its fan-out set (hierarchy-aware when a ``GridTopology`` is given);
    adverts arrive ``exchange_latency_s`` later. A job is placed by the
    peer owning its origin site, from that peer's — possibly stale —
    view of the remote queues; the owning site *reconciles* by simply
    enqueueing whatever arrives (its authoritative queue is ground
    truth, and the next exchange round propagates the correction).
    Placements the submitting peer makes onto remote sites bump its own
    view optimistically so its consecutive placements see each other.

    §IX migration stays a direct poll (queue lengths/jobsAhead come
    from the polled peer), but a congested site's scheduler only polls
    peers whose advertised rows are at most
    ``migration_max_staleness_s`` old (default: two exchange intervals
    plus the latency) — it doesn't trust, so it doesn't ask.

    ``num_peers=1`` with any exchange interval is the omniscient
    special case: every site is home, nothing is ever stale, and the
    event stream is bit-identical to the single-scheduler ``GridSim``.
    """

    #: P2PGridSim accepts the full SimConfig surface as legacy kwargs.
    _LEGACY_FIELDS = _ALL_FIELDS

    def __init__(
        self,
        site_nodes: dict[str, int],
        links: Optional[dict[tuple[str, str], NetworkLink]] = None,
        config: Optional[SimConfig] = None,
        **kw,
    ):
        cfg = resolve_config(config, kw, self._LEGACY_FIELDS, type(self).__name__)
        if cfg.policy != "diana":
            raise ValueError("multi-scheduler mode requires the 'diana' policy")
        if cfg.exchange_interval_s <= 0.0:
            raise ValueError(
                "exchange_interval_s must be > 0 (the run loop schedules "
                "exchange rounds at this period)"
            )
        super().__init__(site_nodes, links=links, config=cfg)
        self.exchange_interval_s = float(cfg.exchange_interval_s)
        self.exchange_latency_s = float(cfg.exchange_latency_s)
        migration_max_staleness_s = cfg.migration_max_staleness_s
        topology = cfg.topology
        gossip_fanout = cfg.gossip_fanout
        names = self._names_sorted
        if cfg.peer_sites is None:
            N = 3 if cfg.num_peers is None else int(cfg.num_peers)
            N = max(1, min(N, len(names)))
            partition = [names[i::N] for i in range(N)]
        else:
            partition = _stated_partition(cfg.peer_sites, names)
            N = len(partition)
            if cfg.num_peers is not None and cfg.num_peers != N:
                raise ValueError(
                    f"num_peers={cfg.num_peers} disagrees with peer_sites, "
                    f"which states {N} peers"
                )
        self.num_peers = N
        if migration_max_staleness_s is None:
            # Default trust horizon in rounds-behind: a freshly-heard
            # row is at most one relay hop old on a full mesh; with a
            # topology a cross-tier row travels owner → rep → rep →
            # member (~3 rounds); a fanout cap rotates the neighbor
            # list, so a given owner is heard only every
            # ceil(neighbors/fanout) rounds. Too tight a default would
            # permanently distrust peers and silently disable §IX
            # migration.
            hops = 3 if topology is not None else 1
            if gossip_fanout is not None and N > 1:
                rotation = -(-(N - 1) // max(1, int(gossip_fanout)))
                hops = max(hops, rotation)
            migration_max_staleness_s = (
                (1 + hops) * self.exchange_interval_s + self.exchange_latency_s
            )
        self.migration_max_staleness_s = float(migration_max_staleness_s)
        states = {n: self.sites[n].state() for n in names}
        # The event loop costs placements on the sim's pair-structured
        # planes and reads only the peers' dynamic (comp) columns, so
        # the peers' own link rows never influence the simulation. They
        # DO back the public PeerScheduler API (sim.peers[i].place_batch
        # / rank_sites_batch), so give each peer its paper-faithful
        # home-relative row of the real table; a partial table falls
        # back to a placeholder (the public cost planes are then
        # meaningless, like the sequential fallback paths).
        self.peers = []
        for home_sites in partition:
            home = home_sites[0]
            try:
                plinks = {n: self.links[(home, n)] for n in names}
            except KeyError:
                plinks = {n: NetworkLink(bandwidth_Bps=1.0) for n in names}
            self.peers.append(
                PeerScheduler(
                    home=home, sites=states, links=plinks,
                    weights=self.weights, home_sites=home_sites, order=names,
                )
            )
        self._peer_by_site = {}
        for p in self.peers:
            p.state_provider = lambda n: self.sites[n].state()
            # Per-job home refreshes re-read only the home columns the
            # simulation actually mutated since the last look (the
            # _dirty_site override below feeds the marks).
            p.enable_home_dirty_tracking()
            for n in p.home_names:
                self._peer_by_site[n] = p
        self.exchange = GossipExchange(
            self.peers, topology=topology,
            latency_s=self.exchange_latency_s, fanout=gossip_fanout,
            wire=cfg.gossip_wire, quant=cfg.gossip_quant,
            full_sync_every=cfg.gossip_full_sync_every,
            transport=cfg.transport_faults,
        )
        # peer index → the home partition it held when it left (churn
        # faults); handed back verbatim on rejoin.
        self._departed: dict[int, list[str]] = {}
        # Suspicion cache, refreshed at gossip activity points (the
        # placement/migration hooks have no exchange-time `now`, so
        # they read what the last exchange/deliver event derived):
        # peer index → suspect-column mask, plus the adaptive
        # max-staleness widening factor. Both stay at rest without a
        # transport model, leaving fault-free behavior untouched.
        self._peer_index = {id(p): i for i, p in enumerate(self.peers)}
        self._suspect_masks: dict[int, np.ndarray] = {}
        self._staleness_widen = 1.0

    def _on_stream_start(self, t0: float) -> None:
        # The construction-time view snapshot is the §IX join
        # protocol's initial full-state exchange — it happens at sim
        # start, so seed the stamp vectors at the first arrival (a
        # trace resuming at large t0 must not read the bootstrap as
        # hours-stale and distrust every peer until the first round).
        if t0 != float("inf"):
            for p in self.peers:
                np.maximum(p.stamp, t0, out=p.stamp)

    def _dirty_site(self, name: str) -> None:
        super()._dirty_site(name)
        p = getattr(self, "_peer_by_site", None)
        if p is not None:
            peer = p.get(name)
            if peer is not None:
                peer.mark_home_dirty(name)

    # -- routing ---------------------------------------------------------------
    def _submit_peer(self, sj: SimJob) -> PeerScheduler:
        """The scheduler a job enters the grid through: the peer owning
        its origin site; off-grid origins hash stably by user (the same
        rule group routing uses, so a user's jobs and groups agree)."""
        p = self._peer_by_site.get(sj.origin_site)
        if p is None:
            pool = self.peers
            if self._departed:
                pool = [
                    pp for i, pp in enumerate(self.peers)
                    if i not in self._departed
                ]
            p = stable_user_peer(sj.user, pool)
        return p

    # -- stale-view placement --------------------------------------------------
    def _comp_vec(self, sj: SimJob) -> np.ndarray:
        """The live computation column, replaced by the submitting
        peer's world view: home columns are re-measured per job (the
        peer owns them — same freshness as the omniscient sim), remote
        columns are whatever the last exchange advertised."""
        with trace.span("diana.p2p.view"):
            peer = self._submit_peer(sj)
            peer.refresh_home()
            out = comp_site_column(peer.view, self.weights) + sj.work / peer.view.cap
            alive = peer.view.alive
            if not alive.all():
                # Mask sites this peer BELIEVES are dead (home columns are
                # authoritative; remote columns only as fresh as the last
                # advert — a stale view may still aim at a dead site and
                # bounce in _admit, which is the point).
                out = np.where(alive, out, np.inf)
            mask = self._suspect_mask_for(peer)
            if mask is not None:
                # Prefer owner-direct knowledge: columns owned by a
                # suspect peer carry state of unknown age, so avoid them —
                # unless that would leave nowhere finite to place.
                masked = np.where(mask, np.inf, out)
                if np.isfinite(masked).any():
                    out = masked
            return out

    def choose_site(self, sj: SimJob) -> str:
        comp = self._comp_vec(sj)
        costs = []
        for i, name in enumerate(self._names_sorted):
            net, dtc = self._static_terms(sj, name)
            costs.append((net + comp[i] + dtc, name))
        return min(costs)[1]

    def choose_sites_batch(self, batch: list[SimJob]) -> list[str]:
        """Snapshot API, vectorized like ``_on_arrive_batch``: the
        memoized static (net, dtc) planes are shared across the batch
        and only the computation column comes from each row's own
        peer view — equivalent to ``[self.choose_site(sj) for sj in
        batch]`` (the omniscient sim's shared-base shortcut doesn't
        apply because rows may belong to different peers' views)."""
        if not self._batch_eligible(batch):
            return [self.choose_site(sj) for sj in batch]
        net, dtc = self._static_cost_rows(batch)
        return [
            self._names_sorted[int(np.argmin((net[i] + self._comp_vec(sj)) + dtc[i]))]
            for i, sj in enumerate(batch)
        ]

    def _admit(self, sj: SimJob, target: str, now: float, events: list) -> str:
        # The base may redirect a stale-view submission off a dead
        # site; the optimistic feedback must follow the job to where
        # it actually landed.
        target = super()._admit(sj, target, now, events)
        # Optimistic local feedback: the submitting peer's next
        # placement sees this one. Home targets get truth on the next
        # refresh; remote targets keep the (dirty, never re-advertised)
        # estimate until the owner's advert corrects it.
        peer = self._submit_peer(sj)
        if trace.on and target not in peer.home_sites:
            trace.count("diana.p2p.remote_placements")
        peer.note_remote_placement(target, sj.work)
        return target

    # -- peer churn (fault plan peer_leave/peer_join) --------------------------
    def _on_fault(self, ev, now: float, events: list) -> None:
        if ev.kind == "peer_leave":
            self._peer_leave(int(ev.peer), now)
        elif ev.kind == "peer_join":
            self._peer_join(int(ev.peer), now)
        else:
            super()._on_fault(ev, now, events)

    def _peer_leave(self, k: int, now: float) -> None:
        """Graceful departure: the leaver hands its whole home
        partition (authoritative refs + epoch/stamp continuity) to the
        next active peer on the ring and drops out of the gossip
        fan-out; its pair state is reset so any rejoin starts from a
        table-bearing full sync."""
        leaver = self.peers[k]
        names = list(leaver.home_names)
        active = [
            i for i in range(self.num_peers)
            if i != k and i not in self._departed
        ]
        succ = min(active, key=lambda i: (i - k) % self.num_peers)
        grant = leaver.handover()
        self.peers[succ].adopt(grant)
        for n in names:
            self._peer_by_site[n] = self.peers[succ]
        self._departed[k] = names
        self.exchange.set_active(k, False)

    def _peer_join(self, k: int, now: float) -> None:
        """Rejoin: the peer takes back exactly the partition it left
        with (whoever holds each site now grants it back — the epoch
        sequence continues through the handover, so receivers' strictly
        -newer merges keep converging) and re-enters the fan-out; the
        delta wire's forced full sync rebuilds its world view."""
        names = self._departed.pop(k)
        joiner = self.peers[k]
        by_owner: dict[int, list[str]] = {}
        for n in names:
            owner = self._peer_by_site[n]
            oi = next(i for i, p in enumerate(self.peers) if p is owner)
            by_owner.setdefault(oi, []).append(n)
        for oi, ns in by_owner.items():
            joiner.adopt(self.peers[oi].handover(names=ns))
        for n in names:
            self._peer_by_site[n] = joiner
        self.exchange.set_active(k, True)

    def _reset_faults(self) -> None:
        # Hand departed peers their partitions back before the base
        # reset, so repeated run() calls replay churn from the
        # construction-time layout.
        for k in sorted(self._departed):
            self._peer_join(k, 0.0)
        # Re-arm the unreliable transport (re-seeded RNG, cleared
        # burst/suspicion state, dropped in-flight messages) so each
        # run replays the same fault draws; no-op without a model.
        self.exchange.reset_transport()
        self._suspect_masks = {}
        self._staleness_widen = 1.0
        super()._reset_faults()

    # -- exchange events -------------------------------------------------------
    def _on_exchange(self, now: float, events: list) -> None:
        self.exchange.deliver_due(now)
        self.exchange.round(now)
        self._refresh_suspicion(now)
        if self.exchange.in_flight:
            heapq.heappush(
                events, (self.exchange.next_due(), next(self._seq), "deliver", None)
            )

    def _on_deliver(self, now: float, events: list) -> None:
        self.exchange.deliver_due(now)
        self._refresh_suspicion(now)
        # Chain to the next in-flight batch: with latency > interval,
        # several batches are airborne at once and the exchange event
        # may already have stopped rescheduling — every sent advert
        # must still land.
        if self.exchange.in_flight:
            heapq.heappush(
                events, (self.exchange.next_due(), next(self._seq), "deliver", None)
            )

    # -- suspicion (unreliable transport) --------------------------------------
    def _refresh_suspicion(self, now: float) -> None:
        """Re-derive the cached suspicion state from the exchange's
        failure detectors. Columns owned by a suspect peer are masked
        out of stale-view placement (when a finite alternative
        remains) and treated as infinitely stale by §IX migration; and
        while any peer is suspect, the migration trust horizon widens
        by how far the transport has stretched real delivery gaps past
        the nominal exchange interval (capped at 8x) — lossy silence
        should degrade trust gradually, not disable migration."""
        ex = self.exchange
        if ex.transport is None:
            return
        if not self._suspect_masks and now < ex.suspicion_quiet_until():
            # Nobody is suspect and no detector's phi can have crossed
            # the threshold yet: the cached state is still exact. This
            # is the overwhelmingly common case — the refresh runs on
            # every delivery event.
            return
        masks: dict[int, np.ndarray] = {}
        for i in range(len(self.peers)):
            m = ex.suspect_mask(i, now)
            if m is not None:
                masks[i] = m
        self._suspect_masks = masks
        widen = 1.0
        if masks:
            gap = ex.mean_delivery_gap()
            if gap is not None and gap > self.exchange_interval_s:
                widen = min(8.0, gap / self.exchange_interval_s)
        self._staleness_widen = widen

    def _suspect_mask_for(self, peer: PeerScheduler) -> Optional[np.ndarray]:
        if not self._suspect_masks:
            return None
        return self._suspect_masks.get(self._peer_index[id(peer)])

    # -- migration trust -------------------------------------------------------
    @property
    def migration_max_staleness_s(self) -> float:
        """The configured trust horizon, widened by the cached
        suspicion factor while the transport is misbehaving."""
        base = self._migration_max_staleness_base
        return base * self._staleness_widen if self._staleness_widen > 1.0 else base

    @migration_max_staleness_s.setter
    def migration_max_staleness_s(self, value: float) -> None:
        self._migration_max_staleness_base = float(value)

    def _migration_staleness(self, name: str, now: float) -> Optional[np.ndarray]:
        peer = self._peer_by_site.get(name)
        if peer is None:
            return None
        peer.refresh_home()
        st = peer.staleness(now)
        mask = self._suspect_mask_for(peer)
        if mask is not None:
            # A suspect owner's columns are infinitely stale: Q4
            # migration won't poll a peer the failure detector says may
            # be unreachable, whatever its last advert's age claims.
            st = np.where(mask, np.inf, st)
        return st

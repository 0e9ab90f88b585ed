"""Batched (jobs × sites) placement engine (paper §IV/§V at bulk scale).

The paper's central loop — "after every job we calculate the cost to
submit the next job" — is O(J·S) Python when driven through
``DianaScheduler.rank_sites``; at bulk scale (10⁴ jobs, Fig 4) the
global cost evaluation dominates. This module evaluates the full §IV
cost matrix as one array program and *replays* the sequential state
updates (queue_length / waiting_work) between rows, so batched results
are bit-identical to the per-job loop:

* ``SitePack`` / ``JobPack`` pack ``SiteState``/``NetworkLink`` dicts
  and job demands into dense arrays (the kernel's ``(8, S)`` row layout
  on one side, ``(J, 1)`` demand columns on the other).
* ``cost_components`` computes the static §IV planes — ``net`` (S,),
  per-site computation state (S,) and ``dtc`` (J, S) — in float64
  NumPy with *exactly* the scalar code's operation order, so costs
  match ``total_cost``/``rank_sites`` to the last bit.
* Per-job-class cost keys (§V COMPUTE / DATA / BOTH) are column masks
  over the ``(net, comp, dtc)`` component planes: one matrix serves
  all three branches.
* ``batched_cost_matrix`` assembles the per-class (J, S) matrix in one
  shot; ``backend="kernel"`` routes through the Pallas §IV kernel
  (``repro.kernels.cost_matrix``) compiled for the TPU, while
  ``backend="numpy"`` is the bit-exact reference path.
* ``replay_place`` commits placements sequentially-equivalently: the
  static planes are computed once, and only the cheap dynamic
  computation term is re-evaluated per row from the running
  queue/work vectors.

``DianaScheduler.rank_sites_batch`` / ``place_batch`` and
``BulkScheduler.schedule_groups`` are thin wrappers over these.
"""
from __future__ import annotations

import math
import warnings
from dataclasses import dataclass
from typing import Optional, Sequence

import numpy as np

from . import trace
from .costs import CostWeights, NetworkLink, SiteState
from .queues import Job
from .scheduler import JobClass, classify

__all__ = [
    "PACK_FIELDS",
    "SitePack",
    "JobPack",
    "BatchPlacement",
    "TierPack",
    "argmin_finite",
    "class_total",
    "commit_placement",
    "comp_site_column",
    "cost_components",
    "batched_cost_matrix",
    "batched_argmin",
    "hier_select",
    "hier_replay",
    "merge_packed_rows",
    "replay_on_pack",
    "replay_place",
]

# Wire/row order of the packed per-site float columns — the "(8, S)"
# layout the P2P layer advertises between peers (repro.core.p2p).
PACK_FIELDS = ("cap", "queue", "work", "load", "bw", "loss", "rtt", "mss")


@dataclass
class SitePack:
    """Dense column-per-site view of ``sites``/``links`` dicts.

    Column order is the ``sites`` dict iteration order, which makes
    first-index argmin tie-breaking identical to the sequential
    ``sorted``-walk in ``DianaScheduler.select_site`` (Python sorts are
    stable over the same iteration order).
    """

    names: list[str]
    cap: np.ndarray       # (S,) float64 — Pi
    queue: np.ndarray     # (S,) — Qi
    work: np.ndarray      # (S,) — Q (aggregate queued work)
    load: np.ndarray      # (S,) — SiteLoad
    bw: np.ndarray        # (S,) nominal bytes/s toward each site
    loss: np.ndarray      # (S,) packet-loss fraction
    rtt: np.ndarray       # (S,) round-trip seconds
    mss: np.ndarray       # (S,) TCP MSS bytes (Mathis model)
    alive: np.ndarray     # (S,) bool

    @classmethod
    def from_scheduler(
        cls,
        sites: dict[str, SiteState],
        links: dict[str, NetworkLink],
        order: Optional[Sequence[str]] = None,
    ) -> "SitePack":
        names = list(order) if order is not None else list(sites)
        f64 = lambda xs: np.asarray(xs, np.float64)
        return cls(
            names=names,
            cap=f64([sites[n].capacity for n in names]),
            queue=f64([sites[n].queue_length for n in names]),
            work=f64([sites[n].waiting_work for n in names]),
            load=f64([sites[n].load for n in names]),
            bw=f64([links[n].bandwidth_Bps for n in names]),
            loss=f64([links[n].loss_rate for n in names]),
            rtt=f64([links[n].rtt_s for n in names]),
            mss=f64([links[n].mss_bytes for n in names]),
            alive=np.asarray([sites[n].alive for n in names], bool),
        )

    def refresh_dynamic(
        self,
        sites: dict[str, SiteState],
        only: Optional[Sequence[str]] = None,
        missing: str = "raise",
    ) -> None:
        """Re-read queue/work/load/alive (between replay rounds).

        ``only`` restricts the refresh to the named columns — the
        migration pass uses it to touch just the (source, target) pair
        a move mutated instead of re-reading every site. A name in
        ``only`` that has no column is a caller bug: ``missing="raise"``
        (the default) raises ``KeyError`` naming the offenders;
        ``missing="warn"`` skips them with a warning instead.
        """
        if missing not in ("raise", "warn"):
            raise ValueError(f"missing must be 'raise' or 'warn', got {missing!r}")
        if only is None:
            pairs: Sequence[tuple[int, str]] = list(enumerate(self.names))
        else:
            idx = {n: i for i, n in enumerate(self.names)}
            unknown = [n for n in only if n not in idx]
            if unknown:
                if missing == "raise":
                    raise KeyError(
                        f"refresh_dynamic: unknown site id(s) in only={unknown!r}; "
                        f"pack columns are {self.names!r}"
                    )
                warnings.warn(
                    f"refresh_dynamic: ignoring unknown site id(s) {unknown!r}",
                    stacklevel=2,
                )
            pairs = [(idx[n], n) for n in only if n in idx]
        for i, n in pairs:
            s = sites[n]
            self.queue[i] = s.queue_length
            self.work[i] = s.waiting_work
            self.load[i] = s.load
            self.alive[i] = s.alive

    def refresh_from(
        self,
        provider,
        only: Optional[Sequence[str]] = None,
        missing: str = "raise",
    ) -> None:
        """Incremental refresh through a measurement callable.

        ``provider(name) -> SiteState`` is consulted only for the
        ``only`` columns (all columns when omitted) — the event-horizon
        simulator keeps one long-lived pack per grid and re-measures
        just the sites an event actually mutated between horizons,
        instead of materializing a full ``sites`` dict per refresh.
        Because each column is re-read whole (never incrementally
        updated), a narrowed refresh is bit-identical to a full one.
        """
        names = self.names if only is None else list(only)
        self.refresh_dynamic(
            {n: provider(n) for n in names}, only=names, missing=missing
        )

    # -- packed-row exchange plumbing (repro.core.p2p wire format) ---------
    def pack_rows(self, cols: Optional[np.ndarray] = None) -> np.ndarray:
        """The (8, S) float64 packed view of the per-site columns in
        ``PACK_FIELDS`` order — the unit the P2P layer advertises. With
        ``cols`` (k,) returns just those columns, shape (8, k)."""
        rows = np.stack([getattr(self, f) for f in PACK_FIELDS])
        return rows if cols is None else rows[:, cols]

    def set_columns(
        self,
        cols: np.ndarray,
        rows: np.ndarray,
        alive: Optional[np.ndarray] = None,
        fields: Optional[Sequence[str]] = None,
    ) -> None:
        """Write (8, k) packed ``rows`` (PACK_FIELDS order) into columns
        ``cols``; ``alive`` optionally overwrites the liveness bits.
        ``fields`` restricts the write to a subset of ``PACK_FIELDS``
        (the P2P merge keeps the receiver's own path measurements)."""
        rows = np.asarray(rows, np.float64)
        for r, f in enumerate(PACK_FIELDS):
            if fields is None or f in fields:
                getattr(self, f)[cols] = rows[r]
        if alive is not None:
            self.alive[cols] = np.asarray(alive, bool)



@dataclass
class JobPack:
    """(J,) demand columns plus per-class component masks.

    ``wcomp``/``wdtc`` are the §V branch selectors: COMPUTE keeps the
    computation plane, DATA the data-transfer plane, BOTH keeps both;
    the network plane is always on.
    """

    bytes_: np.ndarray    # (J,) total bytes to move per job
    work: np.ndarray      # (J,) compute work per job
    wcomp: np.ndarray     # (J,) 1.0 where the class includes computation cost
    wdtc: np.ndarray      # (J,) 1.0 where the class includes data-transfer cost
    classes: list[JobClass]

    @classmethod
    def from_jobs(
        cls,
        jobs: Sequence[Job],
        job_classes: Optional[Sequence[Optional[JobClass]]] = None,
    ) -> "JobPack":
        if job_classes is None:
            job_classes = [None] * len(jobs)
        classes = [c or classify(j) for j, c in zip(jobs, job_classes)]
        return cls(
            bytes_=np.asarray([j.total_bytes for j in jobs], np.float64),
            work=np.asarray([j.compute_work for j in jobs], np.float64),
            wcomp=np.asarray(
                [1.0 if c in (JobClass.COMPUTE, JobClass.BOTH) else 0.0 for c in classes]
            ),
            wdtc=np.asarray(
                [1.0 if c in (JobClass.DATA, JobClass.BOTH) else 0.0 for c in classes]
            ),
            classes=classes,
        )


@dataclass
class BatchPlacement:
    """Result of a batched §V selection over J jobs."""

    site_indices: np.ndarray    # (J,) int64 column index per job
    sites: list[str]            # per-job chosen site name
    costs: np.ndarray           # (J,) float64 chosen-site cost
    classes: list[JobClass]


# ---------------------------------------------------------------------------
# Static §IV component planes (float64, scalar-identical operation order).
# ---------------------------------------------------------------------------

def comp_site_column(
    sites: SitePack, weights: CostWeights = CostWeights()
) -> np.ndarray:
    """Job-independent §IV computation term, W5·Qi/Pi + W6·Q/Pi +
    W7·load, in ``computation_cost``'s exact evaluation order (add
    ``job_work / cap`` for the full per-job term)."""
    return (
        weights.w_queue * sites.queue / sites.cap
        + weights.w_work * sites.work / sites.cap
        + weights.w_load * sites.load
    )


def cost_components(
    jobs: JobPack, sites: SitePack, weights: CostWeights = CostWeights()
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Return ``(net (S,), comp_site (S,), dtc (J, S))``.

    Every expression keeps the scalar code's evaluation order so
    results are bit-identical to ``network_cost`` /
    ``computation_cost`` / ``data_transfer_cost``.
    """
    net = (sites.loss / sites.bw) * 1.0e6
    with np.errstate(divide="ignore", invalid="ignore"):
        mathis = sites.mss / (sites.rtt * np.sqrt(sites.loss))
    eff_bw = np.where(sites.loss > 0.0, np.minimum(sites.bw, mathis), sites.bw)
    dtc = jobs.bytes_[:, None] / eff_bw[None, :]
    return net, comp_site_column(sites, weights), dtc


def class_total(cls: JobClass, net, comp, dtc):
    """Per-class §IV total with the scalar rank-key addition order —
    COMPUTE = comp + net, DATA = dtc + net, BOTH = (net + comp) + dtc —
    the single source of truth for the bit-identical guarantee.
    Broadcasts: works on (S,) rows and (J, S) planes alike. ``comp``
    may be None for DATA (unused)."""
    if cls is JobClass.DATA:
        return dtc + net
    if cls is JobClass.COMPUTE:
        return comp + net
    return (net + comp) + dtc


def _class_rows(
    jobs: JobPack,
    net: np.ndarray,
    comp: np.ndarray,
    dtc: np.ndarray,
) -> np.ndarray:
    """Per-class (J, S) totals: each row gets its own class's
    class_total, evaluated only for the rows of that class."""
    out = np.empty_like(dtc)
    for cls in (JobClass.COMPUTE, JobClass.DATA, JobClass.BOTH):
        m = np.asarray([c is cls for c in jobs.classes])
        if m.any():
            out[m] = class_total(cls, net, comp[m], dtc[m])
    return out


def batched_cost_matrix(
    jobs: JobPack,
    sites: SitePack,
    weights: CostWeights = CostWeights(),
    *,
    mask_dead: bool = True,
    backend: str = "numpy",
) -> np.ndarray:
    """One-shot per-class §IV cost over (J, S); dead sites +inf.

    ``backend="numpy"``  — float64, bit-identical to the scalar loop.
    ``backend="kernel"`` — the Pallas §IV kernel (float32) via
    ``repro.kernels.cost_matrix``, always the kernel and never its jnp
    reference. Off the TPU it raises unless the caller traced it under
    ``jax.experimental.pallas.tpu.force_tpu_interpret_mode()``.
    """
    if backend == "kernel":
        from repro.kernels.cost_matrix.ops import cost_matrix_classed

        cost, _ = cost_matrix_classed(
            jobs.bytes_, jobs.work, jobs.wcomp, jobs.wdtc,
            sites.cap, sites.queue, sites.work, sites.load,
            sites.bw, sites.loss, sites.rtt,
            sites.alive if mask_dead else np.ones_like(sites.alive, bool),
            sites.mss,
            w_queue=weights.w_queue, w_work=weights.w_work, w_load=weights.w_load,
        )
        cost = np.asarray(cost, np.float64)
        if mask_dead:
            cost[:, ~sites.alive] = np.inf
        return cost
    if backend != "numpy":
        raise ValueError(f"unknown backend {backend!r}")
    net, comp_site, dtc = cost_components(jobs, sites, weights)
    comp = comp_site[None, :] + jobs.work[:, None] / sites.cap[None, :]
    cost = _class_rows(jobs, net, comp, dtc)
    if mask_dead:
        cost[:, ~sites.alive] = np.inf
    return cost


def argmin_finite(row: np.ndarray) -> tuple[int, float]:
    """Cheapest column of one (inf-masked) cost row — first index wins
    ties, matching the stable sequential ranking walk; raises when no
    finite (alive) column remains."""
    s = int(np.argmin(row))
    if not np.isfinite(row[s]):
        raise RuntimeError("no alive site available")
    return s, float(row[s])


def batched_argmin(cost: np.ndarray, sites: SitePack) -> BatchPlacement:
    """Per-job cheapest alive site (first index wins ties, like the
    stable sequential ranking walk)."""
    idx = np.argmin(cost, axis=1)
    picked = cost[np.arange(cost.shape[0]), idx]
    if not np.all(np.isfinite(picked)):
        raise RuntimeError("no alive site available")
    return BatchPlacement(
        site_indices=idx,
        sites=[sites.names[i] for i in idx],
        costs=picked,
        classes=[],
    )


# ---------------------------------------------------------------------------
# Row-versioned merge of advertised columns (P2P world-view refresh).
# ---------------------------------------------------------------------------

def merge_packed_rows(
    sp: SitePack,
    version: np.ndarray,
    stamp: np.ndarray,
    cols: np.ndarray,
    rows: np.ndarray,
    new_version: np.ndarray,
    new_stamp: np.ndarray,
    alive: Optional[np.ndarray] = None,
    protect: Optional[np.ndarray] = None,
    fields: Optional[Sequence[str]] = None,
    reclaim: Optional[np.ndarray] = None,
) -> np.ndarray:
    """Merge advertised (8, k) ``rows`` into pack columns ``cols``,
    keeping only strictly newer epochs.

    ``version``/``stamp`` are the receiver's (S,) per-column epoch and
    owner-clock vectors, updated in place for the applied columns.
    ``protect`` marks columns the receiver owns authoritatively (its
    home sites) — hearsay never overwrites those. ``fields`` restricts
    which packed fields an applied column overwrites (see
    ``SitePack.set_columns``) — the P2P layer passes dequantized f32/f16
    owner fields here; versions stay exact int64 so quantization never
    weakens the strictly-newer invariant. Returns the (k,) bool mask of
    applied columns.

    Epochs advance only when the owner's measured state changed, so two
    refinements keep unchanged-but-re-measured rows fresh:

    * an advert carrying the *same* epoch with a strictly newer owner
      stamp refreshes ``stamp`` in place (content is identical by the
      one-owner-per-epoch invariant) without counting as applied;
    * ``reclaim`` marks columns whose content the receiver has
      speculatively modified (optimistic placement feedback): an
      equal-epoch owner advert re-applies the canonical content there,
      reverting the speculation, and does count as applied.
    """
    cols = np.asarray(cols, np.int64)
    new_version = np.asarray(new_version, np.int64)
    new_stamp = np.asarray(new_stamp, np.float64)
    if len(np.unique(cols)) != len(cols):
        # Duplicate columns in one batch (adverts aggregated from
        # several senders): fancy assignment is last-write-wins, which
        # could roll a newer epoch back to an older duplicate. Keep the
        # highest (epoch, stamp) per column — the stamp tie-break makes
        # the merge independent of advert order when two senders relay
        # the same epoch but one heard a fresher re-measurement; the
        # losers report False.
        winner: dict[int, int] = {}
        for k, c in enumerate(cols):
            w = winner.get(c)
            if w is None or (new_version[k], new_stamp[k]) > (
                new_version[w], new_stamp[w]
            ):
                winner[c] = int(k)
        keep = np.zeros(len(cols), bool)
        keep[list(winner.values())] = True
        out = np.zeros(len(cols), bool)
        out[keep] = merge_packed_rows(
            sp, version, stamp, cols[keep],
            np.asarray(rows, np.float64)[:, keep],
            new_version[keep],
            new_stamp[keep],
            None if alive is None else np.asarray(alive, bool)[keep],
            protect,
            fields,
            reclaim,
        )
        return out
    unprotected = np.ones(len(cols), bool)
    if protect is not None:
        unprotected = ~np.asarray(protect, bool)[cols]
    newer = (new_version > version[cols]) & unprotected
    equal = (new_version == version[cols]) & unprotected
    apply = newer
    if reclaim is not None:
        apply = newer | (equal & np.asarray(reclaim, bool)[cols])
    if apply.any():
        take = cols[apply]
        sp.set_columns(
            take,
            np.asarray(rows, np.float64)[:, apply],
            None if alive is None else np.asarray(alive, bool)[apply],
            fields,
        )
        version[take] = new_version[apply]
        stamp[take] = np.maximum(stamp[take], new_stamp[apply])
    # Same epoch, fresher owner clock: the owner re-measured and found
    # nothing changed — refresh the stamp so staleness() doesn't decay
    # rows that are merely *stable*.
    touch = equal & ~apply & (new_stamp > stamp[cols])
    if touch.any():
        stamp[cols[touch]] = new_stamp[touch]
    return apply


# ---------------------------------------------------------------------------
# Sequential-equivalent replay: commit placements between matrix rows.
# ---------------------------------------------------------------------------

def replay_on_pack(
    jp: JobPack,
    sp: SitePack,
    weights: CostWeights = CostWeights(),
) -> BatchPlacement:
    """The replay core against any ``SitePack`` view — fresh or stale.

    The static planes (network + data-transfer, the expensive §IV
    terms) are evaluated once for the whole batch; between rows only
    the computation term is re-derived from the running queue-length /
    waiting-work vectors — the vectorized replay of "after every job we
    calculate the cost to submit the next job". The pack's queue/work
    columns are updated in place with the per-placement feedback, so a
    caller holding authoritative state (``replay_place``) or a stale
    world view (``repro.core.p2p.PeerScheduler``) commits from the
    same arrays. Site choices and costs are bit-identical to the
    sequential per-job loop over the same view.
    """
    with trace.span("diana.plane"):
        net, comp_base, dtc = cost_components(jp, sp, weights)
        comp_base = comp_base.copy()
        dead = ~sp.alive
        # Dead sites poison every class branch through the (always-present)
        # network plane: +inf propagates through the remaining additions.
        net_m = np.where(dead, np.inf, net)
        dtc_m = dtc.copy()
        dtc_m[:, dead] = np.inf

    with trace.span("diana.replay"):
        q = sp.queue.copy()
        w = sp.work.copy()
        wq, ww = weights.w_queue, weights.w_work
        load_term = weights.w_load * sp.load
        cap = sp.cap

        J = len(jp.classes)
        site_idx = np.empty(J, np.int64)
        costs = np.empty(J, np.float64)
        for j in range(J):
            cls = jp.classes[j]
            comp = None if cls is JobClass.DATA else comp_base + jp.work[j] / cap
            row = class_total(cls, net_m, comp, dtc_m[j])
            s, cost = argmin_finite(row)
            site_idx[j] = s
            costs[j] = cost
            q[s] += 1.0
            w[s] += jp.work[j]
            # Only site s changed; re-derive its entry with comp_site_column's
            # elementwise expression so the value stays bit-identical to a
            # full recomputation.
            comp_base[s] = (wq * q[s] / cap[s] + ww * w[s] / cap[s]) + load_term[s]

        sp.queue[:] = q
        sp.work[:] = w
    return BatchPlacement(
        site_indices=site_idx,
        sites=[sp.names[i] for i in site_idx],
        costs=costs,
        classes=jp.classes,
    )


def replay_place(
    jobs: Sequence[Job],
    sites: dict[str, SiteState],
    links: dict[str, NetworkLink],
    weights: CostWeights = CostWeights(),
    job_classes: Optional[Sequence[Optional[JobClass]]] = None,
    commit: bool = True,
) -> BatchPlacement:
    """Batched equivalent of ``[DianaScheduler.place(j) for j in jobs]``.

    Packs the authoritative dicts, runs ``replay_on_pack`` and commits
    the resulting queue/work vectors back — site choices, costs and
    final site state are bit-identical to the sequential loop.
    """
    with trace.span("diana.pack"):
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(jobs, job_classes)
    placement = replay_on_pack(jp, sp, weights)
    if commit:
        commit_placement(jobs, placement, sites, sp)
    return placement


def commit_placement(
    jobs: Sequence[Job],
    placement: BatchPlacement,
    sites: dict[str, SiteState],
    sp: SitePack,
) -> None:
    """Write a replay's results back: each job's site, and each
    site's queue length and waiting work from the pack."""
    with trace.span("diana.commit"):
        for job, name in zip(jobs, placement.sites):
            job.site = name
        for i, name in enumerate(sp.names):
            sites[name].queue_length = float(sp.queue[i])
            sites[name].waiting_work = float(sp.work[i])


# ---------------------------------------------------------------------------
# Two-level placement: tier summaries + pruned argmin ("hier" mode).
#
# A tier is a group of pack columns (a RootGrid of GridTopology, §IX).
# Each tier carries an *admissible* optimistic summary — a lower bound
# on every member's §IV cost built from per-component extrema
# (min(a+b) >= min(a) + min(b)). Per job, the exact cost U of one alive
# column (the previous job's pick) bounds the winner from above, so
# every tier whose bound exceeds U is ruled out; the tiers left enter
# one gathered pass over their columns, laid out tier by tier
# (``TierPack.perm``). The pass evaluates a cheap f32 score, shortlists
# within each tier everything within a relative tolerance of the tier's
# f32 minimum, and re-evaluates only the shortlist in exact f64 with the
# scalar op order — decisions and costs stay bit-identical to the flat
# dense argmin (replay_on_pack / batched_cost_matrix+batched_argmin).
# ---------------------------------------------------------------------------

# f32 shortlist tolerance: the score is a handful (<10) of rounding
# steps over nonnegative terms, so relative error is bounded by
# ~10·2⁻²⁴ ≈ 6e-7; 1e-5 keeps >10x margin. Scores outside the sane
# magnitude window (or with negative inputs, see _f32_gate) fall back
# to exact evaluation of the whole tier.
_F32_SHORTLIST_RTOL = 1e-5
_F32_SHORTLIST_SCALE = np.float64(1.0 + _F32_SHORTLIST_RTOL)  # f64 threshold
_F32_SHORTLIST_MIN = 1e-30
_F32_SHORTLIST_MAX = 1e30
# Nudge finite tier bounds down by a relative ulp-scale guard so f64
# rounding in the bound arithmetic can never push a bound above a
# member's true cost (which would wrongly prune the winning tier).
_BOUND_GUARD_RTOL = 1e-12


def _static_site_planes(sp: SitePack) -> tuple[np.ndarray, np.ndarray]:
    """Per-site ``(net, eff_bw)`` in ``cost_components``' exact op
    order, alive-independent (no dead poisoning)."""
    net = (sp.loss / sp.bw) * 1.0e6
    with np.errstate(divide="ignore", invalid="ignore"):
        mathis = sp.mss / (sp.rtt * np.sqrt(sp.loss))
    eff = np.where(sp.loss > 0.0, np.minimum(sp.bw, mathis), sp.bw)
    return net, eff


@dataclass
class TierPack:
    """Tier membership + static summaries over a ``SitePack``.

    Holds only *static* per-site planes (net, eff_bw — functions of the
    link fields) plus their per-tier extrema and f32 copies for the
    shortlist score. Dynamic state (queue/work/load/alive) is read live
    from the ``SitePack``, so gossip merges and replay feedback need no
    TierPack maintenance; only changes to link fields or capacity
    require ``refresh`` (narrowable to the dirty columns).
    """

    labels: list[str]          # tier label per tier index
    tier_of: np.ndarray        # (S,) int64 tier index per pack column
    members: list[np.ndarray]  # per-tier ascending column indices
    # Tier-contiguous column order (membership only, so ``refresh``
    # leaves it alone): tier 0's members, then tier 1's, ...
    perm: np.ndarray           # (S,) pack column at each position
    pos_of: np.ndarray         # (S,) position of each pack column
    starts: np.ndarray         # (T,) first position of each tier
    sizes: np.ndarray          # (T,) member count of each tier
    tier_p: np.ndarray         # (S,) tier index at each position
    net64: np.ndarray          # (S,) float64 network term, unpoisoned
    eff64: np.ndarray          # (S,) float64 effective bandwidth
    net32: np.ndarray          # (S,) float32 copies for the shortlist score
    eff32: np.ndarray
    cap32: np.ndarray
    net_min: np.ndarray        # (T,) per-tier extrema for the bounds
    eff_max: np.ndarray
    eff_min: np.ndarray
    cap_max: np.ndarray
    cap_min: np.ndarray

    @classmethod
    def from_site_pack(cls, sp: SitePack, tiers=None) -> "TierPack":
        """Build the tier index over ``sp``'s columns.

        ``tiers`` may be ``None`` (every site in one tier), a
        ``{site: tier_label}`` dict (unmapped sites become singleton
        tiers named after themselves), or a ``GridTopology`` (tier =
        RootGrid, via ``site_tiers``).
        """
        names = sp.names
        if tiers is None:
            mapping = {n: "grid" for n in names}
        elif isinstance(tiers, dict):
            mapping = {n: tiers.get(n, n) for n in names}
        elif hasattr(tiers, "site_tiers"):
            mapping = tiers.site_tiers(names)
        else:
            raise TypeError(
                f"tiers must be None, a dict or a GridTopology, got {type(tiers)!r}"
            )
        labels: list[str] = []
        index: dict[str, int] = {}
        tier_of = np.empty(len(names), np.int64)
        groups: list[list[int]] = []
        for i, n in enumerate(names):
            lab = mapping[n]
            t = index.get(lab)
            if t is None:
                t = len(labels)
                index[lab] = t
                labels.append(lab)
                groups.append([])
            tier_of[i] = t
            groups[t].append(i)
        S, T = len(names), len(labels)
        perm = np.argsort(tier_of, kind="stable")
        pos_of = np.empty(S, np.int64)
        pos_of[perm] = np.arange(S)
        sizes = np.asarray([len(g) for g in groups], np.int64)
        tp = cls(
            labels=labels,
            tier_of=tier_of,
            members=[np.asarray(g, np.int64) for g in groups],
            perm=perm,
            pos_of=pos_of,
            starts=np.cumsum(sizes) - sizes,
            sizes=sizes,
            tier_p=tier_of[perm],
            net64=np.empty(S, np.float64),
            eff64=np.empty(S, np.float64),
            net32=np.empty(S, np.float32),
            eff32=np.empty(S, np.float32),
            cap32=np.empty(S, np.float32),
            net_min=np.empty(T, np.float64),
            eff_max=np.empty(T, np.float64),
            eff_min=np.empty(T, np.float64),
            cap_max=np.empty(T, np.float64),
            cap_min=np.empty(T, np.float64),
        )
        tp.refresh(sp)
        return tp

    def refresh(self, sp: SitePack, cols: Optional[np.ndarray] = None) -> None:
        """Recompute static planes + summaries, narrowed to ``cols``.

        Call whenever link fields (bw/loss/rtt/mss) or capacity changed
        on some columns; tier summaries are re-aggregated only for the
        tiers containing a touched column.
        """
        if cols is None:
            net, eff = _static_site_planes(sp)
            self.net64[:] = net
            self.eff64[:] = eff
            self.net32[:] = self.net64.astype(np.float32)
            self.eff32[:] = self.eff64.astype(np.float32)
            self.cap32[:] = sp.cap.astype(np.float32)
            touched: Sequence[int] = range(len(self.labels))
        else:
            cols = np.asarray(cols, np.int64)
            if cols.size == 0:
                return
            loss, bw = sp.loss[cols], sp.bw[cols]
            net = (loss / bw) * 1.0e6
            with np.errstate(divide="ignore", invalid="ignore"):
                mathis = sp.mss[cols] / (sp.rtt[cols] * np.sqrt(loss))
            eff = np.where(loss > 0.0, np.minimum(bw, mathis), bw)
            self.net64[cols] = net
            self.eff64[cols] = eff
            self.net32[cols] = net.astype(np.float32)
            self.eff32[cols] = eff.astype(np.float32)
            self.cap32[cols] = sp.cap[cols].astype(np.float32)
            touched = np.unique(self.tier_of[cols])
        for t in touched:
            mem = self.members[int(t)]
            self.net_min[t] = self.net64[mem].min()
            self.eff_max[t] = self.eff64[mem].max()
            self.eff_min[t] = self.eff64[mem].min()
            self.cap_max[t] = sp.cap[mem].max()
            self.cap_min[t] = sp.cap[mem].min()

    def comp_tier_min(self, comp: np.ndarray) -> np.ndarray:
        """Per-tier minimum of a per-site computation column."""
        return np.minimum.reduceat(comp[self.perm], self.starts)


def _f32_gate(jp: JobPack, sp: SitePack, tp: TierPack, weights: CostWeights) -> bool:
    """True when the f32 shortlist's relative-error bound is sound: all
    score terms nonnegative (no cancellation) and capacities positive.
    Otherwise refinement evaluates whole tiers in exact f64 — still
    tier-pruned, just without the f32 narrowing."""
    if weights.w_queue < 0.0 or weights.w_work < 0.0 or weights.w_load < 0.0:
        return False

    def nn(a: np.ndarray) -> bool:  # nonnegative, NaN-rejecting
        return bool(np.all(a >= 0.0))

    return (
        nn(tp.net64)
        and nn(tp.eff64)
        and nn(sp.queue)
        and nn(sp.work)
        and nn(sp.load)
        and nn(jp.work)
        and nn(jp.bytes_)
        and bool(np.all(sp.cap > 0.0))
        and bool(np.all(np.isfinite(sp.cap)))
    )


class _RegionView:
    """One call's per-column planes in ``TierPack.perm`` order, so a
    job's pass over the tiers that enter reads whole arrays (every tier
    entering) or one gather (some). Rows are net, eff, cap and the
    job-independent computation term, in f64 and, for the shortlist
    score, f32 (``v32`` is None when the f32 gate is off)."""

    __slots__ = ("v64", "v32", "dead", "pos_of")

    def __init__(self, sp: SitePack, tp: TierPack, comp_base: np.ndarray, use32: bool):
        perm = tp.perm
        comp_p = comp_base[perm]
        self.v64 = np.stack([tp.net64[perm], tp.eff64[perm], sp.cap[perm], comp_p])
        self.v32 = None
        if use32:
            self.v32 = np.stack(
                [tp.net32[perm], tp.eff32[perm], tp.cap32[perm], comp_p.astype(np.float32)]
            )
        dead = ~sp.alive[perm]
        self.dead = dead if dead.any() else None
        self.pos_of = tp.pos_of

    def set_comp(self, col: int, value: float) -> None:
        """Column ``col``'s computation term moved (replay feedback)."""
        k = self.pos_of[col]
        self.v64[3, k] = value
        if self.v32 is not None:
            self.v32[3, k] = value


def _tier_bounds(
    tp: TierPack, cls: JobClass, bytes_j: float, work_j: float, comp_min: np.ndarray
) -> np.ndarray:
    """Per-tier admissible lower bound on one job's §IV cost, guarded
    against f64 rounding; NaN bounds become -inf (never ruled out)."""
    comp_lb = None
    if cls is not JobClass.DATA:
        comp_lb = comp_min + work_j / (tp.cap_max if work_j >= 0.0 else tp.cap_min)
    dtc_lb = None
    if cls is not JobClass.COMPUTE:
        # 0/eff is 0 for every finite eff; the shortcut dodges the 0/0
        # NaN an all-zero-bandwidth tier would inject.
        dtc_lb = 0.0 if bytes_j == 0.0 else bytes_j / (
            tp.eff_max if bytes_j > 0.0 else tp.eff_min
        )
    bound = class_total(cls, tp.net_min, comp_lb, dtc_lb)
    fin = np.isfinite(bound)
    if np.count_nonzero(fin) == fin.size:
        bound -= np.abs(bound) * _BOUND_GUARD_RTOL
    else:
        bound[np.isnan(bound)] = -np.inf
        fin = np.isfinite(bound)
        bound[fin] -= np.abs(bound[fin]) * _BOUND_GUARD_RTOL
    return bound


def _hier_argmin_row(
    tp: TierPack,
    rv: _RegionView,
    cls: JobClass,
    bytes_j: float,
    work_j: float,
    comp_min: np.ndarray,
    hint: int,
) -> tuple[int, float, int, int]:
    """One job's two-level argmin: ``(column, cost)`` bit-identical to
    ``argmin_finite`` over the flat dense row, or ``(-1, inf)`` when no
    alive/finite column exists, followed by the number of tiers that
    entered the pass and of columns evaluated in f64.

    ``comp_min`` is the per-tier minimum of the computation column the
    caller keeps in ``rv``; ``hint`` is a column whose exact cost bounds
    the winner from above (-1 for none). Call under ``np.errstate``
    ignoring divide, invalid and over.
    """
    T = len(tp.labels)
    upper = np.inf
    if hint >= 0:
        k = rv.pos_of[hint]
        if rv.dead is None or not rv.dead[k]:
            # The hint's exact cost on Python floats (IEEE f64, the dense
            # row's value); a zero divisor leaves it undefined.
            net, eff, cap, comp = rv.v64[:, k].tolist()
            try:
                upper = class_total(
                    cls, net,
                    None if cls is JobClass.DATA else comp + work_j / cap,
                    None if cls is JobClass.COMPUTE else bytes_j / eff,
                )
            except ZeroDivisionError:
                pass
    n_in = T
    if math.isfinite(upper):
        # <= (not <): a tier whose bound ties U may hold an equal-cost
        # column with a *lower* index, which the flat argmin would pick.
        enter = _tier_bounds(tp, cls, bytes_j, work_j, comp_min) <= upper
        n_in = int(np.count_nonzero(enter))
    if not n_in:
        return -1, np.inf, 0, 0
    pos = None  # positions (in perm order) of the columns in the pass
    v32, dead = rv.v32, rv.dead
    starts, sizes = tp.starts, tp.sizes
    if n_in < T:
        pos = enter[tp.tier_p].nonzero()[0]
        sizes = sizes[enter]
        starts = sizes.cumsum() - sizes
        if v32 is not None:
            v32 = v32.take(pos, axis=1)
        if dead is not None:
            dead = dead[pos]

    if v32 is not None:
        net32, eff32, cap32, comp32 = v32[0], v32[1], v32[2], v32[3]
        if cls is JobClass.DATA:
            score = (np.float32(bytes_j) / eff32) + net32
        else:
            comp32 = comp32 + np.float32(work_j) / cap32
            if cls is JobClass.COMPUTE:
                score = comp32 + net32
            else:
                score = (net32 + comp32) + (np.float32(bytes_j) / eff32)
        if dead is not None:
            score[dead] = np.inf
        m32 = np.minimum.reduceat(score, starts)
        thr = m32 * _F32_SHORTLIST_SCALE
        # argmin/argmax find NaN first, so a NaN fails the window too.
        lo, hi = m32[m32.argmin()], m32[m32.argmax()]
        if not (_F32_SHORTLIST_MIN < lo and hi < _F32_SHORTLIST_MAX):
            # Outside the sane window (or NaN) the tier is refined whole.
            thr[~((m32 > _F32_SHORTLIST_MIN) & (m32 < _F32_SHORTLIST_MAX))] = np.inf
        short = (score <= thr.repeat(sizes)).nonzero()[0]
        if dead is not None:
            dead = dead[short]
        pos = short if pos is None else pos[short]

    # Exact f64 refinement: elementwise ops on gathered columns equal
    # the full-vector results, so these values match the flat dense row
    # bit for bit.
    v64 = rv.v64 if pos is None else rv.v64.take(pos, axis=1)
    net, eff, cap, comp = v64[0], v64[1], v64[2], v64[3]
    comp_s = None if cls is JobClass.DATA else comp + work_j / cap
    dtc_s = None if cls is JobClass.COMPUTE else bytes_j / eff
    row = class_total(cls, net, comp_s, dtc_s)
    if dead is not None:
        row[dead] = np.inf
    k = int(row.argmin())
    c = float(row[k])
    if not math.isfinite(c):
        return -1, np.inf, n_in, row.size
    # The pass runs in perm order, not index order: among equal minima
    # the lowest pack column wins, as in the flat argmin.
    if row.size > 1 and np.count_nonzero(row == c) > 1:
        ties = (row == c).nonzero()[0]
        col = int(tp.perm[ties if pos is None else pos[ties]].min())
    else:
        col = int(tp.perm[k if pos is None else pos[k]])
    return col, c, n_in, row.size


def _count_refined(tiers: int, cols: int) -> None:
    trace.count("diana.hier.tiers_refined", tiers)
    trace.count("diana.hier.cols_refined", cols)


_ROW_ERRSTATE = dict(divide="ignore", invalid="ignore", over="ignore")


def hier_select(
    jp: JobPack,
    sp: SitePack,
    tp: TierPack,
    weights: CostWeights = CostWeights(),
) -> BatchPlacement:
    """Two-level equivalent of
    ``batched_argmin(batched_cost_matrix(jp, sp, weights), sp)`` —
    snapshot costs, no between-row feedback — without ever
    materializing the (J, S) plane."""
    comp_site = comp_site_column(sp, weights)
    comp_min = tp.comp_tier_min(comp_site)
    rv = _RegionView(sp, tp, comp_site, _f32_gate(jp, sp, tp, weights))
    J = len(jp.classes)
    idx = np.empty(J, np.int64)
    costs = np.empty(J, np.float64)
    tiers = cols = 0
    col = -1
    with np.errstate(**_ROW_ERRSTATE):
        for j in range(J):
            col, c, nt, nc = _hier_argmin_row(
                tp, rv, jp.classes[j],
                float(jp.bytes_[j]), float(jp.work[j]),
                comp_min, col,
            )
            if col < 0:
                raise RuntimeError("no alive site available")
            idx[j] = col
            costs[j] = c
            tiers += nt
            cols += nc
    _count_refined(tiers, cols)
    return BatchPlacement(
        site_indices=idx,
        sites=[sp.names[i] for i in idx],
        costs=costs,
        classes=list(jp.classes),
    )


def hier_replay(
    jp: JobPack,
    sp: SitePack,
    tp: TierPack,
    weights: CostWeights = CostWeights(),
) -> BatchPlacement:
    """Two-level equivalent of ``replay_on_pack(jp, sp, weights)``:
    same sequential queue/work feedback between rows (written back to
    the pack), same choices and costs, but each row is resolved through
    the tier bounds instead of a dense (S,) scan."""
    with trace.span("diana.plane"):
        comp_base = comp_site_column(sp, weights).copy()
        comp_min = tp.comp_tier_min(comp_base)
        rv = _RegionView(sp, tp, comp_base, _f32_gate(jp, sp, tp, weights))
    with trace.span("diana.replay"), np.errstate(**_ROW_ERRSTATE):
        q = sp.queue.copy()
        w = sp.work.copy()
        wq, ww = weights.w_queue, weights.w_work
        load_term = weights.w_load * sp.load
        cap = sp.cap
        J = len(jp.classes)
        site_idx = np.empty(J, np.int64)
        costs = np.empty(J, np.float64)
        tiers = cols = 0
        col = -1
        for j in range(J):
            col, c, nt, nc = _hier_argmin_row(
                tp, rv, jp.classes[j],
                float(jp.bytes_[j]), float(jp.work[j]),
                comp_min, col,
            )
            if col < 0:
                raise RuntimeError("no alive site available")
            site_idx[j] = col
            costs[j] = c
            tiers += nt
            cols += nc
            s = col
            q[s] += 1.0
            w[s] += jp.work[j]
            old = comp_base[s]
            # Same elementwise expression as comp_site_column so the value
            # stays bit-identical to a full recomputation (replay_on_pack).
            comp_base[s] = (wq * q[s] / cap[s] + ww * w[s] / cap[s]) + load_term[s]
            rv.set_comp(s, comp_base[s])
            t = int(tp.tier_of[s])
            if comp_base[s] < comp_min[t]:
                comp_min[t] = comp_base[s]
            elif old == comp_min[t] and comp_base[s] != old:
                # The tier minimum itself moved up: re-aggregate exactly.
                comp_min[t] = comp_base[tp.members[t]].min()
        sp.queue[:] = q
        sp.work[:] = w
    _count_refined(tiers, cols)
    return BatchPlacement(
        site_indices=site_idx,
        sites=[sp.names[i] for i in site_idx],
        costs=costs,
        classes=jp.classes,
    )


# Resolve scheduler's lazy "BatchPlacement" return annotations at runtime
# (typing.get_type_hints evaluates them in scheduler's globals; a direct
# import there would be circular).
from . import scheduler as _scheduler  # noqa: E402

_scheduler.BatchPlacement = BatchPlacement

"""Job migration between peers (paper §IX).

Peer-selection criteria: minimum queue length and minimum cost to place
the job remotely. The scheduler polls peers for (queue length, total
cost, jobsAhead) where jobsAhead counts queued jobs with priority ≥ the
candidate job's priority. If the best peer's jobsAhead beats the local
value, the job's priority is bumped and it migrates — once. A migrated
job is pinned ("the site at which it arrives will not attempt to
schedule it again"), which prevents cycling. Only low-priority (Q4)
jobs migrate under congestion (§X).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Callable, Optional, Sequence

import numpy as np

from .queues import Job, MultilevelFeedbackQueues, is_congested

__all__ = [
    "PeerView",
    "MigrationDecision",
    "select_peer",
    "select_peer_targets",
    "select_peers_batch",
    "staleness_excluded",
    "migrate_congested",
]


@dataclass(frozen=True)
class PeerView:
    """What a peer reports when polled (§IX)."""

    name: str
    queue_length: int
    jobs_ahead: int
    total_cost: float          # §IV cost of placing the job there
    alive: bool = True


@dataclass
class MigrationDecision:
    migrate: bool
    target: Optional[str] = None
    reason: str = ""


def select_peer(
    job: Job,
    local_name: str,
    local_jobs_ahead: int,
    local_cost: float,
    peers: list[PeerView],
) -> MigrationDecision:
    """§IX algorithm: find the peer with min jobsAhead, tie-broken by
    min cost; migrate only if it strictly beats the local site."""
    if job.migrated:
        return MigrationDecision(False, reason="pinned: already migrated once")
    alive = [p for p in peers if p.alive and p.name != local_name]
    if not alive:
        return MigrationDecision(False, reason="no alive peers")
    best = min(alive, key=lambda p: (p.jobs_ahead, p.total_cost))
    if best.jobs_ahead < local_jobs_ahead and best.total_cost <= local_cost:
        return MigrationDecision(True, target=best.name, reason="peer has fewer jobs ahead at lower cost")
    if best.jobs_ahead < local_jobs_ahead and best.total_cost < float("inf"):
        # Paper's primary criterion is jobsAhead; cost is the tiebreaker,
        # but a congested local site still prefers the shorter queue.
        return MigrationDecision(True, target=best.name, reason="peer has fewer jobs ahead")
    return MigrationDecision(False, reason="local site is no worse")


def _peer_argmin(
    excluded: np.ndarray,
    jobs_ahead: np.ndarray,
    total_cost: np.ndarray,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Per row, the stable (jobs_ahead, total_cost)-lexicographic min
    over the non-excluded columns: (ja_min (J,), best col (J,), best
    cost (J,)). First index wins ties, like the sequential ``min``."""
    ja = np.where(excluded[None, :], np.inf, np.asarray(jobs_ahead, np.float64))
    cost = np.where(excluded[None, :], np.inf, np.asarray(total_cost, np.float64))
    ja_min = ja.min(axis=1)
    candidates = ja == ja_min[:, None]
    cost_cand = np.where(candidates, cost, np.inf)
    best = np.argmin(cost_cand, axis=1)
    rows = np.arange(ja.shape[0])
    # An all-inf cost row leaves argmin on a non-candidate column; the
    # sequential min then keeps the first candidate in peer order.
    miss = ~candidates[rows, best]
    if miss.any():
        best[miss] = np.argmax(candidates[miss], axis=1)
    return ja_min, best, cost[rows, best]


def staleness_excluded(
    excluded: np.ndarray,
    staleness: Optional[np.ndarray],
    max_staleness: float,
) -> np.ndarray:
    """Fold per-column view staleness into the exclusion mask: §IX
    migration only trusts peers whose advertised rows are fresh enough
    (a P2P peer's world view ages between exchange rounds)."""
    if staleness is None:
        return excluded
    return excluded | (np.asarray(staleness, np.float64) > max_staleness)


def select_peer_targets(
    pinned: np.ndarray,
    local_jobs_ahead: np.ndarray,
    local_cost: np.ndarray,
    excluded: np.ndarray,
    jobs_ahead: np.ndarray,
    total_cost: np.ndarray,
    staleness: Optional[np.ndarray] = None,
    max_staleness: float = float("inf"),
) -> tuple[np.ndarray, np.ndarray]:
    """Array core of ``select_peers_batch``: (migrate (J,) bool, best
    column (J,) int). No per-row Python — the migration hot loop uses
    this and materializes ``MigrationDecision`` objects only for rows
    it actually applies. ``excluded`` marks dead/local columns;
    ``staleness`` (S,) additionally drops columns older than
    ``max_staleness`` seconds (P2P world-view trust)."""
    tc = np.asarray(total_cost, np.float64)
    # J comes from the row count when the plane is 2-D: a (J, 0) plane
    # (jobs but no peers) must still yield (J,) no-migrate rows; only a
    # genuinely empty candidate set yields length-0 arrays. A non-empty
    # 1-D input is a caller shape bug (a single job's row missing its
    # [None, :] lift) and must fail loudly, not drop its decisions.
    if tc.ndim != 2:
        if tc.size == 0:
            return np.zeros(0, bool), np.zeros(0, np.int64)
        raise ValueError(
            f"total_cost must be a (J, S) plane, got shape {tc.shape}"
        )
    J = tc.shape[0]
    if J == 0:
        return np.zeros(0, bool), np.zeros(0, np.int64)
    excluded = staleness_excluded(excluded, staleness, max_staleness)
    if excluded.all():
        return np.zeros(J, bool), np.zeros(J, np.int64)
    ja_min, best, best_cost = _peer_argmin(excluded, jobs_ahead, total_cost)
    lja = np.asarray(local_jobs_ahead, np.float64)
    lcost = np.asarray(local_cost, np.float64)
    migrate = (
        ~np.asarray(pinned, bool)
        & (ja_min < lja)
        & ((best_cost <= lcost) | (best_cost < np.inf))
    )
    return migrate, best


def select_peers_batch(
    jobs: Sequence[Job],
    local_name: str,
    local_jobs_ahead: np.ndarray,
    local_cost: np.ndarray,
    names: Sequence[str],
    jobs_ahead: np.ndarray,
    total_cost: np.ndarray,
    alive: Optional[np.ndarray] = None,
    staleness: Optional[np.ndarray] = None,
    max_staleness: float = float("inf"),
) -> list[MigrationDecision]:
    """Vectorized ``select_peer`` over a (J, S) peer grid.

    ``names`` fixes the peer iteration order: ties on the
    (jobs_ahead, total_cost) key resolve to the lowest column index,
    exactly like the stable ``min`` walk over a ``PeerView`` list in
    the same order. ``jobs_ahead``/``total_cost`` are (J, S) planes,
    ``local_jobs_ahead``/``local_cost`` the (J,) local columns; a
    column named ``local_name`` (and any dead column) is excluded the
    way ``select_peer`` drops the local/dead entries, and ``staleness``
    (S,) drops columns whose advertised rows are older than
    ``max_staleness`` (only sufficiently fresh peers are trusted).
    An empty candidate set (J=0) returns an empty decision list.
    Without staleness, decisions — targets and reason strings — are
    identical to ``[select_peer(j, local_name, lja, lc, peers) ...]``.
    """
    tc = np.asarray(total_cost, np.float64)
    if tc.ndim != 2:
        if tc.size == 0 and len(jobs) == 0:
            return []
        # Same loud failure as select_peer_targets: a non-empty 1-D
        # row is a missing [None, :] lift, not an empty candidate set.
        raise ValueError(f"total_cost must be a (J, S) plane, got shape {tc.shape}")
    J, S = tc.shape
    if J == 0:
        return []
    if alive is None:
        alive = np.ones(S, bool)
    excluded = ~np.asarray(alive, bool) | np.asarray(
        [n == local_name for n in names], bool
    )
    all_dead = excluded.all()
    excluded = staleness_excluded(excluded, staleness, max_staleness)
    if excluded.all():
        # Distinguish "every peer dead/local" (the sequential reason)
        # from "alive peers exist but none fresh enough" (P2P-only).
        no_peer = "no alive peers" if all_dead else "no sufficiently fresh peers"
        return [
            MigrationDecision(False, reason="pinned: already migrated once")
            if j.migrated
            else MigrationDecision(False, reason=no_peer)
            for j in jobs
        ]
    ja_min, best, best_cost = _peer_argmin(excluded, jobs_ahead, total_cost)
    lja = np.asarray(local_jobs_ahead, np.float64)
    lcost = np.asarray(local_cost, np.float64)
    decisions: list[MigrationDecision] = []
    for j in range(J):
        if jobs[j].migrated:
            decisions.append(
                MigrationDecision(False, reason="pinned: already migrated once")
            )
        elif ja_min[j] < lja[j] and best_cost[j] <= lcost[j]:
            decisions.append(
                MigrationDecision(
                    True, target=names[best[j]],
                    reason="peer has fewer jobs ahead at lower cost",
                )
            )
        elif ja_min[j] < lja[j] and best_cost[j] < float("inf"):
            decisions.append(
                MigrationDecision(
                    True, target=names[best[j]],
                    reason="peer has fewer jobs ahead",
                )
            )
        else:
            decisions.append(
                MigrationDecision(False, reason="local site is no worse")
            )
    return decisions


def apply_migration(job: Job, decision: MigrationDecision, priority_bump: float = 0.1) -> Job:
    """§IX: 'increase the job's priority, migrate the job to that site'."""
    if not decision.migrate or decision.target is None:
        return job
    job.priority = min(1.0, job.priority + priority_bump)
    job.migrated = True
    job.site = decision.target
    return job


def migrate_congested(
    queues: MultilevelFeedbackQueues,
    local_name: str,
    poll_peers: Callable[[Job], list[PeerView]],
    local_cost: Callable[[Job], float],
    window: float,
    now: float,
    max_moves: Optional[int] = None,
) -> list[tuple[Job, str]]:
    """§X congestion response: while the arrival/service imbalance
    exceeds Thrs, push low-priority (Q4) jobs to better peers."""
    moved: list[tuple[Job, str]] = []
    if not queues.congested(window, now):
        return moved
    for job in list(queues.low_priority_jobs()):
        if max_moves is not None and len(moved) >= max_moves:
            break
        peers = poll_peers(job)
        decision = select_peer(
            job,
            local_name,
            queues.jobs_ahead(job.priority),
            local_cost(job),
            peers,
        )
        if decision.migrate and decision.target is not None:
            queues.remove(job)
            apply_migration(job, decision)
            moved.append((job, decision.target))
    return moved

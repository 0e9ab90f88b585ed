"""Pure §IV/§V placement engine over packed site views.

The split behind the decentralized deployment (paper §III/§IX): the
*algorithm* — cost planes, per-class ranking, selection, sequential
replay — owns no site state and runs against **any** ``SitePack``
view, fresh or stale. ``DianaScheduler`` (the omniscient single
scheduler) hands it packs built from its authoritative dicts;
``repro.core.p2p.PeerScheduler`` hands it the world view it assembled
from advertised rows. Results are a pure function of the view: the
same pack always yields the same placements, so the single-scheduler
path is exactly the special case of one peer with zero staleness.
"""
from __future__ import annotations

from typing import Optional, Sequence

import numpy as np

from .costs import CostWeights
from .queues import Job
from .scheduler import JobClass
from .batch import (
    BatchPlacement,
    JobPack,
    SitePack,
    TierPack,
    batched_argmin,
    batched_cost_matrix,
    hier_replay,
    hier_select,
    replay_on_pack,
)

__all__ = ["PlacementEngine"]


class PlacementEngine:
    """Stateless-by-construction §IV/§V evaluator: every method takes
    the pack it should believe. Only the cost weights are configuration.
    """

    def __init__(self, weights: CostWeights = CostWeights()):
        self.weights = weights

    # -- §IV -----------------------------------------------------------------
    def cost_matrix(
        self,
        jp: JobPack,
        sp: SitePack,
        *,
        mask_dead: bool = True,
        backend: str = "numpy",
    ) -> np.ndarray:
        """Per-class (J, S) §IV cost over the view; dead sites +inf.
        ``backend`` is ``"numpy"`` (float64 reference) or ``"kernel"``
        (the Pallas kernel on the TPU), as in ``batched_cost_matrix``."""
        return batched_cost_matrix(
            jp, sp, self.weights, mask_dead=mask_dead, backend=backend
        )

    # -- §V ------------------------------------------------------------------
    def rank(self, jp: JobPack, sp: SitePack) -> list[list[tuple[str, float]]]:
        """Ascending-cost ranking per job; dead sites stay in the
        ranking (selection skips them), like ``rank_sites``."""
        cost = self.cost_matrix(jp, sp, mask_dead=False)
        order = np.argsort(cost, axis=1, kind="stable")
        return [
            [(sp.names[s], float(cost[j, s])) for s in order[j]]
            for j in range(cost.shape[0])
        ]

    def select(self, jp: JobPack, sp: SitePack) -> BatchPlacement:
        """Snapshot selection: cheapest alive site per job against one
        frozen view (no feedback between rows)."""
        placement = batched_argmin(self.cost_matrix(jp, sp, mask_dead=True), sp)
        placement.classes = jp.classes
        return placement

    def replay(self, jp: JobPack, sp: SitePack) -> BatchPlacement:
        """Sequential-equivalent placement with per-row queue feedback;
        mutates the pack's queue/work columns (the caller commits them
        wherever its authority lives)."""
        return replay_on_pack(jp, sp, self.weights)

    # -- two-level ("hier") variants ------------------------------------------
    def select_hier(self, jp: JobPack, sp: SitePack, tp: TierPack) -> BatchPlacement:
        """``select`` through the tier bounds — bit-identical choices
        and costs without materializing the (J, S) plane."""
        return hier_select(jp, sp, tp, self.weights)

    def replay_hier(self, jp: JobPack, sp: SitePack, tp: TierPack) -> BatchPlacement:
        """``replay`` through the tier bounds — bit-identical, including
        the pack's queue/work feedback."""
        return hier_replay(jp, sp, tp, self.weights)

    # -- convenience ----------------------------------------------------------
    def pack_jobs(
        self,
        jobs: Sequence[Job],
        job_classes: Optional[Sequence[Optional[JobClass]]] = None,
    ) -> JobPack:
        return JobPack.from_jobs(jobs, job_classes)

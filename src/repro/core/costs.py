"""DIANA cost model (paper §IV).

Three cost terms, each expressed in *seconds* so they are directly
comparable and compose with the roofline terms derived from compiled
artifacts (see ``repro.grid.capacity``):

    Network Cost      = Losses / Bandwidth          (paper §IV)
    Computation Cost  = W5·Qi/Pi + W6·Q/Pi + W7·SiteLoad
    Data Transfer Cost = (input + output + executable bytes) / eff. bandwidth
    Total Cost        = Network + Computation + DTC

The paper cites Mathis et al. (TCP macroscopic model) for loss-dependent
path behaviour; ``mathis_throughput`` implements it and is used as the
*effective bandwidth* of lossy WAN links.

Scalar versions are plain Python (host control plane); ``*_vec``
versions are jnp and are the oracle for the ``cost_matrix`` Pallas
kernel (``repro.kernels.cost_matrix``).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax.numpy as jnp
import numpy as np

__all__ = [
    "NetworkLink",
    "SiteState",
    "CostWeights",
    "JobDemand",
    "mathis_throughput",
    "network_cost",
    "computation_cost",
    "computation_cost_of",
    "data_transfer_cost",
    "total_cost",
    "total_cost_matrix",
]


@dataclass(frozen=True)
class NetworkLink:
    """A (directed) network path between two sites.

    bandwidth_Bps: nominal path bandwidth, bytes/second.
    loss_rate:     packet loss fraction in [0, 1).
    rtt_s:         round-trip time, seconds.
    mss_bytes:     TCP maximum segment size (Mathis model).
    """

    bandwidth_Bps: float
    loss_rate: float = 0.0
    rtt_s: float = 0.05
    mss_bytes: float = 1460.0

    def effective_bandwidth(self) -> float:
        """Bandwidth usable by a bulk transfer: the nominal bandwidth
        capped by the Mathis TCP ceiling when the path is lossy."""
        if self.loss_rate <= 0.0:
            return self.bandwidth_Bps
        return min(self.bandwidth_Bps, mathis_throughput(self))


@dataclass
class SiteState:
    """Dynamic state of a site as seen by the meta-scheduler (§IV/§V)."""

    name: str
    capacity: float                  # Pi — processors (grid) or FLOP/s (pod)
    queue_length: float = 0.0        # Qi — jobs waiting in the site queue
    waiting_work: float = 0.0        # Q  — aggregate queued work (proc·hours or FLOPs)
    load: float = 0.0                # SiteLoad in [0, 1]
    alive: bool = True
    # Currently idle processors; None (unspecified) defaults to an idle
    # site. An explicit 0.0 means saturated and must stay 0.0 — the P2P
    # layer advertises this value grid-wide.
    free_slots: Optional[float] = field(default=None)

    def __post_init__(self) -> None:
        if self.capacity <= 0:
            raise ValueError(f"site {self.name}: capacity must be > 0")
        if self.free_slots is None:
            self.free_slots = self.capacity


@dataclass(frozen=True)
class CostWeights:
    """W5/W6/W7 of the computation-cost formula (paper §IV)."""

    w_queue: float = 1.0     # W5 — weight of Qi/Pi
    w_work: float = 1.0      # W6 — weight of Q/Pi
    w_load: float = 1.0      # W7 — weight of SiteLoad


@dataclass(frozen=True)
class JobDemand:
    """Data/compute demands of one job (or one group treated as a job)."""

    compute_work: float = 1.0        # processor·hours (grid) or FLOPs (pod)
    input_bytes: float = 0.0
    output_bytes: float = 0.0
    executable_bytes: float = 0.0

    @property
    def total_bytes(self) -> float:
        return self.input_bytes + self.output_bytes + self.executable_bytes


def mathis_throughput(link: NetworkLink) -> float:
    """Mathis et al. macroscopic TCP throughput: MSS/(RTT·sqrt(loss))."""
    if link.loss_rate <= 0.0:
        return link.bandwidth_Bps
    return link.mss_bytes / (link.rtt_s * math.sqrt(link.loss_rate))


def network_cost(link: NetworkLink) -> float:
    """Paper §IV: ``Network Cost = Losses / Bandwidth``.

    Dimensionally this is the per-byte penalty of a lossy path; a
    loss-free path costs 0 and a saturated lossy path costs
    loss/bandwidth seconds per byte, scaled to a canonical 1 MB probe so
    the term is comparable with the other (seconds) terms.
    """
    return (link.loss_rate / link.bandwidth_Bps) * 1.0e6


def computation_cost(
    site: SiteState, weights: CostWeights = CostWeights()
) -> float:
    """Paper §IV: W5·Qi/Pi + W6·Q/Pi + W7·SiteLoad."""
    return computation_cost_of(
        site.queue_length, site.waiting_work, site.load, site.capacity, weights
    )


def computation_cost_of(
    queue_length: float, waiting_work: float, load: float, capacity: float,
    weights: CostWeights,
) -> float:
    """``computation_cost`` from a site's numbers, for a caller that
    keeps them without a ``SiteState``."""
    return (
        weights.w_queue * queue_length / capacity
        + weights.w_work * waiting_work / capacity
        + weights.w_load * load
    )


def data_transfer_cost(demand: JobDemand, link: NetworkLink) -> float:
    """Paper §IV: input + output + executable transfer time (seconds)."""
    bw = link.effective_bandwidth()
    return demand.total_bytes / bw


def total_cost(
    demand: JobDemand,
    site: SiteState,
    link: NetworkLink,
    weights: CostWeights = CostWeights(),
) -> float:
    """Paper §IV: Total = Network + Computation + DTC."""
    return (
        network_cost(link)
        + computation_cost(site, weights)
        + data_transfer_cost(demand, link)
    )


# ---------------------------------------------------------------------------
# Vectorized (jobs × sites) cost matrix — oracle for the Pallas kernel.
# ---------------------------------------------------------------------------

def total_cost_matrix(
    job_bytes: jnp.ndarray,       # (J,) total bytes to move per job
    job_work: jnp.ndarray,        # (J,) compute work per job
    site_capacity: jnp.ndarray,   # (S,)
    site_queue: jnp.ndarray,      # (S,) Qi
    site_work: jnp.ndarray,       # (S,) Q (aggregate queued work)
    site_load: jnp.ndarray,       # (S,)
    link_bandwidth: jnp.ndarray,  # (S,) nominal bytes/s toward each site
    link_loss: jnp.ndarray,       # (S,)
    alive: jnp.ndarray,           # (S,) bool
    weights: CostWeights = CostWeights(),
    link_rtt: jnp.ndarray | float = 0.05,
    mss_bytes: float = 1460.0,
) -> jnp.ndarray:
    """Return the (J, S) total-cost matrix; dead sites get +inf.

    Row j, column s is the §IV total cost of running job j at site s.
    ``job_work / capacity`` augments the W5/W6 queue terms with the
    job's own service time so bulk groups of different sizes rank sites
    correctly (§VIII capacity matching). Lossy links are Mathis-capped
    exactly like ``NetworkLink.effective_bandwidth``.
    """
    job_bytes = jnp.asarray(job_bytes, jnp.float32)[:, None]     # (J,1)
    job_work = jnp.asarray(job_work, jnp.float32)[:, None]       # (J,1)
    cap = jnp.asarray(site_capacity, jnp.float32)[None, :]       # (1,S)
    bw = jnp.asarray(link_bandwidth, jnp.float32)
    loss = jnp.asarray(link_loss, jnp.float32)
    rtt = jnp.broadcast_to(jnp.asarray(link_rtt, jnp.float32), bw.shape)
    mathis = mss_bytes / (rtt * jnp.sqrt(jnp.maximum(loss, 1e-12)))
    eff_bw = jnp.where(loss > 0.0, jnp.minimum(bw, mathis), bw)
    net = (loss / bw)[None, :] * 1.0e6
    comp_site = (
        weights.w_queue * jnp.asarray(site_queue, jnp.float32)
        + weights.w_work * jnp.asarray(site_work, jnp.float32)
    )[None, :] / cap + weights.w_load * jnp.asarray(site_load, jnp.float32)[None, :]
    comp = comp_site + job_work / cap
    dtc = job_bytes / eff_bw[None, :]
    cost = net + comp + dtc
    return jnp.where(jnp.asarray(alive, bool)[None, :], cost, jnp.inf)

"""Grids and job demands drawn from a seed.

* ``wlcg_sites``: the WLCG tiers of a configuration (a Tier-0, the
  Tier-1s, the Tier-2s), each tier with its own capacity and WAN link
  class, spread evenly over its ranges so that the seed changes where
  each value lies and not which values there are; every Tier-2 belongs
  to the region of one Tier-1, as in the MONARC model, and each region
  is one RootGrid of the topology;
* ``demands``: the job mix of ``cms_case_study`` in
  ``repro/sim/workloads.py`` (the paper's §II CMS estimates): users
  drawn uniformly, lognormal work and dataset size, output a fixed
  share of the input;
* ``pair_links``: a pair's link from its two endpoints, routed through
  the Tier-0 hub.

The draws are vectorised where the original looped; the distributions
are the originals'. Everything here is plain NumPy: the reference reads
these arrays, and ``Grid.scheduler_inputs`` builds the program's input
objects from them.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np

__all__ = ["Grid", "Demands", "rng_for", "make_grid", "demands", "pair_links"]


def rng_for(seed: int, *tags: int) -> np.random.Generator:
    """An independent stream per (seed, tags); any whole seed works."""
    return np.random.default_rng(np.random.SeedSequence([seed % 2**64, *tags]))


@dataclass
class Grid:
    """Per-site state and links, in site order (the scheduler's dict
    order). ``tier`` is the RootGrid index of each site, or None;
    ``role`` the index of each site's tier in the configuration."""

    names: list[str]
    cap: np.ndarray
    queue: np.ndarray
    work: np.ndarray
    load: np.ndarray
    alive: np.ndarray
    bw: np.ndarray
    loss: np.ndarray
    rtt: np.ndarray
    mss: np.ndarray
    tier: Optional[np.ndarray] = None
    role: Optional[np.ndarray] = None

    def __len__(self) -> int:
        return len(self.names)

    def scheduler_inputs(self):
        """Fresh ``(sites, links, topology)`` for ``DianaScheduler``."""
        from repro.core import GridTopology, NetworkLink, Node, SiteState

        sites, links = {}, {}
        for i, n in enumerate(self.names):
            sites[n] = SiteState(
                name=n, capacity=float(self.cap[i]),
                queue_length=float(self.queue[i]),
                waiting_work=float(self.work[i]),
                load=float(self.load[i]), alive=bool(self.alive[i]),
            )
            links[n] = NetworkLink(
                bandwidth_Bps=float(self.bw[i]), loss_rate=float(self.loss[i]),
                rtt_s=float(self.rtt[i]), mss_bytes=float(self.mss[i]),
            )
        topology = None
        if self.tier is not None:
            topology = GridTopology()
            for i, n in enumerate(self.names):
                topology.join(f"root{int(self.tier[i]):03d}", Node(name=n))
        return sites, links, topology

    def sites_of(self, role: str, config: dict) -> np.ndarray:
        """Indices of the sites of the configuration's tier ``role``."""
        names = [t["name"] for t in config["tiers"]]
        return np.flatnonzero(self.role == names.index(role))


@dataclass
class Demands:
    """Job demands: user index, compute work, input and output bytes."""

    user: np.ndarray
    work: np.ndarray
    input_bytes: np.ndarray
    output_bytes: np.ndarray

    def __len__(self) -> int:
        return len(self.work)

    def jobs(self, lo: int = 0, hi: Optional[int] = None):
        """The program's ``Job`` objects for rows ``lo:hi``."""
        from repro.core import Job

        hi = len(self) if hi is None else hi
        names = [f"u{u}" for u in range(int(self.user.max(initial=0)) + 1)]
        rows = zip(self.user[lo:hi].tolist(), self.work[lo:hi].tolist(),
                   self.input_bytes[lo:hi].tolist(), self.output_bytes[lo:hi].tolist())
        return [Job(user=names[u], compute_work=w, input_bytes=i, output_bytes=o)
                for u, w, i, o in rows]


def _spread(rng: np.random.Generator, lo: float, hi: float, n: int) -> np.ndarray:
    """``n`` values evenly spread over [lo, hi), in an order drawn from
    ``rng``: every seed gets the same values, at other sites."""
    return lo + (hi - lo) * (rng.permutation(n) + 0.5) / n


def wlcg_sites(cfg: dict, rng: np.random.Generator) -> Grid:
    """The configuration's tiers in order, each tier's sites taking its
    ranges' values (``_spread``) in an order drawn from the seed; a
    tier's ``lossless_share`` of its sites has no loss. The first tier
    is the hub, its own region; each site of ``regions`` opens a region,
    and the sites of every later tier join those regions in turn."""
    tiers, s = cfg["tiers"], cfg["site_state"]
    role = np.concatenate([np.full(t["sites"], k) for k, t in enumerate(tiers)])
    n = len(role)
    cap = np.empty(n)
    bw, loss, rtt = np.empty(n), np.empty(n), np.empty(n)
    for k, t in enumerate(tiers):
        m = role == k
        c = int(m.sum())
        cap[m] = np.floor(_spread(rng, *t["capacity"], c))
        bw[m] = _spread(rng, *t["bandwidth_Bps"], c)
        lossless = rng.permutation(c) < round(t["lossless_share"] * c)
        loss[m] = np.where(lossless, 0.0, _spread(rng, *t["loss_rate"], c))
        rtt[m] = _spread(rng, *t["rtt_s"], c)
    queue = np.floor(_spread(rng, *s["queue_length"], n))
    work = _spread(rng, *s["waiting_work"], n)
    load = _spread(rng, *s["load"], n)
    names = [t["name"] for t in tiers]
    opener = names.index(cfg["regions"])
    regions = tiers[opener]["sites"]
    tier = np.zeros(n, np.int64)
    first = int(np.flatnonzero(role == opener)[0])
    tier[first:first + regions] = 1 + np.arange(regions)
    later = np.flatnonzero(role > opener)
    tier[later] = 1 + np.arange(len(later)) % regions
    return Grid(
        names=[f"s{i:05d}" for i in range(n)], cap=cap, queue=queue, work=work,
        load=load, alive=np.ones(n, bool), bw=bw, loss=loss, rtt=rtt,
        mss=np.full(n, float(cfg["mss_bytes"])), tier=tier, role=role,
    )


GRIDS = {"wlcg": wlcg_sites}


def make_grid(cfg: dict, seed: int) -> Grid:
    """The configuration's grid for this seed."""
    return GRIDS[cfg["grid"]](cfg, rng_for(seed, 0))


def demands(cfg: dict, n: int, rng: np.random.Generator) -> Demands:
    """``n`` jobs of the configuration's mix (``cms_case_study``)."""
    d = cfg["jobs"]
    data = rng.lognormal(*d["input_gb_lognormal"], size=n) * 1e9
    return Demands(
        user=rng.integers(0, d["users"], size=n),
        work=rng.lognormal(*d["work_lognormal"], size=n),
        input_bytes=data,
        output_bytes=data * d["output_per_input"],
    )


def pair_links(grid: Grid) -> dict[str, np.ndarray]:
    """The (S, S) link planes of a pairwise grid, each pair routed
    through the hub: its bandwidth is the lower endpoint's, its loss the
    higher's (none on the diagonal), its RTT the sum of the two."""
    bw = np.minimum(grid.bw[:, None], grid.bw[None, :])
    loss = np.maximum(grid.loss[:, None], grid.loss[None, :])
    np.fill_diagonal(loss, 0.0)
    rtt = grid.rtt[:, None] + grid.rtt[None, :]
    return {"bw": bw, "loss": loss, "rtt": rtt,
            "mss": np.full(bw.shape, float(grid.mss[0]))}

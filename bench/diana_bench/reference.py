"""Plain reference of DIANA's §IV cost and §V selection.

Written from the paper's formulas and the scheduler's documented
semantics, importing nothing of the program. One job at a time, as the
paper states the loop ("after every job we calculate the cost to submit
the next job"):

    network   = loss / bandwidth * 1e6
    compute   = W5 * Qi / Pi + W6 * Q / Pi + W7 * load + work / Pi
    transfer  = (input + output) bytes / effective bandwidth,
                effective bandwidth = min(bandwidth, MSS / (RTT sqrt(loss)))
                on a lossy link (Mathis)
    class     = BOTH when data > 1 GB and work > 1, DATA when only data
                is, else COMPUTE
    key       = COMPUTE: compute + network; DATA: transfer + network;
                BOTH: network + compute + transfer
    choice    = the cheapest live site; the first in site order on a tie
    commit    = Qi += 1, Q += work; release: Qi -= 1, Q -= work, floored at 0

Every operation is elementwise over the site vector, in the order
written above, so float64 results equal the scalar formulas bit for
bit. The part of ``compute`` that does not depend on the job is kept
per site and recomputed, by the same formula, only at the site a
commit or release changed; dead sites carry an infinite network term,
so every key is infinite there. ``dtype=np.float32`` gives the control:
the same loop one precision below what the configuration states.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from .grids import Grid

__all__ = ["Placed", "SchedulerReference"]

W_QUEUE = W_WORK = W_LOAD = 1.0


@dataclass
class Placed:
    site: np.ndarray   # (J,) site index per job
    cost: np.ndarray   # (J,) chosen site's cost


class SchedulerReference:
    """Sequential §IV/§V placement over a grid's pristine arrays."""

    def __init__(self, grid: Grid, dtype=np.float64):
        f = self.f = np.dtype(dtype).type
        self.cap = grid.cap.astype(f)
        self.load = grid.load.astype(f)
        self.dead = ~grid.alive
        bw, loss = grid.bw.astype(f), grid.loss.astype(f)
        rtt, mss = grid.rtt.astype(f), grid.mss.astype(f)
        self.net = (loss / bw) * f(1.0e6)
        self.net_live = np.where(self.dead, f(np.inf), self.net).astype(f)
        with np.errstate(divide="ignore", invalid="ignore"):
            mathis = mss / (rtt * np.sqrt(loss))
        self.eff = np.where(loss > 0.0, np.minimum(bw, mathis), bw).astype(f)
        self.q0 = grid.queue.astype(f)
        self.w0 = grid.work.astype(f)
        self.reset()

    def reset(self) -> None:
        """Back to the grid's generated queue state."""
        self.set_state(self.q0, self.w0)

    def set_state(self, q, w) -> None:
        """Every site's queue length and waiting work."""
        f, cap = self.f, self.cap
        self.q = np.array(q, f)
        self.w = np.array(w, f)
        self.base = (f(W_QUEUE) * self.q / cap + f(W_WORK) * self.w / cap) + f(W_LOAD) * self.load

    def _rebase(self, s: int) -> None:
        f, cap = self.f, self.cap
        self.base[s] = (
            f(W_QUEUE) * self.q[s] / cap[s] + f(W_WORK) * self.w[s] / cap[s]
        ) + f(W_LOAD) * self.load[s]

    def place_one(self, work: float, input_bytes: float, output_bytes: float):
        f = self.f
        work, total = f(work), f(input_bytes) + f(output_bytes)
        data = total / f(1e9) > 1.0
        compute = work > 1.0
        if data and compute:
            key = (self.net_live + (self.base + work / self.cap)) + total / self.eff
        elif data:
            key = total / self.eff + self.net_live
        else:
            key = (self.base + work / self.cap) + self.net_live
        s = int(np.argmin(key))
        if not np.isfinite(key[s]):
            raise RuntimeError("no live site")
        self.q[s] += f(1.0)
        self.w[s] += work
        self._rebase(s)
        return s, key[s]

    def place(self, work, input_bytes, output_bytes) -> Placed:
        """Place jobs in order, committing each before the next."""
        J = len(work)
        site = np.empty(J, np.int64)
        cost = np.empty(J, np.float64)
        for j in range(J):
            site[j], cost[j] = self.place_one(work[j], input_bytes[j], output_bytes[j])
        return Placed(site, cost)

    def complete(self, site: int, work: float) -> None:
        f = self.f
        self.q[site] = max(f(0.0), self.q[site] - f(1.0))
        self.w[site] = max(f(0.0), self.w[site] - f(work))
        self._rebase(site)

"""Simulator traces drawn from a seed.

The mix is the paper's §XI queueing experiment (Figs 7 and 8, as
``benchmarks/fig7_8_queue_exec.py`` states it): every job has the same
work, input and output, the users take turns, every job is submitted
from one site and reads its dataset at another, and a job arrives every
1.5 s on the paper's 24-node test grid. Here the arrivals keep that
spacing per node (``node_seconds_between_arrivals`` over the grid's
nodes), the submission site is the configuration's ``origin_tier`` and
each job's dataset lies at a site of ``data_tier`` drawn from the seed.
"""
from __future__ import annotations

import numpy as np

__all__ = ["sim_trace"]


def sim_trace(tr: dict, nodes: np.ndarray, origin: int, data_sites: np.ndarray,
              rng: np.random.Generator) -> dict:
    """One trace of ``tr["trace_jobs"]`` jobs over sites with ``nodes``
    nodes each; arrays in arrival order."""
    J = tr["trace_jobs"]
    gap = tr["node_seconds_between_arrivals"] / float(nodes.sum())
    return {
        "user": np.arange(J) % tr["users"],
        "arrival": np.arange(J) * gap,
        "work": np.full(J, float(tr["work_s"])),
        "input_bytes": np.full(J, float(tr["input_bytes"])),
        "output_bytes": np.full(J, float(tr["output_bytes"])),
        "data_site": rng.choice(data_sites, size=J),
        "origin_site": np.full(J, origin),
    }

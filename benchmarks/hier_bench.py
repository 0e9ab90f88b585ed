"""BENCH: two-level ("hier") placement vs the flat dense argmin.

The bulk regime the hierarchy targets: 10⁴ sites × 10⁵ jobs. The flat
path materializes the (J, S) §IV data-transfer plane — ~8 GB at the
headline size — while the hier path keeps only per-tier summaries and
per-site columns, prunes tiers by admissible §IV lower bounds, f32
shortlists within the winning tier(s) and refines exactly. Decisions
are bit-identical; the win is wall clock and, above all, peak memory.

Sites are tier-structured (each tier draws its WAN quality around a
tier-characteristic bandwidth/loss/RTT — the locality premise behind
the RootGrid hierarchy). On structureless uniform-random link tables
the tier bounds cannot prune and hier degrades to a slower dense scan;
that regime stays on the flat ``place_batch``.

Writes ``BENCH_hier.json`` (the scale record) when run as a script:

    PYTHONPATH=src python benchmarks/hier_bench.py [--jobs N] [--sites S] [--tiers T]
"""
from __future__ import annotations

import argparse
import copy
import json
import pathlib
import time
import tracemalloc

import numpy as np

from repro.core import DianaScheduler, Job, NetworkLink, SiteState

try:
    from .common import emit
except ImportError:                       # run as a script
    from common import emit


def _build_core(sites_n: int, tiers_n: int, jobs_n: int, seed: int = 0):
    """Tier-structured single-origin grid + bulk workload."""
    rng = np.random.default_rng(seed)
    sites, links, tiers = {}, {}, {}
    tier_bw = rng.uniform(1e8, 1e10, tiers_n)
    tier_loss = rng.uniform(1e-4, 0.03, tiers_n)
    tier_rtt = rng.uniform(0.005, 0.3, tiers_n)
    for i in range(sites_n):
        t = i % tiers_n
        n = f"s{i:05d}"
        tiers[n] = f"t{t:03d}"
        sites[n] = SiteState(
            name=n, capacity=float(rng.integers(50, 2000)),
            queue_length=float(rng.integers(0, 50)),
            waiting_work=float(rng.uniform(0, 500)),
            load=float(rng.uniform(0, 1)),
            alive=bool(rng.uniform() > 0.02),
        )
        links[n] = NetworkLink(
            bandwidth_Bps=float(tier_bw[t] * rng.uniform(0.8, 1.25)),
            loss_rate=float(tier_loss[t] * rng.uniform(0.8, 1.25)),
            rtt_s=float(tier_rtt[t] * rng.uniform(0.8, 1.25)),
        )
    jobs = [
        Job(user=f"u{i % 7}", compute_work=float(rng.uniform(0.1, 100)),
            input_bytes=float(rng.uniform(0, 30e9)),
            output_bytes=float(rng.uniform(0, 2e9)))
        for i in range(jobs_n)
    ]
    return sites, links, jobs, tiers


def _place(sites, links, jobs, mode, tiers=None):
    d = DianaScheduler(copy.deepcopy(sites), dict(links))
    js = copy.deepcopy(jobs)
    t0 = time.perf_counter()
    if mode == "hier":
        placement = d.place_batch(js, mode="hier", tiers=tiers)
    else:
        placement = d.place_batch(js)
    return placement, time.perf_counter() - t0


def _peak_bytes(sites, links, jobs, mode, tiers=None) -> int:
    """Peak traced allocation of one placement pass (separate from the
    wall pass — tracemalloc's hooks would distort the timing)."""
    d = DianaScheduler(copy.deepcopy(sites), dict(links))
    js = copy.deepcopy(jobs)
    tracemalloc.start()
    tracemalloc.reset_peak()
    if mode == "hier":
        d.place_batch(js, mode="hier", tiers=tiers)
    else:
        d.place_batch(js)
    _, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    return int(peak)


def bench_scale(jobs: int = 100_000, sites: int = 10_000,
                tiers_n: int = 100, seed: int = 0) -> dict:
    """Headline: flat vs hier ``place_batch`` at scale, wall + peak
    memory, with assignments asserted bit-identical."""
    site_d, link_d, job_list, tier_d = _build_core(sites, tiers_n, jobs, seed)

    hier_p, hier_s = _place(site_d, link_d, job_list, "hier", tier_d)
    flat_p, flat_s = _place(site_d, link_d, job_list, "flat")
    assert hier_p.sites == flat_p.sites, "hier placement diverged from flat"
    assert list(hier_p.costs) == list(flat_p.costs)

    hier_peak = _peak_bytes(site_d, link_d, job_list, "hier", tier_d)
    flat_peak = _peak_bytes(site_d, link_d, job_list, "flat")
    return {
        "bench": "hier_scale",
        "config": {"jobs": jobs, "sites": sites, "tiers": tiers_n, "seed": seed},
        "flat_s": round(flat_s, 3),
        "hier_s": round(hier_s, 3),
        "wall_speedup": round(flat_s / hier_s, 2),
        "flat_peak_mb": round(flat_peak / 1e6, 1),
        "hier_peak_mb": round(hier_peak / 1e6, 1),
        "peak_mem_ratio": round(flat_peak / max(1, hier_peak), 1),
        "identical_assignments": True,
    }


def run() -> dict:
    """Harness entry (reduced size to stay quick)."""
    rec = bench_scale(jobs=5_000, sites=2_000, tiers_n=40)
    emit(
        "hier_vs_flat_place_batch", rec["hier_s"] * 1e6,
        f"wall={rec['wall_speedup']}x mem={rec['peak_mem_ratio']}x "
        f"over {rec['config']['jobs']}x{rec['config']['sites']}",
    )
    return rec


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=100_000)
    ap.add_argument("--sites", type=int, default=10_000)
    ap.add_argument("--tiers", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--smoke", action="store_true",
                    help="toy-size bit-identity gate; no JSON written")
    args = ap.parse_args()
    if args.smoke:
        rec = bench_scale(jobs=2_000, sites=64, tiers_n=4, seed=args.seed)
        print("BENCH " + json.dumps(rec))
        raise SystemExit(0)
    rec = bench_scale(args.jobs, args.sites, args.tiers, args.seed)
    print("BENCH " + json.dumps(rec))
    out = pathlib.Path(__file__).resolve().parent.parent / "BENCH_hier.json"
    out.write_text(json.dumps({"rows": [], "result": rec}, indent=2) + "\n")
    print(f"wrote {out}")

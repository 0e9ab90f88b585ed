"""Find the highest Poisson rate the open-arrival cell sustains.

    python3 bench/tools/knee_sweep.py --workload arrivals_poisson.wlcg_cms \
        --rates 5000,10000,20000 --seconds 10 --seed 7

One process, on the TPU, runs the cell's window once per rate (the
traffic file's ``rate_per_s`` overridden) and prints, per rate, the
latency quantiles and the mean backlog (jobs due but not yet placed,
which is each call's batch) in each quarter of the window. A rate is
sustained when the backlog does not grow from the first quarter to the
last. The rate is found once and written into the traffic file.
"""
import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from diana_bench.harness import Suite, _configure_jax, run_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", default="arrivals_poisson.wlcg_cms")
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--cpu", action="store_true", help="rehearse off the TPU")
    args = ap.parse_args()
    import jax

    _configure_jax(jax)
    suite = Suite()
    for rate in (float(r) for r in args.rates.split(",")):
        over = {"rate_per_s": rate}
        captured = {}
        driver = suite.driver(suite.traffic(suite.cell(args.workload)["traffic"])["driver"])
        window = driver.window

        def keep(state, ctx, t0, window=window):
            res = window(state, ctx, t0)
            captured["series"] = res.series
            return res

        driver.window = keep
        suite.driver = lambda name, d=driver: d
        line = run_cell(suite, args.workload, args.seed, args.seconds, False,
                        t_start=time.perf_counter(), traffic_override=over,
                        require_tpu=not args.cpu)
        s = captured["series"]
        due, batch = np.asarray(s["call_due_s"]), np.asarray(s["batch_jobs"])
        q = [float(batch[(due >= lo) & (due < lo + args.seconds / 4)].mean())
             for lo in np.arange(4) * args.seconds / 4]
        lat = np.asarray(s["latency_s"]) * 1e3
        print(json.dumps({
            "rate_per_s": rate, "jobs": int(len(lat)), "window_s": s["window_s"][0],
            "p50_ms": float(np.percentile(lat, 50)), "p95_ms": float(np.percentile(lat, 95)),
            "p99_ms": float(np.percentile(lat, 99)), "max_ms": float(lat.max()),
            "backlog_by_quarter": q, "calls": int(len(batch)),
            "call_p50_ms": float(np.median(s["call_s"]) * 1e3),
            "correct": line["correct"],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

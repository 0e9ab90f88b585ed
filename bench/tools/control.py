"""Show that each cell's check fails for the control and the faults.

    python3 bench/tools/control.py --workloads bulk_10k.wlcg_cms \
        --seeds 11,12,13 --seconds 10 [--faults]

For each cell and seed, one process runs the cell's whole window and
check with the control (the plain reference in float32, one precision
below what the configurations state: ``ControlScheduler``, or
``SimControl`` for the simulator cell) in the program's place, and with
``--faults`` once more per planted fault of ``diana_bench.control``. It prints each run's compared numbers;
every one of these runs has to come out not correct.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from diana_bench.control import (  # noqa: E402
    FAULTS, SIM_FAULTS, ControlScheduler, SimControl, faulty_scheduler, faulty_sim,
)
from diana_bench.harness import Suite, _configure_jax, run_cell  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workloads", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--cpu", action="store_true", help="rehearse off the TPU")
    args = ap.parse_args()
    import jax

    _configure_jax(jax)
    suite = Suite()
    for w in args.workloads.split(","):
        sim = suite.traffic(suite.cell(w)["traffic"])["driver"] == "sim_stream"
        kinds = {"control_float32": SimControl if sim else ControlScheduler}
        if args.faults:
            kinds.update({f: faulty_sim(f) if sim else faulty_scheduler(f)
                          for f in (SIM_FAULTS if sim else FAULTS)})
        for seed in (int(s) for s in args.seeds.split(",")):
            for kind, factory in kinds.items():
                line = run_cell(suite, w, seed, args.seconds, False,
                                t_start=time.perf_counter(), scheduler_factory=factory,
                                require_tpu=not args.cpu)
                print(json.dumps({"workload": w, "seed": seed, "kind": kind,
                                  "correct": line["correct"], "attempted": line["attempted"],
                                  "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

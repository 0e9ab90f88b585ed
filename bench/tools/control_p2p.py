"""Show that the P2P cell's check fails for the control and the faults.

    python3 bench/tools/control_p2p.py --seeds 11,12,13 --seconds 10 [--faults]

For each seed, one process runs ``p2p_stream.wlcg_cms_p2p``'s whole
window and check with the control (the plain P2P reference in float32,
one precision below what the configuration states: ``P2PControl``) in
the program's place, and with ``--faults`` once more with the
omniscient ``GridSim`` and once per planted fault of
``diana_bench.control_p2p``. It prints each run's compared numbers;
every one of these runs has to come out not correct.
"""
import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from diana_bench.control_p2p import P2P_FAULTS, P2PControl  # noqa: E402
from diana_bench.harness import Suite, _configure_jax, run_cell  # noqa: E402

CELL = "p2p_stream.wlcg_cms_p2p"


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--faults", action="store_true")
    ap.add_argument("--cpu", action="store_true", help="rehearse off the TPU")
    args = ap.parse_args()
    import jax

    _configure_jax(jax)
    kinds = {"control_float32": P2PControl}
    if args.faults:
        kinds.update({f: make() for f, make in P2P_FAULTS.items()})
    for seed in (int(s) for s in args.seeds.split(",")):
        for kind, factory in kinds.items():
            line = run_cell(Suite(), CELL, seed, args.seconds, False,
                            t_start=time.perf_counter(), scheduler_factory=factory,
                            require_tpu=not args.cpu)
            print(json.dumps({"workload": CELL, "seed": seed, "kind": kind,
                              "correct": line["correct"], "attempted": line["attempted"],
                              "checks": line["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Run one cell once with --trace 1 and save the collected trace events
(the format of ``bench/tests/fixtures/trace_tpu_v5e.json``'s ``trace``).

    python3 bench/tools/keep_trace.py <workload> <seed> <seconds> <out.json>
"""
import time

T_START = time.perf_counter()

import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

from diana_bench.harness import Suite, _configure_jax, run_cell  # noqa: E402


def main() -> int:
    import jax

    _configure_jax(jax)
    workload, seed, seconds, out = sys.argv[1], int(sys.argv[2]), float(sys.argv[3]), sys.argv[4]
    line = run_cell(Suite(), workload, seed, seconds, True, t_start=T_START, keep_trace=out)
    print(json.dumps(line))
    return 0


if __name__ == "__main__":
    sys.exit(main())

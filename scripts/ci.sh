#!/usr/bin/env bash
# Fast CI tier: everything except the multi-minute dryrun/model-compile
# tests (marked `slow`), plus a toy-size migration bench smoke so the
# batched §IX path is exercised end to end. Target: < 60 s on a
# laptop-class CPU.
#
#   scripts/ci.sh               # fast tier
#   scripts/ci.sh -k batch      # extra pytest args pass through
#   RUN_SLOW=1 scripts/ci.sh    # full suite, slow tests included
set -euo pipefail
cd "$(dirname "$0")/.."
export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"
if [[ "${RUN_SLOW:-0}" == "1" ]]; then
    python -m pytest -q "$@"
else
    python -m pytest -q -m "not slow" "$@"
fi
# Bench smoke: sequential-vs-batched migration must stay bit-identical
# at toy size (asserts inside the bench; no JSON written).
python benchmarks/migration_bench.py --jobs 100 --sites 16 --smoke
# Compressed-P2P smoke (16 sites × 3 peers): the 1-peer/zero-staleness
# multi-scheduler sim must be bit-identical to the omniscient GridSim
# under BOTH wire formats — delta compression and f32 quantization must
# never touch placement when every site is home — and a 3-peer
# delta-wire run must complete every job (asserts inside the bench; no
# JSON written).
python benchmarks/p2p_bench.py --sites 16 --peers 3 --jobs 200 --smoke
# Chaos smoke (16 sites × 3 peers over a faulty transport): a zero-rate
# TransportFaults must be bit-identical to no transport at all on both
# wires, and a small lossy run (10% loss + 2% duplication + reorder
# jitter) must drop, retransmit, finish every job and reconverge every
# peer's world view (asserts inside the bench; no JSON written).
python benchmarks/p2p_bench.py --sites 16 --peers 3 --jobs 200 --chaos-smoke
# Streaming smoke (~20k jobs × 64 sites): the batched event-horizon
# loop must stay bit-identical to the per-event reference loop (GridSim
# AND P2PGridSim), and an open-loop lazy-ArrivalSource run must finish
# every job with bounded in-flight state and zero retained per-job
# records (asserts inside the bench; no JSON written).
python benchmarks/streaming_bench.py --smoke
# Hier-placement smoke (2k jobs × 64 sites / 4 tiers): place_batch's
# two-level tier-bound path must stay bit-identical to the flat dense
# argmin (asserts inside the bench; no JSON written).
python benchmarks/hier_bench.py --smoke
# Scenario-pack smoke (4 scenarios, ~200 jobs × 16 sites each): every
# generator × verifier pair end to end — fault plans interleaved into
# the run, invariants asserted, metrics checked against the recorded
# baseline envelopes. ScenarioViolation fails the build. (~2 s total.)
python -m repro.scenarios smoke

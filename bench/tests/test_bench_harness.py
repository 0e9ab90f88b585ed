"""The harness: it refuses the CPU, and it finds a cell's files by name."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def test_run_exits_nonzero_with_no_result_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "bulk_10k.wlcg_cms",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
    assert "needs a TPU" in proc.stderr


def test_added_files_are_found_by_name(tmp_path, run_toy):
    """A new configuration, traffic mix and per-layer metric are files
    and entries of their own: the harness finds them with no edit to a
    file that is there."""
    before = {p: p.read_bytes() for p in BENCH.rglob("*.json")}
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".jax_cache", "__pycache__", "tests"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "wlcg_cms.json").read_text())
    tiers = [dict(t, sites=min(t["sites"], 4)) for t in cfg["tiers"]]
    (b / "configs" / "tiny_64.json").write_text(json.dumps(dict(cfg, name="tiny_64", tiers=tiers)))
    (b / "traffic" / "bulk_small.json").write_text(
        json.dumps({"driver": "closed_groups", "group_jobs": 100, "distinct_groups": 2}))
    (b / "metrics" / "replay_share.tiny.json").write_text(json.dumps(
        {"reducer": "host_share", "functions": ["repro/core/batch.py:replay_on_pack"]}))
    spec["configs"].append({"name": "tiny_64", "source": "https://arxiv.org/abs/cs/0608048",
                            "file": "bench/configs/tiny_64.json", "reduced": ["tiers"],
                            "why": "a test"})
    spec["workloads"].append({"name": "bulk_small.tiny_64", "config": "tiny_64",
                              "traffic": "bulk_small", "chips": 1, "why": "a test"})
    for m in spec["end_to_end"]:
        if m["name"] == "bulk_decisions_per_s":
            m["workloads"].append("bulk_small.tiny_64")
    spec["per_layer"].append({"name": "replay_share.tiny", "unit": "%", "better": "lower",
                              "source": "program_span", "layer": "selection and replay",
                              "moves": "bulk_decisions_per_s",
                              "workloads": ["bulk_small.tiny_64"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))

    from diana_bench.harness import Suite

    suite = Suite(tmp_path)
    assert [t["sites"] for t in suite.config("tiny_64")["tiers"]] == [1, 4, 4]
    assert [m["name"] for m in suite.per_layer("bulk_small.tiny_64")] == ["replay_share.tiny"]
    line = run_toy("bulk_small.tiny_64", suite=suite)
    assert line["correct"]
    assert set(line["metrics"]) == {"bulk_decisions_per_s", "setup_s"}
    assert before == {p: p.read_bytes() for p in BENCH.rglob("*.json")}

"""Dry-run path smoke: reduced configs, small forced-device mesh, in a
subprocess (XLA device count is locked at first jax init)."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

pytestmark = pytest.mark.slow  # multi-minute subprocess compiles


def _run_cell(tmp_path, arch, shape, mesh="2x4"):
    # CPU virtual devices: the child must not contend for a TPU
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", arch,
           "--shape", shape, "--mesh", mesh, "--reduced",
           "--out", str(tmp_path)]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    arts = list(tmp_path.glob("*.json"))
    assert len(arts) == 1
    return json.loads(arts[0].read_text())


@pytest.mark.parametrize("arch,shape", [
    ("gemma3-12b", "train_4k"),          # flags-scan dense + patterns
    ("deepseek-v2-236b", "train_4k"),    # MLA + MoE
    ("mamba2-780m", "decode_32k"),       # SSM decode cache
    ("recurrentgemma-2b", "prefill_32k"),  # hybrid periods
])
def test_reduced_cell_compiles_and_reports(tmp_path, arch, shape):
    rec = _run_cell(tmp_path, arch, shape)
    assert rec["arch"] == arch
    t = rec["roofline_terms"]
    assert all(v >= 0 for v in t.values())
    assert rec["dominant_term"] in ("compute_s", "memory_s", "collective_s")
    assert rec["memory"]["argument_bytes"] > 0
    if shape.startswith("train"):
        assert rec["cost"]["hlo_flops"] > 0
        assert rec["params"]["total"] > 0


def test_multi_pod_axis_shards(tmp_path):
    """The 'pod' axis must actually divide the work: a 2x2x2 mesh
    compiles and the batch shards over (pod, data)."""
    rec = _run_cell(tmp_path, "gemma2-9b", "train_4k", mesh="2x2x2")
    assert rec["n_devices"] == 8
    assert rec["roofline_terms"]["compute_s"] >= 0

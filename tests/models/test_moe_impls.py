"""gather- vs a2a-dispatch MoE equivalence (dropless capacity) on a
multi-device mesh, in a subprocess (forced host device count)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

pytestmark = pytest.mark.slow  # multi-minute subprocess compile


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config
from repro.models import moe
from repro.runtime.pspec import logical_axis_rules

cfg = get_config("deepseek-v2-236b", reduced=True).replace(
    param_dtype="float32", compute_dtype="float32",
    capacity_factor=64.0,   # dropless: both impls keep every token
)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
key = jax.random.PRNGKey(0)
params = moe.init_moe(key, cfg)
B, S, d = 2, 16, cfg.d_model
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, d), jnp.float32) * 0.3

with mesh, logical_axis_rules(mesh):
    moe.set_moe_impl("gather")
    y_g, aux_g = jax.jit(lambda p, x: moe.moe_layer(p, x, cfg))(params, x)
    moe.set_moe_impl("a2a")
    y_a, aux_a = jax.jit(lambda p, x: moe.moe_layer(p, x, cfg))(params, x)

np.testing.assert_allclose(np.asarray(y_g), np.asarray(y_a), rtol=2e-4, atol=2e-4)
np.testing.assert_allclose(float(aux_g), float(aux_a), rtol=1e-3, atol=1e-5)

# gradients agree too
def loss_fn(p):
    y, aux = moe.moe_layer(p, x, cfg)
    return jnp.sum(jnp.square(y)) + aux

with mesh, logical_axis_rules(mesh):
    moe.set_moe_impl("gather")
    g_gather = jax.jit(jax.grad(loss_fn))(params)
    moe.set_moe_impl("a2a")
    g_a2a = jax.jit(jax.grad(loss_fn))(params)
for a, b in zip(jax.tree.leaves(g_gather), jax.tree.leaves(g_a2a)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=5e-3, atol=5e-4)
print("OK")
"""


def test_gather_vs_a2a_equivalence():
    # CPU virtual devices: the child must not contend for a TPU
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "OK" in proc.stdout

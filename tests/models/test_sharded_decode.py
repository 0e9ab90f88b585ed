"""Sequence-parallel decode attention ≡ naive decode (multi-device)."""
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parents[2]

pytestmark = pytest.mark.slow  # multi-minute subprocess compiles


SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.attention import (decode_attention, decode_attention_sharded,
                                    init_attention, init_kv_cache)
from repro.runtime.pspec import logical_axis_rules

cfg = get_config("gemma2-9b", reduced=True).replace(
    param_dtype="float32", compute_dtype="float32", local_window=0,
    layer_pattern="G")
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = init_attention(jax.random.PRNGKey(0), cfg)
B, S = 2, 1024
cache = init_kv_cache(cfg, B, S, 1, dtype=jnp.float32)
kc, vc = cache["k"][0], cache["v"][0]
x = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model)) * 0.3

# fill a few positions then compare both paths at each step
kc_a, vc_a = kc, vc
kc_b, vc_b = kc, vc
with mesh, logical_axis_rules(mesh):
    naive = jax.jit(lambda x, k, v, p: decode_attention(params, x, k, v, p, cfg))
    shard = jax.jit(lambda x, k, v, p: decode_attention_sharded(params, x, k, v, p, cfg))
    for t in range(6):
        xt = jax.random.normal(jax.random.PRNGKey(10 + t), (B, 1, cfg.d_model)) * 0.3
        o_a, kc_a, vc_a = naive(xt, kc_a, vc_a, jnp.int32(t))
        o_b, kc_b, vc_b = shard(xt, kc_b, vc_b, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(o_a), np.asarray(o_b),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_allclose(np.asarray(kc_a), np.asarray(kc_b),
                                   rtol=1e-5, atol=1e-6)
print("OK")
"""


def test_sharded_decode_matches_naive():
    # CPU virtual devices: the child must not contend for a TPU
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "OK" in proc.stdout


RING_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.attention import decode_attention_sharded, init_attention, init_kv_cache
from repro.models.decode import _ring_decode
from repro.runtime.pspec import logical_axis_rules

cfg = get_config("gemma2-9b", reduced=True).replace(
    param_dtype="float32", compute_dtype="float32", local_window=512)
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = init_attention(jax.random.PRNGKey(0), cfg)
B, W = 2, 512
cache = init_kv_cache(cfg, B, W, 1, dtype=jnp.float32)
kc_a = kc_b = cache["k"][0]; vc_a = vc_b = cache["v"][0]
with mesh, logical_axis_rules(mesh):
    naive = jax.jit(lambda x, k, v, p: _ring_decode(params, x, k, v, p, cfg,
                                                    cfg.rope_theta))
    shard = jax.jit(lambda x, k, v, p: decode_attention_sharded(
        params, x, k, v, p, cfg, is_global=False, ring=True))
    # drive past one wrap of the ring (W=512 → test a few early + wrapped)
    for t in list(range(4)) + [510, 511, 512, 513, 600]:
        xt = jax.random.normal(jax.random.PRNGKey(30 + t), (B, 1, cfg.d_model)) * 0.3
        o_a, kc_a, vc_a = naive(xt, kc_a, vc_a, jnp.int32(t))
        o_b, kc_b, vc_b = shard(xt, kc_b, vc_b, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(kc_a), np.asarray(kc_b),
                                   rtol=1e-5, atol=1e-6)
        # naive returns post-wo output; sharded likewise
        np.testing.assert_allclose(np.asarray(o_a), np.asarray(o_b),
                                   rtol=3e-4, atol=3e-4)
print("OK")
"""


def test_sharded_ring_decode_matches_naive():
    # CPU virtual devices: the child must not contend for a TPU
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", RING_SCRIPT], env=env,
                          capture_output=True, text=True, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "OK" in proc.stdout


MLA_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import get_config
from repro.models.mla import (init_mla, init_mla_cache, mla_decode,
                              mla_decode_sharded)
from repro.runtime.pspec import logical_axis_rules

cfg = get_config("deepseek-v2-236b", reduced=True).replace(
    param_dtype="float32", compute_dtype="float32")
mesh = jax.make_mesh((2, 4), ("data", "model"),
                     axis_types=(jax.sharding.AxisType.Auto,) * 2)
params = init_mla(jax.random.PRNGKey(0), cfg)
B, S = 2, 1024
cache = init_mla_cache(cfg, B, S, 1, dtype=jnp.float32)
ckv_a = ckv_b = cache["c_kv"][0]
kr_a = kr_b = cache["k_rope"][0]
with mesh, logical_axis_rules(mesh):
    naive = jax.jit(lambda x, c, r, p: mla_decode(params, x, c, r, p, cfg))
    shard = jax.jit(lambda x, c, r, p: mla_decode_sharded(params, x, c, r, p, cfg))
    for t in range(6):
        xt = jax.random.normal(jax.random.PRNGKey(20 + t), (B, 1, cfg.d_model)) * 0.3
        o_a, ckv_a, kr_a = naive(xt, ckv_a, kr_a, jnp.int32(t))
        o_b, ckv_b, kr_b = shard(xt, ckv_b, kr_b, jnp.int32(t))
        np.testing.assert_allclose(np.asarray(o_a), np.asarray(o_b),
                                   rtol=3e-4, atol=3e-4)
        np.testing.assert_allclose(np.asarray(ckv_a), np.asarray(ckv_b),
                                   rtol=1e-5, atol=1e-6)
print("OK")
"""


def test_sharded_mla_decode_matches_naive():
    # CPU virtual devices: the child must not contend for a TPU
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "-c", MLA_SCRIPT], env=env,
                          capture_output=True, text=True, cwd=REPO, timeout=600)
    assert proc.returncode == 0, proc.stdout + "\n" + proc.stderr
    assert "OK" in proc.stdout

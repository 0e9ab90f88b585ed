"""The §IV cost kernel compiles for a TPU v5e at the bulk cells' sizes.

Compiles against a described ``v5e:2x2`` topology: no chip is attached,
so nothing runs, but the TPU compiler refuses what the chip would
refuse (misaligned blocks, too much VMEM). Each compiled program must
hold the Pallas kernel (``tpu_custom_call``), not a jnp fallback.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.cost_matrix.cost_matrix import cost_matrix_pallas
from repro.kernels.cost_matrix.ops import cost_matrix_classed


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")    # else it logs under /tmp
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _f32(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


@pytest.mark.parametrize("J,S", [(10_240, 256), (10_240, 10_112)])
def test_cost_matrix_pallas_compiles(one_chip, J, S):
    col = _f32((J, 1), one_chip)
    fn = jax.jit(lambda jb, jw, rows, wc, wd: cost_matrix_pallas(
        jb, jw, rows, job_wcomp=wc, job_wdtc=wd))
    compiled = fn.lower(col, col, _f32((9, S), one_chip), col, col).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_cost_matrix_classed_wrapper_compiles(one_chip):
    J, S = 10_000, 256
    jobs = [_f32((J,), one_chip)] * 4
    sites = [_f32((S,), one_chip)] * 9      # cap … rtt, alive, mss
    compiled = cost_matrix_classed.lower(*jobs, *sites).compile()
    assert "tpu_custom_call" in compiled.as_text()

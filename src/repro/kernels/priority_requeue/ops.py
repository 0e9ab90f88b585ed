"""jit'd wrapper: pad/reshape (L,) job arrays to lane-aligned (M, 128)
tiles, run the Pallas kernel (or the jnp oracle when the caller passes
``use_kernel=False``), unpad."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .priority_requeue import priority_requeue_pallas
from .ref import priority_requeue_ref


def _pad_to_tiles(x, rows_multiple=64):
    L = x.shape[0]
    lane = 128
    m = -(-L // lane)
    m = -(-m // rows_multiple) * rows_multiple
    pad = m * lane - L
    return jnp.pad(x, (0, pad), constant_values=1.0).reshape(m, lane), L


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def priority_requeue(n, q, t, quota_sum, proc_sum, *, use_kernel=True, interpret=False):
    """§X re-prioritization over L queued jobs → (pr (L,), qidx (L,))."""
    if not use_kernel:
        return priority_requeue_ref(n, q, t, quota_sum, proc_sum)
    n2, L = _pad_to_tiles(jnp.asarray(n, jnp.float32))
    q2, _ = _pad_to_tiles(jnp.asarray(q, jnp.float32))
    t2, _ = _pad_to_tiles(jnp.asarray(t, jnp.float32))
    pr, qidx = priority_requeue_pallas(
        n2, q2, t2, quota_sum, proc_sum,
        interpret=interpret,
    )
    return pr.reshape(-1)[:L], qidx.reshape(-1)[:L]

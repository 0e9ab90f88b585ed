"""jit'd wrapper: pads jobs/sites to tile multiples, packs site state
into the (8, S) row layout, runs the kernel (or the oracle when the
caller passes ``use_kernel=False``), adds the argmin. The kernel is
compiled for the TPU unless the caller passes ``interpret=True``."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .cost_matrix import JOB_BLOCK, SITE_BLOCK, cost_matrix_pallas
from .ref import cost_matrix_classed_ref


def _pad(x, m, value=1.0):
    L = x.shape[0]
    pad = (-L) % m
    return jnp.pad(x, (0, pad), constant_values=value), L


def _pack_site_rows(cap, queue, work, load, bw, loss, rtt, alive, mss=1460.0):
    """(9, S_pad) float32 rows; padding columns are dead (alive=0).
    ``mss`` may be a scalar or a per-link (S,) array."""
    loss = jnp.asarray(loss, jnp.float32)
    mss = jnp.broadcast_to(jnp.asarray(mss, jnp.float32), loss.shape)
    packed = []
    for arr, fill in ((cap, 1.0), (queue, 0.0), (work, 0.0), (load, 0.0),
                      (bw, 1.0), (loss, 0.0), (rtt, 1.0),
                      (jnp.asarray(alive, jnp.float32), 0.0), (mss, 1.0)):
        p, S = _pad(jnp.asarray(arr, jnp.float32), SITE_BLOCK, fill)
        packed.append(p)
    return jnp.stack(packed, axis=0), S


@functools.partial(jax.jit, static_argnames=("use_kernel", "interpret"))
def cost_matrix(
    job_bytes, job_work, cap, queue, work, load, bw, loss, rtt, alive,
    *, use_kernel=True, interpret=False,
):
    """§IV cost over (J, S) + per-job best site. Returns (cost, best).

    All-ones class masks reduce the classed kernel to the plain §IV
    total (net + comp + dtc, same addition order)."""
    ones = jnp.ones_like(jnp.asarray(job_bytes, jnp.float32))
    return cost_matrix_classed(
        job_bytes, job_work, ones, ones,
        cap, queue, work, load, bw, loss, rtt, alive,
        use_kernel=use_kernel, interpret=interpret,
    )


@functools.partial(
    jax.jit,
    static_argnames=("w_queue", "w_work", "w_load", "use_kernel", "interpret"),
)
def cost_matrix_classed(
    job_bytes, job_work, job_wcomp, job_wdtc,
    cap, queue, work, load, bw, loss, rtt, alive, mss=1460.0,
    *, w_queue=1.0, w_work=1.0, w_load=1.0, use_kernel=True, interpret=False,
):
    """§V per-class cost over (J, S): net + wcomp·comp + wdtc·dtc.

    One matrix pass serves all three job-class branches — the
    ``wcomp``/``wdtc`` columns are the class masks the batched
    placement engine (``repro.core.batch``) packs from COMPUTE / DATA /
    BOTH. ``mss`` is the Mathis TCP segment size, scalar or per-link
    (S,). Returns ``(cost, best)`` like ``cost_matrix``.
    """
    if not use_kernel:
        return cost_matrix_classed_ref(
            job_bytes, job_work, job_wcomp, job_wdtc,
            cap, queue, work, load, bw, loss, rtt, alive,
            w_queue=w_queue, w_work=w_work, w_load=w_load, mss=mss,
        )
    jb, J = _pad(jnp.asarray(job_bytes, jnp.float32), JOB_BLOCK)
    jw, _ = _pad(jnp.asarray(job_work, jnp.float32), JOB_BLOCK)
    wc, _ = _pad(jnp.asarray(job_wcomp, jnp.float32), JOB_BLOCK)
    wd, _ = _pad(jnp.asarray(job_wdtc, jnp.float32), JOB_BLOCK)
    site_rows, S = _pack_site_rows(
        cap, queue, work, load, bw, loss, rtt, alive, mss
    )
    cost = cost_matrix_pallas(
        jb[:, None], jw[:, None], site_rows,
        job_wcomp=wc[:, None], job_wdtc=wd[:, None],
        w_queue=w_queue, w_work=w_work, w_load=w_load,
        interpret=interpret,
    )[:J, :S]
    return cost, jnp.argmin(cost, axis=1).astype(jnp.int32)

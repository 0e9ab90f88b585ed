"""Quota-economy priority calculation (paper §X).

For a job from user ``u`` requiring ``t`` processors:

    N  = (q · T) / (Q · t)          — dynamic per-job threshold
    Pr = (N − n) / N   if n ≤ N     — favoured        (in [0, 1))
         (N − n) / n   otherwise    — over-threshold  (in (−1, 0))

where n = user's total jobs in all queues (incl. the new one), q = the
user's quota, Q = sum of quotas of all *distinct* users with queued
jobs, T = total processors required by all queued jobs, t = this job's
processor requirement.

Re-prioritization (§X): on every arrival the priority of *every* queued
job is recomputed with the new (Q, T) totals — q stays per-user, t is
per-job, so N differs per job. When a job is taken out for service the
rest are NOT reprioritized.

Queue bands (§X): Q1: 0.5 ≤ p, Q2: 0 ≤ p < 0.5, Q3: −0.5 ≤ p < 0,
Q4: p < −0.5.

The vectorized path (``reprioritize``) is the oracle for the
``priority_requeue`` Pallas kernel.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

__all__ = [
    "threshold",
    "priority",
    "queue_index",
    "reprioritize",
    "reprioritize_row",
    "NUM_QUEUES",
    "QUEUE_BOUNDS",
]

NUM_QUEUES = 4
# Lower bounds of Q1..Q4, descending priority.
QUEUE_BOUNDS = (0.5, 0.0, -0.5, -1.0)


def threshold(q: float, Q: float, t: float, T: float) -> float:
    """N = (q·T)/(Q·t) — paper equation (VI)."""
    if q <= 0 or Q <= 0 or t <= 0 or T <= 0:
        raise ValueError("quota/processor quantities must be positive")
    return (q * T) / (Q * t)


def priority(n: float, N: float) -> float:
    """Pr(n) per paper §X; always in (−1, 1)."""
    if n <= 0:
        raise ValueError("n counts the user's queued jobs incl. the new one")
    if n <= N:
        return (N - n) / N
    return (N - n) / n


def queue_index(p: float) -> int:
    """Map a priority to its multilevel queue: 0→Q1 … 3→Q4."""
    if p >= 0.5:
        return 0
    if p >= 0.0:
        return 1
    if p >= -0.5:
        return 2
    return 3


def reprioritize(
    user_job_counts: jnp.ndarray,  # (L,) n per queued job (its user's total)
    user_quota: jnp.ndarray,       # (L,) q per queued job
    job_procs: jnp.ndarray,        # (L,) t per queued job
    quota_sum: float,              # Q — sum over *distinct* users
    proc_sum: float,               # T — sum of t over all queued jobs
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Vectorized §X re-prioritization over all L queued jobs.

    Returns (priorities, queue indices), both (L,). This is the jnp
    oracle mirrored by ``repro.kernels.priority_requeue``.
    """
    n = jnp.asarray(user_job_counts, jnp.float32)
    q = jnp.asarray(user_quota, jnp.float32)
    t = jnp.asarray(job_procs, jnp.float32)
    N = (q * proc_sum) / (quota_sum * t)
    pr = jnp.where(n <= N, (N - n) / N, (N - n) / n)
    qidx = queue_index_vec(pr)
    return pr, qidx


def queue_index_vec(p: jnp.ndarray) -> jnp.ndarray:
    """Vectorized queue bucketing: 0→Q1 … 3→Q4."""
    return (
        jnp.asarray(p < 0.5, jnp.int32)
        + jnp.asarray(p < 0.0, jnp.int32)
        + jnp.asarray(p < -0.5, jnp.int32)
    )


def reprioritize_np(
    user_job_counts: np.ndarray,
    user_quota: np.ndarray,
    job_procs: np.ndarray,
    quota_sum: float,
    proc_sum: float,
) -> tuple[np.ndarray, np.ndarray]:
    """Pure-numpy twin of ``reprioritize`` for the host control plane
    (the simulator calls this once per arrival; no XLA dispatch)."""
    n = np.asarray(user_job_counts, np.float64)
    q = np.asarray(user_quota, np.float64)
    t = np.asarray(job_procs, np.float64)
    N = (q * proc_sum) / (quota_sum * t)
    pr = np.where(n <= N, (N - n) / N, (N - n) / n)
    qidx = (pr < 0.5).astype(np.int32) + (pr < 0.0) + (pr < -0.5)
    return pr, qidx.astype(np.int32)


def reprioritize_row(
    n: float, q: float, t: float, quota_sum: float, proc_sum: float
) -> tuple[float, int]:
    """One row of ``reprioritize_np``: the same float64 operations in
    the same order, so the same bits, and the same band (nan in Q1).

    ``n``, ``q`` and ``t`` come rounded through float32, as the rows of
    ``reprioritize_np`` do. Where Python refuses a division by zero the
    row goes through ``reprioritize_np`` itself, with NumPy's inf.
    """
    try:
        N = (q * proc_sum) / (quota_sum * t)
    except ZeroDivisionError:
        pr, qidx = reprioritize_np(
            np.float32([n]), np.float32([q]), np.float32([t]), quota_sum, proc_sum
        )
        return float(pr[0]), int(qidx[0])
    p = (N - n) / N if n <= N else (N - n) / n
    return p, (p < 0.5) + (p < 0.0) + (p < -0.5)


def littles_law_queue_length(arrival_rate: float, wait_time: float) -> float:
    """Little's formula N = R·W (paper §VII)."""
    return arrival_rate * wait_time

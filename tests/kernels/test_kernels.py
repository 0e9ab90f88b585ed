"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.priority_requeue.ops import priority_requeue
from repro.kernels.priority_requeue.ref import priority_requeue_ref
from repro.kernels.cost_matrix.ops import cost_matrix
from repro.kernels.cost_matrix.ref import cost_matrix_ref
from repro.kernels.flash_attention.flash_attention import flash_attention_pallas
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.flash_attention.ref import flash_attention_ref
from repro.kernels.decode_attention.decode_attention import decode_attention_pallas
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.decode_attention.ref import decode_attention_ref


class TestPriorityRequeue:
    @pytest.mark.parametrize("L", [1, 37, 128, 8192, 10_000])
    def test_matches_ref(self, L):
        rng = np.random.default_rng(L)
        n = rng.integers(1, 50, L).astype(np.float32)
        q = rng.uniform(10, 5000, L).astype(np.float32)
        t = rng.uniform(1, 64, L).astype(np.float32)
        Q, T = float(q.sum()), float(t.sum())
        pr_k, qi_k = priority_requeue(n, q, t, Q, T, use_kernel=True, interpret=True)
        pr_r, qi_r = priority_requeue_ref(n, q, t, Q, T)
        np.testing.assert_allclose(np.asarray(pr_k), np.asarray(pr_r), rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(np.asarray(qi_k), np.asarray(qi_r))

    def test_fig6_values_through_kernel(self):
        n = np.array([2, 2, 1], np.float32)
        q = np.array([1900, 1900, 1700], np.float32)
        t = np.array([1, 5, 1], np.float32)
        pr, qi = priority_requeue(n, q, t, 3600.0, 7.0, use_kernel=True, interpret=True)
        np.testing.assert_allclose(np.asarray(pr), [0.4586, -0.6305, 0.6974], atol=1e-4)
        assert list(np.asarray(qi)) == [1, 3, 0]


class TestCostMatrix:
    @pytest.mark.parametrize("J,S", [(1, 1), (5, 3), (300, 130), (1024, 128)])
    def test_matches_ref(self, J, S):
        rng = np.random.default_rng(J * 1000 + S)
        jb = rng.uniform(0, 1e10, J).astype(np.float32)
        jw = rng.uniform(1, 100, J).astype(np.float32)
        cap = rng.uniform(10, 1000, S).astype(np.float32)
        qi = rng.uniform(0, 50, S).astype(np.float32)
        qw = rng.uniform(0, 500, S).astype(np.float32)
        load = rng.uniform(0, 1, S).astype(np.float32)
        bw = rng.uniform(1e8, 1e10, S).astype(np.float32)
        loss = rng.uniform(0, 0.05, S).astype(np.float32)
        rtt = rng.uniform(0.01, 0.3, S).astype(np.float32)
        alive = (rng.uniform(0, 1, S) > 0.2).astype(np.float32)
        ck, bk = cost_matrix(jb, jw, cap, qi, qw, load, bw, loss, rtt, alive,
                             use_kernel=True, interpret=True)
        cr, br = cost_matrix_ref(jb, jw, cap, qi, qw, load, bw, loss, rtt, alive)
        np.testing.assert_allclose(np.asarray(ck), np.asarray(cr), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))


ATTN_CASES = [
    # (B, Sq, Sk, H, KV, D, causal, window, softcap, dtype)
    (1, 128, 128, 4, 4, 64, True, 0, 0.0, jnp.float32),
    (2, 256, 256, 4, 2, 64, True, 0, 0.0, jnp.float32),
    (1, 128, 128, 8, 1, 128, True, 64, 0.0, jnp.float32),   # MQA + window
    (1, 256, 256, 4, 4, 128, True, 0, 50.0, jnp.float32),   # softcap
    (1, 128, 128, 4, 4, 256, True, 0, 0.0, jnp.bfloat16),   # bf16, gemma D
    (1, 128, 256, 2, 2, 64, False, 0, 0.0, jnp.float32),    # non-causal, Sk>Sq
]


class TestFlashAttention:
    @pytest.mark.parametrize("case", ATTN_CASES)
    def test_matches_ref(self, case):
        B, Sq, Sk, H, KV, D, causal, window, cap, dt = case
        rng = jax.random.PRNGKey(hash(case) % 2**31)
        k1, k2, k3 = jax.random.split(rng, 3)
        q = (jax.random.normal(k1, (B, Sq, H, D)) * 0.5).astype(dt)
        k = (jax.random.normal(k2, (B, Sk, KV, D)) * 0.5).astype(dt)
        v = (jax.random.normal(k3, (B, Sk, KV, D)) * 0.5).astype(dt)
        out_k = flash_attention_pallas(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=causal, window=window, softcap=cap,
            blk_q=64, blk_k=64, interpret=True,
        ).transpose(0, 2, 1, 3)
        out_r = flash_attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
        tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=tol, atol=tol)

    def test_matches_models_chunked_path(self):
        """Kernel ≡ the chunked jnp path used by the model stack."""
        from repro.models.attention import _chunked
        B, S, H, KV, D = 1, 256, 4, 2, 64
        ks = jax.random.split(jax.random.PRNGKey(0), 3)
        q = jax.random.normal(ks[0], (B, S, H, D)) * 0.5
        k = jax.random.normal(ks[1], (B, S, KV, D)) * 0.5
        v = jax.random.normal(ks[2], (B, S, KV, D)) * 0.5
        pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
        out_c = _chunked(q, k, v, pos, pos, causal=True, is_global=True,
                         window=0, cap=0.0, scale=D ** -0.5,
                         q_block=64, kv_block=64)
        out_k = flash_attention_pallas(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            causal=True, blk_q=64, blk_k=64, interpret=True,
        ).transpose(0, 2, 1, 3)
        np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_c),
                                   rtol=2e-5, atol=2e-5)


DECODE_CASES = [
    # (B, S, H, KV, D, pos, window, softcap, dtype)
    (1, 128, 4, 4, 64, 0, 0, 0.0, jnp.float32),
    (2, 512, 8, 2, 64, 100, 0, 0.0, jnp.float32),
    (1, 512, 8, 1, 128, 511, 64, 0.0, jnp.float32),
    (2, 256, 16, 8, 256, 200, 0, 50.0, jnp.float32),
    (1, 512, 8, 8, 128, 300, 0, 0.0, jnp.bfloat16),
]


class TestDecodeAttention:
    @pytest.mark.parametrize("case", DECODE_CASES)
    def test_matches_ref(self, case):
        B, S, H, KV, D, pos, window, cap, dt = case
        ks = jax.random.split(jax.random.PRNGKey(hash(case) % 2**31), 3)
        q = (jax.random.normal(ks[0], (B, H, D)) * 0.5).astype(dt)
        k = (jax.random.normal(ks[1], (B, S, KV, D)) * 0.5).astype(dt)
        v = (jax.random.normal(ks[2], (B, S, KV, D)) * 0.5).astype(dt)
        rep = H // KV
        out_k = decode_attention_pallas(
            q.reshape(B, KV, rep, D), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            pos, window=window, softcap=cap, blk_s=128, interpret=True,
        ).reshape(B, H, D)
        out_r = decode_attention_ref(q, k, v, pos, window=window, softcap=cap)
        tol = 2e-2 if dt == jnp.bfloat16 else 2e-5
        np.testing.assert_allclose(
            np.asarray(out_k, np.float32), np.asarray(out_r, np.float32),
            rtol=tol, atol=tol)


def _wrapper_case(name):
    """(jitted ops wrapper, positional args, its jnp oracle) at a small size."""
    rng = np.random.default_rng(7)
    u = lambda lo, hi, n: rng.uniform(lo, hi, n).astype(np.float32)
    if name == "priority_requeue":
        n, q, t = u(1, 50, 37), u(10, 5000, 37), u(1, 64, 37)
        args = (n, q, t, float(q.sum()), float(t.sum()))
        return priority_requeue, args, priority_requeue_ref
    if name == "cost_matrix":
        args = (u(0, 1e10, 5), u(1, 100, 5), u(10, 1000, 3), u(0, 50, 3),
                u(0, 500, 3), u(0, 1, 3), u(1e8, 1e10, 3), u(0, 0.05, 3),
                u(0.01, 0.3, 3), np.ones(3, np.float32))
        return cost_matrix, args, cost_matrix_ref
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    if name == "flash_attention":
        q = jax.random.normal(ks[0], (1, 128, 2, 64)) * 0.5
        k = jax.random.normal(ks[1], (1, 128, 2, 64)) * 0.5
        v = jax.random.normal(ks[2], (1, 128, 2, 64)) * 0.5
        return flash_attention, (q, k, v), flash_attention_ref
    q = jax.random.normal(ks[0], (1, 4, 64)) * 0.5
    k = jax.random.normal(ks[1], (1, 128, 2, 64)) * 0.5
    v = jax.random.normal(ks[2], (1, 128, 2, 64)) * 0.5
    return decode_attention, (q, k, v, 100), decode_attention_ref


class TestWrapperDispatch:
    """The ops wrappers pick kernel, oracle and interpret mode from their
    arguments alone, never from ``jax.default_backend()``."""

    @pytest.mark.parametrize("backend", ["cpu", "tpu"])
    @pytest.mark.parametrize(
        "name",
        ["priority_requeue", "cost_matrix", "flash_attention", "decode_attention"])
    def test_same_path_whatever_the_backend_reports(self, name, backend, monkeypatch):
        fn, args, ref = _wrapper_case(name)
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        jax.clear_caches()          # retrace: no cached program from another case
        with pytest.raises(ValueError, match="interpret mode"):
            fn(*args)               # default: the compiled kernel, which a CPU refuses
        expect = jax.tree.leaves(ref(*args))
        for got in (fn(*args, use_kernel=False), fn(*args, interpret=True)):
            for g, e in zip(jax.tree.leaves(got), expect):
                np.testing.assert_allclose(np.asarray(g), np.asarray(e),
                                           rtol=2e-5, atol=2e-5)

"""§X priority — including the paper's Fig 6 worked example, exactly."""
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import priority as prio


class TestFig6PaperExample:
    """Reproduce the paper's Fig 6 numbers to 4 decimal places."""

    def test_user_a_first_job(self):
        # t=1, q=1900, L=1, n=1, Q=1900, T=1 → N=1 → Pr=0 → Q2
        N = prio.threshold(q=1900, Q=1900, t=1, T=1)
        assert N == 1.0
        p = prio.priority(n=1, N=N)
        assert p == 0.0
        assert prio.queue_index(p) == 1  # Q2

    def test_user_a_second_job(self):
        # t=5: L=2, n=2, T=6, q=Q=1900 → N=1.2 → Pr=-0.4 → Q3
        N = prio.threshold(q=1900, Q=1900, t=5, T=6)
        assert N == pytest.approx(1.2)
        p = prio.priority(n=2, N=N)
        assert p == pytest.approx(-0.4)
        assert prio.queue_index(p) == 2  # Q3

    def test_user_a_first_job_reprioritized(self):
        # After job 2: for job 1, t=1, T=6 → N=6, n=2 → Pr=0.666666 → Q1
        N = prio.threshold(q=1900, Q=1900, t=1, T=6)
        p = prio.priority(n=2, N=N)
        assert p == pytest.approx(0.666666, abs=1e-5)
        assert prio.queue_index(p) == 0  # Q1

    def test_user_b_first_job(self):
        # B: t=1, q=1700, L=3, n=1, T=7, Q=3600 → Pr=0.6974 → Q1
        N = prio.threshold(q=1700, Q=3600, t=1, T=7)
        p = prio.priority(n=1, N=N)
        assert p == pytest.approx(0.6974, abs=1e-4)
        assert prio.queue_index(p) == 0

    def test_user_a_jobs_after_b_arrives(self):
        # Fig 6 table: A job1 → 0.4586 (Q2), A job2 → −0.6305 (Q4)
        N1 = prio.threshold(q=1900, Q=3600, t=1, T=7)
        p1 = prio.priority(n=2, N=N1)
        assert p1 == pytest.approx(0.4586, abs=1e-4)
        assert prio.queue_index(p1) == 1  # migrated Q1 → Q2

        N2 = prio.threshold(q=1900, Q=3600, t=5, T=7)
        p2 = prio.priority(n=2, N=N2)
        assert p2 == pytest.approx(-0.6305, abs=1e-4)
        assert prio.queue_index(p2) == 3  # migrated Q3 → Q4

    def test_vectorized_matches_fig6_final_state(self):
        # The three queued jobs at the end of the Fig 6 walkthrough.
        n = np.array([2, 2, 1], np.float32)
        q = np.array([1900, 1900, 1700], np.float32)
        t = np.array([1, 5, 1], np.float32)
        pr, qidx = prio.reprioritize(n, q, t, quota_sum=3600, proc_sum=7)
        np.testing.assert_allclose(
            np.asarray(pr), [0.4586, -0.6305, 0.6974], atol=1e-4
        )
        assert list(np.asarray(qidx)) == [1, 3, 0]


class TestPriorityProperties:
    @given(
        n=st.integers(1, 10_000),
        q=st.floats(1, 1e6),
        Q_extra=st.floats(0, 1e6),
        t=st.floats(0.5, 512),
        T_extra=st.floats(0, 1e5),
    )
    @settings(max_examples=200, deadline=None)
    def test_priority_always_in_open_interval(self, n, q, Q_extra, t, T_extra):
        """Paper: 'the priority will always lie in the interval {-1, 1}'."""
        Q = q + Q_extra
        T = t + T_extra
        N = prio.threshold(q=q, Q=Q, t=t, T=T)
        p = prio.priority(n=n, N=N)
        assert -1.0 < p < 1.0 or p == pytest.approx(0.0)
        assert p <= 1.0 and p > -1.0

    @given(
        q=st.floats(1, 1e4),
        t=st.floats(0.5, 64),
        T=st.floats(64, 1e4),
    )
    @settings(max_examples=100, deadline=None)
    def test_priority_monotone_decreasing_in_n(self, q, t, T):
        """More jobs from one user ⇒ never-increasing priority (§VII)."""
        N = prio.threshold(q=q, Q=2 * q, t=t, T=T)
        ps = [prio.priority(n, N) for n in range(1, 50)]
        assert all(a >= b - 1e-6 for a, b in zip(ps, ps[1:]))

    @given(st.floats(-0.9999, 0.9999))
    @settings(max_examples=200, deadline=None)
    def test_queue_bands_cover_interval(self, p):
        qi = prio.queue_index(p)
        assert 0 <= qi < prio.NUM_QUEUES
        lo = prio.QUEUE_BOUNDS[qi]
        assert p >= lo
        if qi > 0:
            assert p < prio.QUEUE_BOUNDS[qi - 1]

    @given(
        n_jobs=st.integers(1, 64),
        seed=st.integers(0, 2**31 - 1),
    )
    @settings(max_examples=50, deadline=None)
    def test_vectorized_matches_scalar(self, n_jobs, seed):
        rng = np.random.default_rng(seed)
        n = rng.integers(1, 20, n_jobs).astype(np.float32)
        q = rng.uniform(10, 5000, n_jobs).astype(np.float32)
        t = rng.uniform(1, 32, n_jobs).astype(np.float32)
        Q = float(q.sum())
        T = float(t.sum())
        pr_vec, qi_vec = prio.reprioritize(n, q, t, Q, T)
        for i in range(n_jobs):
            N = prio.threshold(q=float(q[i]), Q=Q, t=float(t[i]), T=T)
            p = prio.priority(n=float(n[i]), N=N)
            assert float(pr_vec[i]) == pytest.approx(p, rel=1e-4, abs=1e-5)
            assert int(qi_vec[i]) == prio.queue_index(float(pr_vec[i]))

    def test_threshold_rejects_nonpositive(self):
        with pytest.raises(ValueError):
            prio.threshold(q=0, Q=1, t=1, T=1)
        with pytest.raises(ValueError):
            prio.priority(n=0, N=1.0)


@pytest.mark.parametrize("n, q, t, Q, T", [
    (3.0, 1900.0, 5.0, 3600.0, 17.0),        # over the threshold
    (1.0, 1700.0, 1.0, 3600.0, 40.0),        # under it
    (2.0, 0.1, 0.5, 2.9, 7.25),              # fractional quota and t
    (1.0, 0.0, 1.0, 1.0, 3.0),               # a zero quota: N = 0
    (1.0, 1.0, 1.0, 0.0, 3.0),               # Q = 0: NumPy's inf
    (1.0, 0.0, 1.0, 0.0, 3.0),               # 0 / 0: NumPy's nan, in Q1
    (1.0, 3.0e38, 1.0, 1.0, 1.0e300),        # q·T overflows: inf / inf
])
def test_reprioritize_row_is_a_row_of_reprioritize_np(n, q, t, Q, T):
    """The scalar row the class-keyed queues use gives the vectorized
    path's bits and band, its inf and nan included."""
    with np.errstate(divide="ignore", invalid="ignore", over="ignore"):
        pr, qidx = prio.reprioritize_np(
            np.float32([n]), np.float32([q]), np.float32([t]), Q, T
        )
        p, qi = prio.reprioritize_row(n, float(np.float32(q)), float(np.float32(t)), Q, T)
    assert np.float64(p).tobytes() == pr[0].tobytes()
    assert qi == qidx[0]

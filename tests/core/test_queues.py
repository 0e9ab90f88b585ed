"""§VI/§VII/§X multilevel feedback queues."""
import copy
from collections import Counter
from types import SimpleNamespace

import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import Job, MultilevelFeedbackQueues, is_congested
from repro.core import priority as prio
from repro.core import trace


def test_fig6_walkthrough_via_queues():
    """Drive the Fig 6 scenario through the queue manager itself."""
    q = MultilevelFeedbackQueues(quotas={"A": 1900.0, "B": 1700.0})
    j1 = q.submit(Job(user="A", t=1, submit_time=0.0))
    assert j1.priority == pytest.approx(0.0)
    assert j1.queue == 1  # Q2

    j2 = q.submit(Job(user="A", t=5, submit_time=1.0))
    assert j2.priority == pytest.approx(-0.4)
    assert j2.queue == 2  # Q3
    # Reprioritization moved j1 Q2 → Q1.
    assert j1.priority == pytest.approx(0.666666, abs=1e-5)
    assert j1.queue == 0

    j3 = q.submit(Job(user="B", t=1, submit_time=2.0))
    assert j3.priority == pytest.approx(0.6974, abs=1e-4)
    assert j3.queue == 0
    assert j1.priority == pytest.approx(0.4586, abs=1e-4)
    assert j1.queue == 1  # Q1 → Q2
    assert j2.priority == pytest.approx(-0.6305, abs=1e-4)
    assert j2.queue == 3  # Q3 → Q4

    # Dispatch order: B's job (0.6974), then A j1, then A j2.
    assert q.pop_next() is j3
    assert q.pop_next() is j1
    assert q.pop_next() is j2
    assert q.pop_next() is None


def test_fcfs_within_equal_priority():
    q = MultilevelFeedbackQueues(quotas={"A": 100.0, "B": 100.0})
    a = q.submit(Job(user="A", t=2, submit_time=0.0))
    b = q.submit(Job(user="B", t=2, submit_time=5.0))
    assert a.priority == pytest.approx(b.priority)
    assert q.pop_next() is a  # older job first (§X timestamp rule)


def test_sjf_batch_arrangement():
    """§VII: fewer processors ⇒ placed (and thus popped) earlier."""
    q = MultilevelFeedbackQueues(quotas={"A": 100.0})
    jobs = [Job(user="A", t=t, submit_time=0.0) for t in (8, 1, 4, 2)]
    q.submit_batch(jobs)
    popped = [q.pop_next().t for _ in range(4)]
    assert popped == sorted(popped)  # 1, 2, 4, 8


def test_service_does_not_reprioritize():
    q = MultilevelFeedbackQueues(quotas={"A": 100.0, "B": 50.0})
    q.submit(Job(user="A", t=1))
    q.submit(Job(user="B", t=1))
    before = [(j.job_id, j.priority) for j in q.jobs]
    q.pop_next()
    after = {j.job_id: j.priority for j in q.jobs}
    for jid, p in before:
        if jid in after:
            assert after[jid] == p


def test_congestion_formula():
    # (arrival − service)/arrival > Thrs
    assert is_congested(10.0, 2.0, thrs=0.5)          # 0.8 > 0.5
    assert not is_congested(10.0, 8.0, thrs=0.5)      # 0.2 < 0.5
    assert not is_congested(0.0, 5.0, thrs=0.5)


def test_jobs_ahead():
    q = MultilevelFeedbackQueues(quotas={"A": 1900.0, "B": 1700.0})
    q.submit(Job(user="A", t=1))
    q.submit(Job(user="A", t=5))
    q.submit(Job(user="B", t=1))
    low = min(q.jobs, key=lambda j: j.priority)
    assert q.jobs_ahead(low.priority) == 3  # everyone incl. itself
    high = max(q.jobs, key=lambda j: j.priority)
    assert q.jobs_ahead(high.priority) == 1


class TestQueueProperties:
    @given(
        arrivals=st.lists(
            st.tuples(
                st.sampled_from(["u1", "u2", "u3"]),
                st.integers(1, 16),
            ),
            min_size=1,
            max_size=40,
        )
    )
    @settings(max_examples=50, deadline=None)
    def test_invariants_after_every_arrival(self, arrivals):
        q = MultilevelFeedbackQueues(quotas={"u1": 100.0, "u2": 200.0, "u3": 300.0})
        for i, (user, t) in enumerate(arrivals):
            q.submit(Job(user=user, t=float(t), submit_time=float(i)))
            # (1) priorities in (−1, 1); (2) band matches priority.
            for j in q.jobs:
                assert -1.0 < j.priority < 1.0
                assert j.queue == prio.queue_index(j.priority)
        # (3) pop drains in non-increasing priority order at pop time
        # (priorities frozen during service — §X).
        order = []
        while True:
            j = q.pop_next()
            if j is None:
                break
            order.append(j.priority)
        assert order == sorted(order, reverse=True) or len(order) <= 1 or all(
            a >= b - 1e-6 for a, b in zip(order, order[1:])
        )

    @given(
        rate=st.floats(0.1, 100.0),
        wait=st.floats(0.0, 50.0),
    )
    @settings(max_examples=50, deadline=None)
    def test_littles_law(self, rate, wait):
        n = prio.littles_law_queue_length(rate, wait)
        assert n == pytest.approx(rate * wait)


def test_littles_law_steady_state_simulation():
    """Empirical check of N = R·W on an M/D/1 run through the queues."""
    rng = np.random.default_rng(0)
    q = MultilevelFeedbackQueues(quotas={"u": 100.0})
    service_time = 1.0
    arrival_rate = 0.5  # utilization 0.5
    t, next_free = 0.0, 0.0
    waits, lengths = [], []
    for _ in range(5000):
        t += float(rng.exponential(1.0 / arrival_rate))
        # Serve every job whose service can start before this arrival.
        while len(q):
            head_arrival = min(j.submit_time for j in q.jobs)
            start = max(next_free, head_arrival)
            if start >= t:
                break
            j = q.pop_next(now=start)
            waits.append(start - j.submit_time)
            next_free = start + service_time
        q.submit(Job(user="u", t=1, submit_time=t), now=t)
        lengths.append(len(q) - 1)  # queue length seen by the arrival (PASTA)
    measured_N = float(np.mean(lengths))
    measured_W = float(np.mean(waits))
    # Little: N = R·W — generous tolerance for finite-run noise.
    assert measured_N == pytest.approx(arrival_rate * measured_W, rel=0.25, abs=0.2)


class _ReferenceQueues:
    """The O(L) §X queues the class-keyed ones replace: every submit
    re-prioritizes every queued job over the list, and a dispatch is a
    linear ``min`` over it."""

    def __init__(self, quotas):
        self.quotas = dict(quotas)
        self.jobs = []

    def submit(self, job):
        self.quotas.setdefault(job.user, 1.0)
        self.jobs.append(job)
        users = {j.user for j in self.jobs}
        Q = sum(self.quotas.get(u, 1.0) for u in users)
        T = sum(j.t for j in self.jobs)
        counts = Counter(j.user for j in self.jobs)
        n = np.array([counts[j.user] for j in self.jobs], np.float32)
        q = np.array([self.quotas[j.user] for j in self.jobs], np.float32)
        t = np.array([j.t for j in self.jobs], np.float32)
        pr, qidx = prio.reprioritize_np(n, q, t, Q, T)
        for j, p, qi in zip(self.jobs, pr, qidx):
            j.priority, j.queue = float(p), int(qi)

    def submit_batch(self, jobs):
        for j in sorted(jobs, key=lambda j: (j.t, j.submit_time, j.job_id)):
            self.submit(j)

    def pop_next(self):
        if not self.jobs:
            return None
        best = min(self.jobs, key=lambda j: (-j.priority, j.submit_time, j.job_id))
        self.remove(best)
        return best

    def remove(self, job):
        del self.jobs[next(i for i, j in enumerate(self.jobs) if j is job)]

    def requeue(self, job):
        self.jobs.append(job)


# (quotas, t values) per regime: integral values take the running
# totals; a fractional quota or t takes the in-order sums while a job
# of it is queued, so the fractional_* regimes cross between the two.
_REGIMES = {
    "integral": ({"u0": 1900.0, "u1": 1700.0, "u2": 1.0, "u3": 3}, [1, 1.0, 5, 2]),
    "fractional": ({"u0": 0.1, "u1": 0.7, "u2": 1 / 3, "u3": 2.5}, [0.5, 1 / 3, 2.25, 1.1]),
    "fractional_quota": ({"u0": 0.1, "u1": 0.7, "u2": 1 / 3, "u3": 2.5}, [1, 2, 5]),
    "fractional_t": ({"u0": 2.0, "u1": 7.0, "u2": 4.0}, [1, 2, 0.5, 4]),
    "one_user_several_t": ({"u0": 7.0}, [1, 2, 3, 5, 8]),
}


@pytest.mark.parametrize("regime", sorted(_REGIMES))
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_class_queues_match_the_reference_bit_for_bit(regime, seed):
    """Random interleavings of submit, submit_batch, pop_next, remove,
    requeue, priority writes (§IX's ``apply_migration`` on a queued job)
    and quota changes: the same pops, the same jobs order, and every
    queued job's priority and band equal to the bit after every
    operation."""
    quotas, ts = _REGIMES[regime]
    users = sorted(quotas) + ["new"]        # "new" has no quota: 1.0
    rng = np.random.default_rng(seed)
    q, ref = MultilevelFeedbackQueues(quotas), _ReferenceQueues(quotas)
    pairs, popped, k = {}, [], 0

    def new_pair():
        nonlocal k
        k += 1
        fields = dict(user=users[rng.integers(len(users))], t=ts[rng.integers(len(ts))],
                      submit_time=float(rng.integers(0, 8)), job_id=k)
        job, rjob = Job(**fields), SimpleNamespace(priority=0.0, queue=1, **fields)
        pairs[k] = (job, rjob)
        return job, rjob

    for _ in range(400):
        op = rng.choice(["submit", "batch", "pop", "pop", "remove", "requeue", "write", "quota"])
        if op == "submit":
            job, rjob = new_pair()
            q.submit(job)
            ref.submit(rjob)
        elif op == "batch":
            made = [new_pair() for _ in range(rng.integers(2, 5))]
            q.submit_batch([m[0] for m in made])
            ref.submit_batch([m[1] for m in made])
        elif op == "pop":
            job, rjob = q.pop_next(), ref.pop_next()
            assert (job and job.job_id) == (rjob and rjob.job_id)
            if job is not None:
                assert (job.priority, job.queue) == (rjob.priority, rjob.queue)
                popped.append(job.job_id)
        elif op == "remove" and len(q):
            jid = list(q.jobs)[rng.integers(len(q))].job_id
            q.remove(pairs[jid][0])
            ref.remove(pairs[jid][1])
        elif op == "requeue" and popped:
            jid = popped.pop(rng.integers(len(popped)))
            q.requeue(pairs[jid][0])
            ref.requeue(pairs[jid][1])
        elif op == "write" and len(q):
            jid = list(q.jobs)[rng.integers(len(q))].job_id
            for j in pairs[jid]:
                j.priority = min(1.0, j.priority + 0.1)
        elif op == "quota":          # read at the next submit; a NumPy float
            user, value = rng.choice(sorted(quotas)), rng.choice(list(quotas.values()))
            q.quotas[user] = ref.quotas[user] = value
        assert [j.job_id for j in q.jobs] == [j.job_id for j in ref.jobs]
        assert [(j.priority, j.queue) for j in q.jobs] == [(j.priority, j.queue) for j in ref.jobs]
        for p in (-0.5, 0.0, 0.5):
            assert q.jobs_ahead(p) == sum(1 for j in ref.jobs if j.priority >= p)
        assert [j.job_id for j in q.low_priority_jobs()] == [
            j.job_id for j in ref.jobs if j.queue == prio.NUM_QUEUES - 1
        ]


# compute_work values per regime: whole ones take the running total; a
# fractional one queued, or a total at or above 2**53, the in-order sum.
# "mixed_then_whole" queues both, then drains the fractional ones and
# goes on with whole works, back on the running total.
_WORKS = {
    "whole": ([30.0, 1, 0.0, 7.0, 120, 3.0], []),
    "fractional": ([0.1, 1 / 3, 2.5, 30.0, 1e-9], []),
    "huge": ([2.0**53, 2**60, 3.0, 1.0, 2.0**52], []),
    "mixed_then_whole": ([30.0, 0.1, 2, 1 / 3, 5.0], [30.0, 2, 5.0, 1.0]),
}


@pytest.fixture
def tracing_on():
    trace.enable()
    trace.reset()
    try:
        yield
    finally:
        trace.disable()
        trace.reset()


@pytest.mark.parametrize("regime", sorted(_WORKS))
@pytest.mark.parametrize("seed", [0, 1])
def test_queued_work_is_the_in_order_sum_bit_for_bit(tracing_on, regime, seed):
    """Random interleavings of submit, submit_batch, pop_next, remove,
    requeue and deep copies: ``queued_work()`` has the bits of the
    builtin ``sum`` over ``jobs`` after every one, and only reads with
    a fractional work queued, or a total at or above 2**53, fall back
    (``diana.mlfq.work_fallback``)."""
    first, then = _WORKS[regime]
    rng = np.random.default_rng(seed)
    q = MultilevelFeedbackQueues({"a": 1.0, "b": 3.0})
    popped, k = [], 0

    def check():
        got, ref = q.queued_work(), sum(j.compute_work for j in q.jobs)
        assert float(got).hex() == float(ref).hex() and got == ref

    def walk(works, steps):
        nonlocal q, k
        for _ in range(steps):
            op = rng.choice(["submit", "batch", "pop", "pop", "remove", "requeue", "copy"])
            made = []
            for _ in range({"submit": 1, "batch": rng.integers(2, 5)}.get(op, 0)):
                k += 1
                made.append(Job(user="ab"[rng.integers(2)], t=float(rng.integers(1, 3)),
                                submit_time=float(rng.integers(0, 8)), job_id=k,
                                compute_work=works[rng.integers(len(works))]))
            if op == "submit":
                q.submit(made[0])
            elif op == "batch":
                q.submit_batch(made)
            elif op == "pop":
                job = q.pop_next()
                if job is not None:
                    popped.append(job)
            elif op == "remove" and len(q):
                q.remove(list(q.jobs)[rng.integers(len(q))])
            elif op == "requeue" and popped:
                q.requeue(popped.pop(rng.integers(len(popped))))
            elif op == "copy":
                q = copy.deepcopy(q)
            check()

    walk(first, 300)
    fallbacks = trace.counters().get("diana.mlfq.work_fallback", 0)
    assert (fallbacks == 0) == (regime == "whole")
    if then:
        for j in [j for j in q.jobs if j.compute_work % 1]:
            q.remove(j)
        popped = [j for j in popped if j.compute_work % 1 == 0]
        check()
        trace.reset()
        walk(then, 300)
        assert trace.counters().get("diana.mlfq.work_fallback", 0) == 0


def test_priority_written_on_a_queued_job_holds_until_the_next_submit():
    """§IX's bump on a job still queued: the next pop sees the written
    priority, only that job's, and the next submit recomputes it."""
    q = MultilevelFeedbackQueues({"a": 1.0, "b": 1.0})
    a1, a2 = q.submit(Job(user="a", submit_time=0.0)), q.submit(Job(user="a", submit_time=1.0))
    b = q.submit(Job(user="b", submit_time=2.0))
    assert b.priority > a1.priority == a2.priority
    a2.priority = 0.99
    assert a1.priority != 0.99
    assert q.jobs_ahead(0.99) == 1
    assert q.pop_next() is a2
    assert a2.priority == 0.99
    q.submit(Job(user="b", submit_time=3.0))
    a1.priority = 0.99
    q.submit(Job(user="a", submit_time=4.0))
    assert a1.priority < 0.99                # recomputed with the rest


def test_requeue_keeps_priority_and_goes_to_the_end():
    """The serving engine's requeue of a request it skipped: back at the
    end of ``jobs`` with the priority it left with, nothing else moved."""
    q = MultilevelFeedbackQueues({"a": 1.0, "b": 4.0})
    jobs = [q.submit(Job(user=u, submit_time=float(i))) for i, u in enumerate("aabb")]
    head = q.pop_next()
    before = [(j.job_id, j.priority, j.queue) for j in q.jobs]
    kept = (head.priority, head.queue)
    q.requeue(head)
    assert list(q.jobs)[-1] is head and len(q) == 4
    assert (head.priority, head.queue) == kept
    assert [(j.job_id, j.priority, j.queue) for j in q.jobs][:3] == before
    assert q.pop_next() is head              # still the highest
    q.requeue(head)
    with pytest.raises(ValueError):
        q.requeue(head)                      # already queued
    q.submit(Job(user="a", submit_time=9.0))
    assert head.user == "b" and head.priority == jobs[3].priority != kept[0]


def test_a_deep_copy_queues_and_serves_like_the_original():
    """A copied queue (a copied simulator holds them) keys its own jobs:
    with classed and loose jobs queued, both copies pop, remove and
    re-prioritize alike, and neither touches the other."""
    import copy

    q = MultilevelFeedbackQueues({"a": 1.0, "b": 4.0})
    jobs = [q.submit(Job(user=u, t=1 + i % 2, submit_time=float(i)))
            for i, u in enumerate("aabbab")]
    jobs[1].priority = 0.99                  # loose until the next submit
    q.requeue(q.pop_next())                  # loose, back at the end
    c = copy.deepcopy(q)
    assert [j.job_id for j in c.jobs] == [j.job_id for j in q.jobs]
    mine = {j.job_id: j for j in c.jobs}
    for queue, job in ((q, jobs[2]), (c, mine[jobs[2].job_id])):
        queue.remove(job)
        queue.submit(Job(user="a", submit_time=9.0, job_id=-1))
    served = [[(j.job_id, j.priority, j.queue) for j in iter(s.pop_next, None)]
              for s in (q, c)]
    assert served[0] == served[1] and len(served[0]) == 6

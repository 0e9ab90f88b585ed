"""Spans and counters (``repro.core.trace``): off by default and free of
effect on decisions; on, the counters read what the calls did."""
import contextlib
import copy

import numpy as np
import pytest

from repro.core import DianaScheduler, Job, MultilevelFeedbackQueues, NetworkLink, SiteState
from repro.core import trace


@pytest.fixture
def tracing_on():
    trace.enable()
    trace.reset()
    try:
        yield
    finally:
        trace.disable()
        trace.reset()


def _scheduler(seed, n_sites=24):
    rng = np.random.default_rng(seed)
    sites, links = {}, {}
    for i in range(n_sites):
        name = f"s{i:02d}"
        sites[name] = SiteState(
            name=name, capacity=float(rng.integers(10, 2000)),
            queue_length=float(rng.integers(0, 50)),
            waiting_work=float(rng.uniform(0, 500)),
            load=float(rng.uniform(0, 1)),
            alive=bool(i == 0 or rng.uniform() > 0.2),
        )
        links[name] = NetworkLink(
            bandwidth_Bps=float(rng.uniform(1e6, 1e10)),
            loss_rate=0.0 if rng.uniform() < 0.3 else float(rng.uniform(1e-4, 0.05)),
            rtt_s=float(rng.uniform(0.001, 0.3)),
        )
    return DianaScheduler(sites, links)


def _jobs(seed, n):
    rng = np.random.default_rng(seed + 1)
    return [
        Job(user=f"u{i % 3}", compute_work=float(rng.uniform(0.1, 200)),
            input_bytes=float(rng.choice([0.0, rng.uniform(0, 50e9)])),
            output_bytes=float(rng.uniform(0, 1e9)))
        for i in range(n)
    ]


def _tiers(sched, n_tiers=5):
    return {n: f"t{i % n_tiers}" for i, n in enumerate(sched.sites)}


def _place_rounds(sched, mode, rounds=3, n=30):
    """Place ``rounds`` batches; returns everything a decision shows."""
    out = []
    tiers = _tiers(sched) if mode == "hier" else None
    for r in range(rounds):
        jobs = _jobs(r, n)
        p = sched.place_batch(jobs, mode=mode, tiers=tiers)
        out.append((
            list(p.sites), p.costs.tolist(), [j.site for j in jobs],
            {k: (s.queue_length, s.waiting_work) for k, s in sched.sites.items()},
        ))
    return out


def test_off_by_default_span_is_shared_noop_and_counts_nothing():
    assert not trace.on
    assert trace.span("diana.pack") is trace.span("diana.replay")
    with trace.span("diana.pack") as entered:
        assert entered is None
    for mode in ("flat", "hier"):
        _place_rounds(_scheduler(3), mode)
    q = MultilevelFeedbackQueues({"a": 1.0})
    q.submit(Job(user="a"))
    q.pop_next()
    assert trace.counters() == {}


@pytest.mark.parametrize("mode", ["flat", "hier"])
def test_decisions_bit_identical_with_tracing_on(mode, tracing_on):
    base = _scheduler(7)
    traced = copy.deepcopy(base)
    trace.disable()
    off = _place_rounds(base, mode)
    trace.enable()
    on = _place_rounds(traced, mode)
    assert on == off


@pytest.mark.parametrize("mode", ["flat", "hier"])
def test_calls_and_jobs_placed(mode, tracing_on):
    _place_rounds(_scheduler(11), mode, rounds=4, n=17)
    c = trace.counters()
    assert c["diana.calls"] == 4
    assert c["diana.jobs_placed"] == 4 * 17
    trace.reset()
    assert trace.counters() == {}


def test_hier_refines_at_least_one_region_and_column_per_job(tracing_on):
    J = 3 * 30
    _place_rounds(_scheduler(13), "hier")
    c = trace.counters()
    assert c["diana.jobs_placed"] == J
    assert c["diana.hier.cols_refined"] >= c["diana.hier.tiers_refined"] >= J
    # at most every region of the five, once per job
    assert c["diana.hier.tiers_refined"] <= 5 * J


def test_reprioritized_counts_queue_depth_at_each_submit(tracing_on):
    q = MultilevelFeedbackQueues({"a": 1.0, "b": 2.0})
    depths = []
    for k, user in enumerate("aabab"):
        q.submit(Job(user=user, t=1.0 + k, submit_time=float(k)))
        depths.append(len(q))
        if k == 2:
            q.pop_next()
    assert depths == [1, 2, 3, 3, 4]
    c = trace.counters()
    assert c["diana.mlfq.submits"] == 5
    assert c["diana.mlfq.reprioritized"] == sum(depths)


@pytest.mark.parametrize("quota_b, t_step", [
    (2.0, 1.0),             # integers: running totals, no fallback
    (2.5, 1.0),             # a fractional quota, while b has jobs queued
    (2.0, 0.5),             # fractional t, while such a job is queued
])
def test_classes_and_exact_fallback_per_submit(tracing_on, quota_b, t_step):
    """``diana.mlfq.classes`` counts the (user, t) rows each submit
    recomputes; ``diana.mlfq.exact_fallback`` the submits whose Q or T
    could not come from running totals exactly."""
    quotas = {"a": 1.0, "b": quota_b}
    q = MultilevelFeedbackQueues(quotas)
    classes, fallbacks = [], 0
    for k, user in enumerate("aabab"):
        q.submit(Job(user=user, t=1.0 + t_step * (k % 2), submit_time=float(k)))
        classes.append(len({(j.user, j.t) for j in q.jobs}))
        fallbacks += any(quotas[j.user] % 1 or j.t % 1 for j in q.jobs)
        if k == 2:
            q.pop_next()
    c = trace.counters()
    assert c["diana.mlfq.classes"] == sum(classes)
    assert c.get("diana.mlfq.exact_fallback", 0) == fallbacks
    assert (fallbacks == 0) == (quota_b % 1 == t_step % 1 == 0)


class _SpanLog:
    """Stands in for ``jax.profiler.TraceAnnotation``: counts each span
    name entered."""

    def __init__(self):
        self.names: list[str] = []

    def __call__(self, name):
        self.names.append(name)
        return contextlib.nullcontext()


def _p2p_run(**kw):
    """A three-peer P2PGridSim run with 2 s gossip latency; migration
    ticks only after the last job, so each job's site is where the
    submitting peer placed it."""
    from repro.sim import P2PGridSim, SimConfig, SimJob, paper_grid_spec

    rng = np.random.default_rng(4)
    names = sorted(paper_grid_spec())
    jobs = [SimJob(user=f"u{k % 3}", arrival=1.5 * k, work=float(rng.uniform(20, 90)),
                   input_bytes=float(rng.uniform(0, 2e9)), output_bytes=1e7,
                   data_site=names[int(rng.integers(5))],
                   origin_site=names[int(rng.integers(5))])
            for k in range(120)]
    sim = P2PGridSim(paper_grid_spec(), config=SimConfig(
        policy="diana", num_peers=3, exchange_interval_s=30.0, exchange_latency_s=2.0,
        migration_interval_s=1e6, **kw))
    return sim, sim.run(jobs)


def test_p2p_off_by_default_counts_nothing():
    assert not trace.on
    _p2p_run()
    assert trace.counters() == {}


def test_p2p_counters_equal_exchange_stats_and_placements(tracing_on, monkeypatch):
    spans = _SpanLog()
    monkeypatch.setattr(trace, "_annotation", spans)
    sim, res = _p2p_run()
    c, stats = trace.counters(), sim.exchange.stats
    assert c["diana.p2p.rounds"] == stats.rounds > 0
    assert c["diana.p2p.packets"] == stats.deliveries > 0
    assert c["diana.p2p.bytes"] == stats.bytes_sent > 0
    assert c["diana.p2p.rows_merged"] == stats.adverts_applied > 0
    remote = sum(1 for j in res.jobs
                 if j.exec_site not in sim._peer_by_site[j.origin_site].home_sites)
    assert c["diana.p2p.remote_placements"] == remote > 0
    assert spans.names.count("diana.p2p.round") == c["diana.p2p.rounds"]
    assert spans.names.count("diana.p2p.view") == len(res.jobs)
    # every exchange event and every delivery event drains the heap once
    assert spans.names.count("diana.p2p.deliver") >= c["diana.p2p.rounds"]


def test_p2p_decisions_bit_identical_with_tracing_on(tracing_on):
    on = _p2p_run()[1]
    trace.disable()
    off = _p2p_run()[1]
    assert [(j.exec_site, j.finish) for j in on.jobs] == [(j.exec_site, j.finish) for j in off.jobs]

"""The program's ``diana.*`` spans reach the profiler's host timeline,
where ``tracing.collect`` finds them, nested as the program nests them,
one span per call the counters count."""
import jax
import numpy as np
import pytest

from diana_bench import tracing

PLACE_SPANS = ("diana.place_batch", "diana.pack", "diana.plane", "diana.replay",
               "diana.commit")
SIM_SPANS = ("diana.sim.run", "diana.sim.arrive", "diana.sim.migrate",
             "diana.mlfq.reprioritize", "diana.mlfq.pop")


@pytest.fixture
def traced(tmp_path):
    """Run a callable with ``repro.core.trace`` on, under a profiler
    trace inside a ``window`` annotation; give back the collected
    ``diana.*`` spans and the counters."""
    from repro.core import trace

    def run(fn, names):
        trace.enable()
        trace.reset()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("window"):
                fn()
        finally:
            jax.profiler.stop_trace()
            counters = trace.counters()
            trace.disable()
            trace.reset()
        raw = tracing.collect(str(tmp_path), names)
        assert raw is not None
        return raw["host"], counters

    return run


def _inside(inner, outer) -> bool:
    _, s, d = inner
    _, so, do = outer
    return so <= s and s + d <= so + do


def _parent(span, spans):
    """The latest-started span that encloses ``span``."""
    around = [o for o in spans if o is not span and _inside(span, o)]
    return max(around, key=lambda o: o[1])[0] if around else None


def _scheduler():
    from repro.core import DianaScheduler, NetworkLink, SiteState

    rng = np.random.default_rng(0)
    sites = {f"s{i}": SiteState(name=f"s{i}", capacity=float(rng.integers(10, 500)))
             for i in range(12)}
    links = {n: NetworkLink(bandwidth_Bps=float(rng.uniform(1e7, 1e9)),
                            loss_rate=0.0, rtt_s=0.01) for n in sites}
    return DianaScheduler(sites, links)


def _jobs(n):
    from repro.core import Job

    return [Job(user="u", compute_work=1.0 + k, input_bytes=1e8 * (k % 3)) for k in range(n)]


def test_place_batch_spans_nest_as_the_program_does(traced):
    sched = _scheduler()
    tiers = {n: f"t{i % 3}" for i, n in enumerate(sched.sites)}

    def calls():
        sched.place_batch(_jobs(20))
        sched.place_batch(_jobs(20), mode="hier", tiers=tiers)
        sched.place_batch(_jobs(5))

    spans, counters = traced(calls, PLACE_SPANS)
    names = [s[0] for s in spans]
    assert names.count("diana.place_batch") == counters["diana.calls"] == 3
    assert counters["diana.jobs_placed"] == 45
    for child in ("diana.pack", "diana.plane", "diana.replay", "diana.commit"):
        assert names.count(child) == 3
    for s in spans:
        want = None if s[0] == "diana.place_batch" else "diana.place_batch"
        assert _parent(s, spans) == want, s


def test_sim_spans_nest_and_count_every_submit(traced):
    from repro.sim import GridSim, SimConfig
    from repro.sim.workloads import SimJob

    rng = np.random.default_rng(1)
    site_nodes = {f"s{i}": 2 for i in range(4)}
    jobs = [SimJob(user=f"u{k % 3}", arrival=float(k) * 0.5, work=float(rng.uniform(1, 20)),
                   input_bytes=1e8, output_bytes=1e6, data_site="s1", origin_site="s0")
            for k in range(60)]
    sim = GridSim(site_nodes, config=SimConfig(policy="diana", migration_interval_s=5.0))

    spans, counters = traced(lambda: sim.run(jobs), SIM_SPANS)
    names = [s[0] for s in spans]
    assert names.count("diana.sim.run") == 1
    assert names.count("diana.sim.migrate") > 0
    # each arrival submits once; a migration submits again at its target
    assert names.count("diana.mlfq.reprioritize") == counters["diana.mlfq.submits"] >= 60
    assert counters["diana.mlfq.reprioritized"] >= counters["diana.mlfq.submits"]
    parents = {"diana.sim.arrive": {"diana.sim.run"},
               "diana.sim.migrate": {"diana.sim.run"},
               "diana.mlfq.reprioritize": {"diana.sim.arrive", "diana.sim.migrate"},
               "diana.mlfq.pop": {"diana.sim.arrive", "diana.sim.run",
                                  "diana.sim.migrate"}}
    for s in spans:
        assert _parent(s, spans) in parents.get(s[0], {None}), s

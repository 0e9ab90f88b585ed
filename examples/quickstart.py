"""Quickstart: the DIANA scheduler API in five minutes.

Builds the paper's world — sites, links, users with quotas — submits a
bulk job group, and shows every §IV–§X mechanism: cost-ranked
placement, quota priorities, multilevel queues, group splitting,
congestion-driven migration.

    PYTHONPATH=src python examples/quickstart.py
"""
import numpy as np

from repro.core import (
    BulkGroup, BulkScheduler, DianaScheduler, Job, JobClass,
    MultilevelFeedbackQueues, NetworkLink, SiteState,
    allocate_proportional, average_makespan,
)

# --- 1. the grid (paper Fig 4 sites) -------------------------------------
sites = {
    "A": SiteState(name="A", capacity=100),
    "B": SiteState(name="B", capacity=200),
    "C": SiteState(name="C", capacity=400),
    "D": SiteState(name="D", capacity=600),
}
links = {
    "A": NetworkLink(bandwidth_Bps=1e9, loss_rate=0.001),
    "B": NetworkLink(bandwidth_Bps=1e9, loss_rate=0.01),   # lossy WAN
    "C": NetworkLink(bandwidth_Bps=10e9, loss_rate=0.0),   # fat pipe
    "D": NetworkLink(bandwidth_Bps=2e9, loss_rate=0.002),
}
diana = DianaScheduler(sites, links)

# --- 2. §V: cost-ranked placement ----------------------------------------
data_job = Job(user="lisa", compute_work=2.0, input_bytes=30e9)   # 30 GB in
decision = diana.select_site(data_job)
print(f"data-intensive job → {decision.site} "
      f"(class={decision.job_class.value}, cost={decision.cost:.1f}s)")
for site, cost in decision.ranking:
    print(f"   {site}: {cost:9.2f}s")

# --- 3. §X: quota economy + multilevel feedback queues --------------------
q = MultilevelFeedbackQueues(quotas={"lisa": 1900.0, "bart": 1700.0})
for i in range(5):
    q.submit(Job(user="bart", t=1, submit_time=float(i)))
vip = q.submit(Job(user="lisa", t=1, submit_time=5.0))
print(f"\nbart floods 5 jobs; lisa submits one → lisa Pr={vip.priority:.3f} "
      f"(Q{vip.queue + 1}), bart head Pr={max(j.priority for j in q.jobs if j.user=='bart'):.3f}")
print("dispatch order:", [q.pop_next().user for _ in range(6)])

# --- 4. §VIII: bulk groups ----------------------------------------------
print("\nFig 4 — 10,000 one-hour jobs, groups vs avg makespan:")
caps = {k: s.capacity for k, s in sites.items()}
for g in (1, 2, 10):
    alloc = allocate_proportional(10_000, g, caps)
    print(f"  {g:>2} group(s): {average_makespan(alloc, caps):5.2f} h   {alloc}")

bulk = BulkScheduler(diana)
group = BulkGroup(user="lisa", jobs=[Job(user="lisa", t=1) for _ in range(5000)],
                  group_id="higgs-scan", division_factor=4)
placement = bulk.schedule_group(group)
print(f"\nbulk group 'higgs-scan' split={placement.split} → "
      + ", ".join(f"{s}:{len(js)}" for s, js in placement.assignments.items()))
print("output aggregation plan:", bulk.aggregate_outputs(placement))

# --- 5. batched placement: the bulk-scale fast path ----------------------
# One (jobs × sites) §IV matrix pass + vectorized replay of the queue
# feedback — bit-identical to calling diana.place() per job, but one
# array program instead of an O(J·S) Python loop (see
# benchmarks/bulk_placement_bench.py: ~25x at 10k jobs × 256 sites).
burst = [Job(user="bart", compute_work=float(w), input_bytes=5e9)
         for w in np.linspace(1, 50, 1000)]
batch = diana.place_batch(burst)
spread = {s: batch.sites.count(s) for s in sites}
print(f"\n1000-job burst placed in one batched pass → {spread}")
print(f"   classes: {sorted({c.value for c in batch.classes})}, "
      f"cost range {batch.costs.min():.2f}–{batch.costs.max():.2f}s")

# Groups batch the same way: one matrix pass for all §VIII selections.
sweeps = [BulkGroup(user=f"grad{i}", group_id=f"sweep-{i}", division_factor=2,
                    jobs=[Job(user=f"grad{i}", t=1) for _ in range(200)])
          for i in range(4)]
for g, p in zip(sweeps, BulkScheduler(diana).schedule_groups(sweeps)):
    print(f"   {g.group_id}: split={p.split} sites={p.sites}")

# --- 6. §IX/§X: congestion-driven migration, batched ----------------------
# In the grid simulator every congested site's Q4 candidates are
# evaluated against all peers as ONE (jobs × sites) matrix pass
# (select_peers_batch over memoized §IV cost planes) — bit-identical to
# polling each peer per job, but vectorized (see
# benchmarks/migration_bench.py: >10x at 10k jobs × 256 sites).
from repro.sim import GridSim, bulk_burst, paper_grid_spec

flood = []
for b in range(6):                       # a low-quota user floods site1
    flood += bulk_burst("bart", 40, at=float(b * 30), work=300.0,
                        input_bytes=2e9, data_site="site1", origin_site="site1")
for i in range(40):                      # a high-quota user queues behind
    flood += bulk_burst("lisa", 1, at=float(i * 20), work=300.0,
                        input_bytes=2e9, data_site="site1", origin_site="site1")
sim = GridSim(paper_grid_spec(), policy="diana",
              quotas={"bart": 10.0, "lisa": 1000.0},
              migration_interval_s=30.0, congestion_window_s=120.0)
res = sim.run(sorted(flood, key=lambda j: j.arrival))
exports = {s: sum(res.timeline[s]["exported"]) for s in res.timeline}
print(f"\ncongestion migration (batched §IX pass): {res.migrations()} moves, "
      "exports " + ", ".join(f"{s}:{n}" for s, n in exports.items() if n))

# --- 7. §III/§IX: decentralized P2P meta-scheduling -----------------------
# The paper's DIANA engine is a *decentralized* Meta Scheduler: each
# site runs its own instance and learns about the others only through
# exchanged packed SitePack rows (one (8, S) float64 array + a version
# vector per peer). A peer's placements run on its own — possibly
# stale — world view; gossip rounds (GossipExchange) re-converge it.
from repro.core import GossipExchange, PeerScheduler

p2p_sites = {
    "A": SiteState(name="A", capacity=100.0),
    "B": SiteState(name="B", capacity=100.0),
    "C": SiteState(name="C", capacity=100.0),
}
p2p_links = {n: NetworkLink(bandwidth_Bps=1e9) for n in p2p_sites}
peers = {
    n: PeerScheduler(home=n, sites=dict(p2p_sites), links=dict(p2p_links))
    for n in p2p_sites
}

# A's own site is busy, and B's queue explodes — but only B's own
# scheduler knows about the flood at first.
peers["A"].authoritative["A"].queue_length = 400.0
peers["B"].authoritative["B"].queue_length = 500.0
probe = lambda: Job(user="lisa", compute_work=1.0)
stale_pick = peers["A"].place_batch([probe()]).sites[0]   # 'B': looks empty!

ex = GossipExchange(list(peers.values()))   # full mesh (pass a
ex.round(now=1.0)                           # GridTopology for tiered fan-out)
fresh_pick = peers["A"].place_batch([probe()]).sites[0]   # 'C': B advertised
print(f"\nP2P (3 peers): A's stale view placed at {stale_pick!r}; "
      f"after one exchange round it places at {fresh_pick!r} "
      f"(B advertised queue=500). "
      f"wire cost: {ex.stats.bytes_sent} B in {ex.stats.adverts_sent} adverts")
staleness = peers["A"].staleness(now=60.0)
print("A's per-row staleness at t=60:",
      {n: float(staleness[i]) for i, n in enumerate(peers['A'].view.names)})

# The exchange above ran the delta-compressed wire (the default): the
# first round is a full sync that negotiates each pair's interned
# site-id table; afterwards a round ships only the columns whose epoch
# advanced since the receiver last acknowledged — quantized to f32
# (quant="f16" opts into half precision), with tiny heartbeats keeping
# unchanged rows' staleness fresh. wire="full" is the uncompressed
# everything-every-round flood:
for wire in ("full", "delta"):
    wpeers = [PeerScheduler(home=n, sites=dict(p2p_sites), links=dict(p2p_links))
              for n in p2p_sites]
    wex = GossipExchange(wpeers, wire=wire)
    for rnd in range(8):                       # steady state: nothing changes
        wex.round(now=60.0 * rnd)
    s = wex.stats
    print(f"wire={wire:5s}: {s.bytes_sent:6d} B over {s.rounds} rounds "
          f"({s.adverts_sent} adverts, {s.heartbeats_sent} heartbeats, "
          f"{s.full_syncs} full syncs)")
# The same protocol drives the simulator at scale: see
# repro.sim.P2PGridSim (gossip_wire=/gossip_quant=) and
# benchmarks/p2p_bench.py (bytes + makespan, compressed vs
# uncompressed, as a function of exchange interval).

# --- 8. event-horizon streaming: one SimConfig, lazy ArrivalSources -------
# Every simulator knob lives in SimConfig now (the old keyword style
# still works behind a deprecation shim). The default run loop drains
# batched event horizons — bit-identical to the per-event reference
# loop (horizon=False) — and run() takes any ArrivalSource: a plain
# job list, or a lazy chunked stream that never materializes, so
# million-job open-loop runs keep bounded in-flight state.
from repro.sim import GridSim, SimConfig, poisson_source, serving_trace_source

cfg = SimConfig(policy="diana", migration_interval_s=120.0, horizon=True)
stream = poisson_source("cms", rate_per_s=0.2, duration_s=7200.0, seed=0,
                        work=90.0, input_bytes=0.0, data_site=None)
res = GridSim(paper_grid_spec(), config=cfg).run(stream)  # lazy chunks
s = res.stats                                        # bounded accumulators
print(f"\nstreaming run: {s.finished} jobs, peak in-flight {s.peak_in_flight}, "
      f"retained records {len(res.jobs)}")
print("turnaround p50/p95/p99:",
      [round(x, 1) for x in res.turnaround_percentiles()])

# serving/engine.py request traces replay through the grid scheduler as
# an open-loop workload (duck-typed: no jax import needed) — each
# InferenceRequest becomes a SimJob whose work scales with tokens and
# whose input bytes are the prompt (the prefix-cache/data-gravity term):
class _Req:                                 # stands in for InferenceRequest
    def __init__(self, user, at):
        import numpy as _np
        self.user, self.submit_time, self.group_id = user, at, "bulk0"
        self.prompt = _np.arange(16, dtype=_np.int32)
        self.max_new_tokens = 8

trace = [_Req("tenantA", float(i)) for i in range(200)]
res = GridSim(paper_grid_spec(), config=cfg).run(
    serving_trace_source(trace, work_per_token=0.5))
print(f"served trace: {res.stats.finished} requests, "
      f"avg turnaround {res.avg_turnaround:.1f}s")

# --- 9. fault-injection scenarios: generators, verifiers, baselines -------
# The scenario pack (src/repro/scenarios/) scripts faults into a run —
# timestamped site-down/up, P2P peer leave/join, WAN link degradation —
# via SimConfig.fault_plan, then asserts invariants against the
# finished run and checks the metrics against recorded envelopes.
from repro.scenarios import run_scenario
from repro.sim import FaultPlan

# Hand-rolled: kill a site mid-run; displaced jobs requeue through the
# §IX migration path and nothing ever completes on the dead site.
plan = (FaultPlan()
        .site_down(120.0, "site3")
        .site_up(600.0, "site3")
        .link_degrade(200.0, site="site2", bandwidth_factor=0.2)
        .link_restore(500.0, site="site2"))
cfg = SimConfig(policy="diana", fault_plan=plan, retain_jobs=True,
                migration_interval_s=60.0)
res = GridSim(paper_grid_spec(), config=cfg).run(
    poisson_source("ops", rate_per_s=0.3, duration_s=900.0, seed=1,
                   work=120.0, input_bytes=5e8, data_site="site3"))
dead = [j for j in res.jobs
        if j.exec_site == "site3" and 120.0 <= j.finish < 600.0]
print(f"\nfault run: {res.stats.finished} finished, "
      f"{res.stats.requeued} requeued off the dead site, "
      f"completions on dead site3 during the outage: {len(dead)}")

# Packaged: each scenario couples a generator (workload + FaultPlan) to
# a verifier (invariants + baseline envelopes). `run_scenario` raises
# ScenarioViolation if any invariant breaks; the same pack runs in CI
# (smoke scale) and benchmarks (bench scale → BENCH_<name>.json).
spec, sim, result, metrics = run_scenario("site_failure", scale="smoke")
print(f"scenario {spec.name}: {metrics['finished']} finished, "
      f"{metrics['requeued']} requeued, makespan {metrics['makespan']:.0f}s "
      f"— all invariants + baseline envelopes verified")

# --- 10. unreliable transport: loss, retransmission, suspicion ------------
# SimConfig.transport_faults attaches a TransportFaults model to the
# P2P gossip wire: every message (delta packets, full-wire datagrams,
# acks) passes through seeded loss (iid + Gilbert–Elliott bursts),
# duplication, reorder jitter, single-bit corruption, and scripted
# PartitionWindows. The protocol absorbs it — per-pair sequence
# numbers + a replay window suppress duplicates, checksums drop
# corrupted packets, un-acked packets retransmit with exponential
# backoff until the pair escalates to a forced full sync, and a
# phi-accrual failure detector grades per-peer suspicion that widens
# the migration staleness gate. All-zero rates are bit-identical to no
# transport model at all.
from repro.sim import P2PGridSim, TransportFaults

faults = TransportFaults(
    seed=1,
    loss=0.10,              # iid drop probability per message
    duplicate=0.02,         # delivered twice (copy jittered separately)
    reorder_jitter_s=4.0,   # extra uniform [0, 4) s per copy
    corrupt=0.01,           # one flipped bit per packet (CRC catches it)
    burst_p=0.05, burst_r=0.5, burst_loss=0.6,   # Gilbert–Elliott layer
)
cfg = SimConfig(policy="diana", num_peers=4, exchange_interval_s=60.0,
                exchange_latency_s=5.0, gossip_wire="delta",
                transport_faults=faults, migration_interval_s=60.0)
sim = P2PGridSim(paper_grid_spec(), config=cfg)
res = sim.run(poisson_source("wan", rate_per_s=0.3, duration_s=900.0,
                             seed=2, work=150.0))
# ExchangeStats carries the transport counters: what the wire did to
# the messages, and what the protocol did about it.
st = sim.exchange.stats
print(f"\nlossy transport: {res.stats.finished} finished | "
      f"dropped={st.dropped} duplicated={st.duplicated} "
      f"corrupted={st.corrupted} reordered={st.reordered}")
print(f"recovery: retransmits={st.retransmits} "
      f"dup_suppressed={st.dup_suppressed} "
      f"full-sync escalations={st.sync_escalations}")
# Suspicion is queryable per (receiver, sender) pair: phi ≈ how
# improbable the current silence is given observed delivery gaps.
phi = sim.exchange.suspicion_phi(0, 1, now=res.makespan)
print(f"peer0's suspicion of peer1 at the end: phi={phi:.2f} "
      f"(suspect past {faults.phi_threshold})")

# --- 11. hierarchical two-level placement: 10k+ sites ---------------------
# Flat placement materializes dense (jobs × sites) float64 planes —
# ~8 GB for the data-transfer term alone at 10k sites × 100k jobs.
# mode="hier" aggregates each RootGrid tier of a GridTopology into a
# summary column (an admissible optimistic lower bound over the §IV
# net/comp/data terms), argmins every job over the small (J, T) tier
# matrix first, and runs the dense pass only inside the winning tier —
# widening to any runner-up tier whose bound still beats the incumbent,
# so decisions stay bit-identical to the flat argmin. SitePack planes
# shrink to f32 with exact f64 refinement on the shortlisted columns
# (TierPack in repro.core.batch). On tier-structured WANs this is
# 67x wall and ~2000x peak memory at the headline scale — 16 GB of
# flat planes vs ~8 MB (benchmarks/hier_bench.py, BENCH_hier.json).
from repro.core import GridTopology, Node

topo = GridTopology()
for i, name in enumerate(sites):          # reuse the §1 grid: 2 regions
    topo.join(f"region{i % 2}", Node(name=name))
hier_sched = DianaScheduler(dict(sites), dict(links), topology=topo)
hier_batch = hier_sched.place_batch(
    [Job(user="lisa", compute_work=float(w), input_bytes=5e9)
     for w in np.linspace(1, 50, 1000)],
    mode="hier")                          # tiers=... overrides the topology
assert hier_batch.sites == batch.sites    # bit-identical to §5's flat pass
print(f"\nhier placement (2 tiers): identical to flat on "
      f"{len(hier_batch.sites)} jobs")

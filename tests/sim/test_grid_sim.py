"""§XI simulation behaviour: DIANA vs baselines, migration dynamics."""
import copy

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.sim import (
    GridSim, P2PGridSim, SimJob, bulk_burst, paper_grid_spec, uniform_links,
)


def _run(policy, jobs, nodes=None, **kw):
    nodes = nodes or paper_grid_spec()
    sim = GridSim(nodes, policy=policy, **kw)
    return sim.run(copy.deepcopy(jobs))


def _data_heavy_workload(n=120, seed=0):
    """Jobs submitted at site1 whose data lives on site3 — DIANA should
    route near the data; 'local' pays WAN fetches; 'greedy' ignores it."""
    rng = np.random.default_rng(seed)
    jobs = []
    for i in range(n):
        jobs.extend(
            bulk_burst(
                user=f"u{i % 4}", n=1, at=float(i * 2),
                work=30.0, input_bytes=5e9, output_bytes=1e8,
                data_site="site3", origin_site="site1", rng=rng,
            )
        )
    return jobs


def test_all_jobs_complete_every_policy():
    jobs = _data_heavy_workload(60)
    for policy in ("diana", "greedy", "local", "fcfs"):
        res = _run(policy, jobs)
        assert all(j.finish >= 0 for j in res.jobs), policy
        assert res.makespan > 0


def test_determinism():
    jobs = _data_heavy_workload(50)
    r1 = _run("diana", jobs)
    r2 = _run("diana", jobs)
    assert r1.avg_queue_time == r2.avg_queue_time
    assert r1.avg_exec_time == r2.avg_exec_time


def test_diana_beats_local_on_data_heavy():
    """Fig 7/8 headline: network/data-aware placement beats move-data-
    to-job on turnaround."""
    jobs = _data_heavy_workload(120)
    diana = _run("diana", jobs)
    local = _run("local", jobs)
    assert diana.avg_turnaround < local.avg_turnaround


def test_diana_beats_greedy_on_data_heavy():
    jobs = _data_heavy_workload(120)
    diana = _run("diana", jobs)
    greedy = _run("greedy", jobs)
    assert diana.avg_exec_time <= greedy.avg_exec_time * 1.05
    assert diana.avg_turnaround <= greedy.avg_turnaround * 1.05


def test_diana_places_near_data():
    jobs = _data_heavy_workload(40)
    res = _run("diana", jobs)
    at_data = sum(1 for j in res.jobs if j.exec_site == "site3")
    assert at_data > len(jobs) * 0.4


def test_queue_time_grows_with_job_count():
    """Fig 7: queue time grows as the number of jobs increases."""
    qts = []
    for n in (25, 100, 400):
        jobs = bulk_burst("u0", n, at=0.0, work=60.0, input_bytes=0.0,
                          data_site="site1", origin_site="site1")
        res = _run("diana", jobs)
        qts.append(res.avg_queue_time)
    assert qts[0] <= qts[1] <= qts[2]
    assert qts[2] > qts[0]


def _overload_workload():
    """Grid-saturating flood from a low-quota 'hog' plus a queued
    high-quota 'polite' stream ⇒ hog jobs cross N and sink to Q4 (§X),
    sites congest, and §IX migration has somewhere cheaper to go."""
    jobs = []
    for b in range(6):
        jobs.extend(
            bulk_burst("hog", 40, at=float(b * 30), work=300.0,
                       input_bytes=2e9, data_site="site1", origin_site="site1")
        )
    for i in range(40):
        jobs.extend(
            bulk_burst("polite", 1, at=float(i * 20), work=300.0,
                       input_bytes=2e9, data_site="site1", origin_site="site1")
        )
    return sorted(jobs, key=lambda j: j.arrival)


QUOTAS = {"hog": 10.0, "polite": 1000.0}


def test_overloaded_site_exports_jobs():
    """Fig 9: submission rate ≫ site capacity ⇒ exports to peers."""
    sim = GridSim(paper_grid_spec(), policy="diana", quotas=QUOTAS,
                  migration_interval_s=30.0, congestion_window_s=120.0)
    res = sim.run(copy.deepcopy(_overload_workload()))
    exported = sum(sum(res.timeline[s]["exported"]) for s in res.timeline)
    assert res.migrations() > 0
    assert exported == sum(sum(res.timeline[s]["imported"]) for s in res.timeline)
    assert exported > 0


def test_underloaded_site_imports_jobs():
    """Fig 10: capacity > submitted jobs ⇒ the big site imports."""
    nodes = dict(paper_grid_spec(), big=50)
    sim = GridSim(nodes, policy="diana", quotas=QUOTAS,
                  migration_interval_s=30.0, congestion_window_s=120.0)
    res = sim.run(copy.deepcopy(_overload_workload()))
    total_imported = sum(sum(res.timeline[s]["imported"]) for s in res.timeline)
    assert total_imported > 0


def test_migrated_jobs_are_pinned():
    sim = GridSim(paper_grid_spec(), policy="diana", quotas=QUOTAS,
                  migration_interval_s=30.0, congestion_window_s=120.0)
    res = sim.run(copy.deepcopy(_overload_workload()))
    # every migrated job finished exactly once (no cycling)
    migrated = [j for j in res.jobs if j.migrated]
    assert migrated and all(j.finish >= 0 for j in migrated)


def test_fcfs_baseline_single_queue():
    jobs = bulk_burst("u", 30, at=0.0, work=10.0, input_bytes=0.0)
    res = _run("fcfs", jobs)
    assert all(j.finish >= 0 for j in res.jobs)
    # FCFS order: starts are non-decreasing in arrival order.
    starts = [j.start for j in res.jobs]
    assert starts == sorted(starts)


def test_policy_ordering_regression():
    """§XI headline regression: DIANA's turnaround never loses to any
    baseline on the data-heavy workload (Fig 7/8 ordering)."""
    jobs = _data_heavy_workload(120)
    turnarounds = {
        policy: _run(policy, jobs).avg_turnaround
        for policy in ("diana", "greedy", "local", "fcfs")
    }
    assert turnarounds["diana"] <= turnarounds["greedy"]
    assert turnarounds["diana"] <= turnarounds["fcfs"]
    assert turnarounds["diana"] <= turnarounds["local"]


class TestArrivalBatchFastPath:
    """The vectorized same-instant arrival path must be bit-identical
    to sequential per-arrival processing."""

    def _burst_workload(self):
        rng = np.random.default_rng(7)
        jobs = []
        for b in range(5):
            jobs.extend(
                bulk_burst(f"u{b % 2}", 40, at=float(b * 40), work=80.0,
                           input_bytes=4e9, output_bytes=2e8,
                           data_site="site3", origin_site="site1",
                           rng=rng, work_jitter=0.3)
            )
        return sorted(jobs, key=lambda j: j.arrival)

    def _compare(self, jobs, **kw):
        seq = GridSim(paper_grid_spec(), policy="diana",
                      batch_arrivals=False, **kw).run(copy.deepcopy(jobs))
        bat = GridSim(paper_grid_spec(), policy="diana",
                      batch_arrivals=True, **kw).run(copy.deepcopy(jobs))
        assert [j.exec_site for j in seq.jobs] == [j.exec_site for j in bat.jobs]
        assert [j.start for j in seq.jobs] == [j.start for j in bat.jobs]
        assert [j.finish for j in seq.jobs] == [j.finish for j in bat.jobs]
        assert seq.avg_turnaround == bat.avg_turnaround

    def test_bulk_bursts_identical(self):
        self._compare(self._burst_workload())

    def test_with_quotas_and_migration_identical(self):
        jobs = _overload_workload()
        self._compare(jobs, quotas=QUOTAS, migration_interval_s=30.0,
                      congestion_window_s=120.0)

    @pytest.mark.parametrize("policy", ["diana", "greedy", "local", "fcfs"])
    def test_choose_sites_batch_matches_choose_site_snapshot(self, policy):
        jobs = self._burst_workload()
        sim = GridSim(paper_grid_spec(), policy=policy)
        assert sim.choose_sites_batch(jobs) == [sim.choose_site(j) for j in jobs]

    def test_off_grid_job_endpoints_fall_back_to_sequential(self):
        """Jobs whose data lives on a link-table-only node (a storage
        element, not a compute site) must not crash the fast path."""
        from repro.sim import uniform_links

        links = uniform_links(["site1", "site2", "storage"])
        nodes = {"site1": 2, "site2": 2}
        jobs = bulk_burst("u", 10, at=0.0, work=5.0, input_bytes=2e9,
                          data_site="storage", origin_site="site1")
        bat = GridSim(nodes, links=links, policy="diana",
                      batch_arrivals=True).run(copy.deepcopy(jobs))
        seq = GridSim(nodes, links=links, policy="diana",
                      batch_arrivals=False).run(copy.deepcopy(jobs))
        assert all(j.finish >= 0 for j in bat.jobs)
        assert [j.exec_site for j in bat.jobs] == [j.exec_site for j in seq.jobs]
        assert [j.finish for j in bat.jobs] == [j.finish for j in seq.jobs]

    def test_assigning_links_invalidates_static_cache(self):
        """The memoized (net, dtc) rows derive from the link table —
        assigning a new table must drop them and the dense matrices."""
        sim = GridSim(paper_grid_spec(), policy="diana")
        jobs = bulk_burst("u", 5, at=0.0, work=5.0, input_bytes=1e9,
                          data_site="site3", origin_site="site1")
        sim.choose_sites_batch(jobs)
        assert sim._static_row_cache
        sim.links = uniform_links(list(paper_grid_spec()), bandwidth_Bps=1e7)
        assert not sim._static_row_cache
        assert sim._loss is None
        # and the rows re-derive from the new table
        sim.choose_sites_batch(jobs)
        assert sim._static_row_cache

    def test_full_link_table_reenables_disabled_fast_path(self):
        """A partial table disables batch arrivals; assigning a complete
        table afterwards restores the requested fast path."""
        names = list(paper_grid_spec())
        partial = {k: v for k, v in uniform_links(names).items()
                   if "site1" in k or k[0] == k[1]}
        sim = GridSim(paper_grid_spec(), policy="diana", links=partial,
                      batch_arrivals=True)
        assert not sim._link_matrices_ready()
        assert sim.batch_arrivals is False
        assert not sim._link_matrices_ready()  # cached failure, no rescan
        sim.links = uniform_links(names)
        assert sim.batch_arrivals is True
        assert sim._link_matrices_ready()

    def test_partial_link_table_falls_back_to_sequential(self):
        """A link dict covering only the pairs the sequential path
        traverses can't be densified — the fast path must disable
        itself, not crash, and results must match the sequential run."""
        from repro.sim import uniform_links

        names = ["site1", "site2", "site3"]
        links = {k: v for k, v in uniform_links(names).items()
                 if "site1" in k or k[0] == k[1]}
        jobs = bulk_burst("u", 20, at=0.0, work=5.0, input_bytes=1e9,
                          data_site="site1", origin_site="site1")
        nodes = {n: 2 for n in names}
        bat = GridSim(nodes, links=links, policy="diana", batch_arrivals=True)
        res = bat.run(copy.deepcopy(jobs))
        assert bat.batch_arrivals is False
        seq = GridSim(nodes, links=links, policy="diana",
                      batch_arrivals=False).run(copy.deepcopy(jobs))
        assert all(j.finish >= 0 for j in res.jobs)
        assert [j.exec_site for j in res.jobs] == [j.exec_site for j in seq.jobs]


class TestLinkInvalidationProperty:
    """Satellite of the PR 4 static-plane cache tests: ANY link-table
    mutation (setter or in-place + invalidate_links) followed by a
    placement must be bit-identical to a sim rebuilt from scratch
    against the same table — no stale derived plane may survive."""

    def _random_links(self, names, rng):
        links = {}
        for a in names:
            for b in names:
                if a == b:
                    links[(a, b)] = uniform_links([a])[(a, a)]
                else:
                    links[(a, b)] = uniform_links(
                        [a, b],
                        bandwidth_Bps=float(rng.uniform(1e8, 5e9)),
                        loss_rate=float(rng.uniform(1e-4, 0.02)),
                    )[(a, b)]
        return links

    def _batch(self, names, rng, n=25):
        jobs = []
        for i in range(n):
            jobs.extend(
                bulk_burst(f"u{i % 3}", 1, at=0.0,
                           work=float(rng.uniform(5, 200)),
                           input_bytes=float(rng.uniform(0, 5e9)),
                           output_bytes=float(rng.uniform(0, 5e8)),
                           data_site=names[int(rng.integers(len(names)))],
                           origin_site=names[int(rng.integers(len(names)))])
            )
        return jobs

    @given(seed=st.integers(0, 10_000), via_setter=st.booleans())
    @settings(max_examples=15, deadline=None)
    def test_placement_after_invalidation_matches_fresh_sim(self, seed, via_setter):
        rng = np.random.default_rng(seed)
        nodes = paper_grid_spec()
        names = sorted(nodes)
        sim = GridSim(nodes, policy="diana")
        # Warm every derived plane: dense matrices + memoized rows.
        sim.choose_sites_batch(self._batch(names, rng))
        assert sim._static_row_cache

        new_links = self._random_links(names, rng)
        if via_setter:
            sim.links = new_links
        else:
            # In-place mutation: the dict object keeps its identity, so
            # only invalidate_links() can drop the derived planes.
            sim.links.clear()
            sim.links.update(new_links)
            sim.invalidate_links()
        assert not sim._static_row_cache
        probe = self._batch(names, rng)
        fresh = GridSim(nodes, links=dict(new_links), policy="diana")
        assert sim.choose_sites_batch(copy.deepcopy(probe)) == \
            fresh.choose_sites_batch(copy.deepcopy(probe))


class TestP2PGridSim:
    """Multi-scheduler mode: the 1-peer special case is the omniscient
    sim, N peers complete the workload deterministically, and stale
    views cost (bounded) placement quality."""

    def _workload(self, n=80, seed=0):
        rng = np.random.default_rng(seed)
        names = sorted(paper_grid_spec())
        jobs = []
        for i in range(n):
            jobs.extend(
                bulk_burst(f"u{i % 4}", 2, at=float(i * 4),
                           work=float(rng.uniform(30, 120)),
                           input_bytes=0.0, output_bytes=0.0, data_site=None,
                           origin_site=names[int(rng.integers(len(names)))],
                           rng=rng, work_jitter=0.2)
            )
        return sorted(jobs, key=lambda j: j.arrival)

    @pytest.mark.parametrize("interval", [30.0, 600.0])
    def test_single_peer_is_bit_identical_to_omniscient(self, interval):
        jobs = self._workload()
        base = GridSim(paper_grid_spec(), policy="diana").run(copy.deepcopy(jobs))
        one = P2PGridSim(paper_grid_spec(), num_peers=1,
                         exchange_interval_s=interval).run(copy.deepcopy(jobs))
        assert [j.exec_site for j in base.jobs] == [j.exec_site for j in one.jobs]
        assert [j.start for j in base.jobs] == [j.start for j in one.jobs]
        assert [j.finish for j in base.jobs] == [j.finish for j in one.jobs]
        assert base.timeline == one.timeline

    def test_multi_peer_completes_and_is_deterministic(self):
        jobs = self._workload()
        runs = []
        for _ in range(2):
            sim = P2PGridSim(paper_grid_spec(), num_peers=3,
                             exchange_interval_s=60.0, exchange_latency_s=5.0)
            runs.append(sim.run(copy.deepcopy(jobs)))
            assert all(j.finish >= 0 for j in runs[-1].jobs)
            assert sim.exchange.stats.rounds > 0
            assert sim.exchange.stats.adverts_sent > 0
        assert [j.exec_site for j in runs[0].jobs] == [j.exec_site for j in runs[1].jobs]
        assert [j.finish for j in runs[0].jobs] == [j.finish for j in runs[1].jobs]

    def test_peers_partition_all_sites(self):
        sim = P2PGridSim(paper_grid_spec(), num_peers=3)
        owned = [n for p in sim.peers for n in p.home_names]
        assert sorted(owned) == sorted(paper_grid_spec())
        assert len(sim.peers) == 3

    def test_delta_wire_completes_with_fewer_bytes(self):
        """The compressed exchange drives the full event loop (acks ride
        the same latency heap) and undercuts the full flood's bytes."""
        jobs = self._workload()
        results, bytes_sent = [], {}
        for wire in ("full", "delta"):
            sim = P2PGridSim(paper_grid_spec(), num_peers=3,
                             exchange_interval_s=60.0, exchange_latency_s=5.0,
                             gossip_wire=wire)
            res = sim.run(copy.deepcopy(jobs))
            assert all(j.finish >= 0 for j in res.jobs)
            results.append(res)
            bytes_sent[wire] = sim.exchange.stats.bytes_sent
            if wire == "delta":
                assert sim.exchange.stats.acks_sent > 0
        assert bytes_sent["delta"] < bytes_sent["full"]
        # Same workload, both views converge: makespans stay close.
        mk_full, mk_delta = (r.makespan for r in results)
        assert mk_delta == pytest.approx(mk_full, rel=0.1)

    def test_migration_respects_staleness_trust(self):
        """With an exchange interval (hence trust horizon) far shorter
        than the time between exchanges, congested sites must not
        migrate — they don't trust any peer row."""
        jobs = _overload_workload()
        trusting = P2PGridSim(paper_grid_spec(), num_peers=5,
                              exchange_interval_s=30.0, quotas=QUOTAS,
                              migration_interval_s=30.0,
                              congestion_window_s=120.0)
        res_trusting = trusting.run(copy.deepcopy(jobs))
        paranoid = P2PGridSim(paper_grid_spec(), num_peers=5,
                              exchange_interval_s=30.0, quotas=QUOTAS,
                              migration_interval_s=30.0,
                              congestion_window_s=120.0,
                              migration_max_staleness_s=-1.0)
        res_paranoid = paranoid.run(copy.deepcopy(jobs))
        assert res_trusting.migrations() > 0
        assert res_paranoid.migrations() == 0
        assert all(j.finish >= 0 for j in res_paranoid.jobs)

    def test_non_diana_policy_rejected(self):
        with pytest.raises(ValueError):
            P2PGridSim(paper_grid_spec(), policy="greedy")

    def test_topology_default_trust_allows_cross_tier_migration(self):
        """Tiered fan-out relays cross-tier rows through representatives
        (up to ~3 rounds old on arrival): the default trust horizon must
        account for the extra hops, or cross-tier migration silently
        never happens."""
        from repro.core import GridTopology, Node

        names = sorted(paper_grid_spec())
        topo = GridTopology()
        for n in names[:2]:
            topo.join("east", Node(name=n))
        for n in names[2:]:
            topo.join("west", Node(name=n))
        sim = P2PGridSim(paper_grid_spec(), num_peers=5, topology=topo,
                         exchange_interval_s=30.0, quotas=QUOTAS,
                         migration_interval_s=30.0, congestion_window_s=120.0)
        assert sim.migration_max_staleness_s >= 4 * 30.0
        res = sim.run(copy.deepcopy(_overload_workload()))
        assert res.migrations() > 0
        # ...and the hog flood at site1 (east) reached a west-tier site.
        west = set(names[2:])
        assert any(j.migrated and j.exec_site in west for j in res.jobs)

    def test_choose_sites_batch_matches_choose_site(self):
        """The vectorized snapshot API must agree with per-job
        choose_site under the per-peer stale views."""
        jobs = self._workload(30)
        sim = P2PGridSim(paper_grid_spec(), num_peers=3, exchange_interval_s=60.0)
        assert sim.choose_sites_batch(jobs) == [sim.choose_site(sj) for sj in jobs]

    def test_late_start_trace_does_not_distrust_bootstrap(self):
        """A trace resuming at large t0 must treat the construction
        snapshot as exchanged at sim start: migration stays enabled in
        the window before the first exchange round."""
        t0 = 86_400.0
        jobs = [SimJob(user=("hog" if i >= 8 else "polite"), arrival=t0 + i,
                       work=300.0, input_bytes=2e9, data_site="site1",
                       origin_site="site1")
                for i in range(80)]
        sim = P2PGridSim(paper_grid_spec(), num_peers=5,
                         exchange_interval_s=600.0, quotas=QUOTAS,
                         migration_interval_s=30.0, congestion_window_s=120.0)
        res = sim.run(copy.deepcopy(jobs))
        assert all(j.finish >= 0 for j in res.jobs)
        assert res.migrations() > 0          # not silently disabled

    def test_fanout_cap_widens_default_trust(self):
        sim = P2PGridSim(paper_grid_spec(), num_peers=5, gossip_fanout=1,
                         exchange_interval_s=60.0)
        # neighbors rotate over 4 peers at 1/round → heard every 4
        # rounds → horizon (1+4)·60.
        assert sim.migration_max_staleness_s == 5 * 60.0

    def test_peer_links_are_home_relative(self):
        """sim.peers' public cost planes run on each peer's real
        home-relative link row, not a placeholder."""
        sim = P2PGridSim(paper_grid_spec(), num_peers=2)
        p = sim.peers[0]
        for n in sim._names_sorted:
            assert p.links[n] is sim.links[(p.home, n)]

    def test_all_sent_adverts_are_delivered(self):
        """Latency > interval keeps several batches airborne at once;
        deliver events must chain so nothing stays in flight forever."""
        jobs = self._workload(40)
        sim = P2PGridSim(paper_grid_spec(), num_peers=3,
                         exchange_interval_s=30.0, exchange_latency_s=100.0)
        res = sim.run(copy.deepcopy(jobs))
        assert all(j.finish >= 0 for j in res.jobs)
        assert sim.exchange.in_flight == 0
        assert sim.exchange.stats.deliveries > 0

    def test_exchange_cost_scales_down_with_interval(self):
        jobs = self._workload()
        sent = []
        for iv in (30.0, 240.0):
            sim = P2PGridSim(paper_grid_spec(), num_peers=3,
                             exchange_interval_s=iv)
            sim.run(copy.deepcopy(jobs))
            sent.append(sim.exchange.stats.adverts_sent)
        assert sent[1] < sent[0]


class TestBatchedMigration:
    """The batched §IX/§X migration pass must be bit-identical to the
    sequential per-job loop: same targets, same export/import buckets,
    same final assignments and finish times."""

    def _compare(self, jobs, nodes=None, **kw):
        nodes = nodes or paper_grid_spec()
        kw.setdefault("quotas", QUOTAS)
        kw.setdefault("migration_interval_s", 30.0)
        kw.setdefault("congestion_window_s", 120.0)
        seq = GridSim(nodes, policy="diana", batch_migration=False,
                      **kw).run(copy.deepcopy(jobs))
        bat = GridSim(nodes, policy="diana", batch_migration=True,
                      **kw).run(copy.deepcopy(jobs))
        assert [j.exec_site for j in seq.jobs] == [j.exec_site for j in bat.jobs]
        assert [j.migrated for j in seq.jobs] == [j.migrated for j in bat.jobs]
        assert [j.start for j in seq.jobs] == [j.start for j in bat.jobs]
        assert [j.finish for j in seq.jobs] == [j.finish for j in bat.jobs]
        assert seq.timeline == bat.timeline
        return seq, bat

    def test_overload_equivalence(self):
        seq, bat = self._compare(_overload_workload())
        assert bat.migrations() > 0  # the comparison actually migrated

    def test_big_site_tiebreak_equivalence(self):
        """'big' sorts first but iterates last: peer tie-breaking must
        follow sites-dict order, not sorted-column order."""
        seq, bat = self._compare(_overload_workload(),
                                 nodes=dict(paper_grid_spec(), big=50))
        assert bat.migrations() > 0

    @pytest.mark.parametrize("seed", [1, 2, 3])
    def test_seeded_random_workloads(self, seed):
        """Mixed origins/data sites exercise the pair-structured static
        planes and the per-signature row cache across seeds."""
        rng = np.random.default_rng(seed)
        sites = list(paper_grid_spec())
        jobs = []
        for b in range(8):
            jobs.extend(
                bulk_burst("hog", 25, at=float(b * 25),
                           work=float(rng.uniform(100, 400)),
                           input_bytes=float(rng.uniform(0, 3e9)),
                           output_bytes=float(rng.uniform(0, 3e8)),
                           data_site=sites[int(rng.integers(len(sites)))],
                           origin_site=sites[int(rng.integers(len(sites)))],
                           rng=rng, work_jitter=0.2)
            )
        for i in range(30):
            jobs.extend(
                bulk_burst("polite", 1, at=float(i * 15), work=200.0,
                           input_bytes=1e9,
                           data_site=sites[int(rng.integers(len(sites)))],
                           origin_site=sites[int(rng.integers(len(sites)))])
            )
        seq, bat = self._compare(sorted(jobs, key=lambda j: j.arrival))
        assert all(j.finish >= 0 for j in bat.jobs)

    def test_off_grid_endpoints_fall_back_per_site(self):
        """Candidates whose data lives on a link-table-only storage
        node route through the per-job fallback for that site — still
        identical to the fully sequential pass."""
        names = ["site1", "site2", "site3"]
        links = uniform_links(names + ["storage"])
        nodes = {n: 2 for n in names}
        jobs = []
        for b in range(6):
            jobs.extend(
                bulk_burst("hog", 12, at=float(b * 30), work=300.0,
                           input_bytes=2e9, data_site="storage",
                           origin_site="site1")
            )
        for i in range(10):
            jobs.extend(
                bulk_burst("polite", 1, at=float(i * 20), work=300.0,
                           input_bytes=2e9, data_site="storage",
                           origin_site="site1")
            )
        jobs = sorted(jobs, key=lambda j: j.arrival)
        self._compare(jobs, nodes=nodes, links=links)

    def test_batched_is_default(self):
        assert GridSim(paper_grid_spec(), policy="diana").batch_migration

    @pytest.mark.parametrize(
        "interval,latency,topology",
        [(30.0, 0.0, False), (60.0, 5.0, False), (60.0, 5.0, True)],
        ids=["30.0-0.0", "60.0-5.0", "60.0-5.0-topology"],
    )
    def test_p2p_staleness_equivalence(self, interval, latency, topology):
        """The batched migration pass must stay bit-identical to the
        per-job loop WITH the P2P staleness gating active: both paths
        filter trusted peers from the same per-column stale vector
        (under the hierarchy-aware fan-out too, whose relayed rows age
        by more rounds)."""
        from repro.core import GridTopology, Node

        topo = None
        if topology:
            topo = GridTopology()
            for i, n in enumerate(paper_grid_spec()):
                topo.join(f"root{i % 2}", Node(name=n))
        jobs = _overload_workload()
        runs = []
        for batched in (False, True):
            sim = P2PGridSim(paper_grid_spec(), num_peers=5,
                             exchange_interval_s=interval,
                             exchange_latency_s=latency,
                             batch_migration=batched, quotas=QUOTAS,
                             migration_interval_s=30.0,
                             congestion_window_s=120.0, topology=topo)
            runs.append(sim.run(copy.deepcopy(jobs)))
        seq, bat = runs
        assert [j.exec_site for j in seq.jobs] == [j.exec_site for j in bat.jobs]
        assert [j.migrated for j in seq.jobs] == [j.migrated for j in bat.jobs]
        assert [j.finish for j in seq.jobs] == [j.finish for j in bat.jobs]
        assert seq.timeline == bat.timeline
        assert bat.migrations() > 0


# Work per job: whole seconds take the queues' running total, lognormal
# work the in-order sum (``diana.mlfq.work_fallback``).
_COLUMN_WORK = {
    "whole": lambda rng: float(np.ceil(rng.lognormal(4.0, 1.0))),
    "lognormal": lambda rng: float(rng.lognormal(4.0, 1.0)),
}


def _column_workload(work, seed=3):
    """Bursts from a low-quota hog and a steady polite stream: queues
    deepen, sites congest and §IX migrates."""
    rng = np.random.default_rng(seed)
    jobs = [SimJob(user="hog", arrival=float(b * 30), work=work(rng), input_bytes=2e9,
                   data_site="site1", origin_site="site1")
            for b in range(6) for _ in range(30)]
    jobs += [SimJob(user="polite", arrival=float(i * 20), work=work(rng), input_bytes=2e9,
                    data_site="site3", origin_site="site1")
             for i in range(40)]
    return sorted(jobs, key=lambda j: j.arrival)


class _ColumnCheckedSim(GridSim):
    """Checks after every arrival batch that each entry of the cached
    §IV computation column is ``computation_cost`` over a ``SiteState``
    whose waiting work is the in-order sum over the site's queue."""

    checked = 0

    def _on_arrive(self, sj, now, events):
        super()._on_arrive(sj, now, events)
        self._check_column()

    def _on_arrive_batch(self, batch, now, events):
        super()._on_arrive_batch(batch, now, events)
        self._check_column()

    def _check_column(self):
        from repro.core import SiteState, computation_cost

        base = self._comp_base_vec()
        for i, name in enumerate(self._names_sorted):
            s = self.sites[name]
            st = SiteState(
                name=name, capacity=float(s.nodes), queue_length=float(len(s.mlfq)),
                waiting_work=sum(j.compute_work for j in s.mlfq.jobs) + s.running_work,
                load=s.busy / s.nodes,
            )
            assert float(base[i]).hex() == computation_cost(st, self.weights).hex()
        self.checked += 1


@pytest.mark.parametrize("weights", [None, (0.7, 1.3, 0.1), (0.7, 1e-4, 0.1)])
@pytest.mark.parametrize("work", sorted(_COLUMN_WORK))
def test_computation_column_is_the_in_order_recompute(work, weights):
    """The column refreshed from each site's scalars and running queued
    work equals an in-order recompute after every arrival batch, through
    a site's death and return, and the default loop decides every job as
    the per-event loop does."""
    from repro.core import CostWeights, trace
    from repro.sim import SimConfig
    from repro.sim.faults import FaultPlan

    plan = FaultPlan().site_down(100.0, "site1").site_up(400.0, "site1")
    extra = {} if weights is None else {"weights": CostWeights(*weights)}
    jobs = _column_workload(_COLUMN_WORK[work])
    runs = []
    trace.enable()
    trace.reset()
    try:
        for loop in ({}, {"horizon": False, "batch_arrivals": False}):
            cfg = SimConfig(policy="diana", quotas=QUOTAS, migration_interval_s=30.0,
                            congestion_window_s=120.0, fault_plan=plan, **extra, **loop)
            sim = _ColumnCheckedSim(paper_grid_spec(), config=cfg)
            res = sim.run(copy.deepcopy(jobs))
            assert sim.checked >= len({j.arrival for j in jobs})
            assert all(j.finish >= 0 for j in res.jobs)
            runs.append(res)
        fallbacks = trace.counters().get("diana.mlfq.work_fallback", 0)
    finally:
        trace.disable()
        trace.reset()
    assert (fallbacks > 0) == (work == "lognormal")
    assert runs[0].migrations() > 0 and sum(j.requeues for j in runs[0].jobs) > 0
    assert [(j.exec_site, j.finish, j.migrated) for j in runs[0].jobs] == [
        (j.exec_site, j.finish, j.migrated) for j in runs[1].jobs
    ]

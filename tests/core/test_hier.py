"""Two-level ("hier") placement equivalence suite.

The hierarchical path prunes with per-tier admissible lower bounds and
f32 shortlist packs, then refines exactly — so every observable output
(site choices, costs, queue/work feedback) must be **bit-identical** to the flat dense argmin. These tests sweep
random topologies, tier skews and dirty-column refresh interleavings
to enforce that contract.
"""
import copy

import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    CostWeights,
    DianaScheduler,
    GridTopology,
    Job,
    JobClass,
    NetworkLink,
    Node,
    SiteState,
)
from repro.core import trace
from repro.core.batch import (
    JobPack,
    SitePack,
    TierPack,
    _f32_gate,
    batched_argmin,
    batched_cost_matrix,
    hier_replay,
    hier_select,
    replay_on_pack,
)


def _grid(rng, n_sites, dead_fraction=0.2):
    sites, links = {}, {}
    for i in range(n_sites):
        name = f"s{i:03d}"
        sites[name] = SiteState(
            name=name, capacity=float(rng.integers(10, 2000)),
            queue_length=float(rng.integers(0, 100)),
            waiting_work=float(rng.uniform(0, 1000)),
            load=float(rng.uniform(0, 1)),
            alive=bool(rng.uniform() > dead_fraction),
        )
        links[name] = NetworkLink(
            bandwidth_Bps=float(rng.uniform(1e6, 1e10)),
            loss_rate=0.0 if rng.uniform() < 0.3 else float(rng.uniform(1e-4, 0.05)),
            rtt_s=float(rng.uniform(0.001, 0.3)),
        )
    if not any(s.alive for s in sites.values()):
        next(iter(sites.values())).alive = True
    return sites, links


def _jobs(rng, n):
    """Job mix with the degenerate corners the shortlist must survive:
    zero-byte and zero-work rows, heavy-tailed sizes."""
    jobs = []
    for i in range(n):
        jobs.append(Job(
            user=f"u{i % 3}",
            compute_work=float(rng.choice([0.0, rng.uniform(0.1, 200)])),
            input_bytes=float(rng.choice([0.0, rng.uniform(0, 50e9)])),
            output_bytes=float(rng.choice([0.0, rng.uniform(0, 1e9)])),
        ))
    return jobs


def _skewed_tiers(rng, names, n_tiers):
    """Random tier map with skew: some huge tiers, some singletons."""
    if n_tiers <= 1:
        return {n: "t0" for n in names}
    weights = rng.uniform(0.05, 1.0, n_tiers) ** 3
    weights /= weights.sum()
    assignment = rng.choice(n_tiers, size=len(names), p=weights)
    return {n: f"t{int(t)}" for n, t in zip(names, assignment)}


def _weights(rng):
    return CostWeights(
        w_queue=float(rng.uniform(0, 2)),
        w_work=float(rng.uniform(0, 2)),
        w_load=float(rng.uniform(0, 2)),
    )


def _assert_select_matches_flat(sites, links, tiers, jobs, w):
    """hier_select against the flat dense argmin: sites and exact costs."""
    sp = SitePack.from_scheduler(sites, links)
    jp = JobPack.from_jobs(jobs)
    tp = TierPack.from_site_pack(sp, tiers)
    flat = batched_argmin(batched_cost_matrix(jp, sp, w), sp)
    hier = hier_select(jp, copy.deepcopy(sp), tp, w)
    assert hier.sites == flat.sites
    assert list(hier.costs) == list(flat.costs)          # exact floats
    return hier


def _assert_replay_matches_flat(sites, links, tiers, jobs, w):
    """hier_replay against replay_on_pack: sites, exact costs and the
    pack's queue/work write-back."""
    spA = SitePack.from_scheduler(sites, links)
    spB = SitePack.from_scheduler(sites, links)
    tp = TierPack.from_site_pack(spB, tiers)
    flat = replay_on_pack(JobPack.from_jobs(jobs), spA, w)
    hier = hier_replay(JobPack.from_jobs(jobs), spB, tp, w)
    assert hier.sites == flat.sites
    assert list(hier.costs) == list(flat.costs)
    np.testing.assert_array_equal(spA.queue, spB.queue)
    np.testing.assert_array_equal(spA.work, spB.work)
    return hier


_PATHS = {"select": _assert_select_matches_flat, "replay": _assert_replay_matches_flat}


class TestHierEquivalence:
    @given(seed=st.integers(0, 100_000), n_sites=st.integers(2, 64),
           n_tiers=st.integers(1, 9), n_jobs=st.integers(1, 40))
    @settings(max_examples=40, deadline=None)
    def test_select_bit_identical_to_flat(self, seed, n_sites, n_tiers, n_jobs):
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        w = _weights(rng)
        tiers = _skewed_tiers(rng, list(sites), n_tiers)
        _assert_select_matches_flat(sites, links, tiers, _jobs(rng, n_jobs), w)

    @given(seed=st.integers(0, 100_000), n_sites=st.integers(2, 48),
           n_tiers=st.integers(1, 7), n_jobs=st.integers(1, 40))
    @settings(max_examples=40, deadline=None)
    def test_replay_bit_identical_to_flat(self, seed, n_sites, n_tiers, n_jobs):
        """Sequential replay: per-row queue feedback must stay exact
        through the tier-pruned path, including the pack write-back."""
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        w = _weights(rng)
        tiers = _skewed_tiers(rng, list(sites), n_tiers)
        _assert_replay_matches_flat(sites, links, tiers, _jobs(rng, n_jobs), w)

    def test_degenerate_single_tier_is_flat(self):
        """One tier = the whole grid: the bound stage is vacuous and
        the refinement IS the dense pass — a structural sanity pin."""
        rng = np.random.default_rng(5)
        sites, links = _grid(rng, 24, dead_fraction=0.0)
        w = _weights(rng)
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(_jobs(rng, 30))
        tp = TierPack.from_site_pack(sp, None)       # None → one tier

        assert len(tp.labels) == 1
        flat = batched_argmin(batched_cost_matrix(jp, sp, w), sp)
        hier = hier_select(jp, sp, tp, w)
        assert hier.sites == flat.sites
        assert list(hier.costs) == list(flat.costs)

    @given(seed=st.integers(0, 100_000))
    @settings(max_examples=25, deadline=None)
    def test_scheduler_hier_mode_matches_flat(self, seed):
        """The public DianaScheduler surface: mode='hier' with a real
        GridTopology must commit identical placements and site state."""
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, 20, dead_fraction=0.1)
        names = sorted(sites)
        topo = GridTopology()
        for i, n in enumerate(names):
            topo.join(f"root{i % 4}", Node(name=n))
        jobs = _jobs(rng, 25)

        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links), topology=topo)
        jA, jB = copy.deepcopy(jobs), copy.deepcopy(jobs)
        a = dA.place_batch(jA)
        b = dB.place_batch(jB, mode="hier")

        assert a.sites == b.sites
        assert list(a.costs) == list(b.costs)
        for n in names:
            assert dA.sites[n].queue_length == dB.sites[n].queue_length
            assert dA.sites[n].waiting_work == dB.sites[n].waiting_work

    def test_bad_mode_rejected(self):
        rng = np.random.default_rng(0)
        sites, links = _grid(rng, 4, dead_fraction=0.0)
        d = DianaScheduler(sites, links)
        with pytest.raises(ValueError):
            d.select_sites_batch(_jobs(rng, 2), mode="tiered")
        with pytest.raises(ValueError):
            d.place_batch(_jobs(rng, 2), mode="tiered")


def _site(name, cap=1000.0, queue=0.0, work=0.0, load=0.0, alive=True):
    return SiteState(name=name, capacity=cap, queue_length=queue,
                     waiting_work=work, load=load, alive=alive)


@pytest.fixture
def tracing_on():
    trace.enable()
    trace.reset()
    try:
        yield
    finally:
        trace.disable()
        trace.reset()


@pytest.mark.parametrize("path", sorted(_PATHS))
class TestGatheredPass:
    """The per-job pass over every region the bounds keep runs in
    region-contiguous order, not pack order; these pin the cases where
    that order, dead columns or the f32 gate could change a decision."""

    def test_interleaved_regions_tie_goes_to_lowest_column(self, path):
        # Pack order s0..s5 over regions X, Y, X, Y, Z, Z: the gathered
        # order is s0, s2, s1, s3, s4, s5, so s2 comes before s1 there.
        # s1 and s2 are twins and the cheapest: every tie must go to s1.
        tiers = {"s0": "X", "s1": "Y", "s2": "X", "s3": "Y", "s4": "Z", "s5": "Z"}
        sites = {n: _site(n, queue=40.0, work=300.0, load=0.5) for n in tiers}
        sites["s1"] = _site("s1")
        sites["s2"] = _site("s2")
        links = {n: NetworkLink(bandwidth_Bps=1e9, rtt_s=0.05) for n in tiers}
        jobs = [Job(user="u", compute_work=5.0, input_bytes=2e9) for _ in range(7)]

        tp = TierPack.from_site_pack(SitePack.from_scheduler(sites, links), tiers)
        assert list(tp.perm) == [0, 2, 1, 3, 4, 5]
        hier = _PATHS[path](sites, links, tiers, jobs, CostWeights())
        assert hier.sites[0] == "s1"
        if path == "replay":   # feedback alternates the twins, s1 first on ties
            assert hier.sites[:4] == ["s1", "s2", "s1", "s2"]

    @given(seed=st.integers(0, 100_000), n_sites=st.integers(2, 40),
           n_tiers=st.integers(1, 7), n_jobs=st.integers(1, 30))
    @settings(max_examples=25, deadline=None)
    def test_f32_gate_off_negative_weight(self, path, seed, n_sites, n_tiers, n_jobs):
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        w = CostWeights(w_queue=-float(rng.uniform(0.01, 2)),
                        w_work=float(rng.uniform(0, 2)),
                        w_load=float(rng.uniform(0, 2)))
        tiers = _skewed_tiers(rng, list(sites), n_tiers)
        jobs = _jobs(rng, n_jobs)
        sp = SitePack.from_scheduler(sites, links)
        assert not _f32_gate(JobPack.from_jobs(jobs), sp,
                             TierPack.from_site_pack(sp, tiers), w)
        _PATHS[path](sites, links, tiers, jobs, w)

    def test_dead_region_and_dead_f32_minimum(self, path):
        # Region D: the two cheapest columns of the grid, both dead.
        # Region E: its cheapest column (the f32 minimum) is dead.
        tiers = {"s0": "E", "s1": "D", "s2": "E", "s3": "D", "s4": "E", "s5": "F"}
        sites = {
            "s0": _site("s0", queue=30.0, work=100.0),
            "s1": _site("s1", alive=False),
            "s2": _site("s2", queue=1.0, alive=False),
            "s3": _site("s3", alive=False),
            "s4": _site("s4", queue=20.0, work=50.0),
            "s5": _site("s5", queue=25.0, work=80.0, load=0.2),
        }
        links = {n: NetworkLink(bandwidth_Bps=1e9, rtt_s=0.05) for n in tiers}
        jobs = [Job(user="u", compute_work=float(k + 1), input_bytes=1e9 * k)
                for k in range(12)]
        hier = _PATHS[path](sites, links, tiers, jobs, CostWeights())
        assert not {"s1", "s2", "s3"} & set(hier.sites)

    def test_separating_bounds_prune_regions(self, path, tracing_on):
        # One region on fast lossless links, three behind slow lossy ones:
        # once a job has a pick, the far regions' bounds exceed its cost.
        sites, links, tiers = {}, {}, {}
        for r, (bw, loss) in enumerate([(1e10, 0.0), (1e6, 0.02), (2e6, 0.03), (5e5, 0.01)]):
            for i in range(5):
                name = f"r{r}s{i}"
                sites[name] = _site(name, cap=500.0 + 100 * i, queue=float(i))
                links[name] = NetworkLink(bandwidth_Bps=bw, loss_rate=loss, rtt_s=0.1)
                tiers[name] = f"region{r}"
        J, T = 40, 4
        jobs = [Job(user="u", compute_work=10.0, input_bytes=5e9) for _ in range(J)]
        hier = _PATHS[path](sites, links, tiers, jobs, CostWeights())
        assert all(name.startswith("r0") for name in hier.sites)
        c = trace.counters()
        assert J <= c["diana.hier.tiers_refined"] < T * J
        assert c["diana.hier.cols_refined"] >= c["diana.hier.tiers_refined"]


class TestTierPackRefresh:
    @given(seed=st.integers(0, 100_000), n_sites=st.integers(3, 40),
           n_tiers=st.integers(1, 6), n_dirty=st.integers(1, 10))
    @settings(max_examples=30, deadline=None)
    def test_narrowed_refresh_matches_rebuild(self, seed, n_sites, n_tiers,
                                              n_dirty):
        """Mutate static link/capacity state at a few columns, then a
        narrowed ``refresh(cols)`` must leave the pack identical to one
        rebuilt from scratch — the dirty-column interleaving the P2P
        cache relies on."""
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        tiers = _skewed_tiers(rng, list(sites), n_tiers)
        sp = SitePack.from_scheduler(sites, links)
        tp = TierPack.from_site_pack(sp, tiers)

        dirty = rng.choice(n_sites, size=min(n_dirty, n_sites), replace=False)
        for c in dirty:
            sp.bw[c] = float(rng.uniform(1e6, 1e10))
            sp.loss[c] = float(rng.uniform(0, 0.05))
            sp.rtt[c] = float(rng.uniform(0.001, 0.3))
            sp.cap[c] = float(rng.integers(10, 2000))
        tp.refresh(sp, np.asarray(dirty, np.int64))
        fresh = TierPack.from_site_pack(sp, tiers)

        for f in ("net64", "eff64", "net32", "eff32", "cap32",
                  "net_min", "eff_max", "eff_min", "cap_max", "cap_min"):
            np.testing.assert_array_equal(getattr(tp, f), getattr(fresh, f),
                                          err_msg=f)

    @given(seed=st.integers(0, 100_000))
    @settings(max_examples=20, deadline=None)
    def test_refresh_interleaved_with_selection(self, seed):
        """refresh → select must equal a fresh pack's select (the
        sequence the P2P hier cache performs every merge round)."""
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, 24)
        w = _weights(rng)
        tiers = _skewed_tiers(rng, list(sites), 4)
        sp = SitePack.from_scheduler(sites, links)
        tp = TierPack.from_site_pack(sp, tiers)
        jp = JobPack.from_jobs(_jobs(rng, 15))

        hier_select(jp, sp, tp, w)                   # warm pass
        dirty = rng.choice(24, size=5, replace=False)
        for c in dirty:
            sp.bw[c] = float(rng.uniform(1e6, 1e10))
            sp.loss[c] = float(rng.uniform(0, 0.05))
        tp.refresh(sp, np.asarray(dirty, np.int64))

        flat = batched_argmin(batched_cost_matrix(jp, sp, w), sp)
        hier = hier_select(jp, sp, tp, w)
        assert hier.sites == flat.sites
        assert list(hier.costs) == list(flat.costs)

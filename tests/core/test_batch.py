"""Batched (jobs × sites) placement engine: parity with the Pallas
kernel and bit-exact equivalence with the sequential §V loop."""
import copy

import jax.experimental.pallas.tpu as pltpu
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    BulkGroup,
    BulkScheduler,
    CostWeights,
    DianaScheduler,
    Job,
    JobClass,
    JobPack,
    NetworkLink,
    SitePack,
    SiteState,
    batched_argmin,
    batched_cost_matrix,
    replay_place,
)
from repro.kernels.cost_matrix.cost_matrix import JOB_BLOCK, SITE_BLOCK


def _grid(rng, n_sites, dead_fraction=0.25, lossless_fraction=0.3):
    sites, links = {}, {}
    for i in range(n_sites):
        name = f"s{i}"
        sites[name] = SiteState(
            name=name, capacity=float(rng.integers(10, 2000)),
            queue_length=float(rng.integers(0, 100)),
            waiting_work=float(rng.uniform(0, 1000)),
            load=float(rng.uniform(0, 1)),
            alive=bool(rng.uniform() > dead_fraction),
        )
        links[name] = NetworkLink(
            bandwidth_Bps=float(rng.uniform(1e8, 1e10)),
            loss_rate=0.0 if rng.uniform() < lossless_fraction
            else float(rng.uniform(1e-4, 0.05)),
            rtt_s=float(rng.uniform(0.001, 0.3)),
            mss_bytes=float(rng.choice([536.0, 1460.0, 9000.0])),
        )
    if not any(s.alive for s in sites.values()):
        next(iter(sites.values())).alive = True
    return sites, links


def _jobs(rng, n):
    return [
        Job(
            user=f"u{i % 3}",
            compute_work=float(rng.uniform(0.1, 200)),
            input_bytes=float(rng.uniform(0, 50e9)),
            output_bytes=float(rng.uniform(0, 1e9)),
        )
        for i in range(n)
    ]


class TestKernelParity:
    """cost_matrix_pallas(interpret=True) vs ref.py vs the NumPy batch
    path — dead sites, loss_rate=0 links, and off-block-size shapes."""

    # J/S deliberately not multiples of JOB_BLOCK/SITE_BLOCK (padding),
    # plus exact-multiple and tiny shapes.
    @pytest.mark.parametrize(
        "J,S",
        [(1, 1), (7, 5), (JOB_BLOCK, SITE_BLOCK), (JOB_BLOCK + 1, SITE_BLOCK + 1),
         (300, 130)],
    )
    def test_classed_kernel_vs_ref_vs_numpy(self, J, S):
        from repro.kernels.cost_matrix.ops import cost_matrix_classed
        from repro.kernels.cost_matrix.ref import cost_matrix_classed_ref

        rng = np.random.default_rng(J * 1000 + S)
        sites, links = _grid(rng, S)
        jobs = _jobs(rng, J)
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(jobs)

        ck, bk = cost_matrix_classed(
            jp.bytes_, jp.work, jp.wcomp, jp.wdtc,
            sp.cap, sp.queue, sp.work, sp.load, sp.bw, sp.loss, sp.rtt, sp.alive,
            sp.mss, use_kernel=True, interpret=True,
        )
        cr, br = cost_matrix_classed_ref(
            jp.bytes_, jp.work, jp.wcomp, jp.wdtc,
            sp.cap, sp.queue, sp.work, sp.load, sp.bw, sp.loss, sp.rtt, sp.alive,
            mss=sp.mss,
        )
        np.testing.assert_allclose(np.asarray(ck), np.asarray(cr), rtol=1e-5)
        np.testing.assert_array_equal(np.asarray(bk), np.asarray(br))

        # NumPy float64 batch path agrees (dead sites +inf vs BIG mask).
        # backend="kernel" compiles for the TPU; on the CPU the test asks
        # Pallas for interpret mode.
        cn = batched_cost_matrix(jp, sp, backend="numpy")
        with pltpu.force_tpu_interpret_mode():
            ckk = batched_cost_matrix(jp, sp, backend="kernel")
        assert cn.shape == (J, S)
        dead = ~sp.alive
        assert np.all(np.isinf(cn[:, dead]))
        alive_cols = ~dead
        np.testing.assert_allclose(
            ckk[:, alive_cols], cn[:, alive_cols], rtol=2e-4, atol=1e-4
        )

    def test_kernel_backend_never_falls_back(self):
        """backend="kernel" runs the Pallas kernel or raises: off the TPU
        it needs interpret mode asked for (the parity test above does),
        and no "auto" backend exists."""
        rng = np.random.default_rng(11)
        sites, links = _grid(rng, 9)
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(_jobs(rng, 13))
        with pytest.raises(ValueError, match="interpret mode"):
            batched_cost_matrix(jp, sp, backend="kernel")
        with pytest.raises(ValueError, match="unknown backend"):
            batched_cost_matrix(jp, sp, backend="auto")

    def test_lossless_links_have_zero_network_cost(self):
        rng = np.random.default_rng(0)
        sites, links = _grid(rng, 6, dead_fraction=0.0, lossless_fraction=1.0)
        jobs = [Job(user="u", compute_work=1.0, input_bytes=30e9)]  # DATA class
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(jobs)
        cost = batched_cost_matrix(jp, sp)
        # DATA class = dtc + net; net == 0 on lossless links, so the
        # matrix must equal bytes / nominal bandwidth exactly.
        np.testing.assert_array_equal(cost[0], jobs[0].total_bytes / sp.bw)

    def test_mathis_cap_applies_only_when_lossy(self):
        sites = {
            "clean": SiteState(name="clean", capacity=100.0),
            "lossy": SiteState(name="lossy", capacity=100.0),
        }
        links = {
            "clean": NetworkLink(bandwidth_Bps=1e9, loss_rate=0.0, rtt_s=0.1),
            "lossy": NetworkLink(bandwidth_Bps=1e9, loss_rate=0.01, rtt_s=0.1),
        }
        jp = JobPack.from_jobs([Job(user="u", input_bytes=2e9, compute_work=0.1)])
        assert jp.classes == [JobClass.DATA]
        sp = SitePack.from_scheduler(sites, links)
        cost = batched_cost_matrix(jp, sp)
        assert cost[0, 0] == pytest.approx(2.0)          # 2 GB over 1 GB/s
        # Mathis ceiling: 1460/(0.1·√0.01) = 146 kB/s ⇒ ~13 700 s ≫ nominal
        assert cost[0, 1] > 6000

    def test_all_dead_raises_on_selection(self):
        rng = np.random.default_rng(1)
        sites, links = _grid(rng, 4, dead_fraction=0.0)
        for s in sites.values():
            s.alive = False
        sp = SitePack.from_scheduler(sites, links)
        jp = JobPack.from_jobs(_jobs(rng, 3))
        cost = batched_cost_matrix(jp, sp)
        with pytest.raises(RuntimeError):
            batched_argmin(cost, sp)


class TestSequentialEquivalence:
    """Batched placement ≡ the per-job loop: same sites, same costs,
    same final state — including tie-breaks and mid-batch updates."""

    @given(seed=st.integers(0, 10_000), n_sites=st.integers(2, 24),
           n_jobs=st.integers(1, 50))
    @settings(max_examples=25, deadline=None)
    def test_place_batch_bit_identical(self, seed, n_sites, n_jobs):
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        jobs = _jobs(rng, n_jobs)
        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links))
        jA, jB = copy.deepcopy(jobs), copy.deepcopy(jobs)

        seq = [dA.place(j) for j in jA]
        bat = dB.place_batch(jB)

        assert [d.site for d in seq] == bat.sites
        assert [d.cost for d in seq] == list(bat.costs)          # exact
        assert [d.job_class for d in seq] == bat.classes
        assert [j.site for j in jA] == [j.site for j in jB]
        for name in dA.sites:
            assert dA.sites[name].queue_length == dB.sites[name].queue_length
            assert dA.sites[name].waiting_work == dB.sites[name].waiting_work

    @given(seed=st.integers(0, 10_000), n_sites=st.integers(2, 16))
    @settings(max_examples=20, deadline=None)
    def test_rank_and_select_bit_identical(self, seed, n_sites):
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, n_sites)
        jobs = _jobs(rng, 12)
        d = DianaScheduler(sites, links)
        assert [d.rank_sites(j) for j in jobs] == d.rank_sites_batch(jobs)
        seq = [d.select_site(j) for j in jobs]
        bat = d.select_sites_batch(jobs)
        assert [s.site for s in seq] == bat.sites
        assert [s.cost for s in seq] == list(bat.costs)

    def test_tie_break_determinism(self):
        """Identical sites/links produce cost ties; both paths must
        prefer the earliest site in dict insertion order."""
        sites = {
            n: SiteState(name=n, capacity=100.0, queue_length=5.0,
                         waiting_work=10.0, load=0.2)
            for n in ("zeta", "alpha", "mid")   # deliberately unsorted
        }
        links = {n: NetworkLink(bandwidth_Bps=1e9, loss_rate=0.001) for n in sites}
        jobs = [Job(user="u", compute_work=5.0, input_bytes=2e9) for _ in range(6)]
        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links))
        seq = [dA.place(j).site for j in copy.deepcopy(jobs)]
        bat = dB.place_batch(copy.deepcopy(jobs)).sites
        assert seq == bat
        assert seq[0] == "zeta"   # first inserted wins the tie

    def test_mid_batch_queue_feedback_diverts_jobs(self):
        """Heavy jobs must spill to other sites as queues grow — and
        identically so in both paths ('after every job we calculate the
        cost to submit the next job')."""
        sites = {
            "big": SiteState(name="big", capacity=1000.0),
            "small": SiteState(name="small", capacity=500.0),
        }
        links = {n: NetworkLink(bandwidth_Bps=1e9) for n in sites}
        jobs = [Job(user="u", compute_work=500.0) for _ in range(20)]
        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links))
        seq = [dA.place(j).site for j in copy.deepcopy(jobs)]
        bat = dB.place_batch(copy.deepcopy(jobs)).sites
        assert seq == bat
        assert len(set(bat)) == 2   # feedback diverted some placements

    def test_dead_site_skipped_in_both_paths(self):
        rng = np.random.default_rng(3)
        sites, links = _grid(rng, 6, dead_fraction=0.0)
        first = DianaScheduler(copy.deepcopy(sites), dict(links)).select_site(
            Job(user="u", compute_work=10.0)
        ).site
        sites[first].alive = False
        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links))
        jobs = [Job(user="u", compute_work=10.0) for _ in range(4)]
        seq = [dA.place(j).site for j in copy.deepcopy(jobs)]
        bat = dB.place_batch(copy.deepcopy(jobs)).sites
        assert seq == bat
        assert first not in bat

    def test_explicit_job_classes_respected(self):
        rng = np.random.default_rng(11)
        sites, links = _grid(rng, 8)
        jobs = _jobs(rng, 9)
        classes = [JobClass.COMPUTE, JobClass.DATA, JobClass.BOTH] * 3
        dA = DianaScheduler(copy.deepcopy(sites), dict(links))
        dB = DianaScheduler(copy.deepcopy(sites), dict(links))
        seq = [dA.place(j, c) for j, c in zip(copy.deepcopy(jobs), classes)]
        bat = dB.place_batch(copy.deepcopy(jobs), classes)
        assert [d.site for d in seq] == bat.sites
        assert bat.classes == classes


class TestRefreshDynamic:
    """refresh_dynamic(only=...) input validation: unknown site ids are
    a caller bug — raise by default, filter-with-warning on request."""

    def _pack(self):
        rng = np.random.default_rng(5)
        sites, links = _grid(rng, 4, dead_fraction=0.0)
        return sites, SitePack.from_scheduler(sites, links)

    def test_unknown_only_ids_raise_keyerror(self):
        sites, sp = self._pack()
        with pytest.raises(KeyError, match="ghost"):
            sp.refresh_dynamic(sites, only=["s0", "ghost"])

    def test_missing_warn_filters_and_refreshes_known(self):
        sites, sp = self._pack()
        sites["s1"].queue_length = 321.0
        with pytest.warns(UserWarning, match="ghost"):
            sp.refresh_dynamic(sites, only=["s1", "ghost"], missing="warn")
        assert sp.queue[1] == 321.0

    def test_invalid_missing_mode_rejected(self):
        sites, sp = self._pack()
        with pytest.raises(ValueError):
            sp.refresh_dynamic(sites, only=["ghost"], missing="skip")

    def test_known_ids_unaffected_by_strictness(self):
        sites, sp = self._pack()
        sites["s2"].waiting_work = 99.0
        sp.refresh_dynamic(sites, only=["s2"])
        assert sp.work[2] == 99.0


class TestBulkGroupsEquivalence:
    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=15, deadline=None)
    def test_schedule_groups_matches_sequential(self, seed):
        rng = np.random.default_rng(seed)
        sites, links = _grid(rng, 8)

        def groups():
            r = np.random.default_rng(seed + 1)
            return [
                BulkGroup(
                    user=f"u{g}",
                    jobs=[
                        Job(user=f"u{g}", t=1.0,
                            compute_work=float(r.uniform(0.5, 5)),
                            input_bytes=float(r.uniform(0, 5e9)))
                        for _ in range(int(r.integers(1, 60)))
                    ],
                    group_id=f"g{g}",
                    division_factor=int(r.integers(1, 5)),
                )
                for g in range(5)
            ]

        bA = BulkScheduler(DianaScheduler(copy.deepcopy(sites), dict(links)))
        bB = BulkScheduler(DianaScheduler(copy.deepcopy(sites), dict(links)))
        seq = [bA.schedule_group(g) for g in groups()]
        bat = bB.schedule_groups(groups())
        for a, b in zip(seq, bat):
            assert a.split == b.split
            assert a.sites == b.sites
            assert {s: len(js) for s, js in a.assignments.items()} == {
                s: len(js) for s, js in b.assignments.items()
            }
        for name in bA.diana.sites:
            assert (bA.diana.sites[name].queue_length
                    == bB.diana.sites[name].queue_length)


class TestMergePackedRows:
    """The P2P merge primitive: strictly-newer epochs, duplicate
    tie-breaks, and equal-epoch stamp semantics."""

    def _pack(self, rng, n_sites=6):
        sites, links = _grid(rng, n_sites, dead_fraction=0.0)
        sp = SitePack.from_scheduler(sites, links)
        S = len(sp.names)
        return sp, np.zeros(S, np.int64), np.zeros(S, np.float64)

    @given(seed=st.integers(0, 10_000))
    @settings(max_examples=25, deadline=None)
    def test_duplicate_merge_is_order_independent(self, seed):
        """Satellite regression: equal epochs used to resolve to the
        first-seen advert, making aggregated-batch merges depend on
        list order; the newest stamp must win either way."""
        from repro.core.batch import merge_packed_rows

        rng = np.random.default_rng(seed)
        k = int(rng.integers(2, 6))
        col = int(rng.integers(0, 6))
        versions = rng.integers(1, 4, size=k).astype(np.int64)
        stamps = np.round(rng.uniform(0, 100, size=k), 3)
        rows = rng.uniform(0, 50, size=(8, k))
        order = rng.permutation(k)

        results = []
        for perm in (np.arange(k), order):
            sp, version, stamp = self._pack(np.random.default_rng(seed))
            merge_packed_rows(
                sp, version, stamp,
                np.full(k, col), rows[:, perm],
                versions[perm], stamps[perm],
            )
            results.append((sp.queue[col], sp.work[col],
                            version[col], stamp[col]))
        assert results[0] == results[1]
        # And the winner is the lexicographically highest (epoch, stamp).
        best = max(range(k), key=lambda i: (versions[i], stamps[i]))
        assert results[0][2] == versions[best]

    def test_equal_epoch_newer_stamp_refreshes_without_applying(self):
        from repro.core.batch import merge_packed_rows

        sp, version, stamp = self._pack(np.random.default_rng(1))
        version[2] = 5
        stamp[2] = 10.0
        held = sp.queue[2]
        applied = merge_packed_rows(
            sp, version, stamp, np.asarray([2]),
            np.full((8, 1), 99.0), np.asarray([5], np.int64),
            np.asarray([25.0]),
        )
        assert not applied.any()          # same epoch: content unchanged
        assert sp.queue[2] == held
        assert stamp[2] == 25.0           # …but the owner clock advanced

    def test_equal_epoch_reclaims_dirty_columns(self):
        """A receiver that speculatively modified a column accepts the
        owner's equal-epoch advert back (canonical content replaces the
        speculation)."""
        from repro.core.batch import merge_packed_rows

        sp, version, stamp = self._pack(np.random.default_rng(2))
        version[3] = 7
        sp.queue[3] = 123.0               # speculative belief
        dirty = np.zeros(len(sp.names), bool)
        dirty[3] = True
        applied = merge_packed_rows(
            sp, version, stamp, np.asarray([3]),
            np.full((8, 1), 4.0), np.asarray([7], np.int64),
            np.asarray([1.0]), reclaim=dirty,
        )
        assert applied.all()
        assert sp.queue[3] == 4.0

"""§IX job migration + §X congestion-driven migration."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:                      # offline CI: vendored shim
    from _hypothesis_compat import given, settings, strategies as st

from repro.core import (
    Job,
    MultilevelFeedbackQueues,
    PeerView,
    migrate_congested,
    select_peer,
)
from repro.core.migration import apply_migration


def _peers(**jobs_ahead):
    return [
        PeerView(name=k, queue_length=v, jobs_ahead=v, total_cost=float(v))
        for k, v in jobs_ahead.items()
    ]


class TestSelectPeer:
    def test_migrates_to_least_loaded(self):
        job = Job(user="u", priority=-0.7)
        d = select_peer(job, "local", local_jobs_ahead=10, local_cost=5.0,
                        peers=_peers(a=7, b=2, c=9))
        assert d.migrate and d.target == "b"

    def test_stays_when_local_best(self):
        job = Job(user="u", priority=-0.7)
        d = select_peer(job, "local", local_jobs_ahead=1, local_cost=0.1,
                        peers=_peers(a=7, b=2))
        assert not d.migrate

    def test_pinned_after_one_migration(self):
        """§IX: no cycling — a migrated job never migrates again."""
        job = Job(user="u", priority=-0.7)
        d = select_peer(job, "local", 10, 5.0, _peers(b=1))
        apply_migration(job, d)
        assert job.migrated and job.site == "b"
        d2 = select_peer(job, "b", 10, 5.0, _peers(c=0))
        assert not d2.migrate
        assert "pinned" in d2.reason

    def test_priority_bumped_on_migration(self):
        job = Job(user="u", priority=-0.7)
        d = select_peer(job, "local", 10, 5.0, _peers(b=1))
        apply_migration(job, d)
        assert job.priority == pytest.approx(-0.6)

    def test_dead_peers_ignored(self):
        job = Job(user="u", priority=-0.7)
        peers = [PeerView(name="dead", queue_length=0, jobs_ahead=0,
                          total_cost=0.0, alive=False)]
        d = select_peer(job, "local", 10, 5.0, peers)
        assert not d.migrate


class TestCongestionMigration:
    def _congested_queue(self):
        q = MultilevelFeedbackQueues(
            quotas={"u": 10.0, "v": 1000.0}, congestion_thrs=0.5
        )
        # A high-quota user with two jobs, then a low-quota user floods
        # the site: u's jobs cross N=(q·T)/(Q·t) and sink to Q4 (§X),
        # no service → heavily congested.
        for i in range(2):
            q.submit(Job(user="v", t=1, submit_time=float(i)), now=float(i))
        for i in range(2, 22):
            q.submit(Job(user="u", t=1, submit_time=float(i)), now=float(i))
        return q

    def test_only_low_priority_jobs_move(self):
        q = self._congested_queue()
        q4 = set(id(j) for j in q.low_priority_jobs())
        assert q4  # the flood created Q4 jobs
        moved = migrate_congested(
            q, "local",
            poll_peers=lambda j: _peers(remote=0),
            local_cost=lambda j: 100.0,
            window=30.0, now=20.0,
        )
        assert moved
        assert all(id(j) in q4 for j, _ in moved)
        assert all(t == "remote" for _, t in moved)
        assert all(j.migrated for j, _ in moved)

    def test_no_migration_without_congestion(self):
        q = MultilevelFeedbackQueues(quotas={"u": 10.0}, congestion_thrs=0.5)
        for i in range(4):
            q.submit(Job(user="u", t=1, submit_time=float(i)), now=float(i))
            q.pop_next(now=float(i) + 0.5)  # service keeps pace
        moved = migrate_congested(
            q, "local",
            poll_peers=lambda j: _peers(remote=0),
            local_cost=lambda j: 100.0,
            window=10.0, now=4.0,
        )
        assert moved == []

    def test_max_moves_respected(self):
        q = self._congested_queue()
        moved = migrate_congested(
            q, "local",
            poll_peers=lambda j: _peers(remote=0),
            local_cost=lambda j: 100.0,
            window=30.0, now=20.0, max_moves=2,
        )
        assert len(moved) <= 2


class TestSelectPeersBatch:
    """Vectorized §IX selection must replicate select_peer row by row —
    targets, migrate flags, and reason strings, tie-breaks included."""

    def _grid(self, jobs_ahead_rows, cost_rows, names):
        import numpy as np

        return np.asarray(jobs_ahead_rows, float), np.asarray(cost_rows, float), names

    def _assert_rows_match(self, jobs, local, lja, lcost, names, ja, cost,
                           alive=None):
        from repro.core import select_peers_batch

        batch = select_peers_batch(jobs, local, lja, lcost, names, ja, cost,
                                   alive=alive)
        for r, job in enumerate(jobs):
            peers = [
                PeerView(name=n, queue_length=int(ja[r][s]),
                         jobs_ahead=int(ja[r][s]), total_cost=cost[r][s],
                         alive=bool(alive[s]) if alive is not None else True)
                for s, n in enumerate(names)
            ]
            ref = select_peer(job, local, lja[r], lcost[r], peers)
            assert batch[r].migrate == ref.migrate, r
            assert batch[r].target == ref.target, r
            assert batch[r].reason == ref.reason, r

    def test_jobs_ahead_tie_broken_by_cost(self):
        ja, cost, names = self._grid([[2, 2, 5]], [[3.0, 1.0, 0.5]],
                                     ["a", "b", "c"])
        self._assert_rows_match([Job(user="u")], "local", [9], [10.0],
                                names, ja, cost)

    def test_full_tie_keeps_first_peer_in_order(self):
        """Equal (jobsAhead, cost) everywhere: the stable min keeps the
        first peer in iteration order — so must argmin."""
        ja, cost, names = self._grid([[1, 1, 1]], [[2.0, 2.0, 2.0]],
                                     ["z", "m", "a"])  # NOT sorted order
        self._assert_rows_match([Job(user="u")], "local", [5], [9.0],
                                names, ja, cost)

    def test_local_column_excluded(self):
        """A column named like the local site is never a target, even
        when it is the cheapest."""
        ja, cost, names = self._grid([[0, 3]], [[0.0, 1.0]], ["local", "b"])
        self._assert_rows_match([Job(user="u")], "local", [4], [5.0],
                                names, ja, cost)

    def test_pinned_and_no_peer_reasons(self):
        import numpy as np

        ja, cost, names = self._grid([[1], [1]], [[1.0], [1.0]], ["a"])
        jobs = [Job(user="u", migrated=True), Job(user="v")]
        self._assert_rows_match(jobs, "local", [5, 5], [9.0, 9.0],
                                names, ja, cost)
        # all peers dead → 'no alive peers' (after the pinned check)
        self._assert_rows_match(jobs, "local", [5, 5], [9.0, 9.0],
                                names, ja, cost, alive=np.asarray([False]))

    def test_fuzz_matches_select_peer(self):
        import numpy as np

        rng = np.random.default_rng(0)
        names = [f"p{i}" for i in range(6)]
        for trial in range(50):
            J = int(rng.integers(1, 8))
            # small int ranges force frequent (jobsAhead, cost) ties
            ja = rng.integers(0, 4, size=(J, 6)).astype(float)
            cost = rng.integers(0, 3, size=(J, 6)).astype(float)
            alive = rng.uniform(size=6) > 0.2
            jobs = [Job(user="u", migrated=bool(rng.uniform() < 0.2))
                    for _ in range(J)]
            lja = rng.integers(0, 5, size=J)
            lcost = rng.integers(0, 3, size=J).astype(float)
            self._assert_rows_match(jobs, "p0", lja, lcost, names, ja, cost,
                                    alive=alive)

    def test_empty_candidate_matrix_returns_empty(self):
        """Regression: J=0 must yield an empty decision list / empty
        target arrays instead of relying on callers to pre-filter."""
        import numpy as np

        from repro.core import select_peers_batch
        from repro.core.migration import select_peer_targets

        names = ["a", "b"]
        empty_plane = np.zeros((0, 2))
        assert select_peers_batch([], "local", np.zeros(0), np.zeros(0),
                                  names, empty_plane, empty_plane) == []
        # An empty 1-D array (the natural result of np.asarray([])) is
        # accepted too — this used to crash on tuple unpacking.
        assert select_peers_batch([], "local", np.zeros(0), np.zeros(0),
                                  names, np.asarray([]), np.asarray([])) == []
        migrate, best = select_peer_targets(
            np.zeros(0, bool), np.zeros(0), np.zeros(0),
            np.zeros(2, bool), empty_plane, empty_plane,
        )
        assert migrate.shape == (0,) and best.shape == (0,)
        migrate, best = select_peer_targets(
            np.zeros(0, bool), np.zeros(0), np.zeros(0),
            np.zeros(2, bool), np.asarray([]), np.asarray([]),
        )
        assert migrate.shape == (0,) and best.shape == (0,)
        # Jobs but NO peers — a (J, 0) plane: every row must come back
        # as a no-migrate row, not be dropped to length 0.
        migrate, best = select_peer_targets(
            np.zeros(3, bool), np.zeros(3), np.zeros(3),
            np.zeros(0, bool), np.zeros((3, 0)), np.zeros((3, 0)),
        )
        assert migrate.shape == (3,) and not migrate.any()
        decisions = select_peers_batch(
            [Job(user="u") for _ in range(3)], "local",
            np.zeros(3), np.zeros(3), [], np.zeros((3, 0)), np.zeros((3, 0)),
        )
        assert len(decisions) == 3
        assert all(not d.migrate for d in decisions)
        # A non-empty 1-D cost row (missing [None, :]) is a shape bug
        # and must fail loudly in both APIs, not silently drop (or
        # crash with a cryptic unpack error on) decisions.
        with pytest.raises(ValueError, match="plane"):
            select_peer_targets(
                np.zeros(1, bool), np.zeros(1), np.zeros(1),
                np.zeros(2, bool), np.zeros(2), np.zeros(2),
            )
        with pytest.raises(ValueError, match="plane"):
            select_peers_batch([Job(user="u")], "local", [9], [5.0],
                               ["a", "b"], np.zeros(2), np.zeros(2))

    def test_stale_columns_are_not_trusted(self):
        """P2P trust horizon: a cheaper-but-stale peer is skipped; with
        every peer stale, nothing migrates and the reason says why."""
        import numpy as np

        from repro.core import select_peers_batch
        from repro.core.migration import select_peer_targets

        names = ["stale", "fresh"]
        ja = np.asarray([[0.0, 2.0]])
        cost = np.asarray([[0.5, 1.0]])
        staleness = np.asarray([900.0, 10.0])
        jobs = [Job(user="u")]
        d = select_peers_batch(jobs, "local", [9], [5.0], names, ja, cost,
                               staleness=staleness, max_staleness=60.0)
        assert d[0].migrate and d[0].target == "fresh"
        migrate, best = select_peer_targets(
            np.asarray([False]), np.asarray([9.0]), np.asarray([5.0]),
            np.zeros(2, bool), ja, cost,
            staleness=staleness, max_staleness=60.0,
        )
        assert migrate[0] and best[0] == 1
        # All stale → no migration, with a staleness-specific reason.
        d = select_peers_batch(jobs, "local", [9], [5.0], names, ja, cost,
                               staleness=np.asarray([900.0, 900.0]),
                               max_staleness=60.0)
        assert not d[0].migrate
        assert d[0].reason == "no sufficiently fresh peers"
        # No staleness vector → unchanged behavior (cheapest peer wins).
        d = select_peers_batch(jobs, "local", [9], [5.0], names, ja, cost)
        assert d[0].migrate and d[0].target == "stale"

    def test_targets_agree_with_decisions(self):
        """The array core (select_peer_targets) and the decision-object
        API pick the same rows and columns."""
        import numpy as np

        from repro.core import select_peers_batch
        from repro.core.migration import select_peer_targets

        rng = np.random.default_rng(1)
        names = [f"p{i}" for i in range(5)]
        ja = rng.integers(0, 4, size=(10, 5)).astype(float)
        cost = rng.integers(0, 3, size=(10, 5)).astype(float)
        jobs = [Job(user="u", migrated=bool(rng.uniform() < 0.2))
                for _ in range(10)]
        lja = rng.integers(0, 5, size=10)
        lcost = rng.integers(0, 3, size=10).astype(float)
        decisions = select_peers_batch(jobs, "p2", lja, lcost, names, ja, cost)
        pinned = np.asarray([j.migrated for j in jobs])
        excluded = np.asarray([n == "p2" for n in names])
        migrate, best = select_peer_targets(pinned, lja, lcost, excluded,
                                            ja, cost)
        for r, d in enumerate(decisions):
            assert d.migrate == bool(migrate[r]), r
            if d.migrate:
                assert d.target == names[best[r]], r

    @given(seed=st.integers(0, 100_000), n_jobs=st.integers(1, 25),
           n_peers=st.integers(1, 20))
    @settings(max_examples=40, deadline=None)
    def test_targets_match_select_peer(self, seed, n_jobs, n_peers):
        """The array core against per-job ``select_peer`` over infinite
        costs, pinned rows and excluded columns."""
        from repro.core.migration import select_peer_targets

        rng = np.random.default_rng(seed)
        names = [f"p{i}" for i in range(n_peers)]
        cost = rng.uniform(0, 100, (n_jobs, n_peers))
        cost[rng.uniform(size=cost.shape) < 0.1] = np.inf
        ja = rng.integers(0, 6, (n_jobs, n_peers)).astype(float)
        lcost = rng.uniform(0, 100, n_jobs)
        lja = rng.integers(0, 6, n_jobs).astype(float)
        pinned = rng.uniform(size=n_jobs) < 0.2
        excluded = rng.uniform(size=n_peers) < 0.3
        migrate, best = select_peer_targets(pinned, lja, lcost, excluded,
                                            ja, cost)
        for r in range(n_jobs):
            peers = [
                PeerView(name=n, queue_length=int(ja[r, s]),
                         jobs_ahead=int(ja[r, s]), total_cost=cost[r, s],
                         alive=not excluded[s])
                for s, n in enumerate(names)
            ]
            ref = select_peer(Job(user="u", migrated=bool(pinned[r])),
                              "local", lja[r], lcost[r], peers)
            assert bool(migrate[r]) == ref.migrate, r
            if ref.migrate:
                assert names[best[r]] == ref.target, r

    @given(seed=st.integers(0, 100_000), n_jobs=st.integers(0, 20))
    @settings(max_examples=25, deadline=None)
    def test_reasons_match_select_peer(self, seed, n_jobs):
        """The decision objects, reason strings included, against
        ``select_peer`` over infinite costs, pinned rows, dead peers and
        the local column."""
        rng = np.random.default_rng(seed)
        n_peers = int(rng.integers(1, 12))
        names = [f"p{i}" for i in range(n_peers)]
        local = names[int(rng.integers(0, n_peers))]
        cost = rng.uniform(0, 50, (n_jobs, n_peers))
        cost[rng.uniform(size=cost.shape) < 0.1] = np.inf
        ja = rng.integers(0, 4, (n_jobs, n_peers)).astype(float)
        lcost = rng.uniform(0, 50, n_jobs)
        lja = rng.integers(0, 4, n_jobs).astype(float)
        alive = rng.uniform(size=n_peers) > 0.25
        jobs = [Job(user="u", migrated=bool(rng.uniform() < 0.2))
                for _ in range(n_jobs)]
        self._assert_rows_match(jobs, local, lja, lcost, names, ja, cost,
                                alive=alive)

"""Serving engine (DIANA queues over decode) + fleet grid runtime."""
import jax
import numpy as np
import pytest

from repro.configs import get_config
from repro.grid import DianaGridRuntime, PodCapacity, WorkItem
from repro.models import LM
from repro.serving import InferenceRequest, ServingEngine


@pytest.fixture(scope="module")
def engine_setup():
    cfg = get_config("gemma2-9b", reduced=True).replace(
        num_layers=2, remat=False, param_dtype="float32",
        compute_dtype="float32")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    return cfg, lm, params


def _req(cfg, user, rng, n_new=4, plen=6):
    return InferenceRequest(
        user=user,
        prompt=rng.integers(0, cfg.vocab_size, plen).astype(np.int32),
        max_new_tokens=n_new)


class TestServingEngine:
    def test_drains_all_requests(self, engine_setup):
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(0)
        eng = ServingEngine(lm, params, num_slots=2, max_len=32)
        reqs = [_req(cfg, "u", rng) for _ in range(5)]
        for r in reqs:
            eng.submit(r)
        stats = eng.run_until_drained()
        assert stats.served == 5
        assert all(r.done and len(r.generated) == 4 for r in reqs)

    def test_generation_deterministic(self, engine_setup):
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(1)
        prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        outs = []
        for _ in range(2):
            eng = ServingEngine(lm, params, num_slots=2, max_len=32)
            r = InferenceRequest(user="u", prompt=prompt.copy(), max_new_tokens=4)
            eng.submit(r)
            eng.run_until_drained()
            outs.append(r.generated)
        assert outs[0] == outs[1]

    def test_later_batch_starts_from_empty_recurrent_state(self):
        """A hybrid (RG-LRU) model carries state that no mask hides: a
        prompt served after another batch must decode exactly as when
        served alone."""
        cfg = get_config("recurrentgemma-2b", reduced=True).replace(
            remat=False, param_dtype="float32", compute_dtype="float32")
        lm = LM(cfg)
        params = lm.init(jax.random.PRNGKey(0))
        rng = np.random.default_rng(6)
        # one-token prompts: the conv tail of the earlier batch would
        # reach the probe's first step directly
        first, probe = (_req(cfg, "u", rng, plen=1) for _ in range(2))
        alone = InferenceRequest(user="u", prompt=probe.prompt.copy(),
                                 max_new_tokens=probe.max_new_tokens)
        eng = ServingEngine(lm, params, num_slots=1, max_len=32)
        eng.submit(first, now=0.0)
        eng.submit(probe, now=1.0)
        assert eng.run_until_drained().batches == 2
        eng = ServingEngine(lm, params, num_slots=1, max_len=32)
        eng.submit(alone)
        eng.run_until_drained()
        assert probe.generated == alone.generated

    def test_quota_priority_orders_batches(self, engine_setup):
        """§X: high-quota tenant jumps the low-quota flood."""
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(2)
        eng = ServingEngine(lm, params, num_slots=2, max_len=32,
                            quotas={"hog": 10.0, "vip": 1000.0})
        hogs = [_req(cfg, "hog", rng) for _ in range(6)]
        eng.submit_group(hogs, now=0.0)
        vip = _req(cfg, "vip", rng)
        eng.submit(vip, now=1.0)
        eng.run_until_drained()
        assert vip.first_token_time is not None
        later_hogs = sum(1 for h in hogs if h.first_token_time > vip.first_token_time)
        assert later_hogs >= 3  # vip overtook most of the flood

    def test_skipped_prompt_lengths_are_requeued(self, engine_setup):
        """A request whose prompt length differs from its batch's goes
        back to the queue with its priority, behind the rest, and is
        served in a later batch."""
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(7)
        eng = ServingEngine(lm, params, num_slots=2, max_len=32)
        reqs = [_req(cfg, "u", rng, plen=p) for p in (6, 4, 6, 4)]
        for i, r in enumerate(reqs):
            eng.submit(r, now=float(i))
        skipped = {r.rid: (j.priority, j.queue) for r in reqs[1::2]
                   for j in eng.queues.jobs if j.job_id == r.rid}
        assert [r.rid for r in eng._form_batch(now=4.0)] == [reqs[0].rid, reqs[2].rid]
        # reqs[3] was never popped; the skipped reqs[1] now queues behind it
        assert [j.job_id for j in eng.queues.jobs] == [reqs[3].rid, reqs[1].rid]
        assert {j.job_id: (j.priority, j.queue) for j in eng.queues.jobs} == skipped
        assert eng.run_until_drained().served == 2
        assert all(r.done for r in reqs[1::2])

    def test_prefix_cache_hits(self, engine_setup):
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(3)
        eng = ServingEngine(lm, params, num_slots=2, max_len=32)
        prompt = rng.integers(0, cfg.vocab_size, 6).astype(np.int32)
        for _ in range(3):
            eng.submit(InferenceRequest(user="u", prompt=prompt.copy(),
                                        max_new_tokens=2))
        eng.run_until_drained()
        assert eng.stats.prefix_hits >= 2

    def test_truncation_raises_by_default(self, engine_setup):
        """Regression: hitting max_cycles used to return partial stats
        silently; it must now raise (or flag, when asked)."""
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(4)
        eng = ServingEngine(lm, params, num_slots=1, max_len=32)
        for _ in range(4):
            eng.submit(_req(cfg, "u", rng))
        with pytest.raises(RuntimeError, match="truncated"):
            eng.run_until_drained(max_cycles=1)
        assert eng.stats.truncated
        assert eng.stats.cycles == 1
        assert len(eng.queues) > 0          # partial drain really happened

    def test_truncation_flag_mode(self, engine_setup):
        cfg, lm, params = engine_setup
        rng = np.random.default_rng(5)
        eng = ServingEngine(lm, params, num_slots=1, max_len=32)
        for _ in range(4):
            eng.submit(_req(cfg, "u", rng))
        stats = eng.run_until_drained(max_cycles=1, on_truncation="flag")
        assert stats.truncated and stats.cycles == 1
        # a full drain afterwards clears the backlog but keeps the flag
        # as a record that an earlier call truncated
        stats = eng.run_until_drained(on_truncation="flag")
        assert stats.served == 4
        with pytest.raises(ValueError):
            eng.run_until_drained(on_truncation="ignore")


def _pods():
    return [
        PodCapacity(name="p0", chips=256),
        PodCapacity(name="p1", chips=256),
        PodCapacity(name="p2", chips=128, flops=128 * 197e12),
    ]


class TestGridRuntime:
    def test_single_placement_prefers_resident_data(self):
        grid = DianaGridRuntime(_pods())
        item = WorkItem(user="u", arch="a", shape="train_4k",
                        data_bytes=500e9, resident_pod="p1")
        assert grid.schedule(item) == "p1"   # no transfer cost at home

    def test_bulk_split_proportional_to_capacity(self):
        grid = DianaGridRuntime(_pods())
        items = [WorkItem(user="u", arch="a", shape="s") for _ in range(10)]
        placed = grid.schedule_bulk(items, division_factor=3)
        assert sum(len(v) for v in placed.values()) == 10
        assert len(placed["p2"]) <= len(placed["p0"])  # smaller pod, fewer jobs

    def test_straggler_migration(self):
        grid = DianaGridRuntime(_pods(), quotas={"u": 10.0, "v": 1000.0})
        # degrade p2 AND give it a deep multi-user queue
        for i in range(6):
            grid.pods["p2"].enqueue(WorkItem(user="u", arch="a", shape="s"), now=float(i))
        grid.pods["p2"].enqueue(WorkItem(user="v", arch="a", shape="s"), now=6.0)
        grid.set_degraded("p2", 0.3)
        moved = grid.mitigate_stragglers()
        assert moved, "degraded pod should shed queued work"
        assert all(t in ("p0", "p1") for _, t in moved)
        assert all(it.migrated for it, _ in moved)

    def test_pod_failure_reschedules_and_fails_over(self):
        grid = DianaGridRuntime(_pods())
        items = [WorkItem(user="u", arch="a", shape="s") for _ in range(4)]
        for it in items:
            grid.pods["p1"].enqueue(it)
        orphans = grid.pod_failed("p1")
        assert len(orphans) == 4
        assert all(o.pod in ("p0", "p2") for o in orphans)
        # dead pod never selected again
        nxt = grid.schedule(WorkItem(user="u", arch="a", shape="s"))
        assert nxt != "p1"

    def test_elastic_join(self):
        grid = DianaGridRuntime(_pods())
        grid.pod_joined(PodCapacity(name="p3", chips=512, flops=512 * 197e12))
        # heavily load existing pods → new big pod wins placement
        for name in ("p0", "p1", "p2"):
            for i in range(8):
                grid.pods[name].enqueue(WorkItem(user="u", arch="a", shape="s"))
        assert grid.schedule(WorkItem(user="u", arch="a", shape="s")) == "p3"

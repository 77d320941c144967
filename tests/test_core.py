"""TeleRAG core unit tests: IVF, lookahead planner, buffer, cache, budget."""

import numpy as np
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.configs import get_arch
from tests.conftest import unit_queries


def test_probe_matches_bruteforce(small_store, small_index, rng):
    q = unit_queries(small_store, rng, 5)
    ids = core.probe(q, small_index, 8)
    sims = q @ small_index.centroids.T
    for b in range(5):
        expect = set(np.argsort(-sims[b])[:8].tolist())
        assert set(ids[b].tolist()) == expect


def test_paged_layout_roundtrip(small_store, small_index):
    paged = small_index.paged
    # every vector appears exactly once across cluster pages
    seen = []
    for c in range(paged.num_clusters):
        ids = paged.cluster_page_ids(c).reshape(-1)
        ids = ids[ids >= 0]
        seen.append(ids)
        # vectors stored under c must be assigned to c
        assert np.all(small_index.assignments[ids] == c)
        # page content matches source embeddings
        flat = paged.cluster_pages(c).reshape(-1, paged.dim)
        np.testing.assert_allclose(flat[:len(ids)][np.argsort(ids.argsort())],
                                   flat[:len(ids)])
    allv = np.concatenate(seen)
    assert len(allv) == small_store.num_vectors
    assert len(np.unique(allv)) == small_store.num_vectors


def test_plan_prefetch_budget_and_skip_rule(small_index):
    paged = small_index.paged
    ranked = list(range(20))
    budget = int(paged.cluster_bytes(0) * 3.5)
    plan = core.plan_prefetch(ranked, paged, budget_bytes=budget,
                              resident=set(), free_pages=10_000)
    assert plan.bytes_planned <= budget
    # skip-whole-cluster rule: skipped clusters would each have overflowed
    rem = budget
    for c in ranked:
        nb = paged.cluster_bytes(c)
        if c in plan.fetch:
            rem -= nb
        elif c in plan.skipped:
            assert nb > rem or paged.cluster_num_pages[c] > 10_000
    # resident clusters are free
    plan2 = core.plan_prefetch(ranked, paged, budget_bytes=budget,
                               resident={ranked[0]}, free_pages=10_000)
    assert ranked[0] in plan2.resident_hits
    assert plan2.bytes_planned <= budget


def test_batched_plan_shares_clusters(small_index):
    paged = small_index.paged
    ranked = [[1, 2, 3], [1, 2, 4], [1, 5, 6]]
    budget = paged.cluster_bytes(1) * 6
    plan, covered = core.plan_batched_prefetch(ranked, paged,
                                               budget_bytes=budget,
                                               resident=set(),
                                               free_pages=10_000)
    assert plan.fetch.count(1) == 1           # shared cluster fetched once
    assert covered.sum() >= 3                 # all three covered cluster 1


def test_batched_plan_empty_batch(small_index):
    plan, covered = core.plan_batched_prefetch([], small_index.paged,
                                               budget_bytes=10_000,
                                               resident=set(),
                                               free_pages=100)
    assert plan.fetch == [] and plan.skipped == [] and plan.resident_hits == []
    assert plan.bytes_planned == 0 and plan.pages_planned == 0
    assert covered.shape == (0,)


def test_batched_plan_shared_cluster_charged_once(small_index):
    """A cluster every query wants is paid for by exactly one query's
    budget split; the others get it free (§4.2)."""
    paged = small_index.paged
    nb = paged.cluster_bytes(1)
    # total budget = 3 * bytes(1) => per-query split is exactly bytes(1)
    plan, covered = core.plan_batched_prefetch(
        [[1], [1], [1]], paged, budget_bytes=3 * nb,
        resident=set(), free_pages=10_000)
    assert plan.fetch == [1]
    assert plan.bytes_planned == nb              # charged once, not thrice
    assert covered.tolist() == [1, 1, 1]         # but all three covered
    assert plan.skipped == []


def test_batched_plan_skipped_is_unique(small_index):
    """Every query skipping the same over-budget cluster reports it once."""
    plan, covered = core.plan_batched_prefetch(
        [[5], [5], [5]], small_index.paged, budget_bytes=1,
        resident=set(), free_pages=10_000)
    assert plan.fetch == []
    assert plan.skipped == [5]
    assert covered.tolist() == [0, 0, 0]


def test_round_state_never_refetches_across_rounds(small_index):
    """§4.3 incremental prefetch: clusters fetched in an earlier round
    are treated as resident forever after."""
    paged = small_index.paged
    rs = core.RoundState()
    budget = int(paged.cluster_bytes(0) * 4)
    ranked = list(range(8))
    p1 = rs.incremental_plan(ranked, paged, budget_bytes=budget,
                             resident=set(), free_pages=10_000)
    assert p1.fetch                               # round one fetches
    p2 = rs.incremental_plan(ranked, paged, budget_bytes=budget,
                             resident=set(), free_pages=10_000)
    assert not set(p2.fetch) & set(p1.fetch)      # no re-fetch
    assert set(p1.fetch) <= set(p2.resident_hits)
    # a drifted ranking still tops up only the missing clusters
    fetched_before = set(rs.fetched)
    p3 = rs.incremental_plan(list(range(4, 12)), paged, budget_bytes=budget,
                             resident=set(), free_pages=10_000)
    assert not set(p3.fetch) & fetched_before
    assert rs.round == 3


def test_buffer_load_evict_consistency(small_index):
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    loaded, rejected = buf.load_clusters([0, 1, 2])
    assert loaded == [0, 1, 2] and not rejected
    used = buf.used_pages
    assert used == sum(int(small_index.paged.cluster_num_pages[c])
                       for c in (0, 1, 2))
    # evict then ensure the device mask excludes it after flush
    buf.evict_clusters([1])
    buf.flush_invalidations()
    pc = np.asarray(buf.page_cluster)
    assert not np.any(pc == 1)
    assert buf.free_pages() == 64 - used + int(
        small_index.paged.cluster_num_pages[1])
    # refetch into (possibly different) slots; no duplicate pages
    buf.load_clusters([1])
    pc = np.asarray(buf.page_cluster)
    assert (pc == 1).sum() == int(small_index.paged.cluster_num_pages[1])


def test_buffer_rejects_whole_cluster_when_full(small_index):
    npg0 = int(small_index.paged.cluster_num_pages[0])
    buf = core.PrefetchBuffer(small_index.paged, num_pages=npg0)
    loaded, rejected = buf.load_clusters([0])
    assert loaded == [0]
    loaded, rejected = buf.load_clusters([1])
    assert rejected == [1] and 1 not in buf.resident


def test_cache_eq6_hotness():
    cache = core.ClusterCache(core.CacheConfig(decay=2.0, h_init=1.0,
                                               h_inc=1.0))
    cache.on_fetched([1, 2])
    cache.round_update([1])            # 1 used, 2 not
    assert cache.hotness[1] == pytest.approx(1.0 / 2 + 1.0)
    assert cache.hotness[2] == pytest.approx(0.5)
    cache.round_update([])
    assert cache.hotness[1] == pytest.approx(0.75)


def test_cache_consolidate_quota(small_index):
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    cache = core.ClusterCache(core.CacheConfig(fraction=0.25))
    buf.load_clusters(list(range(8)))
    cache.on_fetched(range(8))
    cache.round_update([0, 1])
    cache.consolidate(buf)
    assert buf.used_pages <= cache.quota_pages(buf)
    # hottest survive
    if buf.resident:
        assert 0 in buf.resident or 1 in buf.resident


def test_cache_hotness_keys_subset_of_resident(small_index):
    """Invariant the single-pass consolidate relies on: hotness keys are
    always ⊆ resident ∪ just-fetched (every key enters via on_fetched
    and leaves with its cluster's eviction)."""
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    cache = core.ClusterCache(core.CacheConfig(fraction=0.25))
    rng = np.random.default_rng(4)
    for rnd in range(6):
        want = [int(c) for c in rng.choice(16, size=4, replace=False)]
        loaded, rejected = buf.load_clusters(want)
        cache.on_fetched(loaded)                 # never the rejects
        just_fetched = set(loaded)
        assert set(cache.hotness) <= (buf.resident_clusters()
                                      | just_fetched)
        cache.round_update(loaded[:2])
        if rnd % 2:
            cache.make_room(buf, pages_needed=buf.num_pages // 2)
        else:
            cache.consolidate(buf)
        assert set(cache.hotness) <= buf.resident_clusters()


def test_invalidation_only_flush_is_not_a_transfer_round(small_index):
    """flush_invalidations() scatters zero new pages — it must not count
    as an H2D transfer round (or move any bytes) in TransferStats."""
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    buf.load_clusters([0, 1, 2])
    assert buf.stats.rounds == 1
    bytes_before = buf.stats.bytes_h2d
    buf.evict_clusters([1])
    buf.flush_invalidations()                    # pure invalidation scatter
    assert buf.stats.rounds == 1
    assert buf.stats.bytes_h2d == bytes_before
    assert buf.stats.pages_h2d == buf.stats.bytes_h2d // buf.page_nbytes
    # device consistency still holds: evicted cluster unsearchable
    assert not np.any(np.asarray(buf.page_cluster) == 1)
    # a real load folding queued invalidations still counts exactly once
    buf.evict_clusters([2])
    buf.load_clusters([3])
    assert buf.stats.rounds == 2


def test_budget_case1_and_headroom():
    cfg = get_arch("llama3-8b")
    hw = core.TPU_V5E
    b = core.optimal_budget(cfg, hw, gen_tokens=[100], batch=1, chips=8,
                            hbm_headroom_bytes=5e9)
    t_llm = core.generation_window_seconds(cfg, hw, gen_tokens=[100],
                                           batch=1, chips=8)
    assert b == min(int(hw.host_link_bw * t_llm), int(5e9))
    # rwkv decodes faster per token => smaller window => smaller budget
    b_rwkv = core.optimal_budget(get_arch("rwkv6-3b"), hw, gen_tokens=[100],
                                 batch=1, chips=8, hbm_headroom_bytes=5e9)
    assert b_rwkv <= b


@pytest.mark.parametrize("kind,known", [("TPU v5 lite", True),
                                        ("cpu", True),
                                        ("TPU v4", False)])
def test_hardware_profile_by_device_kind(kind, known):
    """The profile follows the device's kind; an unknown kind is an
    error, never a silent v5e."""
    from types import SimpleNamespace
    from repro.core.budget import hardware_profile
    dev = SimpleNamespace(device_kind=kind)
    if known:
        assert hardware_profile(dev) is core.TPU_V5E
    else:
        with pytest.raises(KeyError, match="TPU v4"):
            hardware_profile(dev)


def test_budget_case2_interior_minimum():
    # a steep miss-rate curve rewards prefetching past the window
    fn = core.empirical_miss_curve([0, 1e9, 2e9, 4e9], [0.0, 0.8, 0.97, 1.0])
    b2 = core.case2_budget(fn, link_bw=64e9, nprobe=256, t_cc=2e-3,
                           b_max=4e9)
    assert b2 is not None and 0 < b2 <= 4e9


def test_hybrid_retrieve_bruteforce(small_store, small_index, rng):
    q = unit_queries(small_store, rng, 6)
    ranked = core.probe(q, small_index, 12)
    buf = core.PrefetchBuffer(small_index.paged, num_pages=256)
    plan, _ = core.plan_batched_prefetch(
        list(core.probe(q, small_index, 24)), small_index.paged,
        budget_bytes=80 * small_index.paged.page_nbytes(),
        resident=set(), free_pages=buf.free_pages())
    buf.load_clusters(plan.fetch)
    res = core.hybrid_retrieve(buf, q, ranked, k=5, kernel_mode="ref")
    for b in range(len(q)):
        allowed = set(int(c) for c in ranked[b])
        mask = np.isin(small_index.assignments, list(allowed))
        sims = small_store.embeddings[mask] @ q[b]
        ids = np.where(mask)[0]
        expect = set(ids[np.argsort(-sims)[:5]].tolist())
        got = set(int(x) for x in res.doc_ids[b] if x >= 0)
        assert got == expect, (b, got, expect)


def test_overlap_decreases_with_sigma(small_store, small_index, rng):
    q = unit_queries(small_store, rng, 16)
    covs = []
    for sigma in (0.05, 0.3, 0.8):
        qo = core.synthetic_rewrite(q, sigma, np.random.default_rng(1))
        covs.append(core.coverage(small_index, q, qo, 8))
    assert covs[0] > covs[1] > covs[2]
    assert core.coverage(small_index, q, q.copy(), 8) == 1.0

"""Hypothesis property tests on system invariants."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

import repro.core as core
from repro.core.datastore import build_paged_clusters, Datastore
from repro.distributed import elastic_slices, quantize_int8, dequantize_int8


@st.composite
def paged_store(draw):
    n = draw(st.integers(50, 400))
    d = draw(st.sampled_from([16, 32]))
    nc = draw(st.integers(2, 8))
    ps = draw(st.sampled_from([8, 16]))
    rng = np.random.default_rng(draw(st.integers(0, 2**16)))
    emb = rng.standard_normal((n, d)).astype(np.float32)
    emb /= np.maximum(np.linalg.norm(emb, axis=-1, keepdims=True), 1e-9)
    assign = rng.integers(0, nc, n).astype(np.int32)
    paged = build_paged_clusters(Datastore(embeddings=emb), assign, nc, ps)
    return paged, assign


@given(paged_store())
@settings(max_examples=25, deadline=None)
def test_paged_partition_is_exact(data):
    """Paging is a partition: every vector once, under its own cluster."""
    paged, assign = data
    ids = paged.page_ids.reshape(-1)
    valid = ids >= 0
    assert valid.sum() == len(assign)
    assert len(np.unique(ids[valid])) == len(assign)
    owner = np.repeat(paged.page_cluster, paged.page_size)
    assert np.all(assign[ids[valid]] == owner[valid])


@given(paged_store(), st.integers(0, 2**16), st.floats(0.0, 1.0))
@settings(max_examples=25, deadline=None)
def test_plan_prefetch_invariants(data, seed, frac):
    paged, _ = data
    rng = np.random.default_rng(seed)
    ranked = rng.permutation(paged.num_clusters)
    total = int(paged.all_cluster_bytes().sum())
    budget = int(frac * total)
    plan = core.plan_prefetch(list(ranked), paged, budget_bytes=budget,
                              resident=set(), free_pages=10**9)
    # 1. never exceeds budget
    assert plan.bytes_planned <= budget
    # 2. fetch+skip+resident covers all ranked clusters exactly once
    assert sorted(plan.fetch + plan.skipped) == sorted(int(c) for c in ranked)
    # 3. bytes accounting is exact
    assert plan.bytes_planned == sum(paged.cluster_bytes(c)
                                     for c in plan.fetch)
    # 4. greedy-prefix property: a skipped cluster never fits the budget
    #    remaining at the moment it was considered
    rem = budget
    for c in ranked:
        c = int(c)
        if c in plan.fetch:
            rem -= paged.cluster_bytes(c)
        else:
            assert paged.cluster_bytes(c) > rem


@given(st.integers(1, 64), st.integers(1, 16), st.integers(0, 1000))
@settings(max_examples=50, deadline=None)
def test_elastic_slices_partition(batch, nodes, step):
    healthy = list(range(nodes))
    sl = elastic_slices(step, healthy, batch)
    spans = sorted(sl.values())
    # exact disjoint cover of [0, batch)
    assert spans[0][0] == 0 and spans[-1][1] == batch
    for (a, b), (c, d) in zip(spans, spans[1:]):
        assert b == c
    # determinism
    assert sl == elastic_slices(step, list(reversed(healthy)), batch)


@given(st.lists(st.floats(-100, 100, allow_nan=False), min_size=1,
                max_size=64))
@settings(max_examples=50, deadline=None)
def test_int8_quantization_error_bound(vals):
    x = jnp.asarray(np.asarray(vals, np.float32))
    q, scale = quantize_int8(x)
    err = np.abs(np.asarray(dequantize_int8(q, scale)) - np.asarray(x))
    amax = np.max(np.abs(vals))
    assert np.all(err <= amax / 127.0 * 0.5 + 1e-6)


@given(st.integers(1, 6), st.integers(1, 5), st.integers(0, 2**16))
@settings(max_examples=25, deadline=None)
def test_merge_topk_equals_global_sort(b, k, seed):
    rng = np.random.default_rng(seed)
    ds = rng.standard_normal((b, k)).astype(np.float32)
    hs = rng.standard_normal((b, k)).astype(np.float32)
    di = rng.integers(0, 1000, (b, k)).astype(np.int32)
    hi = rng.integers(1000, 2000, (b, k)).astype(np.int32)
    s, i = core.merge_topk(jnp.asarray(np.sort(ds)[:, ::-1].copy()),
                           jnp.asarray(di),
                           jnp.asarray(np.sort(hs)[:, ::-1].copy()),
                           jnp.asarray(hi), k)
    allscores = np.concatenate([np.sort(ds)[:, ::-1], np.sort(hs)[:, ::-1]], 1)
    expect = np.sort(allscores, axis=1)[:, ::-1][:, :k]
    np.testing.assert_allclose(np.asarray(s), expect, rtol=1e-6)


@given(st.integers(2, 40), st.integers(1, 30), st.integers(1, 8))
@settings(max_examples=40, deadline=None)
def test_ring_cache_position_recovery(W, pos, b):
    """slot -> absolute-position formula used by attn_decode_ring."""
    slots = np.arange(W)
    ks_pos = pos - np.mod(pos - slots, W)
    # recovered positions are exactly the last min(W, pos+1) positions
    got = sorted(p for p in ks_pos if p >= 0)
    lo = max(0, pos - W + 1)
    assert got == list(range(lo, pos + 1))
    # and each sits in its own slot
    for s, p in zip(slots, ks_pos):
        if p >= 0:
            assert p % W == s


@given(st.integers(1, 8), st.integers(1, 3), st.floats(0.1, 0.9))
@settings(max_examples=20, deadline=None)
def test_cache_hotness_monotone(rounds, used_every, frac):
    """A cluster used every round is always at least as hot as one never
    used (Eq. 6 ordering invariant)."""
    c = core.ClusterCache(core.CacheConfig(decay=1.0 / frac if False else 2.0))
    c.on_fetched([1, 2])
    for r in range(rounds):
        c.round_update([1] if r % used_every == 0 else [])
    assert c.hotness[1] >= c.hotness[2]

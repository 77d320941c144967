"""End-to-end behaviour tests for the paper's system.

Builds a real (small) datastore + IVF index, serves full RAG pipelines
through the TeleRAG engine with real decode on a reduced LLM, and checks
the paper's headline claims at test scale:
  * retrieval results identical to the CPU-only baseline (correctness),
  * modeled latency never worse than the baseline (overlap),
  * lookahead bytes respect the Appendix-C budget,
  * multi-replica scheduling + cache raise the prefetch hit rate.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.configs import get_arch
from repro.core.schedulers import TeleRAGScheduler
from repro.models import transformer as tf
from repro.serving import (EngineConfig, RagRequest, TeleRAGEngine,
                           TeleRAGServer, make_traces)
from tests.conftest import unit_queries


def test_end_to_end_rag_query_with_real_llm(small_store, small_index, rng):
    """One full RAG request: lookahead -> REAL decode steps (reduced llama)
    overlapping the prefetch dispatch -> hybrid retrieve -> answer decode."""
    arch = get_arch("llama3-8b").reduced()
    params = tf.init_params(arch, jax.random.PRNGKey(0))
    cache = tf.init_cache(arch, 1, 64)
    step = jax.jit(lambda p, c, i: tf.serve_step(p, c, i, arch))

    cfg = EngineConfig(nprobe=12, top_k=3, buffer_pages=128,
                       lookahead_rank=24, kernel_mode="ref")
    eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))

    q_in = unit_queries(small_store, rng, 1)
    # 1) lookahead prefetch dispatched (async)
    nbytes, nfetch = eng.lookahead(q_in, gen_tokens=[8])
    assert nfetch > 0
    # 2) pre-retrieval generation: REAL decode steps run while the
    #    device_put/scatter from (1) completes
    tok = jnp.zeros((1,), jnp.int32)
    for t in range(8):
        logits, cache = step(params, cache,
                             {"token": tok, "pos": jnp.asarray([t], jnp.int32)})
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
    # 3) rewrite + hybrid retrieval
    q_out = core.synthetic_rewrite(q_in, 0.3, rng)
    res = eng.retrieve(q_out)
    assert res.doc_ids.shape == (1, 3) and np.all(res.doc_ids >= 0)
    assert res.hit_rate > 0  # lookahead found at least one cluster


def test_retrieval_correctness_invariant_across_systems(small_store,
                                                        small_index, rng):
    """TeleRAG accelerates retrieval; it must never change what is
    retrieved (paper's accuracy-preservation claim)."""
    q = unit_queries(small_store, rng, 5)
    ranked = core.probe(q, small_index, 10)

    cpu = [core.host_search(small_index.paged,
                            [int(c) for c in ranked[b]], q[b], 4)
           for b in range(5)]

    cfg = EngineConfig(nprobe=10, top_k=4, buffer_pages=256,
                       lookahead_rank=20, kernel_mode="ref")
    eng = TeleRAGEngine(small_index, cfg, None)
    eng.lookahead(q, gen_tokens=[64])
    res = eng.retrieve(q)
    for b in range(5):
        np.testing.assert_array_equal(np.sort(res.doc_ids[b]),
                                      np.sort(cpu[b][1]))


def test_budget_bounds_transfer(small_store, small_index, rng):
    budget = 20 * small_index.paged.page_nbytes()
    cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=512,
                       lookahead_rank=64, kernel_mode="ref",
                       prefetch_budget_bytes=budget)
    eng = TeleRAGEngine(small_index, cfg, None)
    q = unit_queries(small_store, rng, 4)
    eng.lookahead(q, gen_tokens=[32])
    assert eng.buffer.stats.bytes_h2d <= budget


def test_multi_replica_cache_hit_rate_improves(small_store, small_index, rng):
    cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=200,
                       lookahead_rank=32, kernel_mode="ref",
                       cache_enabled=True)
    srv = TeleRAGServer(small_index, cfg, 2, get_arch("llama3-8b"),
                        scheduler=TeleRAGScheduler(), micro_batch=4)

    def overlap(q, traces):
        """Summed routing overlap of one drained arrival wave."""
        srv.serve([RagRequest(q=q[i], trace=t)
                   for i, t in enumerate(traces)])
        return sum(a[2] for a in srv.wave_log[-1].assignments)

    qs = unit_queries(small_store, rng, 8)
    first = overlap(qs, make_traces("hyde", 8, seed=1))
    # second wave of similar queries: cache-aware router should place them
    # on replicas already holding their clusters
    q2 = qs + 0.02 * rng.standard_normal(qs.shape).astype(np.float32)
    q2 /= np.linalg.norm(q2, axis=-1, keepdims=True)
    assert overlap(q2, make_traces("hyde", 8, seed=2)) > first


def test_hit_rate_grows_with_budget(small_store, small_index, rng):
    """Paper Table 3's budget->hit-rate relationship at test scale."""
    rates = []
    for pages in (16, 64, 256):
        cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=pages,
                           lookahead_rank=64, kernel_mode="ref",
                           prefetch_budget_bytes=pages
                           * small_index.paged.page_nbytes())
        eng = TeleRAGEngine(small_index, cfg, None)
        q = unit_queries(small_store, rng, 4)
        eng.lookahead(q, gen_tokens=[64])
        q_out = core.synthetic_rewrite(q, 0.3, np.random.default_rng(0))
        res = eng.retrieve(q_out)
        rates.append(res.hit_rate)
    assert rates[0] <= rates[1] <= rates[2]
    assert rates[2] > 0.2

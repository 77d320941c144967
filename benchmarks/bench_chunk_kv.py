"""Chunk-KV splice vs re-prefill: how many prefill tokens does the
precomputed chunk-KV path remove from the serve critical path?

One run per (pipeline, coverage) cell: a baseline serve pass (no chunk
store) records which documents each wave retrieves; the chunk store is
then built offline (``data.chunk_kv.build_chunk_kv``) over a
``coverage`` fraction of those docs — mapped to their real IVF clusters
so lookahead prefetch can resolve predicted clusters to pages — and the
same requests are served again with splicing enabled.  The headline
metric is ``prefill_tokens_avoided``: every hit chunk's full token
count that the baseline would have had to prefill is instead attached
to the wave's lease by block-table edit.

The bench is also a CI guard (``run_smoke``): each cell asserts the
splice reduction is at least hit-rate-proportional —
``prefill_tokens_avoided >= hit_rate * chunk_requests * min_len`` (a
hit can never avoid fewer tokens than the shortest chunk) — that waves
actually decoded through the spliced step when coverage > 0, and that
zero coverage avoids exactly zero.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp

from benchmarks.common import (NPROBE, bench_index, bench_queries, emit,
                               summarize_rows, write_report)
from repro.configs import get_arch
from repro.data.chunk_kv import (ChunkKVStore, build_chunk_kv,
                                 cluster_map_from_assignments)
from repro.models import transformer as tf
from repro.serving import (DecodeRunner, EngineConfig, RagRequest,
                           TeleRAGServer, make_traces)

ARCH = get_arch("llama3-8b")
CFG = ARCH.reduced()

PAGE_SIZE = 4          # KV page size (tokens) — the splice granularity
MIN_LEN, MAX_LEN = 6, 10   # chunk token lengths (ragged on purpose)
SEED = 3


def _params():
    return tf.init_params(CFG, jax.random.PRNGKey(0), dtype=jnp.float32)


def _serve(params, q, traces, *, store: Optional[ChunkKVStore],
           micro_batch: int, max_steps: int, slab_seqs: int):
    """One serve pass; returns (runner, server, responses)."""
    runner = DecodeRunner(params, CFG, max_len=24, max_steps=max_steps,
                          page_size=PAGE_SIZE, slab_seqs=slab_seqs,
                          chunk_store=store)
    srv = TeleRAGServer(bench_index(), EngineConfig(
        nprobe=NPROBE, top_k=3, buffer_pages=640, pool_pages=8192,
        lookahead_rank=2 * NPROBE, chips=8, seed=7,
        paged_decode=True, chunk_kv=store is not None), 1, ARCH,
        micro_batch=micro_batch, include_tail=True, decode_hook=runner)
    runner.attach(srv)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i], arrival_t=0.0)
                      for i in range(len(traces))])
    return runner, srv, resp


def _retrieved_docs(resp) -> List[int]:
    """Unique doc ids across every response round, first-seen order."""
    seen: Dict[int, None] = {}
    for r in resp:
        for round_docs in r.doc_ids:
            for d in round_docs:
                seen.setdefault(int(d), None)
    return list(seen)


def run(n_requests: int = 6,
        pipelines: Sequence[str] = ("iter", "irg", "flare"),
        coverages: Sequence[float] = (0.0, 0.5, 1.0),
        max_steps: int = 4, micro_batch: int = 3) -> Dict:
    """The splice-vs-re-prefill table; returns the written report."""
    params = _params()
    cluster_of = cluster_map_from_assignments(bench_index().assignments)
    rows: List[Dict] = []
    for pipeline in pipelines:
        q = bench_queries(n_requests, seed=5)
        traces = make_traces(pipeline, n_requests, seed=11)
        t0 = time.time()
        _, _, resp = _serve(params, q, traces, store=None,
                            micro_batch=micro_batch, max_steps=max_steps,
                            slab_seqs=n_requests + 2)
        base_s = time.time() - t0
        docs = _retrieved_docs(resp)
        full = build_chunk_kv(params, CFG, docs, page_size=PAGE_SIZE,
                              seed=SEED, min_len=MIN_LEN, max_len=MAX_LEN,
                              cluster_of=cluster_of)
        for coverage in coverages:
            subset = docs[:round(coverage * len(docs))]
            store = ChunkKVStore(page_size=PAGE_SIZE, seed=SEED)
            for d in subset:
                store.add(d, full.get(d))
            # slab headroom: wave leases + every built chunk resident
            slab_seqs = n_requests + 2 + (-(-store.total_pages()
                                            // (24 // PAGE_SIZE)) + 1)
            t0 = time.time()
            runner, srv, resp2 = _serve(params, q, traces, store=store,
                                        micro_batch=micro_batch,
                                        max_steps=max_steps,
                                        slab_seqs=slab_seqs)
            spliced_s = time.time() - t0
            st = runner.chunk(0).stats
            requests = st.hits + st.misses
            row = {"pipeline": pipeline, "coverage": coverage,
                   "docs_built": len(store), "chunk_requests": requests,
                   "hit_rate": st.hits / max(requests, 1),
                   "spliced_pages": st.spliced_pages,
                   "prefill_tokens_avoided": st.prefill_tokens_avoided,
                   "spliced_waves": runner.stats["spliced_waves"],
                   "prefetched_pages": st.prefetched_pages,
                   "baseline_s": base_s, "spliced_s": spliced_s}
            rows.append(row)
            # CI guard: the splice must deliver at least a
            # hit-rate-proportional prefill-token reduction
            assert row["prefill_tokens_avoided"] >= (
                row["hit_rate"] * requests * MIN_LEN), row
            if coverage > 0 and requests:
                assert row["hit_rate"] > 0, row
                assert row["spliced_pages"] > 0, row
                assert row["spliced_waves"] > 0, row
            if coverage == 0:
                assert row["prefill_tokens_avoided"] == 0, row
                assert row["spliced_pages"] == 0, row
            emit(f"chunk_kv/{pipeline}/cov{coverage:.2f}",
                 1e6 * spliced_s,
                 f"hit_rate={row['hit_rate']:.2f} "
                 f"avoided={row['prefill_tokens_avoided']}")
    full_cov = [r for r in rows if r["coverage"] == 1.0]
    metrics = dict(summarize_rows(rows),
                   total_prefill_tokens_avoided=float(
                       sum(r["prefill_tokens_avoided"] for r in rows)),
                   full_coverage_hit_rate=float(
                       sum(r["hit_rate"] for r in full_cov)
                       / max(len(full_cov), 1)))
    path = write_report("chunk_kv", metrics=metrics, rows=rows,
                        meta={"page_size": PAGE_SIZE, "min_len": MIN_LEN,
                              "max_len": MAX_LEN, "seed": SEED,
                              "arch": CFG.name})
    return {"rows": rows, "metrics": metrics, "path": path}


def run_smoke() -> Dict:
    """CI smoke cell: one pipeline, full coverage, asserts included."""
    return run(n_requests=4, pipelines=("iter",), coverages=(0.0, 1.0),
               max_steps=3, micro_batch=2)


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: one small cell with assertions")
    a = ap.parse_args()
    run_smoke() if a.smoke else run()

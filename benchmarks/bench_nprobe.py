"""Fig. 15 (App. F): retrieval speedup across nprobe at fixed budget.

Paper: speedups peak at nprobe 256 (7.2–7.4x) and shrink as nprobe grows
past the fixed prefetch budget (more missed clusters land on the CPU).
"""

import numpy as np

import repro.core as core
from repro.serving import EngineConfig, TeleRAGEngine
from repro.configs import get_arch
from benchmarks.common import (N_CLUSTERS, bench_index, bench_queries, emit,
                               paper_scale_tcc, write_csv, PAPER_CLUSTER_BYTES,
                               summarize_rows, write_report)


def run(nprobes=(16, 32, 64, 128), budget_pages: int = 640,
        n_queries: int = 16):
    idx = bench_index()
    rows = []
    for np_ in nprobes:
        cfg = EngineConfig(nprobe=np_, top_k=3, buffer_pages=1024,
                           lookahead_rank=min(4 * np_, N_CLUSTERS),
                           prefetch_budget_bytes=budget_pages
                           * idx.paged.page_nbytes(), chips=4)
        eng = TeleRAGEngine(idx, cfg, get_arch("llama3-8b"))
        q = bench_queries(n_queries, seed=61)
        eng.lookahead(q, gen_tokens=[128] * n_queries)
        q_out = core.synthetic_rewrite(q, 0.3, np.random.default_rng(62))
        res = eng.retrieve(q_out)
        hits = sum(len(h) for h in res.hit_clusters)
        miss = sum(len(m) for m in res.missed_clusters)
        t_cc = paper_scale_tcc(eng.cfg.hw)
        t_cpu = (hits + miss) / n_queries * t_cc
        t_tel = max(miss / n_queries * t_cc,
                    hits / n_queries * PAPER_CLUSTER_BYTES
                    / (eng.cfg.hw.hbm_bw * cfg.chips)) + 2e-5
        rows.append({"nprobe": np_, "hit_rate": round(res.hit_rate, 4),
                     "retrieval_speedup": round(t_cpu / t_tel, 2),
                     "t_cpu_ms": round(t_cpu * 1e3, 2),
                     "t_telerag_ms": round(t_tel * 1e3, 2)})
        emit(f"nprobe/{np_}", t_tel * 1e6,
             f"speedup={rows[-1]['retrieval_speedup']};hit={res.hit_rate:.3f}")
    write_csv("fig15_nprobe", rows)
    write_report("nprobe", metrics=summarize_rows(rows), rows=rows)
    return rows


if __name__ == "__main__":
    run()

"""Fig. 4/5: where RAG latency goes — CPU retrieval vs GPU retrieval vs
runtime-fetch. Also measures REAL host-search wall time on this machine
(the one hardware-honest latency we can measure) for t_cc calibration.
"""

import time

import numpy as np

import repro.core as core
from repro.serving import RetrievalRuntime, make_traces
from benchmarks.common import (bench_index, bench_queries, emit, make_engine,
                               paper_scale_tcc, write_csv,
                               summarize_rows, write_report)
from benchmarks.bench_latency import modeled_latency


def run(n_queries: int = 8):
    idx = bench_index()
    rows = []

    # measured t_cc on this container (real wall time of numpy host search)
    eng = make_engine()
    t_cc_measured = eng.calibrate_tcc(32)
    emit("breakdown/t_cc_measured_this_host", t_cc_measured * 1e6,
         f"paper_scale_model={paper_scale_tcc()*1e6:.0f}us")

    for pipe in ("hyde", "iter", "irg"):
        eng = make_engine(buffer_pages=1024)
        rt = RetrievalRuntime(eng)
        q = bench_queries(n_queries, seed=71)
        recs = [rt.submit(q[i], t) for i, t in
                enumerate(make_traces(pipe, n_queries, seed=72))]
        rt.run()
        res = [r.result for r in recs]
        t_cc = paper_scale_tcc(eng.cfg.hw)
        llm = np.mean([sum(rt.t_llm_window for rt in r.rounds) for r in res])
        cpu_ret = np.mean([sum((rt.hits + rt.misses) * t_cc
                               for rt in r.rounds) for r in res])
        tele = np.mean([modeled_latency(r, eng, "telerag") for r in res])
        cpu = np.mean([modeled_latency(r, eng, "cpu_baseline") for r in res])
        fetch = np.mean([modeled_latency(r, eng, "runtime_fetch")
                         for r in res])
        rows.append({
            "pipeline": pipe,
            "llm_ms": round(llm * 1e3, 2),
            "cpu_retrieval_ms": round(cpu_ret * 1e3, 2),
            "retrieval_frac_cpu_system": round(cpu_ret / (llm + cpu_ret), 3),
            "e2e_cpu_ms": round(cpu * 1e3, 2),
            "e2e_runtime_fetch_ms": round(fetch * 1e3, 2),
            "e2e_telerag_ms": round(tele * 1e3, 2),
        })
        emit(f"breakdown/{pipe}", tele * 1e6,
             f"ret_frac={rows[-1]['retrieval_frac_cpu_system']}")
    write_csv("fig4_5_breakdown", rows)
    write_report("breakdown", metrics=summarize_rows(rows), rows=rows)
    return rows


if __name__ == "__main__":
    run()

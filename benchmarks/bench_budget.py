"""Appendix C: the prefetch-budget model — t1+t2 curve and the optimum —
plus the admission-control path of the shared device page pool.

Empirically builds r_miss(b) by sweeping budgets on the bench index, then
checks Appendix C's conclusion: on realistic link speeds the optimum sits
at b* = B·t̄_LLM (case 1), not at an interior case-2 point.

``run_admission`` (the ``--smoke`` entry CI exercises) serves two query
waves through a pool sized below their combined lookahead plans and
checks the reserve/stall/resume path end to end: the second wave must
park PRESSURE_STALLED and still complete once the first wave's pins
release — no silent plan truncation, no rejected-cluster leaks.
"""

import argparse

import numpy as np

import repro.core as core
from repro.configs import get_arch
from repro.serving import calibration_windows
from benchmarks.common import (NPROBE, bench_index, bench_queries, emit,
                               make_engine, paper_scale_tcc, write_csv,
                               summarize_rows, write_report)


def run(pipeline: str = "hyde", n_queries: int = 16):
    idx = bench_index()
    total = float(idx.paged.all_cluster_bytes().sum())
    budgets = [total * f for f in (0.02, 0.05, 0.1, 0.2, 0.4, 0.7)]
    hit_rates = []
    for b in budgets:
        eng = make_engine(budget_bytes=int(b), buffer_pages=4096)
        q = bench_queries(n_queries, seed=81)
        eng.lookahead(q, gen_tokens=[128] * n_queries)
        q_out = core.synthetic_rewrite(q, core.PIPELINE_SIGMA[pipeline],
                                       np.random.default_rng(82))
        res = eng.retrieve(q_out)
        hit_rates.append(res.hit_rate)

    miss_fn = core.empirical_miss_curve(budgets, hit_rates)
    hw = core.TPU_V5E
    t_cc = paper_scale_tcc(hw)
    wins = calibration_windows(pipeline, 64)
    cfg = get_arch("llama3-8b")
    t_llm = core.generation_window_seconds(cfg, hw, gen_tokens=wins, batch=1,
                                           chips=4)
    b_case1 = core.case1_budget(t_llm, hw.host_link_bw)
    b_case2 = core.case2_budget(miss_fn, link_bw=hw.host_link_bw,
                                nprobe=NPROBE, t_cc=t_cc, b_max=total)
    rows = [{"budget_frac": round(b / total, 3),
             "hit_rate": round(h, 4),
             "t_total_ms": round((max(t_llm, b / hw.host_link_bw)
                                  + miss_fn(b) * NPROBE * t_cc) * 1e3, 3)}
            for b, h in zip(budgets, hit_rates)]
    write_csv("appC_budget", rows)
    write_report("budget", metrics=summarize_rows(rows), rows=rows)
    emit("budget/case1", t_llm * 1e6,
         f"b1_frac={b_case1/total:.3f};case2={'none' if b_case2 is None else round(b_case2/total,3)}")
    # hit rate must be monotone in budget
    assert all(a <= b + 0.02 for a, b in zip(hit_rates, hit_rates[1:])), \
        hit_rates
    return rows


def run_admission(n_queries: int = 8):
    """Serve disjoint-neighbourhood waves through a pool too small for
    all plans at once; report stall/resume/spill admission stats.  Runs
    the default per-request (reform) runtime: queries are ordered so the
    EDF wave former's FIFO chunks of ``micro_batch`` are the disjoint
    neighbourhoods by construction, and parked requests rejoin waves as
    completions free pages."""
    from repro.serving import (EngineConfig, RequestState, RetrievalRuntime,
                               TeleRAGEngine, make_traces)

    store = core.synthetic_datastore(24_000, dim=96, seed=7, num_topics=48)
    index = core.build_ivf(store, 48, page_size=64, kmeans_iters=3)
    # pool sized below one wave's combined plan => admission must arbitrate
    pages_per_cluster = float(np.mean(index.paged.cluster_num_pages))
    pool_pages = int(10 * pages_per_cluster)
    eng = TeleRAGEngine(index, EngineConfig(
        nprobe=12, top_k=3, buffer_pages=pool_pages, lookahead_rank=16,
        chips=4, seed=3), get_arch("llama3-8b"))
    runtime = RetrievalRuntime(eng, micro_batch=2)

    cents = index.centroids / np.linalg.norm(index.centroids, axis=-1,
                                             keepdims=True)
    half = max(2, n_queries // 2)
    q = np.concatenate([cents[:half], cents[-half:]]).astype(np.float32)
    traces = make_traces("hyde", len(q), seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(len(q))]
    runtime.run()
    adm = eng.admission.stats
    assert all(r.state == RequestState.COMPLETE for r in recs)
    assert not eng.admission.parked, "parked waves leaked past the drain"
    # the whole point of this smoke: the pressure path actually ran
    assert adm.stalled > 0 and adm.resumed > 0, adm
    stalls = [rid for _, label, rid in runtime.event_log
              if label == "pressure_stall"]
    rows = [{"pool_pages": pool_pages,
             "admitted": adm.admitted, "stalled": adm.stalled,
             "resumed": adm.resumed, "capped": adm.capped,
             "spilled_pages": adm.spilled_pages,
             "stalled_requests": len(set(stalls)),
             "ledger_peak_mb": round(eng.ledger.peak_bytes / 1e6, 3)}]
    write_csv("admission_smoke", rows)
    write_report("admission", metrics=summarize_rows(rows), rows=rows)
    emit("budget/admission", adm.stalled,
         f"resumed={adm.resumed};capped={adm.capped};"
         f"spill_pages={adm.spilled_pages}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: exercise the admission path only")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.smoke:
        run_admission()
    else:
        run()
        run_admission()

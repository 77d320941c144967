"""Overlap efficiency from the flight-recorder trace (the paper's
central claim, measured off the event timeline).

Drives a hyde/iter request mix through the continuous-batching server,
then runs ``repro.obs.analyze`` over the recorded trace: per-round
lookahead overlap ratio (the fraction of each member's modeled H2D copy
hidden under its generation window), stall-time attribution (link vs
pressure vs queue), and wave-fragmentation stats.  Asserts the TeleRAG
property the whole repo exists to reproduce — the mean overlap ratio on
a prefetching mix is strictly positive — and that every admitted
request's lifecycle events are well-ordered in the trace.

``--smoke`` is the CI-sized guard (also in ``run.py --smoke``).
"""

import argparse
import dataclasses

import numpy as np

from repro.core.schedulers import TeleRAGScheduler
from repro.obs import analyze
from repro.serving import make_traces
from benchmarks.common import (bench_queries, emit, make_server,
                               serve_requests, write_csv,
                               summarize_rows, write_report)


def run(n_requests: int = 24, replicas: int = 2, micro_batch: int = 4,
        seed: int = 71):
    srv = make_server(replicas=replicas, cache=True, buffer_pages=768,
                      scheduler=TeleRAGScheduler(),
                      micro_batch=micro_batch)
    # hyde/iter mix: both pipelines prefetch, with different round
    # shapes; re-id so the mix's request ids stay unique (make_traces
    # numbers 0..n-1 per call and the recorder correlates by id)
    half = n_requests // 2
    traces = [dataclasses.replace(t, request_id=i) for i, t in enumerate(
        make_traces("hyde", half, seed=seed)
        + make_traces("iter", n_requests - half, seed=seed + 1))]
    q = bench_queries(n_requests, seed=seed + 2)
    rng = np.random.default_rng(seed + 3)
    arrivals = np.cumsum(rng.exponential(0.02, n_requests))
    resp = serve_requests(srv, q, traces, arrivals)
    assert len(resp) == n_requests

    rec = srv.recorder
    report = analyze(rec)
    print(report.summary())

    # the TeleRAG claim: on a prefetching mix, part of the copy hides
    # under generation — the trace must show a positive overlap ratio
    assert report.prefetched_rounds, "no prefetched rounds in the trace"
    assert report.mean_overlap_ratio > 0.0, report.mean_overlap_ratio

    # lifecycle sanity straight off the trace: admit <= first generate
    # <= complete for every admitted request
    marks = {}
    for r in resp:
        m = rec.request_marks(r.request_id)
        assert "admit" in m and "complete" in m, m
        assert m["admit"] <= m.get("generate", m["complete"]) + 1e-12
        assert m.get("generate", m["admit"]) <= m["complete"] + 1e-12
        marks[r.request_id] = m

    rows = [{
        "requests": n_requests, "replicas": replicas,
        "prefetched_rounds": len(report.prefetched_rounds),
        "rounds": len(report.rounds),
        "mean_overlap_ratio": round(report.mean_overlap_ratio, 4),
        "fully_hidden_frac": round(report.fully_hidden_frac, 4),
        "mean_wave_size": round(report.mean_wave_size, 3),
        "singleton_wave_frac": round(report.singleton_wave_frac, 4),
        "link_stall_ms": round(report.stall.get("link_s", 0.0) * 1e3, 3),
        "pressure_stall_ms": round(
            report.stall.get("pressure_s", 0.0) * 1e3, 3),
        "queue_ms": round(report.stall.get("queue_s", 0.0) * 1e3, 3),
        "trace_events": len(rec.events),
    }]
    write_csv("overlap_trace", rows)
    write_report("overlap_trace", metrics=summarize_rows(rows), rows=rows)
    emit("overlap_trace", report.mean_overlap_ratio * 1e6,
         f"hidden={report.mean_overlap_ratio:.3f};"
         f"waves={len(report.wave_sizes)}")
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: small fast trace-analysis pass")
    args = ap.parse_args()
    if args.smoke:
        run(n_requests=12, replicas=2)
    else:
        run()

"""Multi-tenant SLO serving: a latency-sensitive tenant sharing
replicas with a throughput batch tenant.

Two open-loop tenant mixes hit the same fleet:

  * **latency** — low-rate trickle of requests with a tight
    arrival→complete deadline and priority class 0;
  * **batch** — a simultaneous burst of deadline-free requests at
    priority class 1 (throughput traffic that should soak up whatever
    the fleet has left).

The SLO-aware configuration (the server default: ``EdfDispatch``
priority classes + earliest-deadline-first, plus per-tenant pool
reservations giving the latency tenant a guaranteed page floor and
capping the batch tenant's burst) is compared against a mixed baseline
(``FifoDispatch``, no reservations) on the *identical* workload.  The
replica's wave former serves priority classes first under both, so the
two differ by the reservations.  Asserts every request of BOTH tenants completes
under both configurations and that the latency tenant's deadline-miss
rate under the SLO configuration is no worse than under the baseline;
``--smoke`` is the CI guard.

Prints per-tenant ``TenantTelemetry`` lines and writes
``experiments/bench/tenant_slo.csv``.
"""

import argparse
import dataclasses

import numpy as np

from repro.configs import get_arch
from repro.core.schedulers import EdfDispatch, FifoDispatch, TeleRAGScheduler
from repro.serving import RagRequest, TeleRAGServer, make_traces
from benchmarks.common import (bench_cfg, bench_index, bench_queries, emit,
                               write_csv,
                               summarize_rows, write_report)


def _server(dispatch, tenant_shares, replicas, micro_batch, seed):
    cfg = dataclasses.replace(bench_cfg(seed=seed, buffer_pages=512),
                              tenant_shares=tenant_shares)
    return TeleRAGServer(bench_index(), cfg, replicas, get_arch("llama3-8b"),
                         scheduler=TeleRAGScheduler(),
                         micro_batch=micro_batch, dispatch=dispatch)


def _workload(n_latency, n_batch, deadline_s, spacing_s, seed):
    """The identical two-tenant request stream both configurations
    serve: a batch-tenant burst at t=0 plus a latency-tenant trickle
    arriving while the burst is queued."""
    q_lat = bench_queries(n_latency, seed=seed)
    q_bat = bench_queries(n_batch, seed=seed + 1)
    t_lat = make_traces("hyde", n_latency, seed=seed + 2)
    t_bat = make_traces("hyde", n_batch, seed=seed + 3)
    reqs = [RagRequest(q=q_bat[i], trace=t_bat[i], tenant="batch",
                       priority=1) for i in range(n_batch)]
    reqs += [RagRequest(q=q_lat[i], trace=t_lat[i], tenant="latency",
                        priority=0, deadline_s=deadline_s,
                        arrival_t=0.005 + i * spacing_s)
             for i in range(n_latency)]
    return reqs


def _solo_latency(micro_batch, seed):
    """One latency request on an idle fleet: the no-contention service
    time the deadline is calibrated from."""
    srv = _server(EdfDispatch(), None, 1, micro_batch, seed)
    q = bench_queries(1, seed=seed + 9)
    trace = make_traces("hyde", 1, seed=seed + 9)[0]
    resp = srv.serve([RagRequest(q=q[0], trace=trace, tenant="latency")])
    return resp[0].latency_s


def run(n_latency: int = 8, n_batch: int = 24, replicas: int = 2,
        micro_batch: int = 2, seed: int = 71):
    solo = _solo_latency(micro_batch, seed)
    deadline_s = 3.0 * solo              # met when served promptly,
    spacing_s = 0.5 * solo               # missed when parked behind the burst
    pool_pages = 512
    shares = {"latency": (pool_pages // 4, None),
              "batch": (0, 3 * pool_pages // 4)}

    rows = []
    miss_rate = {}
    for label, dispatch, tenant_shares in (
            ("slo", EdfDispatch(), shares),
            ("fifo_baseline", FifoDispatch(), None)):
        srv = _server(dispatch, tenant_shares, replicas, micro_batch, seed)
        resp = srv.serve(_workload(n_latency, n_batch, deadline_s,
                                   spacing_s, seed))
        assert len(resp) == n_latency + n_batch
        assert all(r.state.value == "complete" for r in resp), \
            f"{label}: both tenants must fully complete"
        tele = srv.telemetry()
        lat = tele.tenant("latency")
        bat = tele.tenant("batch")
        assert lat.completed == n_latency and bat.completed == n_batch
        # telemetry counters must agree with the per-response flags
        assert lat.deadline_missed == sum(r.deadline_missed for r in resp
                                          if r.tenant == "latency")
        miss_rate[label] = lat.deadline_missed / max(1, lat.with_deadline)
        rows.append({
            "config": label, "replicas": replicas,
            "n_latency": n_latency, "n_batch": n_batch,
            "deadline_ms": round(deadline_s * 1e3, 2),
            "lat_p50_ms": round(lat.p50_latency_s * 1e3, 2),
            "lat_p99_ms": round(lat.p99_latency_s * 1e3, 2),
            "lat_miss": lat.deadline_missed,
            "lat_miss_queue": lat.missed_in_queue,
            "lat_attain": round(lat.attainment, 3),
            "bat_p50_ms": round(bat.p50_latency_s * 1e3, 2),
            "bat_completed": bat.completed,
            "demoted_rounds": lat.demoted_rounds + bat.demoted_rounds,
        })
        emit(f"tenants/{label}", lat.p99_latency_s * 1e6,
             f"attain={lat.attainment:.2f};miss={lat.deadline_missed}/"
             f"{lat.with_deadline}")
        print(f"# tenants config={label} deadline={deadline_s*1e3:.0f}ms")
        print("#   " + lat.line())
        print("#   " + bat.line())

    # the acceptance bar: SLO-aware serving never makes the
    # latency-sensitive tenant's miss rate worse than the mixed baseline
    assert miss_rate["slo"] <= miss_rate["fifo_baseline"] + 1e-12, miss_rate
    write_csv("tenant_slo", rows)
    write_report("tenants", metrics=summarize_rows(rows), rows=rows)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI guard: small fast two-tenant pass")
    args = ap.parse_args()
    if args.smoke:
        run(n_latency=4, n_batch=10)
    else:
        run()

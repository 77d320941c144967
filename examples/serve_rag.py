"""End-to-end driver: multi-replica TeleRAG serving through the unified
``TeleRAGServer`` front-end.

Exercises the full Fig.-7 system as ONE surface: wave 1 is closed-loop
batch replay (simultaneous arrivals), wave 2 is an OPEN-LOOP Poisson
arrival stream — the continuous dispatcher admits requests at their
arrival times, routes them per wave with the cache-aware scheduler
(reading live cache residency + ledger occupancy), and interleaves the
replica runtimes on one shared event clock, so queue wait and
latency-under-load are measured quantities.  Wave 3 kills a replica to
show the re-queue path, then a replica snapshot/restore round-trips the
admission telemetry.  Wave 4 is the multi-tenant SLO mix: a
deadline-carrying interactive tenant (with a guaranteed pool floor)
shares the fleet with a bursty batch tenant; EDF dispatch + per-tenant
reservations keep the interactive tenant's deadlines while both
complete, and the per-tenant telemetry lines show the split.  Wave 5
is per-request continuous batching on heterogeneous round counts: the
dynamic wave former re-batches at every round frontier, so a request
with more rounds never drags its batch-mates.

Run: PYTHONPATH=src python examples/serve_rag.py [--requests 24]
"""

import argparse
import time

import numpy as np

import repro.core as core
from repro.configs import get_arch
from repro.core.schedulers import TeleRAGScheduler
from repro.serving import (EngineConfig, RagRequest, TeleRAGServer,
                           make_traces, summarize_latency)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--replicas", type=int, default=3)
    ap.add_argument("--micro-batch", type=int, default=4)
    ap.add_argument("--pipeline", default="hyde")
    ap.add_argument("--rate", type=float, default=200.0,
                    help="open-loop offered load for wave 2 (modeled req/s)")
    args = ap.parse_args()

    store = core.synthetic_datastore(60_000, dim=160, seed=1)
    index = core.build_ivf(store, 96, page_size=96, kmeans_iters=4)
    cfg = EngineConfig(nprobe=24, top_k=3, buffer_pages=384,
                       lookahead_rank=48,
                       cache_enabled=True, chips=4)
    srv = TeleRAGServer(index, cfg, args.replicas, get_arch("llama3-8b"),
                        scheduler=TeleRAGScheduler(),
                        micro_batch=args.micro_batch)

    rng = np.random.default_rng(2)

    def wave(n):
        q = store.embeddings[rng.choice(store.num_vectors, n)]
        q = q + 0.05 * rng.standard_normal(q.shape).astype(np.float32)
        return q / np.linalg.norm(q, axis=-1, keepdims=True)

    print(f"== wave 1: {args.requests} simultaneous requests on "
          f"{args.replicas} replicas ==")
    q1 = wave(args.requests)
    traces = make_traces(args.pipeline, args.requests, seed=3)
    t0 = time.time()
    resp = srv.serve([RagRequest(q=q1[i], trace=traces[i])
                      for i in range(args.requests)])
    hits = sum(rt.hits for r in resp for rt in r.rounds)
    miss = sum(rt.misses for r in resp for rt in r.rounds)
    w = srv.wave_log[-1]
    print(f"done in {time.time()-t0:.1f}s wall; hit {hits/(hits+miss):.0%}; "
          f"sched overhead {w.sched_overhead_s*1e3:.0f} ms; "
          f"assignments {w.assignments}")
    print(summarize_latency(resp))

    print(f"\n== wave 2: open-loop Poisson arrivals at {args.rate:.0f} "
          f"modeled req/s (warm caches raise routing overlap) ==")
    q2 = wave(args.requests)
    traces2 = make_traces(args.pipeline, args.requests, seed=4)
    arrivals = np.cumsum(rng.exponential(1.0 / args.rate, args.requests))
    n_waves0 = len(srv.wave_log)
    resp2 = srv.serve([RagRequest(q=q2[i], trace=traces2[i],
                                  arrival_t=float(arrivals[i]))
                       for i in range(args.requests)])
    waves2 = srv.wave_log[n_waves0:]
    print(f"{len(waves2)} arrival waves; cache-overlap per assignment: "
          f"{[a[2] for w in waves2 for a in w.assignments]}")
    print(summarize_latency(resp2))
    by_replica = {}
    for r in resp2:
        by_replica.setdefault(r.replica, []).append(r)
    for i in sorted(by_replica):
        rs = by_replica[i]
        print(f"replica {i}: {len(rs)} requests, "
              f"mean queue {np.mean([r.queue_s for r in rs])*1e3:.1f}ms")

    print("\n== wave 3: replica 1 dies; micro-batches re-queue ==")
    srv.mark_dead(1)
    q3 = wave(args.requests)
    traces3 = make_traces(args.pipeline, args.requests, seed=5)
    n_waves0 = len(srv.wave_log)
    resp3 = srv.serve([RagRequest(q=q3[i], trace=traces3[i])
                       for i in range(args.requests)])
    requeued = [b for w in srv.wave_log[n_waves0:] for b in w.requeued]
    print(f"re-queued micro-batches: {requeued}; "
          f"all {len(resp3)} requests served "
          f"(replicas used: {sorted({r.replica for r in resp3})})")
    print(summarize_latency(resp3))
    srv.mark_alive(1)

    print("\n== wave 4: multi-tenant SLO mix (interactive floor + "
          "batch burst) ==")
    cfg_mt = EngineConfig(nprobe=24, top_k=3, buffer_pages=384,
                          lookahead_rank=48,
                          cache_enabled=True, chips=4,
                          tenant_shares={"interactive": (96, None),
                                         "batch": (0, 288)})
    srv_mt = TeleRAGServer(index, cfg_mt, 2, get_arch("llama3-8b"),
                           scheduler=TeleRAGScheduler(), micro_batch=2)
    n_i, n_b = max(1, args.requests // 3), args.requests
    q_i, q_b = wave(n_i), wave(n_b)
    t_i = make_traces(args.pipeline, n_i, seed=6)
    t_b = make_traces(args.pipeline, n_b, seed=7)
    # calibrate the deadline on a throwaway server so the solo run does
    # not pollute srv_mt's per-tenant telemetry
    srv_cal = TeleRAGServer(index, cfg_mt, 1, get_arch("llama3-8b"))
    solo = srv_cal.serve([RagRequest(q=q_i[0], trace=t_i[0],
                                     tenant="interactive")])[0].latency_s
    reqs = [RagRequest(q=q_b[i], trace=t_b[i], tenant="batch", priority=1)
            for i in range(n_b)]
    reqs += [RagRequest(q=q_i[i], trace=t_i[i], tenant="interactive",
                        priority=0, deadline_s=5.0 * solo,
                        arrival_t=0.01 + 0.5 * solo * i)
             for i in range(n_i)]
    resp4 = srv_mt.serve(reqs)
    tele = srv_mt.telemetry()
    for t in tele.tenants:
        print(t.line())
    missed = [r.request_id for r in resp4 if r.deadline_missed]
    print(f"all {len(resp4)} served; deadline misses: {missed or 'none'}")

    print("\n== wave 5: per-request continuous batching "
          "(heterogeneous round counts) ==")
    n5 = args.requests
    q5 = wave(n5)
    pipes = ["hyde", "iter", "irg", "flare"]
    mixed = [make_traces(pipes[i % len(pipes)], 1, seed=8 + i)[0]
             for i in range(n5)]
    for i, t in enumerate(mixed):
        t.request_id = i
    srv5 = TeleRAGServer(index, cfg, 1, get_arch("llama3-8b"),
                         micro_batch=args.micro_batch)
    resp5 = srv5.serve([
        RagRequest(q=q5[i], trace=mixed[i], arrival_t=0.002 * i)
        for i in range(n5)])
    n_waves = sum(len(rt.wave_log) for rt in srv5.runtimes)
    sizes = [len(w.members) for rt in srv5.runtimes for w in rt.wave_log]
    print(f"per-request: {summarize_latency(resp5)} ({n_waves} waves "
          f"executed, {min(sizes)}-{max(sizes)} members each)")

    print("\n== unified telemetry snapshot ==")
    print(srv.telemetry().summary())

    print("\n== replica snapshot/restore (fault tolerance) ==")
    snap = srv.engines[0].snapshot()
    srv.engines[0].restore(snap)
    print(f"replica 0 restored: {len(snap['resident'])} clusters resident, "
          f"{snap['stats'][0]/1e6:.1f} MB lifetime h2d, admission stats "
          f"carried (admitted={snap['admission']['admitted']})")


if __name__ == "__main__":
    main()

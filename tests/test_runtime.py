"""Event-driven RetrievalRuntime: transfer engine, the event clock
against the closed-form latency model, and continuous-batching overlap
timelines."""

import numpy as np
import pytest

import repro.core as core
from repro.configs import get_arch
from repro.core.transfer import TransferEngine
from repro.serving import (EngineConfig, LatencyContext, RequestState,
                           RetrievalRuntime, TeleRAGEngine, make_traces)
from repro.serving.trace import RequestTrace, StageTrace
from tests.conftest import unit_queries

MODES = ("telerag", "cpu_baseline", "runtime_fetch")


def make_engine(small_index, mode="telerag", seed=5, buffer_pages=160):
    cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=buffer_pages,
                       lookahead_rank=32, kernel_mode="ref", chips=8,
                       mode=mode, seed=seed)
    return TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))


# ---------------------------------------------------------------------------
# TransferEngine: double-buffered link, in-flight events
# ---------------------------------------------------------------------------


def test_transfer_engine_double_buffered_link(small_index):
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    te = TransferEngine(buf, link_bw=1e9, channels=2)
    # two copies submitted together start together (separate channels)
    e1 = te.submit([], now=0.0, nbytes=int(1e9))      # 1 s copy
    e2 = te.submit([], now=0.0, nbytes=int(5e8))      # 0.5 s copy
    assert e1.channel != e2.channel
    assert e1.start_t == e2.start_t == 0.0
    assert e1.end_t == pytest.approx(1.0)
    # a third queues on the earliest-free channel
    e3 = te.submit([], now=0.1, nbytes=int(1e8))
    assert e3.channel == e2.channel
    assert e3.start_t == pytest.approx(0.5)           # waited for channel
    assert e3.queued_s == pytest.approx(0.4)
    assert te.in_flight(0.25) == [e1, e2]
    assert te.drained_at() == pytest.approx(max(e1.end_t, e3.end_t))
    # overlap is interval intersection, not totals
    assert e1.overlaps(0.9, 2.0) and not e1.overlaps(1.0, 2.0)


def test_transfer_engine_dispatches_real_loads(small_index):
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    te = TransferEngine(buf, link_bw=32e9)
    ev = te.submit([0, 1], now=0.0)
    assert buf.is_resident(0) and buf.is_resident(1)
    assert ev.nbytes == sum(small_index.paged.cluster_bytes(c)
                            for c in (0, 1))
    assert ev.duration == pytest.approx(ev.nbytes / 32e9)


def test_transfer_ready_t_per_request_view(small_index):
    buf = core.PrefetchBuffer(small_index.paged, num_pages=64)
    te = TransferEngine(buf, link_bw=1e9)
    ev = te.submit([], now=0.0, nbytes=int(1e9))      # [0, 1]
    # consumer dispatching later sees the window from its own boundary
    assert te.ready_t(ev, 0.0) == pytest.approx(1.0)
    assert te.ready_t(ev, 0.4) == pytest.approx(1.4)
    # but never earlier than the physical completion
    assert te.ready_t(ev, -1.0) == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# Acceptance: the event clock == the max()-composed closed forms
# ---------------------------------------------------------------------------


def _closed_form_latency(result, mode, *, t_cc, cluster_bytes, link_bw):
    """The closed forms on RoundTelemetry, composed per round."""
    tot = 0.0
    for r in result.rounds:
        if mode == "telerag":
            tot += r.t_telerag()
        elif mode == "cpu_baseline":
            tot += r.t_cpu_baseline(t_cc)
        else:
            tot += r.t_runtime_fetch(cluster_bytes, link_bw)
    return tot


@pytest.mark.parametrize("mode", MODES)
@pytest.mark.parametrize("pipe", ("hyde", "iter", "irg"))
def test_event_clock_matches_closed_form_model(
        small_store, small_index, rng, mode, pipe):
    """Where a request's wave never changes, its admit→complete latency
    on the event clock is the max()-composed closed forms of its rounds'
    telemetry: four simultaneous single-round requests form one wave,
    and a lone multi-round request rides a wave of its own every round.
    (Several multi-round requests re-form into waves that overlap on the
    event clock, which the per-request closed form does not model.)"""
    eng = make_engine(small_index, mode)
    t_cc = eng.effective_tcc()
    ctx = LatencyContext(t_cc=t_cc, cluster_bytes=1e6, link_bw=32e9)
    runtime = RetrievalRuntime(eng, ctx=ctx)
    n = 4 if pipe == "hyde" else 1
    q = unit_queries(small_store, rng, n)
    traces = make_traces(pipe, n, seed=11)
    recs = [runtime.submit(q[i], traces[i]) for i in range(n)]
    runtime.run()
    for rec in recs:
        assert rec.state == RequestState.COMPLETE
        assert len(rec.result.rounds) == rec.trace.rounds
        assert {rt.batch for rt in rec.result.rounds} == {n}
        model = _closed_form_latency(rec.result, mode, t_cc=t_cc,
                                     cluster_bytes=1e6, link_bw=32e9)
        assert rec.latency == pytest.approx(model, abs=1e-6)
        # the policy-registry path agrees with the closed forms too
        assert rec.result.latency(mode, t_cc=t_cc, cluster_bytes=1e6,
                                  link_bw=32e9) == pytest.approx(model,
                                                                 abs=1e-9)


def test_timeline_spans_are_causal(small_store, small_index, rng):
    eng = make_engine(small_index, "telerag")
    runtime = RetrievalRuntime(eng)
    q = unit_queries(small_store, rng, 3)
    recs = [runtime.submit(q[i], t)
            for i, t in enumerate(make_traces("iter", 3, seed=2))]
    runtime.run()
    for rec in recs:
        assert rec.admit_t <= rec.complete_t
        for rnd in range(rec.trace.rounds):
            gen = [s for s in rec.spans("generate") if s.round_index == rnd]
            ret = [s for s in rec.spans("retrieve") if s.round_index == rnd]
            assert len(gen) == 1 and len(ret) == 1
            assert gen[0].start <= gen[0].end <= ret[0].start <= ret[0].end
    # the request-lifecycle events are emitted in time order
    times = [e.t for e in eng.recorder.events if e.kind == "request"]
    assert times and times == sorted(times)


# ---------------------------------------------------------------------------
# Acceptance: interleaved arrivals — prefetch in flight during another
# request's generation window (event timeline, not totals)
# ---------------------------------------------------------------------------


def _long_gen_trace(request_id, gen_tokens):
    return RequestTrace(pipeline="hyde", request_id=request_id,
                        stages=[StageTrace("generate", gen_tokens),
                                StageTrace("retrieve"),
                                StageTrace("generate", 8)],
                        rewrite_sigma=0.1)


def test_interleaved_arrival_prefetch_overlaps_generation(
        small_store, small_index, rng):
    # buffer headroom: the planner never plans past free pages, so a
    # mid-flight wave needs slack left over from the first wave's budget
    eng = make_engine(small_index, "telerag", seed=7, buffer_pages=512)
    runtime = RetrievalRuntime(eng)
    # disjoint cluster neighbourhoods so wave B must move fresh bytes
    cents = small_index.centroids / np.linalg.norm(
        small_index.centroids, axis=-1, keepdims=True)
    qa = cents[:2].astype(np.float32)
    qb = cents[-2:].astype(np.float32)

    t_llm_a = eng.llm_window_seconds(4000, 2)
    assert t_llm_a > 0
    mid = 0.5 * t_llm_a       # wave B lands mid-way through A's windows

    recs_a = [runtime.submit(qa[i], _long_gen_trace(i, 4000))
              for i in range(2)]
    recs_b = [runtime.submit(qb[i], _long_gen_trace(10 + i, 4000), mid)
              for i in range(2)]
    runtime.run()

    assert all(r.state == RequestState.COMPLETE for r in recs_a + recs_b)
    # wave B was admitted while wave A was still generating
    assert recs_b[0].admit_t == pytest.approx(mid)
    assert all(r.admit_t == 0.0 for r in recs_a)

    b_transfers = [e for e in eng.transfer.events
                   if e.kind == "prefetch" and e.nbytes > 0
                   and e.submit_t >= mid * 0.999]
    assert b_transfers, "wave B dispatched no prefetch bytes"

    a_gen = [s for r in recs_a for s in r.spans("generate")
             if s.round_index == 0]
    # event-timeline assertion: B's copy occupies the link strictly
    # inside an A generation window — overlap as interval intersection
    hits = [(e, s) for e in b_transfers for s in a_gen
            if e.overlaps(s.start, s.end)]
    assert hits, (b_transfers, a_gen)
    ev, span = hits[0]
    assert span.start < ev.start_t < span.end     # starts mid-window
    # and A's requests were still incomplete when B's transfer started
    assert all(ev.start_t < r.complete_t for r in recs_a)


def test_runtime_is_reusable_across_waves(small_store, small_index, rng):
    """Clock is monotonic across run() calls; latencies stay relative."""
    eng = make_engine(small_index, "telerag")
    runtime = RetrievalRuntime(eng)
    q = unit_queries(small_store, rng, 2)
    r1 = [runtime.submit(q[i], t)
          for i, t in enumerate(make_traces("hyde", 2, seed=3))]
    runtime.run()
    r2 = [runtime.submit(q[i], t)
          for i, t in enumerate(make_traces("hyde", 2, seed=4))]
    runtime.run()
    assert r2[0].admit_t >= r1[0].complete_t      # no time travel
    assert all(r.latency > 0 for r in r1 + r2)

"""Unified serving front-end: TeleRAGServer facade, typed
request/response lifecycle, and cross-replica continuous dispatch.

Pins the front end's contract:
  * what a request retrieves does not depend on how it was served:
    replicas, routing and arrival times change the waves, never the
    doc ids;
  * staggered arrivals interleave replica work on ONE shared event
    clock;
  * per-request arrival→complete latency is monotone in offered load;
  * results come back in submission order everywhere;
  * a dead replica is routed nothing, and comes back with mark_alive;
  * the recorder holds each replica's request lifecycle on its own lane;
  * static-group execution (``continuous=False``) is refused.
"""

import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.schedulers import TeleRAGScheduler
from repro.serving import (EngineConfig, RagRequest, RequestState,
                           TeleRAGServer, make_traces)
from repro.serving.trace import RequestTrace, StageTrace
from tests.conftest import unit_queries


def _cfg(seed=5, **kw):
    defaults = dict(nprobe=16, top_k=3, buffer_pages=200, lookahead_rank=32,
                    kernel_mode="ref", chips=8, cache_enabled=True,
                    seed=seed)
    defaults.update(kw)
    return EngineConfig(**defaults)


# ---------------------------------------------------------------------------
# Acceptance: served doc ids do not depend on replicas, routing or arrivals
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipe", ("hyde", "iter", "irg"))
def test_served_doc_ids_do_not_depend_on_replicas_or_arrivals(
        small_store, small_index, rng, pipe):
    """The same 6 requests served on one replica all at once, and on two
    replicas behind the cache-aware router in two staggered waves, ride
    different waves but retrieve the same doc ids in every round: the
    rewrite noise is keyed by request and round, and hybrid search is
    exact whatever is resident."""
    q = unit_queries(small_store, rng, 6)
    traces = make_traces(pipe, 6, seed=11)

    one = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"))
    at_once = one.serve([RagRequest(q=q[i], trace=traces[i])
                         for i in range(6)])
    two = TeleRAGServer(small_index, _cfg(), 2, get_arch("llama3-8b"),
                        scheduler=TeleRAGScheduler(), micro_batch=2)
    staggered = two.serve([RagRequest(q=q[i], trace=traces[i],
                                      arrival_t=0.003 * (i // 3))
                           for i in range(6)])
    assert {r.replica for r in at_once} == {0}
    assert {r.replica for r in staggered} == {0, 1}
    assert {r.rounds[0].batch for r in at_once} == {6}
    assert max(r.rounds[0].batch for r in staggered) <= 2
    for a, b in zip(at_once, staggered):
        assert a.request_id == b.request_id
        assert a.state == b.state == RequestState.COMPLETE
        assert len(a.rounds) == len(b.rounds)
        assert len(a.doc_ids) == len(b.doc_ids) > 0
        for got, want in zip(b.doc_ids, a.doc_ids):
            np.testing.assert_array_equal(got, want)


def test_static_group_execution_is_refused(small_index):
    with pytest.raises(ValueError, match="static-group execution"):
        TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                      continuous=False)


# ---------------------------------------------------------------------------
# Acceptance: staggered arrivals interleave replicas on ONE clock
# ---------------------------------------------------------------------------


def _long_gen_trace(request_id, gen_tokens=4000):
    return RequestTrace(pipeline="hyde", request_id=request_id,
                        stages=[StageTrace("generate", gen_tokens),
                                StageTrace("retrieve"),
                                StageTrace("generate", 8)],
                        rewrite_sigma=0.1)


def test_staggered_arrivals_interleave_replicas_on_shared_clock(
        small_store, small_index):
    """Wave B arrives mid-way through wave A's generation window and
    runs on the OTHER replica: B's spans must lie inside A's — overlap
    on the shared clock."""
    cfg = _cfg(seed=7, buffer_pages=512, cache_enabled=False)
    srv = TeleRAGServer(small_index, cfg, 2, get_arch("llama3-8b"))
    cents = small_index.centroids / np.linalg.norm(
        small_index.centroids, axis=-1, keepdims=True)
    qa = cents[:2].astype(np.float32)
    qb = cents[-2:].astype(np.float32)
    t_llm = srv.engines[0].llm_window_seconds(4000, 2)
    assert t_llm > 0
    mid = 0.5 * t_llm

    reqs = ([RagRequest(q=qa[i], trace=_long_gen_trace(i)) for i in range(2)]
            + [RagRequest(q=qb[i], trace=_long_gen_trace(10 + i),
                          arrival_t=mid) for i in range(2)])
    resp = srv.serve(reqs)
    a_resp, b_resp = resp[:2], resp[2:]
    assert all(r.state == RequestState.COMPLETE for r in resp)
    # round-robin routing: the two waves land on different replicas
    assert {r.replica for r in a_resp} == {0}
    assert {r.replica for r in b_resp} == {1}
    # B admitted at its true arrival time, while A was still running
    for b in b_resp:
        assert b.admit_t == pytest.approx(mid)
        assert b.queue_s == pytest.approx(0.0, abs=1e-9)
    assert all(a.complete_t > mid for a in a_resp)
    # cross-replica overlap as interval intersection on the one clock
    a_spans = [s for a in a_resp for s in a.timeline if s.end > s.start]
    b_spans = [s for b in b_resp for s in b.timeline if s.end > s.start]
    hits = [(sa, sb) for sa in a_spans for sb in b_spans
            if sa.overlaps(sb.start, sb.end)]
    assert hits, (a_spans, b_spans)
    # and replica-B work STARTED strictly inside a replica-A span
    assert any(sa.start < sb.start < sa.end for sa, sb in hits)


def test_latency_monotone_in_offered_load(small_store, small_index, rng):
    """Same request stream at shrinking inter-arrival spacing: the data
    ops are identical (same batches, same replicas), so arrival→complete
    latency can only grow with offered load — queueing is real."""
    q = unit_queries(small_store, rng, 6)
    means = []
    for spacing in (100.0, 0.01, 0.0):
        srv = TeleRAGServer(small_index, _cfg(seed=3, cache_enabled=False),
                            2, get_arch("llama3-8b"), micro_batch=1)
        traces = make_traces("hyde", 6, seed=9)
        resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                     arrival_t=i * spacing)
                          for i in range(6)])
        assert all(r.state == RequestState.COMPLETE for r in resp)
        means.append(float(np.mean([r.latency_s for r in resp])))
    assert means[0] <= means[1] + 1e-9 <= means[2] + 2e-9
    # saturation genuinely queues: simultaneous arrivals wait for slots
    assert means[2] > means[0]


# ---------------------------------------------------------------------------
# Submission-order guarantees
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("replicas", (1, 2, 3))
def test_server_drain_returns_submission_order(small_store, small_index,
                                               rng, replicas):
    """Later-submitted requests can arrive (and finish) earlier; the
    drain still answers in submission order."""
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("hyde", 4, seed=13)
    srv = TeleRAGServer(small_index, _cfg(cache_enabled=False), replicas,
                        get_arch("llama3-8b"), micro_batch=1)
    # reverse arrival order: request 0 arrives last
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                 arrival_t=(3 - i) * 0.5)
                      for i in range(4)])
    assert [r.request_id for r in resp] == [t.request_id for t in traces]
    assert resp[3].complete_t < resp[0].complete_t


# ---------------------------------------------------------------------------
# Replica health and the per-replica event stream
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("replicas,dead", ((2, 0), (2, 1), (3, 1)))
def test_dead_replica_is_routed_nothing_until_marked_alive(
        small_store, small_index, rng, replicas, dead):
    """No micro-batch is routed to, and no wave runs on, a replica
    marked dead: its share re-routes to the live ones and every request
    completes.  After ``mark_alive`` the router uses it again."""
    q = unit_queries(small_store, rng, 8)
    srv = TeleRAGServer(small_index, _cfg(), replicas,
                        get_arch("llama3-8b"), micro_batch=2)
    srv.mark_dead(dead)
    resp = srv.serve([RagRequest(q=q[i], trace=t) for i, t in
                      enumerate(make_traces("iter", 8, seed=29))])
    assert all(r.state == RequestState.COMPLETE for r in resp)
    assert {r.replica for r in resp} == set(range(replicas)) - {dead}
    assert srv.wave_log[0].requeued
    assert all(a[1] != dead for w in srv.wave_log for a in w.assignments)
    assert not srv.runtimes[dead].wave_log

    srv.mark_alive(dead)
    resp = srv.serve([RagRequest(q=q[i], trace=t) for i, t in
                      enumerate(make_traces("hyde", 8, seed=30))])
    assert all(r.state == RequestState.COMPLETE for r in resp)
    assert dead in {r.replica for r in resp}
    assert srv.runtimes[dead].wave_log


@pytest.mark.parametrize("replicas", (1, 2, 3))
def test_each_replica_lane_admits_and_completes_its_requests_once(
        small_store, small_index, rng, replicas):
    """In the server's one recorder, each replica's request events carry
    that replica's id, are stamped in non-decreasing event-clock time,
    and admit and complete every request the replica served exactly
    once; the server's own ``submit`` marks sit on lane -1."""
    q = unit_queries(small_store, rng, 6)
    traces = make_traces("iter", 6, seed=31)
    srv = TeleRAGServer(small_index, _cfg(), replicas,
                        get_arch("llama3-8b"), micro_batch=2)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                 arrival_t=0.002 * i) for i in range(6)])
    assert {r.replica for r in resp} == set(range(replicas))
    events = srv.recorder.of("request")
    for rep in range(replicas):
        served = sorted(r.request_id for r in resp if r.replica == rep)
        lane = [e for e in events if e.replica == rep]
        times = [e.t for e in lane]
        assert times == sorted(times)
        for label in ("admit", "complete"):
            assert sorted(e.request_id for e in lane
                          if e.label == label) == served
    assert {e.replica for e in events} == set(range(-1, replicas))
    assert sorted(e.request_id for e in events if e.replica == -1) \
        == sorted(t.request_id for t in traces)
    assert all(e.label == "submit" for e in events if e.replica == -1)


# ---------------------------------------------------------------------------
# Decode hook + unified telemetry
# ---------------------------------------------------------------------------


def test_decode_hook_fires_per_round_and_prefetch_dispatches_once(
        small_store, small_index, rng):
    """The serve drivers' real decode runs through the hook INSIDE the
    round frontier: prefetch is dispatched exactly once (by the policy),
    so H2D bytes match a hook-less run byte for byte — the legacy driver
    double-prefetched by calling eng.lookahead() manually first."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("iter", 3, seed=21)

    srv0 = TeleRAGServer(small_index, _cfg(seed=4), 1,
                         get_arch("llama3-8b"))
    srv0.serve([RagRequest(q=q[i], trace=traces[i]) for i in range(3)])
    baseline_h2d = srv0.engines[0].buffer.stats.bytes_h2d

    calls = []
    srv = TeleRAGServer(small_index, _cfg(seed=4), 1, get_arch("llama3-8b"),
                        decode_hook=lambda r, recs, toks, rnd:
                        calls.append((r, len(recs), tuple(toks), rnd)))
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i]) for i in range(3)])
    n_frontiers = max(len(r.rounds) for r in resp)
    assert len(calls) == n_frontiers
    assert [c[3] for c in calls] == list(range(n_frontiers))
    assert srv.engines[0].buffer.stats.bytes_h2d == baseline_h2d


def test_server_telemetry_unifies_replica_counters(small_store, small_index,
                                                   rng):
    q = unit_queries(small_store, rng, 6)
    traces = make_traces("hyde", 6, seed=17)
    srv = TeleRAGServer(small_index, _cfg(), 2, get_arch("llama3-8b"),
                        scheduler=TeleRAGScheduler(), micro_batch=3)
    srv.serve([RagRequest(q=q[i], trace=traces[i]) for i in range(6)])
    tele = srv.telemetry()
    assert tele.completed == 6
    assert tele.dispatched_batches >= 2
    assert tele.bytes_h2d == sum(e.buffer.stats.bytes_h2d
                                 for e in srv.engines)
    for rt, eng in zip(tele.replicas, srv.engines):
        assert rt.ledger == eng.ledger.snapshot()
        assert rt.admission == eng.admission.stats
        assert rt.admission is not eng.admission.stats   # a snapshot copy
        assert 0.0 <= rt.occupancy <= 1.0
        assert rt.transfers == len(eng.transfer.events)
    s = tele.summary()
    assert "server:" in s and "replica 0" in s and "replica 1" in s


def test_response_lifecycle_fields(small_store, small_index, rng):
    """RagResponse decomposes arrival→complete into queue + service and
    its breakdown sums the timeline spans; deadlines are stamped."""
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("hyde", 2, seed=19)
    srv = TeleRAGServer(small_index, _cfg(cache_enabled=False), 1,
                        get_arch("llama3-8b"), micro_batch=1)
    resp = srv.serve([
        RagRequest(q=q[0], trace=traces[0], deadline_s=1e-9),
        RagRequest(q=q[1], trace=traces[1], deadline_s=1e6)])
    for r in resp:
        assert r.latency_s == pytest.approx(r.queue_s + r.service_s)
        bd = r.breakdown()
        assert bd["queue"] == pytest.approx(r.queue_s)
        assert bd.get("generate", 0) > 0 and bd.get("retrieve", 0) > 0
    assert resp[0].deadline_missed and not resp[1].deadline_missed


def test_failed_drain_returns_undispatched_work_to_inbox(
        small_store, small_index, rng):
    """A drain that dies before dispatch (every replica dead) must not
    swallow the submitted requests: after recovery a retry serves them."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("hyde", 3, seed=23)
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"))
    srv.mark_dead(0)
    for i in range(3):
        srv.submit(RagRequest(q=q[i], trace=traces[i]))
    with pytest.raises(RuntimeError, match="no healthy replicas"):
        srv.drain()
    srv.mark_alive(0)
    resp = srv.drain()
    assert [r.request_id for r in resp] == [t.request_id for t in traces]
    assert all(r.state == RequestState.COMPLETE for r in resp)


def test_pipeline_name_synthesizes_trace(small_store, small_index, rng):
    q = unit_queries(small_store, rng, 2)
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"))
    resp = srv.serve([RagRequest(q=q[i], pipeline="hyde")
                      for i in range(2)])
    assert all(r.pipeline == "hyde" and len(r.rounds) == 1 for r in resp)
    with pytest.raises(ValueError):
        RagRequest(q=q[0])

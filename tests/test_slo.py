"""Multi-tenant SLO-aware serving: deadline-driven dispatch, per-tenant
pool reservations, and the per-tenant telemetry contract.

Pins the PR-4 acceptance criteria:
  * scheduling is demonstrably SLO-aware — the identical workload with
    swapped priorities produces a different served order
    AND a different deadline-miss count;
  * a tenant's guaranteed page floor is never violated by another
    tenant's burst (reservation accounting AND the spill path);
  * ``ServerTelemetry`` per-tenant deadline counters match the
    per-response ``deadline_missed`` / ``deadline_missed_in_queue``
    flags exactly;
  * a round whose members are already past deadline demotes its
    lookahead prefetch (no pool pages, no link bytes);
  * without deadlines the served order is priority class, then FIFO.

Admission onto a replica is immediate under per-request continuous
batching, so the served order is read from the wave each request rode
(round 0's ``round_start_t``, then its ``wave_id``), not from
``admit_t``.
"""

import numpy as np
import pytest

from repro.core.schedulers import (EdfDispatch, FifoDispatch,
                                   assign_to_replicas)
from repro.memory import AdmissionController, DevicePagePool
from repro.serving import (EngineConfig, RagRequest, RequestState,
                           TeleRAGEngine, TeleRAGServer, make_traces)
from repro.configs import get_arch
from tests.conftest import unit_queries


def _cfg(**kw):
    defaults = dict(nprobe=16, top_k=3, buffer_pages=200, lookahead_rank=32,
                    kernel_mode="ref", chips=8, cache_enabled=False, seed=5)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _solo_latency(small_index, q, trace):
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"))
    return srv.serve([RagRequest(q=q, trace=trace)])[0].latency_s


def _served_order(resp):
    """Request ids in the order their first round started."""
    return [r.request_id for r in sorted(
        resp, key=lambda r: (r.rounds[0].round_start_t,
                             r.rounds[0].wave_id))]


# ---------------------------------------------------------------------------
# Deadline/priority-aware dispatch ordering
# ---------------------------------------------------------------------------


def test_swapped_priorities_change_dispatch_order_and_miss_count(
        small_store, small_index, rng):
    """Two simultaneous requests, one replica, micro_batch=1: request A
    carries a deadline only one of them can meet (~1.5x solo service).
    When A outranks B its wave runs first and meets its deadline; the
    identical workload with priorities swapped runs B first and A
    misses — same data ops, different order, different miss count."""
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("hyde", 2, seed=31)
    solo_a = _solo_latency(small_index, q[0], traces[0])
    solo_b = _solo_latency(small_index, q[1], traces[1])
    # met when A serves first (~solo_a), missed when it waits behind B
    # (~solo_b + solo_a)
    deadline = solo_a + 0.5 * solo_b

    def serve(prio_a, prio_b):
        srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                            micro_batch=1)
        resp = srv.serve([
            RagRequest(q=q[0], trace=traces[0], priority=prio_a,
                       deadline_s=deadline),
            RagRequest(q=q[1], trace=traces[1], priority=prio_b)])
        assert all(r.state == RequestState.COMPLETE for r in resp)
        return resp, srv.telemetry()

    fast, tele_fast = serve(prio_a=0, prio_b=1)       # A outranks B
    slow, tele_slow = serve(prio_a=1, prio_b=0)       # swapped

    # different served order on the replica
    ids = [t.request_id for t in traces]
    assert _served_order(fast) == ids
    assert _served_order(slow) == ids[::-1]
    assert fast[0].rounds[0].round_start_t < fast[1].rounds[0].round_start_t
    assert slow[0].rounds[0].round_start_t > slow[1].rounds[0].round_start_t
    # ... and a different miss count for the identical workload
    assert not fast[0].deadline_missed
    assert slow[0].deadline_missed
    assert tele_fast.deadline_missed == 0
    assert tele_slow.deadline_missed == 1


def test_edf_orders_by_deadline_within_priority_class(
        small_store, small_index, rng):
    """Three same-priority requests in one wave, served one at a time:
    the tightest deadline is served first regardless of submission
    order.  The wave former orders the ready requests by (priority,
    deadline, arrival) itself, so ``FifoDispatch`` serves the same
    stream in the same order: the dispatch policy does not change it."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("hyde", 3, seed=37)
    deadlines = [30.0, 10.0, 20.0]          # submission order != EDF order

    def served_order(dispatch):
        srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                            micro_batch=1, dispatch=dispatch)
        resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                     deadline_s=deadlines[i])
                          for i in range(3)])
        assert all(r.admit_t == r.arrival_t for r in resp)
        return _served_order(resp)

    ids = [t.request_id for t in traces]
    assert served_order(EdfDispatch()) == [ids[1], ids[2], ids[0]]
    assert served_order(FifoDispatch()) == [ids[1], ids[2], ids[0]]


def test_default_dispatch_without_deadlines_is_priority_then_fifo_order(
        small_store, small_index, rng):
    """No deadlines anywhere: requests are served by priority class,
    then in submission order within a class."""
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("hyde", 4, seed=41)
    prios = [1, 0, 1, 0]
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                        micro_batch=1)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i], priority=prios[i])
                      for i in range(4)])
    want = [traces[i].request_id for i in (1, 3, 0, 2)]  # prio, then FIFO
    assert _served_order(resp) == want


# ---------------------------------------------------------------------------
# Per-tenant pool reservations
# ---------------------------------------------------------------------------


def test_tenant_floor_survives_other_tenants_burst(small_index):
    """Reservation accounting: tenant B bursting to everything it can
    see must leave tenant A's unclaimed floor reservable."""
    pool = DevicePagePool(small_index.paged, 32)
    pool.set_tenant_share("lat", floor_pages=8)
    # B can never see A's unclaimed floor
    assert pool.reservable_pages_for("batch") == 24
    assert pool.reserve(25, "burst", tenant="batch") is None
    res_b = pool.reserve(24, "burst", tenant="batch")
    assert res_b is not None
    assert pool.reservable_pages_for("batch") == 0
    # A's floor is still fully claimable, burst or no burst
    assert pool.reservable_pages_for("lat") == 8
    res_a = pool.reserve(8, "floor", tenant="lat")
    assert res_a is not None
    # with the floor claimed, nothing is withheld anymore
    assert pool.withheld_floor_pages("batch") == 0


def test_tenant_burst_cap_bounds_total_hold(small_index):
    """max_pages caps a tenant's leases+reservations even when the pool
    has free pages left."""
    pool = DevicePagePool(small_index.paged, 32)
    pool.set_tenant_share("batch", floor_pages=0, max_pages=12)
    lease = pool.lease_slots(8, "prefetch", tenant="batch")
    assert lease is not None
    assert pool.reservable_pages_for("batch") == 4
    assert pool.reserve(5, "b2", tenant="batch") is None
    assert pool.reserve(4, "b2", tenant="batch") is not None
    # an uncapped tenant still sees the remaining free pages
    assert pool.reservable_pages_for("other") == 32 - 12


def test_request_above_tenant_ceiling_caps_instead_of_parking(
        small_index):
    """A plan that exceeds what the tenant could EVER reserve (its
    burst cap) must take a capped grant immediately — parking would
    starve it on page-free retries no future free can satisfy — while
    a reachable request under the same pressure still parks."""
    pool = DevicePagePool(small_index.paged, 32)
    pool.set_tenant_share("batch", floor_pages=0, max_pages=12)
    adm = AdmissionController(pool)
    # a KV lease creates pressure AND a future page-free event
    kv = pool.lease_bytes(24 * pool.page_nbytes, "kv")
    assert kv is not None and adm.holds_pending_release()
    # reachable (10 <= cap 12) but blocked: parks as before
    assert adm.admit(10, "w1", can_wait=True, tenant="batch") is None
    # unreachable (20 > cap 12): caps NOW with everything available
    t = adm.admit(20, "w2", can_wait=True, tenant="batch")
    assert t is not None and t.capped
    assert t.pages_granted == min(pool.free_pages(), 12)
    assert adm.per_tenant["batch"].capped == 1


def test_spill_never_evicts_under_floor_tenants_residency(
        small_store, small_index):
    """The admission spill path: tenant "batch" needs room, tenant
    "lat" holds residency at/below its floor — spill must take its
    victims from the over-floor tenant only."""
    cfg = _cfg(buffer_pages=40, pool_pages=40, cache_enabled=True,
               tenant_shares={"lat": (10, None)})
    eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
    paged = small_index.paged
    # residency: "lat" holds a few clusters (<= floor), "batch" many
    lat_clusters, batch_clusters, pages = [], [], 0
    for c in range(small_index.num_clusters):
        npg = int(paged.cluster_num_pages[c])
        if pages + npg > 36:
            break
        tenant = "lat" if eng.pool.tenant_pages("lat") + npg <= 10 \
            else "batch"
        res = eng.pool.reserve(npg, f"c{c}", tenant=tenant)
        if res is None:
            break
        loaded, _ = eng.buffer.load_clusters([c], reservation=res)
        eng.pool.cancel(res)
        assert loaded == [c]
        eng.cache.on_fetched([c])
        (lat_clusters if tenant == "lat" else batch_clusters).append(c)
        pages += npg
    assert lat_clusters and batch_clusters
    lat_before = set(lat_clusters) & eng.buffer.resident_clusters()
    assert eng.pool.tenant_pages("lat") <= 10

    # batch asks for more than is free -> admission must spill
    ticket = eng.admission.admit(eng.pool.free_pages() + 4, "burst",
                                 can_wait=False, tenant="batch")
    assert ticket.spilled_pages > 0 or ticket.capped
    # every "lat" cluster is still resident; victims came from "batch"
    assert lat_before <= eng.buffer.resident_clusters()
    assert set(batch_clusters) - eng.buffer.resident_clusters()


def test_spill_stops_at_an_over_floor_tenants_floor(small_store,
                                                    small_index):
    """An over-floor tenant exposes only its excess as spill victims:
    eviction on another tenant's behalf never pulls it below its
    guaranteed floor (the protect set is per-page, not all-or-nothing)."""
    cfg = _cfg(buffer_pages=40, pool_pages=40, cache_enabled=True,
               tenant_shares={"lat": (6, None)})
    eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
    paged = small_index.paged
    # "lat" bursts OVER its 6-page floor
    pages = 0
    for c in range(small_index.num_clusters):
        npg = int(paged.cluster_num_pages[c])
        if pages + npg > 16:
            break
        res = eng.pool.reserve(npg, f"c{c}", tenant="lat")
        if res is None:
            break
        loaded, _ = eng.buffer.load_clusters([c], reservation=res)
        eng.pool.cancel(res)
        assert loaded == [c]
        eng.cache.on_fetched([c])
        pages += npg
    assert eng.pool.tenant_pages("lat") > 6
    # batch demands everything: spill may take lat's excess, not floor
    eng.admission.admit(eng.pool.num_pages, "burst", can_wait=False,
                        tenant="batch")
    assert eng.pool.tenant_pages("lat") >= 6
    # the O(1) running counters agree with a full scan after the churn
    for t in ("lat", "batch"):
        slow = (sum(l.num_pages for l in eng.pool.leases.values()
                    if l.tenant == t)
                + sum(r.pages for r in eng.pool.reservations.values()
                      if r.tenant == t))
        assert eng.pool.tenant_pages(t) == slow


def test_snapshot_restore_carries_per_tenant_admission_stats(
        small_index):
    """Replica restart must not zero the per-tenant admission slices
    (the PR-3 aggregate-stats guarantee, extended to tenants)."""
    cfg = _cfg(tenant_shares={"lat": (8, None)})
    eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
    eng.admission.admit(4, "w", can_wait=False, tenant="lat")
    before = dict(eng.admission.per_tenant)
    assert before["lat"].admitted == 1
    eng.restore(eng.snapshot())
    assert eng.admission.per_tenant == before
    assert eng.admission.per_tenant["lat"].admitted == 1


# ---------------------------------------------------------------------------
# Telemetry contract
# ---------------------------------------------------------------------------


def test_tenant_telemetry_counters_match_response_flags(
        small_store, small_index, rng):
    """Per-tenant deadline counters are exactly the sums of the
    per-response flags, and the attainment/miss-in-service identities
    hold."""
    q = unit_queries(small_store, rng, 8)
    traces = make_traces("hyde", 8, seed=47)
    srv = TeleRAGServer(small_index, _cfg(), 2, get_arch("llama3-8b"),
                        micro_batch=1)
    solo = _solo_latency(small_index, q[0], traces[0])
    reqs = []
    for i in range(8):
        tenant = "lat" if i % 2 == 0 else "batch"
        # tight deadlines on the lat tenant guarantee a mix of hits+misses
        deadline = solo * (0.5 if i in (0, 2) else 20.0) \
            if tenant == "lat" else None
        reqs.append(RagRequest(q=q[i], trace=traces[i], tenant=tenant,
                               deadline_s=deadline, arrival_t=0.001 * i))
    resp = srv.serve(reqs)
    tele = srv.telemetry()
    assert {t.tenant for t in tele.tenants} == {"lat", "batch"}
    for name in ("lat", "batch"):
        sub = [r for r in resp if r.tenant == name]
        t = tele.tenant(name)
        assert t.completed == len(sub)
        assert t.deadline_missed == sum(r.deadline_missed for r in sub)
        assert t.missed_in_queue == sum(r.deadline_missed_in_queue
                                        for r in sub)
        assert t.with_deadline == sum(r.deadline_s is not None for r in sub)
        assert t.missed_in_service == t.deadline_missed - t.missed_in_queue
        if t.with_deadline:
            assert t.attainment == pytest.approx(
                1.0 - t.deadline_missed / t.with_deadline)
    assert tele.tenant("lat").deadline_missed >= 1   # the tight ones
    assert tele.deadline_missed == sum(r.deadline_missed for r in resp)
    # a missed-in-queue response is by definition also missed overall
    for r in resp:
        if r.deadline_missed_in_queue:
            assert r.deadline_missed
    # tenant lines show up in the printable summary
    s = tele.summary()
    assert "tenant lat:" in s and "tenant batch:" in s


def test_missed_in_queue_distinguished_from_missed_in_service(
        small_store, small_index, rng):
    """Missed-in-queue means the deadline passed before ``admit_t``.
    Per-request continuous batching admits every request onto its
    replica at arrival, so a request whose deadline expires while it
    waits for a wave (here: behind the pool pages its predecessors'
    waves hold) reports missed-in-service, its wait showing as a
    ``pressure_stall`` span; one that starts in time and finishes late
    reports missed-in-service too."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("hyde", 3, seed=53)
    solo = [_solo_latency(small_index, q[i], traces[i]) for i in range(3)]
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                        micro_batch=1)
    deadline_1 = solo[0] + 0.5 * solo[1]
    deadline_2 = 0.5 * solo[0]
    resp = srv.serve([
        RagRequest(q=q[0], trace=traces[0]),                 # head of line
        # starts in time (after request 0) but expires mid-service
        RagRequest(q=q[1], trace=traces[1], priority=1,
                   deadline_s=deadline_1),
        # expires while still waiting behind requests 0 and 1
        RagRequest(q=q[2], trace=traces[2], priority=2,
                   deadline_s=deadline_2)])
    assert _served_order(resp) == [t.request_id for t in traces]
    assert all(r.admit_t == r.arrival_t for r in resp)
    assert resp[1].rounds[0].round_start_t < deadline_1
    assert resp[1].deadline_missed and not resp[1].deadline_missed_in_queue
    assert resp[2].rounds[0].round_start_t > deadline_2
    assert resp[2].stall_s == pytest.approx(
        resp[2].rounds[0].round_start_t)
    assert resp[2].deadline_missed and not resp[2].deadline_missed_in_queue
    t = srv.telemetry().tenant("shared")
    assert t.deadline_missed == 2
    assert t.missed_in_queue == 0
    assert t.missed_in_service == 2


# ---------------------------------------------------------------------------
# Slack-based prefetch demotion
# ---------------------------------------------------------------------------


def test_past_deadline_rounds_demote_prefetch(small_store, small_index,
                                              rng):
    """A multi-round request already past its deadline stops spending
    pool pages and link bytes on lookahead: later rounds demote, H2D
    drops below the no-deadline run, and results stay identical."""
    q = unit_queries(small_store, rng, 1)
    traces = make_traces("iter", 1, seed=59)         # multi-round pipeline
    assert len([s for s in traces[0].stages if s.kind == "retrieve"]) >= 2

    def serve(deadline):
        srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"))
        resp = srv.serve([RagRequest(q=q[0], trace=traces[0],
                                     deadline_s=deadline)])
        return resp[0], srv

    free_run, srv_free = serve(None)
    doomed, srv_doomed = serve(1e-9)                 # past-deadline at once
    assert doomed.state == RequestState.COMPLETE
    assert doomed.demoted_rounds >= 1
    assert free_run.demoted_rounds == 0
    # demoted rounds move no prefetch bytes
    assert (srv_doomed.engines[0].buffer.stats.bytes_h2d
            < srv_free.engines[0].buffer.stats.bytes_h2d)
    # retrieval results are unchanged — misses just route to host search
    for got, want in zip(doomed.doc_ids, free_run.doc_ids):
        np.testing.assert_array_equal(got, want)
    assert srv_doomed.telemetry().tenant("shared").demoted_rounds \
        == doomed.demoted_rounds


# ---------------------------------------------------------------------------
# Routing reads per-tenant occupancy
# ---------------------------------------------------------------------------


def test_assign_tie_breaks_away_from_tenant_loaded_replica():
    """Equal overlap, equal ledger occupancy: the batch routes to the
    replica where its tenant holds the least pool share."""
    out = assign_to_replicas([set()], [set(), set()],
                             occupancy=[0.5, 0.5],
                             tenant_occupancy=[[0.9, 0.1]])
    assert out[0].replica == 1
    # ledger occupancy still dominates tenant spreading
    out = assign_to_replicas([set()], [set(), set()],
                             occupancy=[0.2, 0.8],
                             tenant_occupancy=[[0.9, 0.0]])
    assert out[0].replica == 0

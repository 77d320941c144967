"""Per-request continuous batching: dynamic wave forming, straggler
isolation, mid-stream admission, pressure-park rejoin, decode events
driving the clock, decode-only requests, per-tenant KV accounting,
and KV-pressure shedding."""

import numpy as np
import pytest

from repro.configs import get_arch
from repro.serving import (DecodeEvent, EngineConfig, KVCacheManager,
                           RagRequest, RequestState, RetrievalRuntime,
                           TeleRAGEngine, TeleRAGServer, make_traces)
from repro.serving.trace import RequestTrace, StageTrace
from tests.conftest import unit_queries


def _cfg(seed=5, **kw):
    defaults = dict(nprobe=16, top_k=3, buffer_pages=256, lookahead_rank=32,
                    kernel_mode="ref", chips=8, seed=seed)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _engine(small_index, **kw):
    return TeleRAGEngine(small_index, _cfg(**kw), get_arch("llama3-8b"))


def _two_round_trace(request_id, gen0, gen1=64, sigma=0.0):
    """Two retrieval rounds with controllable window lengths (sigma=0
    keeps query drift deterministic across wave compositions)."""
    return RequestTrace(
        pipeline="iter", request_id=request_id,
        stages=[StageTrace("generate", gen0), StageTrace("retrieve"),
                StageTrace("generate", gen1), StageTrace("retrieve"),
                StageTrace("generate", 8)],
        rewrite_sigma=sigma)


# ---------------------------------------------------------------------------
# Acceptance: straggler isolation — a slow batch-mate never drags the
# fast request's next round
# ---------------------------------------------------------------------------


def _run_straggler(small_index, q):
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng)
    slow = runtime.submit(q[0], _two_round_trace(0, gen0=4000))
    fast = runtime.submit(q[1], _two_round_trace(1, gen0=64))
    runtime.run()
    assert slow.state == fast.state == RequestState.COMPLETE
    return eng, runtime, slow, fast


def test_straggler_isolation_fast_request_reforms_alone(
        small_store, small_index, rng):
    """Request B (fast) starts — and here even finishes — its round 1
    before slow batch-mate A finishes round 0: wave re-forming runs B's
    round 1 in a wave of its own the moment B is ready."""
    q = unit_queries(small_store, rng, 2)
    eng, runtime, slow, fast = _run_straggler(small_index, q)

    slow_r0_end = slow.result.rounds[0].round_end_t
    fast_r1 = fast.result.rounds[1]
    # B's round 1 started (and was wave-formed) before A finished round 0
    assert fast_r1.round_start_t < slow_r0_end
    # ... in a wave WITHOUT the straggler: its decode batch is 1
    assert fast_r1.batch == 1
    w = next(w for w in runtime.wave_log if w.wid == fast_r1.wave_id)
    assert w.request_ids == (fast.request_id,)
    assert w.t == pytest.approx(fast_r1.round_start_t)
    # round 0 DID batch them together (same arrival instant)
    assert fast.result.rounds[0].batch == 2
    assert fast.result.rounds[0].wave_id == slow.result.rounds[0].wave_id
    # the straggler's own round 1 runs later, in its own wave
    assert slow.result.rounds[1].wave_id != fast_r1.wave_id
    assert slow.result.rounds[1].round_start_t > fast_r1.round_start_t


# ---------------------------------------------------------------------------
# Acceptance: mid-stream admission joins an in-flight wave
# ---------------------------------------------------------------------------


def test_midstream_admission_joins_inflight_wave(small_store, small_index,
                                                 rng):
    """A request arriving exactly at a round frontier is wave-formed
    WITH the in-flight requests' next rounds — mixed round indices in
    one wave."""
    q = unit_queries(small_store, rng, 3)
    # probe run: find the (deterministic) round-1 frontier time
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng)
    a = runtime.submit(q[0], _two_round_trace(0, gen0=256))
    b = runtime.submit(q[0], _two_round_trace(1, gen0=256))   # same q/trace
    runtime.run()
    t1 = a.result.rounds[1].round_start_t
    assert t1 == b.result.rounds[1].round_start_t             # same frontier

    # live run: C arrives exactly when A and B become ready for round 1
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng)
    a = runtime.submit(q[0], _two_round_trace(0, gen0=256))
    b = runtime.submit(q[0], _two_round_trace(1, gen0=256))
    c = runtime.submit(q[2], _two_round_trace(2, gen0=64), arrival_t=t1)
    runtime.run()
    assert c.admit_t == pytest.approx(t1)
    joined = c.result.rounds[0]
    assert joined.batch == 3                    # C decodes WITH a and b
    w = next(w for w in runtime.wave_log if w.wid == joined.wave_id)
    assert sorted(w.request_ids) == [0, 1, 2]
    assert sorted(w.rounds) == [0, 1, 1]        # mixed round indices
    assert a.result.rounds[1].wave_id == joined.wave_id


# ---------------------------------------------------------------------------
# Acceptance: a pressure-parked request rejoins a freshly-formed wave
# on wake (its ex-batch-mates were never stalled)
# ---------------------------------------------------------------------------


def test_parked_request_rejoins_wave_on_wake(small_store, small_index, rng):
    pages_per_cluster = float(np.mean(small_index.paged.cluster_num_pages))
    eng = TeleRAGEngine(
        small_index,
        EngineConfig(nprobe=12, top_k=3,
                     buffer_pages=int(6 * pages_per_cluster),
                     lookahead_rank=16, kernel_mode="ref", chips=8, seed=3),
        get_arch("llama3-8b"))
    runtime = RetrievalRuntime(eng, micro_batch=2)
    cents = small_index.centroids / np.linalg.norm(
        small_index.centroids, axis=-1, keepdims=True)
    q = np.concatenate([cents[:2], cents[-2:]]).astype(np.float32)
    traces = make_traces("hyde", 4, seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(4)]
    runtime.run()

    assert all(r.state == RequestState.COMPLETE for r in recs)
    assert not eng.admission.parked
    stalled = [r for r in recs if r.spans("pressure_stall")]
    assert stalled, "the pool pressure never parked anyone"
    clean = [r for r in recs if not r.spans("pressure_stall")]
    assert clean, "everyone stalled — no isolation to show"
    # the stall was fully isolated to the pressured wave: the clean
    # requests ran unstalled, and the park lifted exactly when one of
    # them completed and freed its per-request pins (fine-grained
    # release — not "when the whole ex-wave drained")
    first_resume = min(s.end for r in stalled
                       for s in r.spans("pressure_stall"))
    assert min(r.complete_t for r in clean) <= first_resume + 1e-12
    assert any(abs(first_resume - r.complete_t) < 1e-9 for r in clean)
    for r in stalled:
        # the resumed request rode a wave formed AT its wake-up time
        resume = r.spans("pressure_stall")[0].end
        rt0 = r.result.rounds[0]
        assert rt0.round_start_t == pytest.approx(resume)
        w = next(w for w in runtime.wave_log if w.wid == rt0.wave_id)
        assert w.t == pytest.approx(resume)
        assert r.request_id in w.request_ids


# ---------------------------------------------------------------------------
# Decode events drive the event clock
# ---------------------------------------------------------------------------


def test_decode_events_replace_modeled_generation_windows(
        small_store, small_index, rng):
    """When the decode hook returns per-request DecodeEvents, each
    request's generation window on the event clock is the OBSERVED
    decode time (extrapolated per-step), not the hardware model's."""
    per_tok = 1e-3
    calls = []

    def hook(records, gen_tokens, rnd):
        calls.append(tuple(r.request_id for r in records))
        # "observed": half the steps ran, at per_tok seconds each
        return [DecodeEvent(request_id=r.request_id,
                            tokens=max(1, g // 2),
                            seconds=per_tok * max(1, g // 2))
                for r, g in zip(records, gen_tokens)]

    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng, on_generate=hook)
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("iter", 2, seed=7)
    recs = [runtime.submit(q[i], traces[i]) for i in range(2)]
    runtime.run()
    assert calls
    for rec in recs:
        assert rec.state == RequestState.COMPLETE
        for rt in rec.result.rounds:
            # extrapolated to the full window at the observed rate
            assert rt.t_llm_window == pytest.approx(per_tok * rt.gen_tokens)
            model = eng.llm_window_seconds(rt.gen_tokens, rt.batch)
            assert rt.t_llm_window != pytest.approx(model)

    # an event with zero observed steps (nothing to decode for that
    # member's window) must fall back to the MODELED window, not erase
    # the generation time — regression: a [retrieve, generate] trace
    # has a 0-token round window but a real tail
    eng3 = _engine(small_index)
    runtime3 = RetrievalRuntime(
        eng3, include_tail=True,
        on_generate=lambda recs, toks, rnd: [
            DecodeEvent(r.request_id, tokens=0, seconds=0.0)
            for r in recs])
    trace = RequestTrace(pipeline="irg", request_id=0,
                         stages=[StageTrace("retrieve"),
                                 StageTrace("generate", 96)],
                         rewrite_sigma=0.0)
    rec3 = runtime3.submit(unit_queries(small_store, rng, 1)[0], trace)
    runtime3.run()
    tail = rec3.spans("generate_tail")
    assert tail and tail[0].end - tail[0].start == pytest.approx(
        eng3.llm_window_seconds(96, 1))

    # a hook returning None keeps the modeled windows (back-compat)
    eng2 = _engine(small_index)
    runtime2 = RetrievalRuntime(eng2,
                                on_generate=lambda recs, toks, rnd: None)
    recs2 = [runtime2.submit(q[i], t)
             for i, t in enumerate(make_traces("iter", 2, seed=7))]
    runtime2.run()
    for rec in recs2:
        for rt in rec.result.rounds:
            assert rt.t_llm_window == pytest.approx(
                eng2.llm_window_seconds(rt.gen_tokens, rt.batch))


# ---------------------------------------------------------------------------
# Regression: decode-only traces ride the normal per-request path
# ---------------------------------------------------------------------------


def _decode_only_trace(request_id, gen=64):
    return RequestTrace(pipeline="hyde", request_id=request_id,
                        stages=[StageTrace("generate", gen)],
                        rewrite_sigma=0.0)


def test_decode_only_requests_complete_with_their_window(
        small_store, small_index, rng):
    """A trace with zero retrieval rounds is a decode-only request on
    the normal path (no special-case admit branch): it completes after
    its generation window (include_tail) instead of instantaneously,
    and it joins the decode batch like anyone."""
    q = unit_queries(small_store, rng, 2)
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng, include_tail=True)
    normal = runtime.submit(q[0], make_traces("hyde", 1, seed=3)[0])
    dec = runtime.submit(q[1], _decode_only_trace(99))
    runtime.run()
    assert dec.state == RequestState.COMPLETE
    assert not dec.result.rounds and not dec.result.doc_ids
    tail = dec.spans("generate_tail")
    assert len(tail) == 1
    assert dec.complete_t == pytest.approx(tail[0].end)
    assert dec.complete_t > dec.admit_t
    assert normal.state == RequestState.COMPLETE
    # it was wave-formed together with the normal request
    w = runtime.wave_log[0]
    assert sorted(w.request_ids) == sorted([normal.request_id, 99])


def test_decode_only_wavemate_survives_a_pressure_park(small_store,
                                                       small_index, rng):
    """A decode-only request wave-formed next to retrieval requests
    whose admission parks must NOT be swallowed by the park: it needs
    no pool pages, so it runs (as its own wave) and completes while its
    retrieval wave-mates sit PRESSURE_STALLED."""
    pages_per_cluster = float(np.mean(small_index.paged.cluster_num_pages))
    eng = TeleRAGEngine(
        small_index,
        EngineConfig(nprobe=12, top_k=3,
                     buffer_pages=int(6 * pages_per_cluster),
                     lookahead_rank=16, kernel_mode="ref", chips=8, seed=3),
        get_arch("llama3-8b"))
    runtime = RetrievalRuntime(eng, include_tail=True)
    cents = small_index.centroids / np.linalg.norm(
        small_index.centroids, axis=-1, keepdims=True)
    mid = 1e-5                         # while wave A is still in flight
    a = [runtime.submit(cents[i].astype(np.float32),
                        make_traces("hyde", 2, seed=5)[i]) for i in range(2)]
    b = runtime.submit(cents[-1].astype(np.float32),
                       make_traces("hyde", 3, seed=5)[2], arrival_t=mid)
    dec = runtime.submit(unit_queries(small_store, rng, 1)[0],
                         _decode_only_trace(50), arrival_t=mid)
    runtime.run()
    assert all(r.state == RequestState.COMPLETE for r in a + [b, dec])
    assert b.spans("pressure_stall"), "b never parked — no pressure"
    # the decode-only wave-mate ran through the park, unstalled
    assert not dec.spans("pressure_stall")
    assert dec.complete_t < b.spans("pressure_stall")[0].end


def test_decode_only_without_tail_completes_at_admit(small_store,
                                                     small_index, rng):
    q = unit_queries(small_store, rng, 1)
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng, include_tail=False)
    dec = runtime.submit(q[0], _decode_only_trace(5))
    runtime.run()
    assert dec.state == RequestState.COMPLETE
    assert dec.complete_t == pytest.approx(dec.admit_t)


# ---------------------------------------------------------------------------
# The wave former: EDF / tenant-purity / caps (SchedulerPolicy hook)
# ---------------------------------------------------------------------------


def test_reform_wave_default_is_edf_tenant_pure_and_capped():
    from repro.core.schedulers import SchedulerPolicy

    class R:
        def __init__(self, tenant, priority=0, deadline_t=float("inf")):
            self.tenant, self.priority, self.deadline_t = (tenant, priority,
                                                           deadline_t)

    ready = [R("a", deadline_t=3.0), R("b"), R("a", deadline_t=1.0),
             R("a", priority=-1), R("b"), R("a")]
    waves = SchedulerPolicy().reform_wave(ready, micro_batch=2)
    # every request placed exactly once
    placed = sorted(i for w in waves for i in w)
    assert placed == list(range(len(ready)))
    # tenant-pure waves, capped at 2
    for w in waves:
        assert len({ready[i].tenant for i in w}) == 1
        assert len(w) <= 2
    # priority class first, then EDF: request 3 leads the first wave,
    # then tenant a's deadline holders in deadline order
    assert waves[0][0] == 3
    a_order = [i for w in waves for i in w if ready[i].tenant == "a"]
    assert a_order == [3, 2, 0, 5]

    # no micro_batch cap => one wave per tenant
    waves = SchedulerPolicy().reform_wave(ready)
    assert len(waves) == 2


def test_deferring_former_cannot_livelock_the_drain(small_store,
                                                    small_index, rng):
    """A custom former that always defers lone requests (waiting for a
    batch-mate that never comes) must not hang run(): the forced
    frontier places deferred requests with the base former."""
    from repro.core.schedulers import SchedulerPolicy

    class WaitForPair(SchedulerPolicy):
        def reform_wave(self, ready, *, micro_batch=None, now=0.0):
            waves = super().reform_wave(ready, micro_batch=2, now=now)
            return [w for w in waves if len(w) >= 2]   # defer singletons

    q = unit_queries(small_store, rng, 3)
    eng = _engine(small_index)
    runtime = RetrievalRuntime(eng, scheduler=WaitForPair())
    traces = make_traces("hyde", 3, seed=3)
    recs = [runtime.submit(q[i], traces[i]) for i in range(3)]
    runtime.run()                                      # must terminate
    assert all(r.state == RequestState.COMPLETE for r in recs)


def test_continuous_server_forwards_scheduler_as_wave_former(
        small_store, small_index, rng):
    """A custom SchedulerPolicy.reform_wave override drives the replica
    runtimes' wave forming under continuous dispatch."""
    from repro.core.schedulers import TeleRAGScheduler

    calls = []

    class Spy(TeleRAGScheduler):
        def reform_wave(self, ready, *, micro_batch=None, now=0.0):
            calls.append(len(ready))
            return super().reform_wave(ready, micro_batch=micro_batch,
                                       now=now)

    q = unit_queries(small_store, rng, 4)
    srv = TeleRAGServer(small_index, _cfg(), 1, get_arch("llama3-8b"),
                        scheduler=Spy(), micro_batch=2)
    resp = srv.serve([RagRequest(q=q[i], pipeline="hyde")
                      for i in range(4)])
    assert all(r.state == RequestState.COMPLETE for r in resp)
    assert calls, "the custom former never ran"


# ---------------------------------------------------------------------------
# Per-tenant KV accounting (satellite): leases tag the owning tenant
# ---------------------------------------------------------------------------


def test_kv_leases_carry_tenant_bytes_on_ledger(small_index):
    arch = get_arch("llama3-8b").reduced()
    eng = TeleRAGEngine(
        small_index,
        _cfg(buffer_pages=64, pool_pages=1024,
             tenant_shares={"a": (8, None), "b": (8, None)}),
        get_arch("llama3-8b"))
    kv = KVCacheManager(arch, pool=eng.pool)
    lease = kv.acquire(2, 64, tenant="a")
    nb = lease.nbytes
    assert nb > 0
    assert eng.ledger.tenant_bytes("a") == nb
    assert eng.ledger.snapshot()["tenant:a"] == nb
    assert eng.pool.tenant_bytes("a", owner="kv") == nb
    assert eng.pool.tenant_pages("a") == lease.page_lease.num_pages

    # recycling re-attributes the bucket to whoever reuses it
    kv.release(lease)
    assert eng.ledger.tenant_bytes("a") == nb      # bytes stay resident
    lease_b = kv.acquire(2, 64, tenant="b")
    assert eng.ledger.tenant_bytes("a") == 0
    assert eng.ledger.tenant_bytes("b") == nb
    assert eng.pool.tenant_bytes("b", owner="kv") == nb
    kv.release(lease_b)
    kv.drop_all()
    assert eng.ledger.tenant_bytes("b") == 0
    assert eng.ledger.bytes_of("kv") == 0


def test_server_telemetry_surfaces_tenant_kv_bytes(small_store, small_index,
                                                   rng):
    q = unit_queries(small_store, rng, 2)
    arch = get_arch("llama3-8b")
    holder = {}

    def decode_hook(replica, records, gen_tokens, rnd):
        if "kv" not in holder:
            holder["kv"] = KVCacheManager(arch.reduced(),
                                          pool=srv.engines[replica].pool)
        kv = holder["kv"]
        lease = kv.acquire(len(records), 32, tenant=records[0].tenant)
        kv.release(lease)
        holder["nbytes"] = lease.nbytes

    srv = TeleRAGServer(
        small_index,
        _cfg(buffer_pages=64, pool_pages=2048,
             tenant_shares={"a": (8, None)}),
        1, arch, decode_hook=decode_hook)
    resp = srv.serve([RagRequest(q=q[i], pipeline="hyde", tenant="a")
                      for i in range(2)])
    assert all(r.state == RequestState.COMPLETE for r in resp)
    tele = srv.telemetry().tenant("a")
    assert tele is not None
    # the recycled bucket's live lease is attributed to tenant "a"
    assert tele.kv_bytes == holder["nbytes"]
    assert srv.engines[0].ledger.tenant_bytes("a") >= holder["nbytes"]


# ---------------------------------------------------------------------------
# The continuous server: mid-stream dispatch + per-request completions
# ---------------------------------------------------------------------------


def test_continuous_server_completes_all_and_counts_per_request(
        small_store, small_index, rng):
    """Heterogeneous round counts, staggered arrivals, two replicas:
    every request completes, responses stay in submission order, and
    telemetry counts completions per request (not per batch drain)."""
    q = unit_queries(small_store, rng, 8)
    traces = (make_traces("hyde", 4, seed=3)
              + make_traces("iter", 4, seed=4))
    srv = TeleRAGServer(small_index, _cfg(seed=3), 2,
                        get_arch("llama3-8b"), micro_batch=2)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                 arrival_t=0.005 * (i % 3))
                      for i in range(8)])
    assert [r.request_id for r in resp] == [t.request_id for t in traces]
    assert all(r.state == RequestState.COMPLETE for r in resp)
    tele = srv.telemetry()
    assert tele.completed == 8
    assert tele.dispatched_batches >= 2


# ---------------------------------------------------------------------------
# KV-slab exhaustion mid-decode: shed what fits, park the rest
# ---------------------------------------------------------------------------


def _paged_hook(kv, max_len=32, steps=4):
    """A decode hook on the real paged KV API (lease accounting only —
    no model compute): ``acquire_paged`` raises ``PoolExhausted`` when
    the wave's block tables do not fit the slab."""
    calls = []

    def hook(records, gen_tokens, rnd):
        calls.append(len(records))
        lease = kv.acquire_paged(len(records), max_len,
                                 tenant=records[0].tenant)
        try:
            for _ in range(steps):
                kv.append_paged(lease)
        finally:
            kv.release_paged(lease)
        return [DecodeEvent(request_id=r.request_id, tokens=steps,
                            seconds=0.0) for r in records]

    return hook, calls


def test_kv_slab_exhaustion_mid_decode_parks_and_rejoins(
        small_store, small_index, rng):
    """``acquire_paged`` failing at a round frontier is an admission
    decision, not a hook crash: the runtime sheds the older half of the
    wave (re-executed immediately at the smaller batch), parks the
    younger half ``PRESSURE_STALLED``, and the parked members rejoin on
    the page-free event the running half's ``release_paged`` fires —
    every request completes, nothing raises out of ``run()``."""
    eng = _engine(small_index, pool_pages=4096)
    kv = KVCacheManager(get_arch("llama3-8b").reduced(), pool=eng.pool)
    # room for exactly TWO max_len=32 block tables (4 pages each)
    kv.init_paged(num_pages=8, page_size=8)
    hook, calls = _paged_hook(kv)
    runtime = RetrievalRuntime(eng, include_tail=True, on_generate=hook)
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("hyde", 4, seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(4)]
    runtime.run()
    assert all(r.state == RequestState.COMPLETE for r in recs)
    # the 4-wave could never fit: it shed, and everything that actually
    # decoded did so at a batch the slab can hold
    assert calls[0] == 4
    assert all(c <= 2 for c in calls[1:])
    marks = [getattr(e, "label", "") for e in runtime.recorder.events
             if getattr(e, "kind", "") == "request"]
    assert "pressure_stall" in marks, "no member ever parked"
    assert "pressure_resume" in marks, "parked members never rejoined"
    # the parked members' rounds produced results like everyone else's
    for r in recs:
        assert r.result.doc_ids


def test_kv_pool_shortfall_spills_cold_prefetch_and_retries(
        small_store, small_index, rng):
    """``PoolExhausted`` tagged with ``bytes_needed`` is a *pool-bytes*
    shortfall eviction can cure (paged KV returns its bytes between
    waves, so warm prefetch residency creeps into them): the runtime
    spills cold unpinned residency toward the failed lease and retries
    the decode hook once — same wave, no shed, no park."""
    from repro.memory.pool import PoolExhausted

    eng = _engine(small_index, pool_pages=4096)
    calls = []
    room_targets = []
    orig_make_room = eng.cache.make_room

    def spying_make_room(buffer, pages, protect=None):
        room_targets.append(pages)
        return orig_make_room(buffer, pages, protect=protect)

    eng.cache.make_room = spying_make_room

    def hook(records, gen_tokens, rnd):
        calls.append([r.request_id for r in records])
        if len(calls) == 1:
            raise PoolExhausted("kv bytes",
                                bytes_needed=3 * eng.pool.page_nbytes)
        return [DecodeEvent(request_id=r.request_id, tokens=2,
                            seconds=0.0) for r in records]

    runtime = RetrievalRuntime(eng, include_tail=True, on_generate=hook)
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("hyde", 2, seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(2)]
    runtime.run()
    assert all(r.state == RequestState.COMPLETE for r in recs)
    # one relief spill, sized at least the lease's pages, and the retry
    # re-ran the SAME wave (no shed split)
    assert len(room_targets) == 1 and room_targets[0] >= 3
    assert calls[1] == calls[0]
    marks = [getattr(e, "label", "") for e in runtime.recorder.events
             if getattr(e, "kind", "") == "request"]
    assert "pressure_stall" not in marks


def test_kv_pool_shortfall_singleton_parks_until_pages_free(
        small_store, small_index, rng):
    """A singleton wave has no half to shed, but raising is only right
    when NO future event could free pages.  Here another in-flight
    wave's cluster pins are pending release at its completion, so the
    stuck singleton parks ``PRESSURE_STALLED`` whole and rejoins on
    that page-free event instead of raising out of ``run()``."""
    from repro.memory.pool import PoolExhausted

    eng = _engine(small_index, pool_pages=4096)
    failures = []

    def hook(records, gen_tokens, rnd):
        rids = [r.request_id for r in records]
        if rids == [1] and len(failures) < 2:
            # first attempt + the relief retry both fail: nothing cold
            # to spill (every resident cluster is pinned)
            failures.append(tuple(rids))
            raise PoolExhausted("kv bytes",
                                bytes_needed=eng.pool.page_nbytes)
        return [DecodeEvent(request_id=r.request_id, tokens=2,
                            seconds=0.0) for r in records]

    runtime = RetrievalRuntime(eng, micro_batch=1, include_tail=True,
                               on_generate=hook)
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("hyde", 2, seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(2)]
    runtime.run()
    assert all(r.state == RequestState.COMPLETE for r in recs)
    assert len(failures) == 2
    marks = [getattr(e, "label", "") for e in runtime.recorder.events
             if getattr(e, "kind", "") == "request"]
    assert "pressure_stall" in marks, "the singleton never parked"
    assert "pressure_resume" in marks, "the parked singleton never woke"
    for r in recs:
        assert r.result.doc_ids


def test_kv_exhaustion_on_singleton_wave_still_raises(small_store,
                                                      small_index, rng):
    """With nothing left to shed (batch of one) slab exhaustion is real
    exhaustion: the legacy raise-out behavior is preserved."""
    from repro.memory.pool import PoolExhausted

    eng = _engine(small_index, pool_pages=4096)
    kv = KVCacheManager(get_arch("llama3-8b").reduced(), pool=eng.pool)
    kv.init_paged(num_pages=2, page_size=8)     # one seq of 32 needs 4
    hook, _calls = _paged_hook(kv)
    runtime = RetrievalRuntime(eng, on_generate=hook)
    runtime.submit(unit_queries(small_store, rng, 1)[0],
                   make_traces("hyde", 1, seed=5)[0])
    with pytest.raises(PoolExhausted):
        runtime.run()

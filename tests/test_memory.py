"""Unified paged device-memory subsystem: pool leases/refcounts and
reservations, byte-accurate ledger accounting, admission control
(reserve/stall/spill), and the PRESSURE_STALLED runtime path."""

import numpy as np
import pytest

import repro.core as core
from repro.configs import get_arch
from repro.core.schedulers import TeleRAGScheduler
from repro.memory import (AdmissionController, DevicePagePool, MemoryLedger,
                          PoolExhausted)
from repro.serving import (EngineConfig, KVCacheManager, RequestState,
                           RetrievalRuntime, TeleRAGEngine, make_traces)
from tests.conftest import unit_queries


# ---------------------------------------------------------------------------
# DevicePagePool: leases, refcounts, reservations, block tables
# ---------------------------------------------------------------------------


def test_pool_lease_release_accounting(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=32)
    pb = pool.page_nbytes
    lease = pool.lease_slots(5, "prefetch", tag=7)
    assert lease is not None and lease.num_pages == 5
    assert sorted(lease.slots) == sorted(set(lease.slots))   # distinct slots
    assert pool.free_pages() == 27 and pool.used_pages == 5
    assert pool.ledger.bytes_of("prefetch") == 5 * pb
    # refcount: retain defers the free until the last holder releases
    pool.retain(lease)
    assert pool.release(lease) == 0
    assert pool.free_pages() == 27
    assert pool.release(lease) == 5
    assert pool.free_pages() == 32
    assert pool.ledger.bytes_of("prefetch") == 0
    assert pool.ledger.peak_bytes == 5 * pb


def test_pool_byte_lease_exact_ledger_page_rounded_slots(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=16)
    pb = pool.page_nbytes
    nbytes = int(2.5 * pb)
    lease = pool.lease_bytes(nbytes, "kv", tag=("b", "l"))
    assert lease.num_pages == 3                 # page-rounded slab footprint
    assert lease.nbytes == nbytes               # exact bytes on the ledger
    assert pool.ledger.bytes_of("kv") == nbytes
    pool.release(lease)
    assert pool.ledger.bytes_of("kv") == 0


def test_pool_reservations_gate_allocation(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=10)
    res = pool.reserve(6, "waveA")
    assert res is not None and pool.reservable_pages() == 4
    # a second reservation cannot eat into the first's headroom
    assert pool.reserve(5, "waveB") is None
    # nor can an unreserved lease
    assert pool.lease_slots(5, "prefetch") is None
    # allocation under the reservation consumes it
    lease = pool.lease_slots(4, "prefetch", reservation=res)
    assert lease is not None and res.pages == 2
    assert pool.cancel(res) == 2                # remainder returns
    assert pool.reservable_pages() == 6
    pool.release(lease)
    assert pool.reservable_pages() == 10


def test_pool_free_events_notify_subscribers(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=8)
    freed = []
    pool.subscribe(freed.append)
    lease = pool.lease_slots(3, "prefetch")
    assert freed == []
    pool.release(lease)
    assert freed == [3]
    res = pool.reserve(4, "w")
    pool.cancel(res)
    assert freed == [3, 4]                      # cancelled headroom counts


# ---------------------------------------------------------------------------
# MemoryLedger: byte accuracy, occupancy, snapshots
# ---------------------------------------------------------------------------


def test_ledger_charges_and_occupancy():
    led = MemoryLedger(capacity_bytes=1000)
    led.charge("weights", 600)
    led.charge("kv", 150)
    led.credit("kv", 50)
    assert led.bytes_of("kv") == 100
    assert led.total_bytes() == 700
    assert led.occupancy() == pytest.approx(0.7)
    assert led.peak_bytes == 750
    snap = led.snapshot()
    assert snap["total"] == 700 and snap["capacity"] == 1000
    with pytest.raises(ValueError):
        led.credit("weights", 601)              # over-credit is a bug


def test_engine_ledger_tracks_buffer_and_weights(small_store, small_index,
                                                 rng):
    eng = TeleRAGEngine(small_index,
                        EngineConfig(nprobe=16, top_k=3, buffer_pages=160,
                                     lookahead_rank=32, kernel_mode="ref",
                                     chips=8),
                        get_arch("llama3-8b"))
    assert eng.ledger.bytes_of("weights") == \
        get_arch("llama3-8b").param_count() * 2
    runtime = RetrievalRuntime(eng)
    q = unit_queries(small_store, rng, 2)
    for i, t in enumerate(make_traces("hyde", 2, seed=1)):
        runtime.submit(q[i], t)
    runtime.run()
    # end_batch evicted everything: prefetch charge fully credited back
    assert eng.ledger.bytes_of("prefetch") == 0
    assert eng.ledger.peak_bytes > eng.ledger.bytes_of("weights")


# ---------------------------------------------------------------------------
# KVCacheManager leases from the shared pool
# ---------------------------------------------------------------------------


def test_kv_lease_charges_ledger_byte_accurately(small_index):
    arch = get_arch("llama3-8b").reduced()
    pool = DevicePagePool(small_index.paged, num_pages=4096)
    kv = KVCacheManager(arch, pool=pool)
    lease = kv.acquire(2, 64)
    assert lease.nbytes == kv.nbytes(2, 64)
    assert pool.ledger.bytes_of("kv") == lease.nbytes     # exact, to the byte
    kv.release(lease)
    assert pool.ledger.bytes_of("kv") == lease.nbytes     # recycled, resident
    assert kv.drop(2, 64) == lease.nbytes
    assert pool.ledger.bytes_of("kv") == 0


def test_kv_and_prefetch_compete_for_the_same_pages(small_index):
    arch = get_arch("llama3-8b").reduced()
    pool = DevicePagePool(small_index.paged, num_pages=8)
    buf = core.PrefetchBuffer(small_index.paged, pool=pool)
    kv = KVCacheManager(arch, pool=pool)
    need = -(-kv.nbytes(1, 32) // pool.page_nbytes)
    assert need <= 8, "test arch too large for the test pool"
    # fill the slab with cluster pages, leaving less than the KV needs
    cs, pages = [], 0
    for c in range(small_index.paged.num_clusters):
        npg = int(small_index.paged.cluster_num_pages[c])
        if pool.free_pages() - (pages + npg) < need:
            break
        cs.append(c)
        pages += npg
    extra = [c for c in range(small_index.paged.num_clusters)
             if c not in cs][:1]
    buf.load_clusters(cs + extra)               # now < need pages free
    with pytest.raises(PoolExhausted):
        kv.acquire(1, 32)
    buf.evict_clusters(cs + extra)              # prefetch frees -> KV fits
    lease = kv.acquire(1, 32)
    assert lease.page_lease is not None
    kv.release(lease)


@pytest.mark.parametrize("arch_name,zeroed", [("llama3-8b", False),
                                              ("rwkv6-3b", True)])
def test_kv_reuse_zeroing_policy(arch_name, zeroed):
    """Recurrent (SSM) state is zeroed on reuse; attention caches are
    recycled unzeroed (stale entries are masked by per-sequence pos)."""
    import jax
    import jax.numpy as jnp
    kv = KVCacheManager(get_arch(arch_name).reduced())
    l1 = kv.acquire(1, 32)
    l1.cache = jax.tree.map(lambda a: a + 1.0, l1.cache)   # poison
    kv.release(l1)
    l2 = kv.acquire(1, 32)
    peak = max(float(jnp.max(jnp.abs(a)))
               for a in jax.tree.leaves(l2.cache))
    if zeroed:
        assert peak == 0.0
    else:
        assert peak > 0.0


# ---------------------------------------------------------------------------
# AdmissionController: reserve / spill / cap decisions
# ---------------------------------------------------------------------------


def test_admission_reserve_then_cap_without_waiters(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=10)
    adm = AdmissionController(pool)
    t1 = adm.admit(6, "w1", can_wait=True)
    assert t1 is not None and not t1.capped
    # an outstanding reservation is a pending release -> w2 stalls on it
    assert adm.admit(8, "w2", can_wait=True) is None
    assert adm.stats.stalled == 1
    # consume w1's grant as plain unpinned residency, commit the rest:
    # now nothing outstanding will ever free pages -> cap, never deadlock
    lease = pool.lease_slots(6, "prefetch", reservation=t1.reservation)
    adm.commit(t1)
    t2 = adm.admit(8, "w2", can_wait=True)
    assert t2 is not None and t2.capped and t2.pages_granted == 4
    assert adm.stats.capped == 1 and adm.stats.shortfall_pages == 4
    adm.commit(t2)
    pool.release(lease)
    assert pool.reservable_pages() == 10


def test_admission_stalls_when_a_future_free_exists(small_index):
    pool = DevicePagePool(small_index.paged, num_pages=10)
    adm = AdmissionController(pool)
    kv_lease = pool.lease_bytes(6 * pool.page_nbytes, "kv")
    assert adm.holds_pending_release()
    assert adm.admit(8, "w", can_wait=True) is None       # parks instead
    assert adm.stats.stalled == 1
    pool.release(kv_lease)
    t = adm.admit(8, "w", can_wait=True)
    assert t is not None and not t.capped
    adm.commit(t)


def test_admission_spills_cold_residency(small_store, small_index, rng):
    eng = TeleRAGEngine(small_index,
                        EngineConfig(nprobe=16, top_k=3, buffer_pages=64,
                                     lookahead_rank=32, kernel_mode="ref",
                                     seed=2),
                        get_arch("llama3-8b"))
    cs = []
    for c in range(small_index.paged.num_clusters):
        if eng.pool.free_pages() < int(small_index.paged.cluster_num_pages[c]):
            break
        cs.append(c)
    eng.buffer.load_clusters(cs)
    eng.cache.on_fetched(cs)
    full = eng.pool.free_pages()
    ticket = eng.admission.admit(20, "wave", can_wait=False)
    assert ticket is not None and ticket.pages_granted == 20
    assert ticket.spilled_pages >= 20 - full              # evicted cold pages
    eng.admission.commit(ticket)


# ---------------------------------------------------------------------------
# Acceptance: PRESSURE_STALLED lifecycle under a pool sized below one
# wave's plan — stall, resume on the page-free event, complete; and
# telemetry equivalence under an over-provisioned pool
# ---------------------------------------------------------------------------


def _pressured_runtime(small_index, pool_clusters=6):
    pages_per_cluster = float(np.mean(small_index.paged.cluster_num_pages))
    pool_pages = int(pool_clusters * pages_per_cluster)
    eng = TeleRAGEngine(small_index,
                        EngineConfig(nprobe=12, top_k=3,
                                     buffer_pages=pool_pages,
                                     lookahead_rank=16, kernel_mode="ref",
                                     chips=8, seed=3),
                        get_arch("llama3-8b"))
    return eng, RetrievalRuntime(
        eng, scheduler=TeleRAGScheduler(cache_aware=False), micro_batch=2)


def test_pressure_stall_event_ordering_and_completion(small_store,
                                                      small_index, rng):
    eng, runtime = _pressured_runtime(small_index)
    cents = small_index.centroids / np.linalg.norm(
        small_index.centroids, axis=-1, keepdims=True)
    # two similarity groups with disjoint cluster neighbourhoods
    q = np.concatenate([cents[:2], cents[-2:]]).astype(np.float32)
    traces = make_traces("hyde", 4, seed=5)
    recs = [runtime.submit(q[i], traces[i]) for i in range(4)]
    runtime.run()

    # every request completed despite the pressure (no deadlock, no drop)
    assert all(r.state == RequestState.COMPLETE for r in recs)
    assert not eng.admission.parked
    assert eng.admission.stats.stalled >= 1
    assert eng.admission.stats.resumed >= 1

    marks = [(e.t, e.label, e.request_id) for e in eng.recorder.events
             if e.kind == "request"]
    stall_t = {rid: t for t, label, rid in marks
               if label == "pressure_stall"}
    resume_t = {rid: t for t, label, rid in marks
                if label == "pressure_resume"}
    complete_t = {rid: t for t, label, rid in marks
                  if label == "complete"}
    assert stall_t, "no request ever entered PRESSURE_STALLED"
    stalled_ids = set(stall_t)
    first_wave_completes = [t for rid, t in complete_t.items()
                            if rid not in stalled_ids]
    for rid in stalled_ids:
        # pinned event ordering: stall at admit-time pressure, resume
        # exactly on the page-free event of a completing request of the
        # first wave (each member releases its own pins at its own
        # completion), complete after
        assert rid in resume_t and rid in complete_t
        assert stall_t[rid] <= resume_t[rid] <= complete_t[rid]
        assert any(resume_t[rid] == pytest.approx(t)
                   for t in first_wave_completes)
        rec = next(r for r in recs if r.request_id == rid)
        stall_spans = rec.spans("pressure_stall")
        assert stall_spans and stall_spans[0].end == \
            pytest.approx(resume_t[rid])
        # the stall is real latency, not hidden time
        assert rec.latency >= stall_spans[0].end - stall_spans[0].start

    # no rejected-cluster leaks: every hotness entry is resident (the
    # invariant the cache cleanup relies on), nothing half-loaded
    assert set(eng.cache.hotness) <= eng.buffer.resident_clusters() or \
        not eng.cache.hotness


def test_overprovisioned_pool_matches_default_telemetry(small_store,
                                                        small_index, rng):
    """Pool size must be invisible to telemetry when memory is ample:
    two drains under a 4x over-provisioned pool reproduce the
    default-sized run's RoundTelemetry to 1e-6."""
    q = unit_queries(small_store, rng, 4)
    results = []
    # ample: 400 pages hold every wave the four iter requests re-form
    # into without a stall (160 stall them)
    for pool_pages in (400, 1600):
        # cache on: the second batch also exercises consolidate's quota,
        # which must key off buffer_pages, never the pool extent
        cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=400,
                           pool_pages=pool_pages, lookahead_rank=32,
                           kernel_mode="ref", chips=8, seed=5,
                           cache_enabled=True)
        eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
        runtime = RetrievalRuntime(eng)
        res = []
        for seed in (11, 12):
            recs = [runtime.submit(q[i].copy(), t) for i, t in
                    enumerate(make_traces("iter", 4, seed=seed))]
            runtime.run()
            res += [r.result for r in recs]
        assert eng.admission.stats.stalled == 0
        assert eng.admission.stats.capped == 0
        results.append(res)
    base, over = results
    for rb, ro in zip(base, over):
        np.testing.assert_array_equal(np.concatenate(rb.doc_ids),
                                      np.concatenate(ro.doc_ids))
        assert len(rb.rounds) == len(ro.rounds)
        for a, b in zip(rb.rounds, ro.rounds):
            for f in ("t_llm_window", "bytes_prefetched", "t_prefetch",
                      "hits", "misses", "t_host_search", "t_dev_search",
                      "t_merge"):
                assert getattr(a, f) == pytest.approx(getattr(b, f),
                                                      abs=1e-6), f

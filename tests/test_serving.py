"""Serving engine integration: pipelines, modes, fault tolerance."""

import numpy as np
import pytest

import repro.core as core
from repro.configs import get_arch
from repro.core.schedulers import TeleRAGScheduler
from repro.serving import (EngineConfig, RagRequest, RequestState,
                           RetrievalRuntime, TeleRAGEngine, TeleRAGServer,
                           make_traces, calibration_windows, PIPELINES)
from tests.conftest import unit_queries


def _execute(runtime, q, traces):
    """Serve one batch of simultaneous requests; their results in
    submission order."""
    recs = [runtime.submit(q[i], t) for i, t in enumerate(traces)]
    runtime.run()
    return [r.result for r in recs]


@pytest.fixture()
def engine(small_index):
    cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=160,
                       lookahead_rank=32, kernel_mode="ref", chips=8)
    return TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))


@pytest.mark.parametrize("pipe", PIPELINES)
def test_pipeline_executes_and_speedup_model(small_store, small_index, rng,
                                             engine, pipe):
    q = unit_queries(small_store, rng, 4)
    traces = make_traces(pipe, 4, seed=1)
    res = _execute(RetrievalRuntime(engine), q, traces)
    assert len(res) == 4
    for r, t in zip(res, traces):
        assert len(r.rounds) == t.rounds
        assert all(d.shape == (3,) for d in r.doc_ids)
        tele = r.latency("telerag", t_cc=engine.effective_tcc(),
                         cluster_bytes=1e6, link_bw=32e9)
        cpu = r.latency("cpu_baseline", t_cc=engine.effective_tcc(),
                        cluster_bytes=1e6, link_bw=32e9)
        assert tele <= cpu + 1e-9         # overlap can only help the model


def test_modes_agree_on_results(small_store, small_index, rng):
    """All three modes must return identical retrieval results — they
    differ in WHERE the search runs, never in WHAT it returns."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("hyde", 3, seed=9)
    outs = {}
    for mode in ("telerag", "cpu_baseline", "runtime_fetch"):
        cfg = EngineConfig(nprobe=12, top_k=3, buffer_pages=256,
                           lookahead_rank=24, kernel_mode="ref", mode=mode,
                           seed=5)
        eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
        res = _execute(RetrievalRuntime(eng), q.copy(), traces)
        outs[mode] = np.stack([np.sort(np.concatenate(r.doc_ids))
                               for r in res])
    np.testing.assert_array_equal(outs["telerag"], outs["cpu_baseline"])
    np.testing.assert_array_equal(outs["telerag"], outs["runtime_fetch"])


def test_multi_round_incremental_prefetch(small_store, small_index, rng,
                                          engine):
    """IRG does 3 rounds on drifting queries: later rounds should reuse
    earlier fetches (bytes decrease or stay flat per round)."""
    q = unit_queries(small_store, rng, 2)
    traces = make_traces("irg", 2, seed=3)
    res = _execute(RetrievalRuntime(engine), q, traces)
    per_round = {}
    for r in res:
        for rt in r.rounds:
            per_round.setdefault(rt.round_index, 0)
            per_round[rt.round_index] += rt.bytes_prefetched
    # IRG round 0 retrieves before any generation window -> no lookahead
    # budget (t_LLM = 0); rounds 1,2 prefetch and later rounds reuse
    # earlier fetches on the drifting query (incremental top-up, §4.3)
    assert per_round[0] == 0
    assert per_round[1] > 0
    assert per_round[2] <= per_round[1] * 1.5 + 1


def test_cache_improves_second_batch(small_store, small_index, rng):
    cfg = EngineConfig(nprobe=16, top_k=3, buffer_pages=200,
                       lookahead_rank=32, kernel_mode="ref",
                       cache_enabled=True, seed=2)
    eng = TeleRAGEngine(small_index, cfg, get_arch("llama3-8b"))
    runtime = RetrievalRuntime(eng)
    q = unit_queries(small_store, rng, 4)
    _execute(runtime, q, make_traces("hyde", 4, seed=4))
    h2d_first = eng.buffer.stats.bytes_h2d
    # same neighbourhood of queries again: cached clusters cut transfers
    q2 = q + 0.02 * rng.standard_normal(q.shape).astype(np.float32)
    q2 /= np.linalg.norm(q2, axis=-1, keepdims=True)
    _execute(runtime, q2, make_traces("hyde", 4, seed=5))
    h2d_second = eng.buffer.stats.bytes_h2d - h2d_first
    assert h2d_second <= h2d_first


def test_engine_snapshot_restore_roundtrip(small_store, small_index, rng,
                                           engine):
    q = unit_queries(small_store, rng, 2)
    engine.cfg.cache_enabled = True
    _execute(RetrievalRuntime(engine), q, make_traces("iter", 2, seed=6))
    snap = engine.snapshot()
    eng2 = TeleRAGEngine(small_index, engine.cfg, get_arch("llama3-8b"))
    eng2.restore(snap)
    assert eng2.buffer.resident_clusters() == engine.buffer.resident_clusters()
    assert eng2.cache.hotness == engine.cache.hotness
    # restored replica serves correctly
    res = _execute(RetrievalRuntime(eng2), q, make_traces("hyde", 2, seed=7))
    assert all(len(r.doc_ids) > 0 for r in res)


def test_restore_rebinds_subscribers_and_keeps_admission_stats(
        small_store, small_index, rng, engine):
    """Replica restart must (a) carry page-free listeners onto the
    replacement pool through the public rebind API — long-lived runtimes
    keep waking on pressure events — and (b) restore admission telemetry
    instead of silently zeroing it."""
    q = unit_queries(small_store, rng, 2)
    engine.cfg.cache_enabled = True
    _execute(RetrievalRuntime(engine), q, make_traces("iter", 2, seed=6))
    freed = []
    engine.pool.subscribe(freed.append)
    snap = engine.snapshot()
    assert snap["admission"]["admitted"] > 0
    stats_before = engine.admission.stats

    engine.restore(snap)
    assert engine.admission.stats == stats_before
    # the pre-restore listener still hears the REPLACEMENT pool
    lease = engine.pool.lease_slots(2)
    engine.pool.release(lease)
    assert freed and freed[-1] == 2
    # restoring into a fresh replica carries the stats too; snapshots
    # from before the admission key keep the fresh zeros (back-compat)
    eng2 = TeleRAGEngine(small_index, engine.cfg, get_arch("llama3-8b"))
    eng2.restore(snap)
    assert eng2.admission.stats == stats_before
    del snap["admission"]
    eng3 = TeleRAGEngine(small_index, engine.cfg, get_arch("llama3-8b"))
    eng3.restore(snap)
    assert eng3.admission.stats.admitted == 0


def test_orchestrator_with_dead_replica(small_store, small_index, rng):
    cfg = EngineConfig(nprobe=12, top_k=3, buffer_pages=128,
                       lookahead_rank=24, kernel_mode="ref",
                       cache_enabled=True)
    srv = TeleRAGServer(small_index, cfg, 3, get_arch("llama3-8b"),
                        scheduler=TeleRAGScheduler(), micro_batch=4)
    srv.mark_dead(1)
    q = unit_queries(small_store, rng, 12)
    traces = make_traces("hyde", 12, seed=8)
    resp = srv.serve([RagRequest(q=q[i], trace=t)
                      for i, t in enumerate(traces)])
    assert all(a[1] != 1 for w in srv.wave_log for a in w.assignments)
    assert len(resp) == 12
    assert all(r.replica != 1 and r.state == RequestState.COMPLETE
               for r in resp)


def test_calibration_windows_positive():
    for p in PIPELINES:
        ws = calibration_windows(p, n=16)
        assert len(ws) >= 16 and all(w >= 0 for w in ws)
        if p != "irg":                       # IRG round 1 has no window
            assert np.mean(ws) > 0

"""Differential parity: serving decode on the paged block-table KV
substrate vs the pinned dense bucket path.

Each test runs the SAME request set through two servers whose only
difference is ``EngineConfig.paged_decode`` — the ``DecodeRunner`` hook
leases block-table KV and attends through ``flash_decode_paged`` in one
run, a dense ``[B, max_len]`` bucket and ``flash_decode`` in the other —
and pins the outputs equal: retrieved doc ids exact, greedy tokens
exact per request per round, round telemetry within 1e-6, and both
runs' KV bytes fully returned to the ledger.  Shapes deliberately cross
page boundaries and leave the last block partially filled, batches are
ragged against ``micro_batch``, and the continuous-batching machinery
(mid-stream joins, stragglers, park-rejoin) runs in both modes.

Every server's flight-recorder stream is additionally replayed through
the happens-before invariant checker by the autouse conftest fixture,
so the paged lease discipline (acquire -> append* -> release, page
conservation, no append past capacity) is verified on every run here.
"""

import dataclasses
import math
from types import SimpleNamespace

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.analysis import check_recorder
from repro.configs import get_arch
from repro.data.chunk_kv import build_chunk_kv
from repro.models import transformer as tf
from repro.serving import (DecodeRunner, EngineConfig, KVCacheManager,
                           RagRequest,
                           RequestState, TeleRAGServer, make_traces,
                           supports_paged_decode)
from repro.serving.trace import RequestTrace, StageTrace
from tests.conftest import unit_queries

ARCH = get_arch("llama3-8b")
CFG = ARCH.reduced()
MOE_ARCH = get_arch("granite-moe-3b-a800m")


@pytest.fixture(scope="module")
def params():
    return tf.init_params(CFG, jax.random.PRNGKey(0))


def _serve(small_index, q, traces, *, paged, params, micro_batch=3,
           max_len=24, max_steps=6, page_size=4, slab_seqs=None,
           arrivals=None, tenants=None, arch=ARCH):
    """One full serve run of ``arch`` reduced; returns (runner, server,
    responses)."""
    n = len(traces)
    runner = DecodeRunner(params, arch.reduced(), max_len=max_len,
                          max_steps=max_steps, page_size=page_size,
                          slab_seqs=slab_seqs if slab_seqs is not None
                          else n + 2)
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        lookahead_rank=16, kernel_mode="ref", chips=8, seed=7,
        paged_decode=paged), 1, arch, micro_batch=micro_batch,
        include_tail=True, decode_hook=runner, continuous=True)
    runner.attach(srv)
    resp = srv.serve([RagRequest(
        q=q[i], trace=traces[i],
        arrival_t=0.0 if arrivals is None else arrivals[i],
        tenant="shared" if tenants is None else tenants[i])
        for i in range(n)])
    return runner, srv, resp


def _assert_token_parity(rp, rd):
    """Per-request, per-round greedy tokens must be EXACTLY equal."""
    assert set(rp.generated) == set(rd.generated)
    assert rp.generated, "no decode ran at all"
    for rid in rp.generated:
        assert rp.generated[rid] == rd.generated[rid], (
            f"request {rid}: paged tokens {rp.generated[rid]} != "
            f"dense {rd.generated[rid]}")


def _assert_full_parity(rp, respp, rd, respd):
    """Tokens exact, doc ids exact, telemetry pinned to 1e-6."""
    _assert_token_parity(rp, rd)
    assert [r.request_id for r in respp] == [r.request_id for r in respd]
    for a, b in zip(respp, respd):
        assert a.state == b.state == RequestState.COMPLETE
        assert len(a.doc_ids) == len(b.doc_ids)
        for da, db in zip(a.doc_ids, b.doc_ids):
            assert [int(x) for x in da] == [int(x) for x in db]
        assert a.latency_s == pytest.approx(b.latency_s, abs=1e-6)
        assert len(a.rounds) == len(b.rounds)
        for ta, tb in zip(a.rounds, b.rounds):
            fa = dataclasses.asdict(ta)
            fb = dataclasses.asdict(tb)
            assert fa.keys() == fb.keys()
            for key in fa:
                va, vb = fa[key], fb[key]
                if isinstance(va, float):
                    if math.isnan(va):
                        assert math.isnan(vb), (key, va, vb)
                    else:
                        assert va == pytest.approx(vb, abs=1e-6), (
                            key, va, vb)
                else:
                    assert va == vb, (key, va, vb)


def _assert_kv_drained(*runs):
    """Both runs hand every KV byte back to the pool ledger.  Paged
    leases free on release; dense buckets recycle by design, so the
    dense manager drops its recycling pool first."""
    for runner, srv in runs:
        for r, eng in enumerate(srv.engines):
            runner.kv(r).drop_all()
            assert eng.ledger.bytes_of("kv") == 0


# ---------------------------------------------------------------------------
# Acceptance: the paged serve path IS the paged substrate — and its
# output is indistinguishable from the pinned dense path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pipeline,n,micro_batch,max_steps,page_size", [
    ("hyde", 5, 3, 6, 4),     # ragged waves (3+2), partial last block (6%4)
    ("iter", 4, 2, 7, 4),     # multi-round rejoins, 7 crosses page 0->1
    ("irg", 3, 3, 5, 2),      # lengths cross two page boundaries
    ("flare", 4, 4, 4, 4),    # exactly one full page per round
])
def test_pipeline_parity_paged_vs_dense(small_store, small_index, rng,
                                        params, pipeline, n, micro_batch,
                                        max_steps, page_size):
    q = unit_queries(small_store, rng, n)
    traces = make_traces(pipeline, n, seed=11)
    rp, sp, respp = _serve(small_index, q, traces, paged=True,
                           params=params, micro_batch=micro_batch,
                           max_steps=max_steps, page_size=page_size)
    rd, sd, respd = _serve(small_index, q, traces, paged=False,
                           params=params, micro_batch=micro_batch,
                           max_steps=max_steps, page_size=page_size)
    # the paged run really ran paged (and only paged) decode
    assert rp.paged and rp.stats["paged_waves"] > 0
    assert rp.stats["dense_waves"] == 0
    assert rp.stats["paged_appends"] > 0
    assert not rd.paged and rd.stats["dense_waves"] > 0
    assert rd.stats["paged_waves"] == 0
    _assert_full_parity(rp, respp, rd, respd)
    _assert_kv_drained((rp, sp), (rd, sd))


@pytest.mark.parametrize("pipeline,n,micro_batch", [
    ("hyde", 7, 4),           # waves of 4 and 3: the 3 decode on 4 rows
    ("iter", 5, 4),           # multi-round waves shorter than 4
])
def test_moe_parity_paged_vs_dense_on_short_waves(small_store, small_index,
                                                  rng, pipeline, n,
                                                  micro_batch):
    """An MoE arch's expert capacity follows a wave's live rows: a paged
    wave shorter than the micro-batch decodes on padded rows, the dense
    wave on exactly its rows, and the tokens still pin exactly."""
    params = tf.init_params(MOE_ARCH.reduced(), jax.random.PRNGKey(0))
    q = unit_queries(small_store, rng, n)
    traces = make_traces(pipeline, n, seed=3)
    kw = dict(params=params, micro_batch=micro_batch, max_steps=6,
              page_size=4, arch=MOE_ARCH)
    rp, sp, respp = _serve(small_index, q, traces, paged=True, **kw)
    rd, sd, respd = _serve(small_index, q, traces, paged=False, **kw)
    assert rp.paged and not rd.paged
    _assert_full_parity(rp, respp, rd, respd)
    _assert_kv_drained((rp, sp), (rd, sd))


def test_paged_run_emits_lease_events_and_drains(small_store, small_index,
                                                 rng, params):
    """The paged run's recorder stream carries the full lease lifecycle
    (kv.acquire -> kv.append* -> kv.release with lease ids and page
    counts) and satisfies the checker's drained end-state."""
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("hyde", 4, seed=2)
    rp, sp, resp = _serve(small_index, q, traces, paged=True, params=params)
    assert all(r.state == RequestState.COMPLETE for r in resp)
    evs = [e for e in sp.recorder.events
           if getattr(e, "kind", "").startswith("kv.")]
    acq = [e for e in evs if e.kind == "kv.acquire"]
    app = [e for e in evs if e.kind == "kv.append"]
    rel = [e for e in evs if e.kind == "kv.release"]
    assert acq and app and rel
    lease_ids = {e.lease_id for e in acq}
    assert all(lid >= 0 for lid in lease_ids)
    assert len(lease_ids) == len(acq), "paged lease ids must be unique"
    assert {e.lease_id for e in rel} == lease_ids
    assert {e.lease_id for e in app} <= lease_ids
    # every acquire/release pair conserves its slab page count
    pages = {e.lease_id: e.pages for e in acq}
    assert all(e.pages == pages[e.lease_id] for e in rel)
    # appends never advance past the lease capacity
    assert all(0 < e.length <= e.max_len for e in app)
    rep = check_recorder(sp.recorder, drained=True, must_drain=("kv",))
    assert rep.ok, rep.summary()
    assert rep.stats["paged_leases"] == len(acq)


# ---------------------------------------------------------------------------
# Continuous-batching machinery in both modes: mid-stream joins,
# stragglers, mixed-pipeline rounds
# ---------------------------------------------------------------------------


def test_mid_stream_join_parity(small_store, small_index, rng, params):
    """Late arrivals join in-flight decode batches; wave composition is
    identical across modes (the event clock is deterministic in both),
    so parity holds through the re-forming machinery."""
    q = unit_queries(small_store, rng, 5)
    traces = make_traces("iter", 5, seed=4)
    arrivals = [0.0, 0.0, 1e-5, 2e-5, 3e-5]   # staggered mid-stream joins
    kw = dict(params=params, micro_batch=3, max_steps=5, page_size=4,
              arrivals=arrivals)
    rp, sp, respp = _serve(small_index, q, traces, paged=True, **kw)
    rd, sd, respd = _serve(small_index, q, traces, paged=False, **kw)
    _assert_full_parity(rp, respp, rd, respd)
    _assert_kv_drained((rp, sp), (rd, sd))


def test_straggler_and_mixed_round_parity(small_store, small_index, rng,
                                          params):
    """A slow request's batch-mates re-form without it (different
    per-wave batch shapes between rounds) — tokens and telemetry still
    pin across substrates, including the mixed hyde/iter rounds."""
    q = unit_queries(small_store, rng, 4)
    traces = [RequestTrace(
        pipeline="iter", request_id=0,
        stages=[StageTrace("generate", 4000), StageTrace("retrieve"),
                StageTrace("generate", 64), StageTrace("retrieve"),
                StageTrace("generate", 8)], rewrite_sigma=0.0)]
    traces += make_traces("hyde", 2, seed=6)
    traces += make_traces("iter", 2, seed=6)[1:]
    traces = [dataclasses.replace(t, request_id=i)
              for i, t in enumerate(traces)]
    kw = dict(params=params, micro_batch=4, max_steps=4, page_size=4)
    rp, sp, respp = _serve(small_index, q, traces, paged=True, **kw)
    rd, sd, respd = _serve(small_index, q, traces, paged=False, **kw)
    _assert_full_parity(rp, respp, rd, respd)
    _assert_kv_drained((rp, sp), (rd, sd))


def test_park_rejoin_token_parity_under_slab_pressure(small_store,
                                                      small_index, rng,
                                                      params):
    """A slab sized below the wave (slab_seqs=2, wave of 4) forces the
    paged run through the shed/park/rejoin path; the dense run never
    parks.  Wave compositions then differ between the runs — but the
    greedy tokens each request generates must STILL be exactly equal
    (decode is per-sequence deterministic), and everyone completes."""
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("hyde", 4, seed=9)
    kw = dict(params=params, micro_batch=4, max_steps=4, page_size=4)
    rp, sp, respp = _serve(small_index, q, traces, paged=True,
                           slab_seqs=2, **kw)
    rd, sd, respd = _serve(small_index, q, traces, paged=False, **kw)
    assert all(r.state == RequestState.COMPLETE for r in respp + respd)
    # the paged run really hit pressure: someone parked AND resumed
    # (marks, not spans — on the deterministic event clock the older
    # half's decode is instantaneous, so the stall interval is empty)
    marks = [getattr(e, "label", "") for e in sp.recorder.events
             if getattr(e, "kind", "") == "request"]
    assert "pressure_stall" in marks, "slab_seqs=2 never forced a park"
    assert "pressure_resume" in marks, "parked members never rejoined"
    # the shed split the wave: more paged waves ran than dense waves
    assert rp.stats["paged_waves"] > rd.stats["dense_waves"]
    _assert_token_parity(rp, rd)
    _assert_kv_drained((rp, sp), (rd, sd))
    # doc ids are wave-composition independent too
    for a, b in zip(respp, respd):
        for da, db in zip(a.doc_ids, b.doc_ids):
            assert [int(x) for x in da] == [int(x) for x in db]


# ---------------------------------------------------------------------------
# Arch gating + randomized sweep
# ---------------------------------------------------------------------------


def test_supports_paged_decode_gates_arches():
    assert supports_paged_decode(CFG)
    assert not supports_paged_decode(
        dataclasses.replace(CFG, sliding_window=8))
    assert not supports_paged_decode(
        dataclasses.replace(CFG, attn_kind="none"))
    # an unsupported arch falls back to dense even when asked for paged
    runner = DecodeRunner(None, dataclasses.replace(CFG, sliding_window=8),
                          paged=True)
    assert not runner.paged


def test_randomized_shape_parity(small_store, small_index, params):
    """Hypothesis-driven differential sweep over batch shapes, page
    sizes and step counts (ragged batches, boundary-crossing lengths,
    partially-filled last blocks)."""
    @settings(max_examples=5, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(pipeline=st.sampled_from(["hyde", "iter", "irg", "flare"]),
           n=st.integers(2, 5), micro_batch=st.integers(2, 4),
           max_steps=st.integers(3, 7),
           page_size=st.sampled_from([2, 4, 8]),
           seed=st.integers(0, 2**16))
    def check(pipeline, n, micro_batch, max_steps, page_size, seed):
        rng = np.random.default_rng(seed)
        q = unit_queries(small_store, rng, n)
        traces = make_traces(pipeline, n, seed=seed % 97)
        kw = dict(params=params, micro_batch=micro_batch,
                  max_steps=max_steps, page_size=page_size)
        rp, sp, respp = _serve(small_index, q, traces, paged=True, **kw)
        rd, sd, respd = _serve(small_index, q, traces, paged=False, **kw)
        _assert_full_parity(rp, respp, rd, respd)
        _assert_kv_drained((rp, sp), (rd, sd))

    check()


def _aligned_int32(values, align: int = 64) -> np.ndarray:
    """An int32 array whose buffer starts on an ``align``-byte boundary:
    the buffers ``jnp.asarray`` adopts without a copy on the CPU."""
    n = len(values)
    raw = np.zeros(n + align // 4, np.int32)
    off = (-raw.ctypes.data % align) // 4
    out = raw[off:off + n]
    out[:] = values
    assert out.ctypes.data % align == 0
    return out


def test_step_sees_pre_append_lengths_on_aligned_buffers():
    """A step dispatched with the lease's ``tables()`` must read the
    lengths as they were at dispatch, even when ``append_paged`` runs
    before the step does and the lease's buffer is one the device may
    alias (both uploads below adopt such a buffer on the CPU)."""
    kv = KVCacheManager(CFG)
    kv.init_paged(num_pages=8, page_size=4)
    lease = kv.acquire_paged(2, 8)
    try:
        for upload in (jax.device_put, jnp.asarray):
            lease.lengths = _aligned_int32([1, 2])
            bt, lens = map(upload, lease.tables())
            seen = jax.jit(lambda b, l: l + 0 * b[:, 0])(bt, lens)
            kv.append_paged(lease)
            np.testing.assert_array_equal(np.asarray(seen), [1, 2])
            np.testing.assert_array_equal(np.asarray(lens), [1, 2])
            np.testing.assert_array_equal(lease.lengths, [2, 3])
    finally:
        kv.release_paged(lease)


# ---------------------------------------------------------------------------
# Token read-back: one batched transfer per wave
# ---------------------------------------------------------------------------


def _direct_runner(small_index, params, mode, *, micro_batch=3):
    """A runner attached to a one-replica server, to be called directly
    as the decode hook; ``mode`` is ``paged``, ``dense`` or ``spliced``
    (paged over a chunk store holding docs 0-5).  Returns the runner
    and ``waves``, which keeps every wave's step arrays."""
    store = None
    if mode == "spliced":
        store = build_chunk_kv(params, CFG, range(6), page_size=4, seed=3,
                               min_len=6, max_len=8)
    runner = DecodeRunner(params, CFG, max_len=32, max_steps=6, page_size=4,
                          slab_seqs=micro_batch + 8, chunk_store=store)
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        lookahead_rank=16, kernel_mode="ref", chips=8, seed=7,
        paged_decode=mode != "dense", chunk_kv=store is not None), 1, ARCH,
        micro_batch=micro_batch, include_tail=True, decode_hook=runner,
        continuous=True)
    runner.attach(srv)
    waves = []

    def keep(run):
        def wrapped(*args, **kw):
            toks, per_step = run(*args, **kw)
            waves.append(toks)
            return toks, per_step
        return wrapped

    runner._run_paged = keep(runner._run_paged)
    runner._run_dense = keep(runner._run_dense)
    return runner, waves


def _records(ids):
    """Stand-in wave members: what the decode hook reads of a record.
    Each has retrieved its own docs, so spliced rows decode apart."""
    return [SimpleNamespace(request_id=i, tenant="shared",
                            result=SimpleNamespace(
                                doc_ids=[[i % 6, (i + 2) % 6][:1 + i % 2]]))
            for i in ids]


@pytest.mark.parametrize("mode", ["paged", "dense", "spliced"])
def test_readback_equals_per_element_read(small_index, params, mode):
    """``generated`` from the one batched read equals a read of every
    token on its own, ``int(t[j])``: the same values, Python ints, in
    round order, for a full wave, a wave with fewer live rows than the
    micro-batch (its padding rows stay out) and a wave of 0 steps."""
    runner, waves = _direct_runner(small_index, params, mode)
    plan = [(_records([0, 1, 2]), [5, 2, 4]),
            (_records([3]), [3]),
            (_records([4, 5]), [0, 0]),
            (_records([0, 1]), [2, 6])]
    want = {}
    for recs, gen in plan:
        runner(0, recs, gen, 1)
        toks = waves[-1]
        assert len(toks) == max(gen)
        for j, r in enumerate(recs):
            want.setdefault(r.request_id, []).append(
                tuple(int(t[j]) for t in toks))
    assert runner.generated == want
    assert all(type(x) is int for rounds in runner.generated.values()
               for row in rounds for x in row)
    assert runner.generated[4] == runner.generated[5] == [()]
    assert [len(x) for x in runner.generated[0]] == [5, 6]
    rows = waves[1][0].shape[0]
    assert rows == (1 if mode == "dense" else 3)   # padded to micro_batch
    assert runner.stats["readback_syncs"] == 3     # the 0-step wave reads none
    if mode == "spliced":
        assert runner.stats["spliced_waves"] > 0
        # rows splice other docs, so a mixed-up column would show
        assert len({runner.generated[i][0][:2] for i in (0, 1, 2)}) > 1
    runner.kv(0).drop_all()
    if runner.chunk(0) is not None:
        runner.chunk(0).drain()


def test_readback_syncs_once_per_wave_and_compiles_nothing(small_index,
                                                           params):
    """After a first wave, waves of step counts never seen before read
    back with one sync each and compile no program: a device-side stack
    of the step arrays would compile once per wave length."""
    jax.clear_caches()                     # only this test's first wave warms
    runner, _ = _direct_runner(small_index, params, "paged")
    runner(0, _records([0, 1, 2]), [4, 1, 3], 1)
    compiles = []

    def listen(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(secs)

    jax.monitoring.register_event_duration_secs_listener(listen)
    try:
        runner(0, _records([3, 4, 5]), [5, 2, 5], 1)
        runner(0, _records([6, 7, 8]), [1, 6, 2], 1)
    finally:
        jax.monitoring.unregister_event_duration_listener(listen)
    assert compiles == []
    assert runner.stats["readback_syncs"] == runner.stats["paged_waves"] == 3
    assert [len(runner.generated[i][0]) for i in (0, 3, 6)] == [4, 5, 6]
    runner.kv(0).drop_all()

"""Flight recorder, exporters, metrics registry, and overlap analyzer.

Pins the observability subsystem's contracts:
  * ``Span.intersects`` open-interval edge semantics (zero-length
    spans, touching endpoints) — the overlap accounting rests on it;
  * recorder correctness on a served run: every admitted request's
    lifecycle marks are ordered admit <= generate-dispatch <= complete,
    every dispatched wave has a form and a complete, and every issued
    transfer lands;
  * ``ServerTelemetry``/``TenantTelemetry`` are registry-backed views
    numerically equal to the response stream they summarize;
  * the Perfetto export passes ``tools/check_trace.py`` (the CI gate)
    including the required counter tracks;
  * ``analyze`` reports a positive mean overlap ratio on a
    hyde/iter prefetching mix;
  * ``benchmarks.common.write_report`` round-trips through
    ``validate_report``;
  * host-clock spans: off, they read no clock and store nothing; on,
    every wave nests its lookahead, decode and retrieval spans, the
    request stamps agree with the benchmark probe's host-clock latency,
    the event stream is unchanged, and the Perfetto host lanes pass
    ``tools/check_trace.py``.
"""

import dataclasses
import importlib.util
import json
import os

import jax
import numpy as np
import pytest

from repro.configs import get_arch
from repro.models import transformer as tf
from repro.obs import (SYSTEM_CLOCK, EventClock, FlightRecorder,
                       MetricsRegistry, analyze, to_jsonl, to_perfetto,
                       write_trace)
from repro.serving import (DecodeRunner, EngineConfig, RagRequest, Span,
                           TeleRAGServer, make_traces)
from tests.conftest import unit_queries

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _cfg(**kw):
    defaults = dict(nprobe=16, top_k=3, buffer_pages=200, lookahead_rank=32,
                    kernel_mode="ref", chips=8, cache_enabled=True, seed=5)
    defaults.update(kw)
    return EngineConfig(**defaults)


def _serve_mix(small_store, small_index, rng, n=10, replicas=2,
               trace=None, stagger=True):
    """A hyde/iter mix through a continuous 2-replica server; returns
    (server, responses)."""
    srv = TeleRAGServer(small_index, _cfg(), replicas, get_arch("llama3-8b"),
                        micro_batch=3, trace=trace)
    q = unit_queries(small_store, rng, n)
    half = n // 2
    # make_traces numbers ids 0..n-1 per call — re-id so the mix's
    # request ids are unique (the recorder correlates by request_id)
    traces = [dataclasses.replace(t, request_id=i) for i, t in enumerate(
        make_traces("hyde", half, seed=3)
        + make_traces("iter", n - half, seed=4))]
    arr = np.cumsum(rng.exponential(0.03, n)) if stagger else np.zeros(n)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i],
                                 arrival_t=float(arr[i]))
                      for i in range(n)])
    assert len(resp) == n
    return srv, resp


# ---------------------------------------------------------------------------
# Span.intersects: open-interval edge semantics
# ---------------------------------------------------------------------------


def test_span_intersects_open_interval_edges():
    # zero-length span strictly inside the open interval: intersects
    assert Span("x", 1.0, 1.0).intersects(0.0, 2.0)
    # zero-length span AT either endpoint: does not
    assert not Span("x", 0.0, 0.0).intersects(0.0, 2.0)
    assert not Span("x", 2.0, 2.0).intersects(0.0, 2.0)
    # touching endpoints (span ends where interval starts / vice versa)
    assert not Span("x", -1.0, 0.0).intersects(0.0, 2.0)
    assert not Span("x", 2.0, 3.0).intersects(0.0, 2.0)
    # any positive-measure intersection counts
    assert Span("x", -1.0, 0.5).intersects(0.0, 2.0)
    assert Span("x", 1.5, 9.0).intersects(0.0, 2.0)
    assert Span("x", -1.0, 9.0).intersects(0.0, 2.0)
    # degenerate query interval: an instant strictly inside the span's
    # interior counts, an instant at a span endpoint does not
    assert Span("x", 0.0, 2.0).intersects(1.0, 1.0)
    assert not Span("x", 0.0, 2.0).intersects(0.0, 0.0)
    assert not Span("x", 0.0, 2.0).intersects(2.0, 2.0)
    # overlaps() is the back-compat alias
    assert Span("x", 1.0, 1.0).overlaps(0.0, 2.0)
    assert not Span("x", 2.0, 3.0).overlaps(0.0, 2.0)


# ---------------------------------------------------------------------------
# Recorder correctness on a served run
# ---------------------------------------------------------------------------


def test_request_lifecycle_marks_are_ordered(small_store, small_index, rng):
    srv, resp = _serve_mix(small_store, small_index, rng)
    rec = srv.recorder
    for r in resp:
        m = rec.request_marks(r.request_id)
        assert "submit" in m and "admit" in m and "complete" in m, m
        # submit <= admit <= first generate dispatch <= complete
        assert m["submit"] <= m["admit"] + 1e-9
        gen = m.get("generate", m["admit"])
        assert m["admit"] <= gen + 1e-9
        assert gen <= m["complete"] + 1e-9
        # the marks agree with the typed response record
        assert m["complete"] == pytest.approx(r.complete_t)
        assert m["admit"] == pytest.approx(r.admit_t)


def test_no_orphan_wave_or_transfer_events(small_store, small_index, rng):
    srv, _ = _serve_mix(small_store, small_index, rng)
    rec = srv.recorder
    formed = {(e.replica, e.wave_id) for e in rec.of("wave.form")}
    completed = {(e.replica, e.wave_id) for e in rec.of("wave.complete")}
    dispatched = rec.of("wave.dispatch")
    assert dispatched, "continuous run must dispatch waves"
    for ev in dispatched:
        key = (ev.replica, ev.wave_id)
        assert key in formed, f"dispatch without form: {ev}"
        assert key in completed, f"dispatch without complete: {ev}"
        assert ev.size == len(ev.request_ids) > 0
    # every issued transfer lands, ordered, with matching byte counts
    issues = {(e.replica, e.transfer_id): e for e in rec.of("transfer.issue")}
    lands = {(e.replica, e.transfer_id): e for e in rec.of("transfer.land")}
    assert issues and set(issues) == set(lands)
    for key, iss in issues.items():
        assert lands[key].nbytes == iss.nbytes
        assert iss.t <= lands[key].t + 1e-9
    # every dispatch-correlated transfer id was actually issued
    for ev in dispatched:
        if ev.transfer_id >= 0:
            assert (ev.replica, ev.transfer_id) in issues


def test_shared_recorder_injection(small_store, small_index, rng):
    """A caller-supplied recorder receives the whole server's stream."""
    mine = FlightRecorder()
    srv, _ = _serve_mix(small_store, small_index, rng, trace=mine)
    assert srv.recorder is mine
    assert mine.of("request") and mine.of("pool.lease")
    replicas = {e.replica for e in mine.events}
    assert {0, 1} <= replicas, replicas


def test_recorder_capacity_drops_oldest_half():
    rec = FlightRecorder(capacity=8)
    from repro.obs.recorder import RequestEvent  # noqa: PLC0415
    for i in range(9):
        rec.emit(RequestEvent(t=float(i), kind="request", request_id=i,
                              label="admit"))
    assert rec.dropped > 0
    assert len(rec.events) <= 8
    # the recent past is kept
    assert rec.events[-1].request_id == 8


# ---------------------------------------------------------------------------
# Telemetry == registry views, numerically pinned
# ---------------------------------------------------------------------------


def test_tenant_telemetry_is_registry_view(small_store, small_index, rng):
    srv, resp = _serve_mix(small_store, small_index, rng)
    tel = srv.telemetry()
    assert tel.completed == len(resp)
    lats = np.array([r.latency_s for r in resp])
    queues = np.array([r.queue_s for r in resp])
    (tt,) = tel.tenants
    assert tt.tenant == "shared"
    assert tt.completed == len(resp)
    assert tt.p50_latency_s == pytest.approx(
        float(np.percentile(lats, 50)), abs=1e-6)
    assert tt.p99_latency_s == pytest.approx(
        float(np.percentile(lats, 99)), abs=1e-6)
    assert tt.mean_queue_s == pytest.approx(float(queues.mean()), abs=1e-6)
    # the registry carries the same series under the same labels
    hist = srv.metrics.histogram("request_latency_s", tenant="shared")
    assert hist.count == len(resp)
    assert srv.metrics.counter("requests_completed",
                               tenant="shared").value == len(resp)


def test_metrics_registry_primitives():
    m = MetricsRegistry()
    c = m.counter("hits", tenant="a")
    c.inc()
    c.inc(2.0)
    assert c.value == 3.0
    assert m.counter("hits", tenant="a") is c          # get-or-create
    assert m.counter("hits", tenant="b") is not c      # distinct labels
    g = m.gauge("depth")
    g.set(7.0)
    assert g.value == 7.0
    h = m.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    assert h.count == 4
    assert h.percentile(50) == pytest.approx(np.percentile(
        [1.0, 2.0, 3.0, 4.0], 50))


# ---------------------------------------------------------------------------
# Perfetto export passes the CI validator
# ---------------------------------------------------------------------------


def _load_check_trace():
    path = os.path.join(REPO, "tools", "check_trace.py")
    spec = importlib.util.spec_from_file_location("check_trace", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_perfetto_export_validates(small_store, small_index, rng, tmp_path):
    srv, resp = _serve_mix(small_store, small_index, rng)
    doc = to_perfetto(srv.recorder)
    check = _load_check_trace()
    phases = check.validate_trace(doc)
    assert phases.get("X", 0) > 0                      # spans on lanes
    assert phases.get("C", 0) > 0                      # counter tracks
    # async request spans balance and cover every request
    assert phases.get("b", 0) == phases.get("e", 0) == len(resp)
    counters = {e["name"] for e in doc["traceEvents"] if e["ph"] == "C"}
    assert {"ledger_occupancy", "pool_free_pages"} <= counters
    # write_trace round-trips through JSON to the identical document
    out = tmp_path / "trace.json"
    write_trace(srv.recorder, str(out))
    with open(out) as f:
        assert json.load(f) == json.loads(json.dumps(doc))
    assert check.main(["check_trace", str(out)]) == 0


# ---------------------------------------------------------------------------
# Overlap analyzer on a prefetching mix
# ---------------------------------------------------------------------------


def test_analyzer_positive_overlap_on_prefetch_mix(small_store, small_index,
                                                   rng):
    srv, resp = _serve_mix(small_store, small_index, rng)
    rep = analyze(srv.recorder)
    assert rep.n_requests == len(resp)
    assert rep.prefetched_rounds, "mix must move prefetch bytes"
    assert 0.0 < rep.mean_overlap_ratio <= 1.0
    for rnd in rep.rounds:
        assert 0.0 <= rnd.ratio <= 1.0 + 1e-9
        assert rnd.hidden_s <= rnd.transfer_s + 1e-9
    assert rep.wave_sizes and min(rep.wave_sizes) >= 1
    for key in ("link_s", "pressure_s", "queue_s"):
        assert rep.stall[key] >= 0.0
    # pure function of the trace: re-analysis is identical
    rep2 = analyze(srv.recorder)
    assert rep2.mean_overlap_ratio == rep.mean_overlap_ratio
    assert rep.summary()                               # printable


# ---------------------------------------------------------------------------
# Bench report schema round-trip
# ---------------------------------------------------------------------------


def test_bench_report_roundtrip(tmp_path):
    from benchmarks import common  # noqa: PLC0415
    rows = [{"rate": 1.0, "p50_ms": 3.5}, {"rate": 2.0, "p50_ms": 4.5}]
    common.set_report_dir(str(tmp_path))
    try:
        path = common.write_report("unittest",
                                   metrics=common.summarize_rows(rows),
                                   rows=rows, meta={"seed": 0})
        with open(path) as f:
            report = json.load(f)
    finally:
        common.set_report_dir(None)
    assert os.path.basename(path) == "BENCH_unittest.json"
    common.validate_report(report)
    assert report["schema"] == common.REPORT_SCHEMA
    assert report["metrics"]["n_rows"] == 2
    assert report["metrics"]["mean_p50_ms"] == pytest.approx(4.0)
    assert report["rows"] == rows
    bad = dict(report, schema="nope")
    with pytest.raises(AssertionError):
        common.validate_report(bad)


# ---------------------------------------------------------------------------
# Host-clock spans
# ---------------------------------------------------------------------------


class CountingClock:
    """A real clock that counts its reads."""

    real = True

    def __init__(self):
        self.reads = 0

    def perf(self) -> float:
        self.reads += 1
        return SYSTEM_CLOCK.perf()


@pytest.fixture(scope="module")
def dense_params():
    return tf.init_params(get_arch("llama3-8b").reduced(),
                          jax.random.PRNGKey(0))


def _serve_decode(small_store, small_index, dense_params, *, spans,
                  wall_clock=None, hook=None, n=6):
    """A tiny dense model decoding an iter/hyde mix through one
    continuous replica, host spans on or off; returns (server, runner,
    responses).  ``hook(runner, server)`` may wrap the decode hook."""
    arch = get_arch("llama3-8b")
    runner = DecodeRunner(dense_params, arch.reduced(), max_len=32,
                          max_steps=6, page_size=4, slab_seqs=n + 2)
    decode = hook(runner) if hook is not None else runner
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        lookahead_rank=16, kernel_mode="ref", chips=8, seed=7,
        cache_enabled=True), 1, arch, micro_batch=3, include_tail=True,
        decode_hook=decode, wall_clock=wall_clock)
    runner.attach(srv)
    if hasattr(decode, "wrap"):
        decode.wrap(srv.engines[0])
    if spans:
        srv.recorder.enable_host_spans(SYSTEM_CLOCK)
    q = unit_queries(small_store, np.random.default_rng(3), n)
    traces = [dataclasses.replace(t, request_id=i) for i, t in enumerate(
        make_traces("iter", n // 2, seed=4)
        + make_traces("hyde", n - n // 2, seed=5))]
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i])
                      for i in range(n)])
    return srv, runner, resp


def test_host_spans_off_read_no_clock(small_store, small_index,
                                      dense_params, monkeypatch):
    made = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation",
                        lambda *a, **k: made.append(a))
    clock = CountingClock()
    srv, runner, resp = _serve_decode(small_store, small_index, dense_params,
                                      spans=False, wall_clock=clock)
    rec = srv.recorder
    assert rec.host_clock is None
    assert rec.host_spans == [] and rec.host_requests == [] and made == []
    assert all(r.host_latency_s is None and r.host_submit_s is None
               for r in resp)
    # the only reads are the timing the program does with spans off: a
    # decode wave's two, and a routed wave's scheduler overhead two
    assert clock.reads == (2 * runner.stats["paged_waves"]
                           + 2 * len(srv.wave_log))


def test_host_spans_refuse_a_modeled_clock():
    rec = FlightRecorder()
    with pytest.raises(ValueError):
        rec.enable_host_spans(EventClock(rec))
    assert rec.host_clock is None


def _inside(child, parent) -> bool:
    return parent.start <= child.start and child.end <= parent.end


def test_host_spans_nest_in_every_wave(small_store, small_index,
                                       dense_params):
    srv, runner, resp = _serve_decode(small_store, small_index, dense_params,
                                      spans=True)
    spans = srv.recorder.host_spans
    waves = [s for s in spans if s.name == "telerag.wave"]
    assert len(waves) == len(srv.runtimes[0].wave_log) > 1
    by_wave = {}
    for s in spans:
        if s.name != "telerag.wave":
            by_wave.setdefault(s.wave_id, []).append(s)
    issued = 0
    for w in waves:
        kids = sorted(by_wave.get(w.wave_id, []), key=lambda s: s.start)
        assert w.replica == 0 and all(_inside(k, w) for k in kids)
        names = [k.name for k in kids]
        steps = [k for k in kids if k.name == "telerag.decode.steps"]
        assert len(steps) == 1 and names.count(
            "telerag.decode.readback") == 1
        (st,) = steps
        dispatch = [k for k in kids if k.name == "telerag.decode.dispatch"]
        assert len(dispatch) == st.args["steps"]
        assert all(_inside(d, st) for d in dispatch)
        assert [d.args["step"] for d in dispatch] == list(
            range(st.args["steps"]))
        rb = next(k for k in kids if k.name == "telerag.decode.readback")
        assert st.end <= rb.start
        for k in kids:
            if k.name.startswith("telerag.lookahead."):
                assert k.end <= st.start       # issued before decode
        issued += names.count("telerag.lookahead.issue")
        ret = [k for k in kids if k.name == "telerag.retrieve"]
        if ret:
            (r,) = ret
            assert rb.end <= r.start
            parts = [k for k in kids if k.name.startswith(
                "telerag.retrieve.")]
            assert [p.name for p in parts] == [
                "telerag.retrieve.device", "telerag.retrieve.host",
                "telerag.retrieve.merge"]
            assert all(_inside(p, r) for p in parts)
            assert all(a.end <= b.start for a, b in zip(parts, parts[1:]))
    assert issued > 0
    issues = [s for s in spans if s.name == "telerag.lookahead.issue"]
    assert sum(s.args["pages"] for s in issues) == \
        srv.engines[0].buffer.stats.pages_h2d
    assert all(s.args["bytes"] > 0 for s in issues)


def test_host_latency_is_the_probe_latency(small_store, small_index,
                                           dense_params):
    """``host_latency_s`` is the benchmark's request latency: drain
    start to the end of the last decode wave or retrieve call that
    worked for the request, on the same clock."""
    from benchmarks.chip.probe import Probe  # noqa: PLC0415

    probes = []

    def hook(runner):
        probes.append(Probe(runner, rows=3))
        return probes[0]

    t0 = SYSTEM_CLOCK.perf()
    srv, _, resp = _serve_decode(small_store, small_index, dense_params,
                                 spans=True, wall_clock=SYSTEM_CLOCK,
                                 hook=hook)
    (probe,) = probes
    for r in resp:
        assert r.host_latency_s is not None and r.host_latency_s > 0
        assert r.host_submit_s >= t0
        assert r.host_done_s == pytest.approx(
            probe.last_touch[r.request_id], abs=5e-3)


def test_host_spans_leave_the_event_stream_unchanged(small_store,
                                                     small_index,
                                                     dense_params):
    off, _, _ = _serve_decode(small_store, small_index, dense_params,
                              spans=False)
    on, _, _ = _serve_decode(small_store, small_index, dense_params,
                             spans=True)
    assert on.recorder.host_spans

    def stream(rec):
        # paged lease ids count up across the process: number them by
        # first appearance
        ids = {}
        out = []
        for line in to_jsonl(rec):
            ev = json.loads(line)
            if ev.get("lease_id", -1) >= 0:
                ev["lease_id"] = ids.setdefault(ev["lease_id"], len(ids))
            out.append(ev)
        return out

    assert stream(on.recorder) == stream(off.recorder)
    # the request-lifecycle events, lease-free, compare as they are
    assert on.recorder.of("request") == off.recorder.of("request")


def test_perfetto_host_lanes_validate(small_store, small_index, dense_params,
                                     tmp_path):
    srv, _, resp = _serve_decode(small_store, small_index, dense_params,
                                 spans=True)
    doc = to_perfetto(srv.recorder)
    check = _load_check_trace()
    check.validate_trace(doc)
    host = [e for e in doc["traceEvents"] if e.get("cat") == "host"]
    assert len(host) == len(srv.recorder.host_spans)
    assert check.validate_host_lanes(doc["traceEvents"]) == len(host)
    lanes = {(e["args"]["name"]) for e in doc["traceEvents"]
             if e["ph"] == "M" and e["name"] == "thread_name"
             and e["pid"] >= 10000}
    assert lanes == {"wave", "decode", "lookahead", "retrieval"}
    reqs = [e for e in doc["traceEvents"] if e.get("cat") == "host_request"]
    assert sorted(e["ph"] for e in reqs) == ["b"] * len(resp) + [
        "e"] * len(resp)
    assert min(e["ts"] for e in host + reqs) == 0.0
    out = tmp_path / "trace.json"
    write_trace(srv.recorder, str(out))
    assert check.main(["check_trace", str(out)]) == 0
    # a span that crosses the end of the one it started in is refused
    steps = next(e for e in host if e["name"] == "telerag.decode.steps")
    bad = dict(next(e for e in host if e["name"] == "telerag.decode.dispatch"
                    and e["ts"] >= steps["ts"]))
    bad["dur"] = steps["ts"] + steps["dur"] + 1e3 - bad["ts"]
    with pytest.raises(AssertionError):
        check.validate_host_lanes(doc["traceEvents"] + [bad])

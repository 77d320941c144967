"""The program's host-clock spans as the benchmark reads them, on the
CPU: the four span metrics on spans of known lengths, a traced run of
the tiny dense configuration with spans on (every span metric read and
in range, the recorder's spans and the profiler's agreeing, the host
latency equal to the harness's), and a trace recorded on the chip
with program spans (``spans.py --fixture``), whose idle gaps the
read-back labels."""

from __future__ import annotations

import os
import time

import jax
import pytest

from benchmarks.chip import cell, spans, xplane
from repro.obs import HostSpan

HERE = os.path.dirname(os.path.abspath(__file__))
OLD_FIXTURE = os.path.join(HERE, "fixtures", "decode_steps.xplane.pb")
FIXTURE = os.path.join(HERE, "fixtures", "program_spans.xplane.pb")


def test_span_metrics_on_known_spans():
    s = [HostSpan("telerag.decode.readback", 1.0, 3.0),
         HostSpan("telerag.decode.readback", 5.0, 6.0),
         HostSpan("telerag.decode.dispatch", 0.0, 0.002),
         HostSpan("telerag.decode.dispatch", 0.1, 0.104),
         HostSpan("telerag.lookahead.issue", 0.0, 0.010, args={"pages": 4}),
         HostSpan("telerag.lookahead.issue", 0.0, 0.001, args={"pages": 0}),
         HostSpan("telerag.retrieve", 7.0, 7.1),
         HostSpan("telerag.retrieve.host", 7.02, 7.08)]
    got = {n: read(s, 0.0, 10.0) for n, read in spans.SPAN_METRICS.items()}
    assert got == pytest.approx({"decode.readback_share": 0.3,
                                 "decode.dispatch_ms": 3.0,
                                 "lookahead.issue_ms": 10.0,
                                 "retrieval.host_share": 0.6})
    assert all(read([], 0.0, 1.0) is None
               for read in spans.SPAN_METRICS.values())
    assert spans.window_spans(s, 0.5, 6.5) == s[:2]


def tiny_cell() -> cell.Cell:
    bench = cell.load_benchmark()
    td = os.path.join(HERE, "testdata")
    return cell.Cell(name="tiny-dense.spans", chips=1,
                     config=cell.load_json(os.path.join(td,
                                                        "tiny-dense.json")),
                     traffic=cell.load_json(os.path.join(
                         td, "tiny-traffic.json")),
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def test_a_traced_run_with_spans_reads_every_span_metric(tmp_path):
    res = spans.run(tiny_cell(), seed=2**33 + 5, seconds=0.2, spans=True,
                    trace_dir=str(tmp_path / "trace"),
                    device=jax.devices()[0], t_start=time.perf_counter())
    assert 0.0 < res["decode.readback_share"] < 1.0
    assert 0.0 < res["decode.dispatch_ms"] < 1e3 * res["window_s"]
    assert 0.0 < res["lookahead.issue_ms"] < 1e3 * res["window_s"]
    assert 0.0 <= res["retrieval.host_share"] <= 1.0
    assert res["tokens_per_s"] > 0 and res["decode.step_ms"] > 0
    assert res["host_latency_gap_s"] < 5e-3
    # the same spans in the profiler's trace: one per span, each as long
    # as the recorder measured it
    clock = res["clock"]
    assert clock["pairs"] >= res["host_spans"] > 0
    assert clock["outside"] == 0, clock
    assert res["idle_cover"]["share_labelled"] is not None


def test_a_run_with_spans_off_reads_none():
    res = spans.run(tiny_cell(), seed=2**33 + 6, seconds=0.1, spans=False,
                    trace_dir=None, device=jax.devices()[0],
                    t_start=time.perf_counter())
    assert res["tokens_per_s"] > 0
    assert not set(spans.SPAN_METRICS) & set(res)


def test_program_spans_leave_a_trace_without_them_as_it_was():
    t = xplane.load(OLD_FIXTURE)
    assert spans.load_program_spans(OLD_FIXTURE) == []
    window, ops = t.window(), t.ops[0]
    both = spans.labelled(t.spans, [])
    assert xplane.breakdown(ops, window, both) == xplane.breakdown(
        ops, window, t.spans)
    assert xplane.idle_by_span(ops, window, both) == xplane.idle_by_span(
        ops, window, t.spans)


def test_the_read_back_labels_idle_gaps_in_a_chip_trace():
    assert os.path.getsize(FIXTURE) < 1 << 20
    t = xplane.load(FIXTURE)
    traced = spans.load_program_spans(FIXTURE)
    # the trace stops inside the first retrieve call's wave, so that
    # wave's own span is not in it
    assert {n for n, _, _, _ in traced} >= {
        "telerag.decode.steps", "telerag.decode.dispatch",
        "telerag.decode.readback", "telerag.retrieve",
        "telerag.retrieve.device", "telerag.retrieve.host",
        "telerag.retrieve.merge"}
    window, ops = t.window(), t.ops[0]
    gaps = xplane.idle_gaps(ops, window, spans.labelled(t.spans, traced))
    assert "telerag.decode.readback" in {label for label, _, _ in gaps}

#!/usr/bin/env python3
"""The program's host-clock spans in one run of one cell, in one process.

  python benchmarks/chip/spans.py --workload granite-20b-stage.hyde \\
      --seed 7 --seconds 30 --spans 1 --trace 1
  python benchmarks/chip/spans.py --fixture <output.xplane.pb>

Builds, warms and drives the cell as ``harness.run_cell`` does, with the
recorder's host spans (``FlightRecorder.enable_host_spans``) turned on
just before the window when ``--spans 1``.  The last line of standard
output is one JSON object: the end-to-end numbers and ``decode.step_ms``
from the benchmark's own readers, the four span metrics
(``SPAN_METRICS``) over the spans inside the window, and the largest
gap between a request's ``host_latency_s`` and the harness's latency.
With ``--trace 1`` it adds the benchmark's traced per-layer metrics, the
idle gaps labelled with the innermost of the ``bench.*`` and
``telerag.*`` spans, the share of idle seconds inside
``bench.decode_wave`` and ``bench.retrieve`` that a ``telerag.*`` span
labels, and how closely each span's duration in the recorder matches the
same span (same ``seq``) in the profiler trace.  There is no comparison
with the references: this measures spans, it decides nothing.

``--fixture`` records the trace of one drain of the cell's
configuration cut to one layer and 8 steps a wave, with host spans on,
for ``test_program_spans.py``.  Both need the chip, as ``run.py`` does.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.join(ROOT, "benchmarks", "chip")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")

PREFIX = "telerag."
ProgramSpan = Tuple[str, float, float, int]      # name, start_ns, end_ns, seq


# -- the span metrics: each reads the recorder's spans inside the window --

def _durations(spans, name: str) -> List[float]:
    return [s.dur for s in spans if s.name == name]


def readback_share(spans, t0: float, t1: float) -> Optional[float]:
    """Seconds reading decoded tokens back to the host over the window."""
    d = _durations(spans, "telerag.decode.readback")
    return sum(d) / (t1 - t0) if d else None


def dispatch_ms(spans, t0: float, t1: float) -> Optional[float]:
    """Mean host time of one decode step's dispatch, in ms."""
    d = _durations(spans, "telerag.decode.dispatch")
    return 1e3 * sum(d) / len(d) if d else None


def issue_ms(spans, t0: float, t1: float) -> Optional[float]:
    """Mean host time of one lookahead issue that moved pages, in ms."""
    d = [s.dur for s in spans if s.name == "telerag.lookahead.issue"
         and s.args.get("pages", 0) > 0]
    return 1e3 * sum(d) / len(d) if d else None


def host_share(spans, t0: float, t1: float) -> Optional[float]:
    """Seconds in the host miss partition over seconds in ``retrieve``."""
    total = sum(_durations(spans, "telerag.retrieve"))
    return (sum(_durations(spans, "telerag.retrieve.host")) / total
            if total else None)


SPAN_METRICS = {"decode.readback_share": readback_share,
                "decode.dispatch_ms": dispatch_ms,
                "lookahead.issue_ms": issue_ms,
                "retrieval.host_share": host_share}


def window_spans(spans, t0: float, t1: float) -> list:
    """The host spans that lie wholly inside [t0, t1]."""
    return [s for s in spans if t0 <= s.start and s.end <= t1]


# -- the same spans in a profiler trace -------------------------------------

def load_program_spans(path: str) -> List[ProgramSpan]:
    """The ``telerag.*`` host events of an ``.xplane.pb`` file, each with
    the ``seq`` its ``TraceAnnotation`` carries."""
    from jax.profiler import ProfileData

    out: List[ProgramSpan] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIX):
                        seq = dict(e.stats).get("seq", -1)
                        out.append((e.name, e.start_ns,
                                    e.start_ns + e.duration_ns, int(seq)))
    return out


def clock_agreement(host_spans, traced: List[ProgramSpan]) -> dict:
    """Pairs each traced span with the recorder's span of the same
    ``seq`` and name; counts the pairs whose durations differ by more
    than 5% or 50 µs, whichever is larger."""
    by_seq = {s.seq: s for s in host_spans}
    diffs = []
    for name, s, e, seq in traced:
        h = by_seq.get(seq)
        if h is not None and h.name == name:
            x = (e - s) * 1e-9
            diffs.append((abs(h.dur - x), max(0.05 * x, 50e-6)))
    return {"pairs": len(diffs),
            "outside": sum(d > tol for d, tol in diffs),
            "largest_gap_s": max((d for d, _ in diffs), default=None)}


def labelled(bench_spans, traced: List[ProgramSpan]):
    """``bench.*`` and ``telerag.*`` spans together, as ``xplane`` reads
    spans: (name, start_ns, end_ns)."""
    return list(bench_spans) + [(n, s, e) for n, s, e, _ in traced]


def idle_cover(ops, window, bench_spans, traced: List[ProgramSpan]) -> dict:
    """Idle seconds inside ``bench.decode_wave`` and ``bench.retrieve``
    calls, and the share of them labelled with a ``telerag.*`` span.  A
    gap counts for the part of it that lies inside a call, labelled by
    the innermost span at that part's middle."""
    from benchmarks.chip import xplane

    calls = [(s, e) for n, s, e in bench_spans if n in xplane.CALL_SPANS]
    spans = labelled(bench_spans, traced)
    inside = named = 0.0
    for _, s, e in xplane.idle_gaps(ops, window, spans):
        for cs, ce in calls:
            lo, hi = max(s, cs), min(e, ce)
            if hi > lo:
                inside += hi - lo
                if xplane.span_at(spans, (lo + hi) / 2).startswith(PREFIX):
                    named += hi - lo
    return {"idle_in_calls_s": inside * 1e-9,
            "share_labelled": named / inside if inside else None}


# -- one run -----------------------------------------------------------------

READERS = ("tokens_per_s", "latency_p50_s", "latency_p90_s",
           "decode.step_ms")


def run(c, *, seed: int, seconds: float, spans: bool, trace_dir, device,
        t_start: float) -> dict:
    """One run of cell ``c``; returns the result line's object."""
    import numpy as np

    from benchmarks.chip import cell as cell_mod
    from benchmarks.chip import datastore, harness, xplane
    from benchmarks.chip.peaks import peak_for
    from benchmarks.chip.traffic import Traffic
    from repro.obs import SYSTEM_CLOCK

    cfg, ds = c.config, c.config["datastore"]
    layout = cell_mod.load_layout(cfg)
    traffic = Traffic.from_dict(c.traffic)
    corpus = datastore.make_corpus(ds, seed)
    index = datastore.program_index(corpus, ds)
    params = layout.program_params(cfg, seed, device)
    srv, runner, probe = harness.build(cfg, layout.arch_config(cfg), params,
                                       index, device, seed)
    harness.warm_up(srv, traffic, corpus, cfg, seed)
    setup_s = time.perf_counter() - t_start
    rec = srv.recorder
    if spans:
        rec.enable_host_spans(SYSTEM_CLOCK)
    if trace_dir:
        shutil.rmtree(trace_dir, ignore_errors=True)
    t0, t1, drains, waves, rounds, wave_s, h2d, traced = harness.run_window(
        srv, probe, runner, traffic, corpus, cfg, seed, seconds, trace_dir)
    w = harness.Window(
        shape=layout.Shape.from_config(cfg), t0=t0, t1=t1, drains=drains,
        waves=waves, retrievals=rounds,
        latencies=[probe.last_touch[rid] - d.t0 for d in drains
                   for rid in d.request_ids],
        wave_seconds=list(wave_s), bytes_h2d=h2d,
        cluster_pages=np.asarray(index.paged.cluster_num_pages),
        page_size=int(ds["page_size"]),
        kv_page_size=int(cfg["serving"]["kv_page_size"]), setup_s=setup_s,
        peak=peak_for(device.device_kind) if device.platform == "tpu"
        else None, device_id=device.id)
    out: Dict[str, object] = {"seed": seed, "spans": spans,
                              "setup_s": setup_s, "window_s": t1 - t0,
                              "drains": len(drains)}
    names = list(READERS)
    if trace_dir:
        w.trace = xplane.load(xplane.find_xplane(trace_dir))
        w.trace_window = w.trace.window()
        w.traced_waves, w.traced_retrievals = traced
        names += [m["name"] for m in c.per_layer if m["name"] not in names]
    for name in names:
        out[name] = cell_mod.load_reader(name)(w)
    if not spans:
        return out
    in_window = window_spans(rec.host_spans, t0, t1)
    out["host_spans"] = len(in_window)
    for name, read in SPAN_METRICS.items():
        out[name] = read(in_window, t0, t1)
    out["host_latency_gap_s"] = max(
        abs(r.host_latency_s - (probe.last_touch[r.request_id] - d.t0))
        for d in drains for r in d.responses)
    if trace_dir:
        traced_spans = load_program_spans(xplane.find_xplane(trace_dir))
        ops = w.device_ops()
        both = labelled(w.trace.spans, traced_spans)
        out["clock"] = clock_agreement(rec.host_spans, traced_spans)
        out["idle_cover"] = idle_cover(ops, w.trace_window, w.trace.spans,
                                       traced_spans)
        out["breakdown"] = xplane.breakdown(ops, w.trace_window, both)
        out["idle_by_span"] = xplane.idle_by_span(ops, w.trace_window, both)
    return out


def record_fixture(path: str, device,
                   config: str = "granite-20b-stage") -> int:
    """One drain of the cell's HyDE traffic on configuration ``config``
    cut to one layer and 8 decode steps a wave, with host spans on,
    traced through its first decode wave and retrieve call; the trace
    is copied to ``path``.  The widths and the datastore stay as the
    cell runs them, so the kernels compile as they do there."""
    from benchmarks.chip import cell as cell_mod
    from benchmarks.chip import harness, xplane

    bench = cell_mod.load_benchmark()
    cfg = cell_mod.load_json(os.path.join(HERE, "configs",
                                          f"{config}.json"))
    cfg["model"]["num_hidden_layers"] = 1
    cfg["serving"]["max_steps"] = 8
    c = cell_mod.Cell(
        name=f"{config}.fixture", chips=1, config=cfg,
        traffic=cell_mod.load_json(os.path.join(HERE, "traffic",
                                                "hyde.json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
    trace_dir = os.path.join(TRACE_DIR, "fixture")
    harness.TRACE_SECONDS = 0.0         # stop once one of each is traced
    res = run(c, seed=1, seconds=1e-3, spans=True, trace_dir=trace_dir,
              device=device, t_start=time.perf_counter())
    shutil.copy(xplane.find_xplane(trace_dir), path)
    print(json.dumps(res), file=sys.stderr)
    print(f"{path}: {os.path.getsize(path)} bytes")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int)
    ap.add_argument("--seconds", type=float)
    ap.add_argument("--spans", type=int, choices=(0, 1), default=1)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--fixture", metavar="PATH")
    args = ap.parse_args(argv)
    if not args.fixture and None in (args.workload, args.seed, args.seconds):
        ap.error("--workload, --seed and --seconds, or --fixture")

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"spans.py: no TPU (JAX found {device.platform}); nothing was "
              f"run", file=sys.stderr)
        return 2
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(ROOT, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    if args.fixture:
        return record_fixture(args.fixture, device)
    from benchmarks.chip import cell as cell_mod
    c = cell_mod.load_cell(args.workload)
    res = run(c, seed=args.seed, seconds=args.seconds,
              spans=bool(args.spans),
              trace_dir=TRACE_DIR if args.trace else None, device=device,
              t_start=T_START)
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

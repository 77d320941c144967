#!/usr/bin/env python
"""Validate a Chrome/Perfetto trace-event JSON file emitted by
``repro.obs.export.write_trace`` (CI runs this on the openloop smoke
trace so the exporter cannot silently drift from the format
ui.perfetto.dev loads; format documented in docs/OBSERVABILITY.md).

Checks, beyond JSON well-formedness:

* top level is ``{"traceEvents": [...]}``;
* every event has a phase ``ph`` and a ``pid``, with ``ts >= 0`` on
  timed phases;
* complete spans (``"X"``) have non-negative ``dur``;
* async begin/end pairs (``"b"``/``"e"``) balance per (cat, id);
* counter events (``"C"``) exist and include the ledger-occupancy and
  pool-free-pages tracks the acceptance criteria require;
* host-clock processes (named "host clock: ..."): every span is a
  ``telerag.*`` span of cat ``host`` on one of the named lanes
  (wave / decode / lookahead / retrieval), and the spans of one lane
  nest — each lies wholly inside or wholly after the one before.

After format validation the trace is replayed through the
happens-before invariant checker (``repro.analysis.invariants``):
use-before-land races, double releases, ledger drift and
stall-without-resume all fail the check.  The lossless sibling
``<trace>.jsonl`` stream is preferred (full checks, including pool
conservation); when only the Perfetto JSON exists the events are
reconstructed from it (race/ordering checks only).  Pass an explicit
JSONL path as a second argument to override the sibling lookup.

Usage:  python tools/check_trace.py experiments/bench/openloop_trace.json
        python tools/check_trace.py trace.json stream.jsonl
"""

from __future__ import annotations

import json
import os
import sys
from typing import Dict, Tuple

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), os.pardir, "src"))

from repro.analysis import (check_events, events_from_jsonl,     # noqa: E402
                            events_from_perfetto)
from repro.obs.export import HOST_LANES                          # noqa: E402

# phases that must carry a timestamp
_TIMED = {"X", "B", "E", "b", "e", "i", "C"}

# counter tracks write_trace always emits on a served run
REQUIRED_COUNTERS = {"ledger_occupancy", "pool_free_pages"}


def validate_trace(doc: Dict) -> Dict[str, int]:
    """Assert ``doc`` is a loadable trace; returns phase counts."""
    assert isinstance(doc, dict), type(doc)
    events = doc.get("traceEvents")
    assert isinstance(events, list), "missing traceEvents list"
    assert events, "empty traceEvents"

    phases: Dict[str, int] = {}
    async_open: Dict[Tuple[str, object], int] = {}
    counters = set()
    for i, ev in enumerate(events):
        assert isinstance(ev, dict), (i, ev)
        ph = ev.get("ph")
        assert isinstance(ph, str) and ph, f"event {i} missing ph: {ev}"
        assert "pid" in ev, f"event {i} missing pid: {ev}"
        phases[ph] = phases.get(ph, 0) + 1
        if ph in _TIMED:
            ts = ev.get("ts")
            assert isinstance(ts, (int, float)) and ts >= -1e-9, \
                f"event {i} bad ts: {ev}"
        if ph == "X":
            dur = ev.get("dur")
            assert isinstance(dur, (int, float)) and dur >= -1e-9, \
                f"event {i} bad dur: {ev}"
        elif ph in ("b", "e"):
            key = (ev.get("cat"), ev.get("id"))
            assert key[1] is not None, f"async event {i} missing id: {ev}"
            async_open[key] = async_open.get(key, 0) + (1 if ph == "b" else -1)
        elif ph == "C":
            assert isinstance(ev.get("args"), dict) and ev["args"], \
                f"counter event {i} missing args: {ev}"
            counters.add(ev.get("name"))

    unbalanced = {k: v for k, v in async_open.items() if v != 0}
    assert not unbalanced, f"unbalanced async spans: {unbalanced}"
    missing = REQUIRED_COUNTERS - counters
    assert not missing, \
        f"missing required counter tracks: {sorted(missing)} " \
        f"(have {sorted(counters)})"
    validate_host_lanes(events)
    return phases


def validate_host_lanes(events) -> int:
    """Assert the host-clock processes' lanes hold nested ``telerag.*``
    spans; returns how many host spans there are."""
    host = {ev["pid"] for ev in events
            if ev.get("ph") == "M" and ev.get("name") == "process_name"
            and str(ev["args"].get("name", "")).startswith("host clock")}
    lanes = {(ev["pid"], ev["tid"]): ev["args"].get("name")
             for ev in events if ev.get("ph") == "M"
             and ev.get("name") == "thread_name" and ev["pid"] in host}
    per_lane: Dict[Tuple[int, int], list] = {}
    for i, ev in enumerate(events):
        if ev.get("ph") != "X" or ev["pid"] not in host:
            continue
        key = (ev["pid"], ev.get("tid"))
        assert lanes.get(key) in HOST_LANES, \
            f"host span {i} on an unnamed lane: {ev}"
        assert ev.get("cat") == "host" and \
            str(ev.get("name", "")).startswith("telerag."), \
            f"host span {i} is not a telerag.* host span: {ev}"
        per_lane.setdefault(key, []).append(
            (ev["ts"], ev["ts"] + ev["dur"], ev["name"]))
    eps = 1e-3                                          # µs
    for key, spans in per_lane.items():
        open_: list = []
        for s, e, name in sorted(spans, key=lambda x: (x[0], -x[1])):
            while open_ and open_[-1][1] <= s + eps:
                open_.pop()
            assert not open_ or e <= open_[-1][1] + eps, \
                f"host span {name} [{s}, {e}] crosses the end of " \
                f"{open_[-1][2]} on lane {lanes[key]} of pid {key[0]}"
            open_.append((s, e, name))
    return sum(len(v) for v in per_lane.values())


def check_invariants(doc: Dict, path: str,
                     jsonl: str = None) -> int:
    """Replay the trace's happens-before invariants; returns the
    violation count (0 = clean).  Prefers the lossless JSONL stream."""
    if int(doc.get("otherData", {}).get("dropped_events", 0) or 0):
        print("invariants: skipped (recorder dropped events — the "
              "surviving window cannot balance)")
        return 0
    if jsonl is None:
        sibling = os.path.splitext(path)[0] + ".jsonl"
        jsonl = sibling if os.path.exists(sibling) else None
    if jsonl is not None:
        events, src = events_from_jsonl(jsonl), jsonl
    else:
        events = events_from_perfetto(doc)
        src = f"{path} (reconstructed — race/ordering checks only)"
    rep = check_events(events)
    for v in rep.violations:
        print(v.render())
    print(f"invariants {src}: {rep.summary()}")
    return len(rep.violations)


def main(argv) -> int:
    if len(argv) not in (2, 3):
        print(__doc__)
        return 2
    with open(argv[1]) as f:
        doc = json.load(f)
    phases = validate_trace(doc)
    total = sum(phases.values())
    print(f"OK {argv[1]}: {total} events "
          + " ".join(f"{ph}={n}" for ph, n in sorted(phases.items())))
    return 1 if check_invariants(doc, argv[1],
                                 argv[2] if len(argv) == 3 else None) else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))

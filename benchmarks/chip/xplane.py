"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read: device busy time, each kernel's device time, and
the device's idle gaps, each labelled with the host span it fell in.

Device operations are the events on the ``XLA Ops`` line of each
``/device:TPU:<n>`` plane; host spans are the ``bench.*`` annotations the
harness writes (``bench.drain``, ``bench.decode_wave``,
``bench.retrieve``, ``bench.lookahead``) on the host plane.  Both are on
one clock.  An op's event name is its HLO text; ops are matched by the
instruction name before `` = `` (``flash_decode_paged.6``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[float, float]           # (start_ns, end_ns)

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
CALL_SPANS = ("bench.decode_wave", "bench.retrieve")
# control-flow ops span the ops of their bodies: not listed on their own
CONTAINERS = (" while(", " conditional(", " call(")


def op_name(event_name: str) -> str:
    """The HLO instruction name of an op event (``%x.3 = ...`` -> x.3)."""
    return event_name.split(" = ", 1)[0].lstrip("%")


@dataclasses.dataclass
class Trace:
    """The events of one traced window."""

    ops: Dict[int, List[Tuple[str, float, float]]]   # device -> ops
    spans: List[Tuple[str, float, float]]            # host bench.* spans

    def window(self) -> Interval:
        """From the start of the first decode-wave or retrieve span to
        the end of the last one the trace holds whole."""
        calls = [(s, e) for n, s, e in self.spans if n in CALL_SPANS]
        if not calls:
            raise ValueError("the trace holds no bench.decode_wave or "
                             "bench.retrieve span")
        return min(s for s, _ in calls), max(e for _, e in calls)

    def calls(self, name: str) -> int:
        """How many ``name`` spans the trace holds."""
        return sum(n == name for n, _, _ in self.spans)


def start(trace_dir: str) -> None:
    """Start the profiler for a trace this module reads: device ops and
    the host's ``TraceAnnotation`` spans, without the Python tracer,
    which records every Python call and is most of a trace's size."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_xplane(trace_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace dir."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read the device ops and host spans of an ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    ops: Dict[int, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            dev = int(plane.name.rsplit(":", 1)[1])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops.setdefault(dev, []).extend(
                        (e.name, e.start_ns, e.start_ns + e.duration_ns)
                        for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return Trace(ops=ops, spans=spans)


def clip(ops: Sequence[Tuple[str, float, float]], window: Interval):
    """The ops that overlap ``window``, cut to it."""
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ops
            if e > lo and s < hi]


def union(intervals: Sequence[Interval]) -> List[Interval]:
    """Merge intervals into disjoint sorted ones."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_ns(ops, window: Interval) -> float:
    """Nanoseconds of ``window`` in which some op ran."""
    return sum(e - s for s, e in union([(s, e) for _, s, e in
                                        clip(ops, window)]))


def kernel_ns(ops, window: Interval, names: Sequence[str]) -> Tuple[float, int]:
    """Summed device time and count of the ops in ``window`` whose
    instruction name starts with any of ``names``."""
    hit = [(s, e) for n, s, e in clip(ops, window)
           if op_name(n).startswith(tuple(names))]
    return sum(e - s for s, e in hit), len(hit)


def span_at(spans, t: float) -> str:
    """The innermost (shortest) host span around time ``t``."""
    best: Optional[Tuple[float, str]] = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[0]):
            best = (e - s, n)
    return best[1] if best else "outside bench spans"


def idle_gaps(ops, window: Interval, spans) -> List[Tuple[str, float, float]]:
    """Gaps in ``window`` with no op running: (label, start, end), the
    label being the host span around the gap's middle."""
    lo, hi = window
    gaps, t = [], lo
    for s, e in union([(s, e) for _, s, e in clip(ops, window)]):
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if hi > t:
        gaps.append((t, hi))
    return [(span_at(spans, (s + e) / 2), s, e) for s, e in gaps]


def breakdown(ops, window: Interval, spans, top: int = 10) -> dict:
    """The ``breakdown`` of a result line: the device ops that took most
    time (summed by name) and the longest idle gaps by host span, each
    in seconds."""
    per_op: Dict[str, float] = defaultdict(float)
    for n, s, e in clip(ops, window):
        if not any(c in n for c in CONTAINERS):
            per_op[op_name(n)] += e - s
    dev = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]
    gaps = sorted(idle_gaps(ops, window, spans), key=lambda g: g[1] - g[2])
    return {"device_ops": [[n, ns * 1e-9] for n, ns in dev],
            "idle_gaps": [[f"{label} @{(s - window[0]) * 1e-9:.3f}s",
                           (e - s) * 1e-9] for label, s, e in gaps[:top]]}


def idle_by_span(ops, window: Interval, spans) -> Dict[str, float]:
    """Idle seconds summed by the host span they fell in."""
    out: Dict[str, float] = defaultdict(float)
    for label, s, e in idle_gaps(ops, window, spans):
        out[label] += (e - s) * 1e-9
    return dict(out)

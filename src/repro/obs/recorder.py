"""The flight recorder: one structured event bus for the serving stack.

Every subsystem emits typed ``TraceEvent``s — request lifecycle marks,
timeline spans, wave form/dispatch/complete, transfer issue/land,
admission decisions, pool lease/release, decode steps, counter samples
— stamped on the **shared event clock** (modeled seconds, the same
clock ``RetrievalRuntime`` and ``TeleRAGServer`` advance).  One
``FlightRecorder`` serves a whole ``TeleRAGServer``: every replica
engine's components are attached to it with their replica id, so
cross-replica correlation (which wave, which tenant, which request)
is a filter over one stream instead of a join across ad-hoc logs.

Clock discipline: components deep in the stack (the pool, the
admission controller) do not receive ``now`` — they stamp events at
``recorder.now``, which the runtime advances via ``tick()`` at every
event-loop step.  Events may therefore be *appended* slightly out of
``t`` order (a wave's completion is emitted at schedule time with its
future timestamp); consumers that need time order use
``sorted_events()``.

``legacy_tuples()`` is the compatibility shim for the retired
``RetrievalRuntime.event_log`` list: the same ``(t, label,
request_id)`` 3-tuples, in emission order, filtered to one replica's
lane — existing tests and benches keep iterating it unchanged.

Host-clock spans are the recorder's second, opt-in stream.  Event-clock
stamps are modeled; ``span(name, **args)`` times real host work on an
injected real clock (``enable_host_spans``).  Off by default, a span is
one shared null context; on, it stamps start and end from
``clock.perf()``, carries the replica and wave ids of the wave it runs
in, lands in ``host_spans`` (apart from ``events``, so the event stream
stays replay-deterministic) and opens a
``jax.profiler.TraceAnnotation`` of the same name, so a profiler trace
holds the same span on the clock of the device's ops.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

# the request-lifecycle labels the retired ``runtime.event_log`` carried;
# ``legacy_tuples()`` reproduces exactly these (a server-side "submit"
# mark is NOT one of them — it never appeared in the legacy log)
LEGACY_LABELS = frozenset({
    "admit", "prefetch", "generate", "retrieve", "complete",
    "pressure_stall", "pressure_resume", "prefetch_demoted",
})


@dataclass(frozen=True)
class TraceEvent:
    """Base event: a kind, a stamp on the shared event clock (seconds),
    and the correlation ids every consumer filters by.  ``replica=-1``
    means "not attached to a replica" (a standalone engine, or the
    server itself); ``request_id``/``wave_id`` are -1 when the event is
    not about one request/wave."""

    t: float
    kind: str
    replica: int = -1
    request_id: int = -1
    wave_id: int = -1
    tenant: str = "shared"


@dataclass(frozen=True)
class RequestEvent(TraceEvent):
    """One request-lifecycle mark (``kind="request"``): ``label`` is
    the lifecycle step (``submit`` / ``admit`` / ``prefetch`` /
    ``generate`` / ``retrieve`` / ``complete`` / ``pressure_stall`` /
    ``pressure_resume`` / ``prefetch_demoted``)."""

    label: str = ""
    round_index: int = -1


@dataclass(frozen=True)
class SpanEvent(TraceEvent):
    """One request-timeline interval (``kind="span"``): mirrors the
    ``Span`` appended to ``RequestRecord.timeline`` (``name`` is the
    span kind, ``t`` its start, ``dur`` its length — 0 for instants)."""

    name: str = ""
    dur: float = 0.0
    round_index: int = -1


@dataclass(frozen=True)
class WaveEvent(TraceEvent):
    """One wave-lifecycle mark: ``wave.form`` when the executor takes
    the wave up, ``wave.dispatch`` when it actually executes (a parked
    wave forms but never dispatches — it dissolves and its members ride
    a later wave), ``wave.complete`` at its last member's scheduled
    round end.  ``transfer_id`` correlates the dispatch with the wave's
    lookahead copy (-1 = no prefetch moved)."""

    size: int = 0
    request_ids: Tuple[int, ...] = ()
    rounds: Tuple[int, ...] = ()
    transfer_id: int = -1
    nbytes: int = 0


@dataclass(frozen=True)
class TransferRecord(TraceEvent):
    """One H2D copy on the modeled link: ``transfer.issue`` at submit,
    ``transfer.land`` at its modeled completion (emitted at schedule
    time with the future stamp).  Mirrors ``TransferEvent``."""

    transfer_id: int = -1
    nbytes: int = 0
    n_clusters: int = 0
    channel: int = -1
    start_t: float = 0.0
    end_t: float = 0.0
    transfer_kind: str = "prefetch"


@dataclass(frozen=True)
class AdmissionEvent(TraceEvent):
    """One admission decision: ``admission.admit`` (full headroom),
    ``admission.stall`` (parked ``PRESSURE_STALLED``),
    ``admission.cap`` (granted below the request),
    ``admission.spill`` (the spill hook reclaimed pages), or
    ``admission.resume`` (a parked wave woken by a page-free)."""

    owner: str = ""
    pages_requested: int = 0
    pages_granted: int = 0
    spilled_pages: int = 0


@dataclass(frozen=True)
class PoolEvent(TraceEvent):
    """One page-pool allocation edge: ``pool.lease`` / ``pool.release``
    with the post-op free-page count and ledger occupancy — the
    exporters' counter tracks (pool free pages, ledger occupancy,
    per-tenant KV bytes) are derived from this stream."""

    owner: str = ""                   # ledger category: prefetch | kv | ...
    pages: int = 0
    nbytes: int = 0
    free_pages: int = 0
    occupancy: float = 0.0


@dataclass(frozen=True)
class KVEvent(TraceEvent):
    """One decode-cache lease edge (``kv.acquire`` / ``kv.append`` /
    ``kv.splice`` / ``kv.release`` / ``kv.drop``): the KV manager's view
    on top of the pool's byte accounting.  Dense bucket leases emit
    acquire/release with ``lease_id=-1`` (``recycled=True`` when the
    acquire reused a released bucket instead of allocating, and
    ``kv.drop`` when a recycled bucket's bytes finally return to the
    pool — together these keep the checker's kv accounting
    conservation-exact across bucket recycling); paged (block-table)
    leases additionally carry a globally unique ``lease_id``, their slab
    page count (``pages``) and — on every ``kv.append`` — the
    post-append max sequence ``length``, which is what the invariant
    checker conserves (page conservation per lease,
    append-within-lease ordering, no append past ``max_len``).
    ``kv.splice`` marks precomputed chunk-KV pages attached to an open
    paged lease by block-table edit: ``pages`` spliced page slots,
    ``length`` the post-splice max length, ``max_len`` the lease's
    raised capacity."""

    batch: int = 0
    max_len: int = 0
    nbytes: int = 0
    lease_id: int = -1                # paged leases only; -1 = dense bucket
    pages: int = 0                    # slab page slots held by the lease
    length: int = 0                   # kv.append: max lengths after the write
    recycled: bool = False            # dense acquire reused a released bucket


@dataclass(frozen=True)
class ChunkKVEvent(TraceEvent):
    """One chunk-KV residency edge (``chunk.load`` / ``chunk.pin`` /
    ``chunk.unpin`` / ``chunk.evict``): the lifecycle of one document's
    precomputed KV pages on device.  ``chunk.load`` lands ``pages``
    slab pages H2D (charged to the pool as owner ``"chunk_kv"``);
    ``chunk.pin``/``chunk.unpin`` bracket a wave's splice (``pinned``
    is the post-op pin count — pinned residency is protected from
    spill); ``chunk.evict`` returns the pages (legal only at
    ``pinned == 0``).  The invariant checker conserves pages per
    (replica, doc) and rejects pin-before-load (the splice-before-land
    race) and evict-while-pinned."""

    doc_id: int = -1
    pages: int = 0
    nbytes: int = 0
    pinned: int = 0                   # pin count after this event


@dataclass(frozen=True)
class DecodeStep(TraceEvent):
    """One observed decode outcome (``kind="decode"``): the hook ran
    ``tokens`` real steps in ``seconds`` measured wall clock for a wave
    of ``batch`` (mirrors ``DecodeEvent``, which drives the clock)."""

    tokens: int = 0
    seconds: float = 0.0
    batch: int = 0


@dataclass(frozen=True)
class CounterSample(TraceEvent):
    """One sampled scalar (``kind="counter"``) for exporter counter
    tracks the pool stream cannot derive (e.g. per-replica queue
    depth)."""

    name: str = ""
    value: float = 0.0


@dataclass(frozen=True)
class HostSpan:
    """One interval of host work on the real clock: ``start`` and
    ``end`` in seconds of the injected clock's ``perf()``, the replica
    and wave it ran in (-1 outside a wave), ``seq`` (the span's number,
    also an arg of its ``TraceAnnotation``, which pairs the two) and the
    call's args."""

    name: str
    start: float
    end: float
    replica: int = -1
    wave_id: int = -1
    seq: int = -1
    args: Dict[str, object] = field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclass
class HostRequest:
    """One request's host-clock stamps: submitted at ``submit_s``; the
    last decode wave or retrieve call that worked for it ended at
    ``done_s`` (None until one has)."""

    request_id: int
    replica: int
    submit_s: float
    done_s: Optional[float] = None


class _NullSpan:
    """The span while host spans are off: enters, takes args and exits
    as nothing."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set(self, **args: object) -> None:
        pass


NULL_SPAN = _NullSpan()
"""The one shared span of a recorder with host spans off (and of code
with no recorder)."""


class _LiveSpan:
    """One open host span (see ``FlightRecorder.span``)."""

    __slots__ = ("rec", "name", "args", "replica", "wave_id", "seq",
                 "start", "_ann", "_outer")

    def __init__(self, rec: "FlightRecorder", name: str,
                 replica: Optional[int], wave_id: Optional[int],
                 args: Dict[str, object]):
        self.rec, self.name, self.args = rec, name, args
        self.replica = rec._replica if replica is None else replica
        self.wave_id = rec._wave if wave_id is None else wave_id

    def __enter__(self) -> "_LiveSpan":
        rec = self.rec
        self._outer = (rec._replica, rec._wave)
        rec._replica, rec._wave = self.replica, self.wave_id
        rec._seq += 1
        self.seq = rec._seq
        self._ann = rec._annotate(self.name, seq=self.seq,
                                  replica=self.replica, wave=self.wave_id,
                                  **self.args)
        self._ann.__enter__()
        self.start = rec.host_clock.perf()
        return self

    def set(self, **args: object) -> None:
        """Add args known only once the work has run (pages moved)."""
        self.args.update(args)
        self._ann.set_metadata(**args)

    def __exit__(self, *exc) -> bool:
        rec = self.rec
        end = rec.host_clock.perf()
        self._ann.__exit__(*exc)
        rec._replica, rec._wave = self._outer
        rec.host_spans.append(HostSpan(
            name=self.name, start=self.start, end=end,
            replica=self.replica, wave_id=self.wave_id, seq=self.seq,
            args=self.args))
        if rec.capacity is not None and len(rec.host_spans) > rec.capacity:
            del rec.host_spans[:len(rec.host_spans) // 2]
        return False


@dataclass
class FlightRecorder:
    """Append-only typed event log on the shared event clock.

    ``now`` is the recorder's clock cursor, advanced monotonically by
    ``tick()`` from whichever runtime is stepping — it is what
    emitters without a ``now`` of their own (pool, admission) stamp
    with.  ``capacity`` bounds memory for long-lived servers: when
    exceeded, the oldest half of the log is dropped (a flight recorder
    keeps the recent past; ``dropped`` counts the loss so analyzers
    can report a truncated window instead of silently lying).  The same
    bound halves ``host_spans``."""

    capacity: Optional[int] = None
    now: float = 0.0
    events: List[TraceEvent] = field(default_factory=list)
    dropped: int = 0
    # host-clock spans (``span``): off while ``host_clock`` is None
    host_clock: Optional[object] = None
    host_spans: List[HostSpan] = field(default_factory=list)
    host_requests: List[HostRequest] = field(default_factory=list)
    _annotate: Optional[Callable] = field(default=None, repr=False)
    _replica: int = field(default=-1, repr=False)
    _wave: int = field(default=-1, repr=False)
    _seq: int = field(default=0, repr=False)

    def tick(self, t: float) -> float:
        """Advance the clock cursor (monotone); returns the cursor."""
        if t > self.now:
            self.now = t
        return self.now

    def emit(self, ev: TraceEvent) -> TraceEvent:
        """Append one event (also advances ``now`` to the event's stamp
        when it is ahead — emitters schedule future completions)."""
        self.events.append(ev)
        if self.capacity is not None and len(self.events) > self.capacity:
            drop = len(self.events) // 2
            del self.events[:drop]
            self.dropped += drop
        return ev

    # -- host-clock spans ----------------------------------------------------
    def enable_host_spans(self, clock) -> None:
        """Turn host spans on, timed by ``clock`` (``obs.clock``'s
        ``SystemClock``).  A clock that does not measure (``real`` False,
        the ``EventClock``) is refused: its spans would all read 0."""
        if not getattr(clock, "real", False):
            raise ValueError("host spans need a real clock, not "
                             f"{type(clock).__name__}")
        from jax.profiler import TraceAnnotation
        self._annotate = TraceAnnotation
        self.host_clock = clock

    def span(self, name: str, *, replica: Optional[int] = None,
             wave: Optional[int] = None, **args: object):
        """Context manager timing one piece of host work as a
        ``HostSpan`` (yields an object whose ``set(**args)`` adds args
        at the end).  ``replica``/``wave`` set the ids for this span and
        every span opened inside it; left out, they are inherited.  Off,
        it returns ``NULL_SPAN``: no clock read, nothing stored."""
        if self.host_clock is None:
            return NULL_SPAN
        return _LiveSpan(self, name, replica, wave, args)

    def host_now(self) -> Optional[float]:
        """The host clock's reading, or None while host spans are off."""
        return None if self.host_clock is None else self.host_clock.perf()

    def host_request(self, request_id: int, replica: int,
                     submit_s: Optional[float] = None,
                     ) -> Optional[HostRequest]:
        """Start one request's host stamps (submitted at ``submit_s``,
        default now); None while host spans are off."""
        if self.host_clock is None:
            return None
        hr = HostRequest(request_id, replica,
                         self.host_clock.perf() if submit_s is None
                         else submit_s)
        self.host_requests.append(hr)
        return hr

    # -- queries -------------------------------------------------------------
    def of(self, *kinds: str) -> List[TraceEvent]:
        """Events whose kind is one of ``kinds`` (emission order)."""
        want = set(kinds)
        return [e for e in self.events if e.kind in want]

    def for_request(self, request_id: int) -> List[TraceEvent]:
        """Every event correlated to one request (emission order)."""
        return [e for e in self.events if e.request_id == request_id]

    def sorted_events(self) -> List[TraceEvent]:
        """All events in event-clock order (stable for equal stamps)."""
        return sorted(self.events, key=lambda e: e.t)

    def request_marks(self, request_id: int) -> Dict[str, float]:
        """label -> first event-clock time, over one request's
        lifecycle marks (the admit<=dispatch<=complete ordering check
        reads this)."""
        out: Dict[str, float] = {}
        for e in self.events:
            if (e.kind == "request" and e.request_id == request_id
                    and e.label not in out):
                out[e.label] = e.t
        return out

    def legacy_tuples(self, replica: Optional[int] = None,
                      ) -> List[Tuple[float, str, int]]:
        """The retired ``runtime.event_log`` view: ``(t, label,
        request_id)`` tuples in emission order, filtered to one
        replica's lane (None = all lanes) and to the labels the legacy
        log carried."""
        return [(e.t, e.label, e.request_id) for e in self.events
                if e.kind == "request" and e.label in LEGACY_LABELS
                and (replica is None or e.replica == replica)]

    def clear(self) -> None:
        """Drop all events and host spans (the clock cursor is kept —
        it is shared with live runtimes and must stay monotone)."""
        self.events.clear()
        self.dropped = 0
        self.host_spans.clear()
        self.host_requests.clear()

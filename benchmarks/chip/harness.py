"""One run of one cell: set-up, the measured window, the metrics and the
comparison that decides ``correct``.

``run_cell`` takes the cell's files as loaded by ``cell.py`` and the
device to run on; ``run.py`` is the command that checks for the chip
first.  The served path is the program's own: ``TeleRAGServer`` with
continuous batching, ``DecodeRunner`` paged decode through
``flash_decode_paged``, lookahead into the ``DevicePagePool`` and the
fused ``probe_and_topk`` with the host miss partition, built as
``launch/serve.build_server`` builds it but with the cell's sizes.
"""

from __future__ import annotations

import dataclasses
import gc
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from benchmarks.chip import cell as cell_mod
from benchmarks.chip import check, counts, datastore, xplane
from benchmarks.chip.peaks import peak_for
from benchmarks.chip.probe import Probe, Retrieval, Wave
from benchmarks.chip.traffic import Traffic, make_requests

_COMPILES: List[float] = []     # backend compile seconds, this process
_LISTENING = False
TRACE_SECONDS = 10.0            # a traced run traces this much of its window


def _listen_compiles() -> None:
    """Count backend compiles (a persistent-cache read is one too)."""
    global _LISTENING
    if not _LISTENING:
        jax.monitoring.register_event_duration_secs_listener(
            lambda event, secs, **_: _COMPILES.append(secs)
            if event == "/jax/core/compile/backend_compile_duration" else None)
        _LISTENING = True


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Drain:
    t0: float
    t1: float
    request_ids: List[int]
    responses: list


@dataclasses.dataclass
class Window:
    """What a metric reader reads: the window's calls, counts and times,
    and the trace of a traced run."""

    shape: counts.Shape
    t0: float
    t1: float
    drains: List[Drain]
    waves: List[Wave]
    retrievals: List[Retrieval]
    latencies: List[float]
    wave_seconds: List[float]      # DecodeRunner.wave_step_seconds
    bytes_h2d: int
    cluster_pages: np.ndarray      # pages of each cluster
    page_size: int
    kv_page_size: int
    setup_s: float
    peak: object
    trace: Optional[xplane.Trace] = None
    trace_window: Optional[tuple] = None
    traced_waves: List[Wave] = dataclasses.field(default_factory=list)
    traced_retrievals: List[Retrieval] = dataclasses.field(
        default_factory=list)
    device_id: int = 0

    @property
    def span_s(self) -> float:
        return self.t1 - self.t0

    def device_ops(self):
        return self.trace.ops.get(self.device_id, []) if self.trace else []


def build(cfg: dict, arch, params, index, device, seed: int):
    """The served path with the cell's sizes: one replica on ``device``."""
    from repro.obs import SystemClock
    from repro.serving import (DecodeRunner, EngineConfig, KVCacheManager,
                               TeleRAGServer)

    sv, ds = cfg["serving"], cfg["datastore"]
    runner = DecodeRunner(params, arch, max_len=sv["max_len"],
                          max_steps=sv["max_steps"],
                          page_size=sv["kv_page_size"],
                          slab_seqs=sv["slab_seqs"])
    probe = Probe(runner, rows=sv["micro_batch"])
    kv_bytes = KVCacheManager(arch).nbytes(sv["micro_batch"], sv["max_len"])
    pool_pages = ds["buffer_pages"] + -(-kv_bytes
                                        // index.paged.page_nbytes())
    srv = TeleRAGServer(index, EngineConfig(
        nprobe=ds["nprobe"], top_k=ds["top_k"],
        buffer_pages=ds["buffer_pages"], pool_pages=pool_pages,
        lookahead_rank=min(2 * ds["nprobe"], index.num_clusters),
        kernel_mode="auto", cache_enabled=True, chips=1, paged_decode=True,
        seed=seed), 1, arch, micro_batch=sv["micro_batch"],
        include_tail=True, decode_hook=probe, continuous=True,
        wall_clock=SystemClock(), devices=[device])
    runner.attach(srv)
    for eng in srv.engines:
        eng.calibrate_tcc()
        probe.wrap(eng)
    return srv, runner, probe


def warm_up(srv, traffic: Traffic, corpus, cfg: dict, seed: int) -> None:
    """Compile every shape the window meets: one drain of the cell's
    traffic on another stream of the seed, every retrieval and lookahead
    batch size up to the micro-batch, and every page-scatter bucket up
    to twice the prefetch buffer (a load scatters its pages and the
    invalidations queued before it together)."""
    sv, ds = cfg["serving"], cfg["datastore"]
    srv.serve(make_requests(traffic, corpus.vectors, seed=seed, drain=0,
                            max_len=sv["max_len"], warm=True))
    eng = srv.engines[0]
    rng = np.random.default_rng(0)
    q = rng.standard_normal((sv["micro_batch"], corpus.vectors.shape[1]))
    q = (q / np.linalg.norm(q, axis=-1, keepdims=True)).astype(np.float32)
    for b in range(1, sv["micro_batch"] + 1):
        eng.plan_lookahead(q[:b], [32] * b)
        eng.retrieve(q[:b])
    pool = eng.pool
    page = np.zeros((ds["page_size"], corpus.vectors.shape[1]), np.float32)
    ids = np.full(ds["page_size"], -1, np.int32)
    cap = 8
    while True:
        # every slot out of range: the scatter drops them, state unchanged
        pool.scatter([pool.pages.shape[0]] * cap, [page] * cap, [ids] * cap,
                     [-1] * cap)
        if cap >= 2 * ds["buffer_pages"]:
            break
        cap *= 2
    jax.block_until_ready(pool.pages)


class Tracer:
    """Traces the first ``TRACE_SECONDS`` of the window: the profiler
    stops after the first decode wave or retrieve call that ends past
    them once the trace holds one of each, so the trace holds whole
    calls, and remembers how many."""

    def __init__(self, probe, trace_dir: str):
        self.probe, self.calls, self.kinds = probe, None, set()
        xplane.start(trace_dir)
        self.t0 = time.perf_counter()
        probe.after_call = self.maybe_stop

    def maybe_stop(self, kind: str) -> None:
        self.kinds.add(kind)
        if (len(self.kinds) == 2
                and time.perf_counter() - self.t0 >= TRACE_SECONDS):
            self.stop()

    def stop(self) -> None:
        if self.calls is None:
            jax.profiler.stop_trace()
            self.calls = (len(self.probe.waves), len(self.probe.retrievals))
            self.probe.after_call = None


def run_window(srv, probe, runner, traffic: Traffic, corpus, cfg: dict,
               seed: int, seconds: float, trace_dir: Optional[str]):
    """Whole drains of the closed loop until ``seconds`` have passed:
    every drain started before then runs to its end."""
    eng = srv.engines[0]
    w0, r0 = len(probe.waves), len(probe.retrievals)
    s0 = len(runner.wave_step_seconds)
    h2d0 = eng.buffer.stats.bytes_h2d
    tracer = Tracer(probe, trace_dir) if trace_dir else None
    drains: List[Drain] = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        reqs = make_requests(traffic, corpus.vectors, seed=seed,
                             drain=len(drains),
                             max_len=cfg["serving"]["max_len"])
        with jax.profiler.TraceAnnotation("bench.drain"):
            d0 = time.perf_counter()
            responses = srv.serve(reqs)
            d1 = time.perf_counter()
        drains.append(Drain(d0, d1, [r.trace.request_id for r in reqs],
                            responses))
    t1 = time.perf_counter()
    traced = None
    if tracer is not None:
        tracer.stop()
        traced = (probe.waves[w0:tracer.calls[0]],
                  probe.retrievals[r0:tracer.calls[1]])
    return (t0, t1, drains, probe.waves[w0:], probe.retrievals[r0:],
            runner.wave_step_seconds[s0:], eng.buffer.stats.bytes_h2d - h2d0,
            traced)


def failures(drains: List[Drain], num_vectors: int) -> int:
    """Requests that did not complete with in-range doc ids each round."""
    from repro.serving import RequestState
    bad = 0
    for d in drains:
        got = {r.request_id: r for r in d.responses}
        for rid in d.request_ids:
            r = got.get(rid)
            ok = (r is not None and r.state == RequestState.COMPLETE
                  and len(r.doc_ids) == len(r.rounds))
            if ok:
                for ids in r.doc_ids:
                    a = np.asarray(ids).ravel()
                    ok &= bool(a.size and ((a >= 0) & (a < num_vectors)).all())
            bad += not ok
    return bad


def run_cell(c: "cell_mod.Cell", *, seed: int, seconds: float, trace: bool,
             device, t_start: float, trace_dir: str,
             control: bool = False,
             after_build: Optional[Callable] = None) -> dict:
    """One run of cell ``c``; returns the result line's object.

    ``control`` adds the controls' readings to ``compared`` (for setting
    limits; the benchmark's runs leave it off).  ``after_build`` is
    called with (server, runner) before the warm-up: tests use it to
    break the served path underneath."""
    _listen_compiles()
    cfg = c.config
    limits = check.limits_of(cfg)
    layout = cell_mod.load_layout(cfg)
    shape = layout.Shape.from_config(cfg)
    traffic = Traffic.from_dict(c.traffic)
    ds = cfg["datastore"]
    peak = peak_for(device.device_kind) if device.platform == "tpu" else None

    t = time.perf_counter()
    corpus = datastore.make_corpus(ds, seed)
    index = datastore.program_index(corpus, ds)
    t_index = time.perf_counter() - t
    t = time.perf_counter()
    params = layout.program_params(cfg, seed, device)
    jax.block_until_ready(params)
    t_weights = time.perf_counter() - t
    t = time.perf_counter()
    srv, runner, probe = build(cfg, layout.arch_config(cfg), params, index,
                               device, seed)
    t_server = time.perf_counter() - t
    if after_build is not None:
        after_build(srv, runner)
    n0 = len(_COMPILES)
    t = time.perf_counter()
    warm_up(srv, traffic, corpus, cfg, seed)
    t_warm = time.perf_counter() - t
    compile_s = sum(_COMPILES[n0:])
    setup_s = time.perf_counter() - t_start
    log(f"# setup_s {setup_s:.3f}: index {t_index:.3f}, weights "
        f"{t_weights:.3f}, server {t_server:.3f}, compile or cache read "
        f"{compile_s:.3f} over {len(_COMPILES) - n0} compiles, warm-up "
        f"{t_warm - compile_s:.3f}, before these "
        f"{setup_s - t_index - t_weights - t_server - t_warm:.3f}")

    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    n1 = len(_COMPILES)
    t0, t1, drains, waves, rounds, wave_s, h2d, traced = run_window(
        srv, probe, runner, traffic, corpus, cfg, seed, seconds,
        trace_dir if trace else None)
    in_window = len(_COMPILES) - n1
    lat = [probe.last_touch[rid] - d.t0 for d in drains
           for rid in d.request_ids]
    log(f"# window {t1 - t0:.3f} s over {len(drains)} whole drains of "
        f"{traffic.clients} clients; {len(lat)} request latencies; "
        f"{len(waves)} decode waves, {len(rounds)} retrieve calls; "
        f"{in_window} compiles inside the window")

    peaks = {}
    for dev in [device]:
        st = dev.memory_stats() or {}
        peaks[dev.id] = int(st.get("peak_bytes_in_use", 0))
        log(f"# device {dev.id}: peak_bytes_in_use {peaks[dev.id]} of "
            f"bytes_limit {st.get('bytes_limit')}")
    from repro.kernels import ops
    modes = ops.resolved_modes()
    attempted = sum(len(d.request_ids) for d in drains)
    failed = failures(drains, corpus.vectors.shape[0])

    w = Window(shape=shape, t0=t0, t1=t1, drains=drains,
               waves=waves, retrievals=rounds, latencies=lat,
               wave_seconds=list(wave_s), bytes_h2d=h2d,
               cluster_pages=np.asarray(index.paged.cluster_num_pages),
               page_size=int(ds["page_size"]),
               kv_page_size=int(cfg["serving"]["kv_page_size"]),
               setup_s=setup_s, peak=peak, device_id=device.id)
    seqs = check.decode_sample(runner.generated, waves, seed,
                               int(cfg["check"]["sample_requests"]))
    # the program's state goes before the reference runs
    del srv, runner, probe, params, index
    gc.collect()

    device_out = {"platform": device.platform, "kind": device.device_kind,
                  "count": 1, "memory_peak_bytes": max(peaks.values())}
    result: Dict[str, object] = {"correct": False, "attempted": attempted,
                                 "failed": failed}
    if trace:
        w.trace = xplane.load(xplane.find_xplane(trace_dir))
        w.trace_window = w.trace.window()
        w.traced_waves, w.traced_retrievals = traced
        log(f"# traced {len(w.traced_waves)} decode waves "
            f"({w.trace.calls('bench.decode_wave')} spans in the trace) and "
            f"{len(w.traced_retrievals)} retrieve calls "
            f"({w.trace.calls('bench.retrieve')} spans)")
        ops_ = w.device_ops()
        lo, hi = w.trace_window
        device_out["busy_s"] = xplane.busy_ns(ops_, w.trace_window) * 1e-9
        device_out["window_s"] = (hi - lo) * 1e-9
        result["breakdown"] = xplane.breakdown(ops_, w.trace_window,
                                               w.trace.spans)
        for label, s in sorted(xplane.idle_by_span(
                ops_, w.trace_window, w.trace.spans).items(),
                key=lambda kv: -kv[1]):
            log(f"# idle {s:.6f} s in {label}")
    metrics = {}
    for m in c.metrics(trace):
        val = cell_mod.load_reader(m["name"])(w)
        if val is None:
            log(f"# {m['name']}: nothing to read in this run")
            continue
        metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        log(f"# {m['name']} {val!r} {m['unit']}")

    numbers = {}
    numbers.update(check.decode_gaps(cfg, seed, seqs, control=control))
    numbers.update(check.retrieval_gaps(
        corpus, ds, rounds, cfg["check"]["probe_edge_precision"],
        control=control))
    ok, lines = check.verdict(numbers, limits)
    bad_modes = {k: v for k, v in modes.items()
                 if k in ("flash_decode_paged", "probe_and_topk")
                 and v != "kernel"}
    for k, v in numbers.items():
        if k not in limits:
            log(f"# check {k} {v!r}")
    if device.platform == "tpu" and bad_modes:
        ok = False
        lines.append(f"kernel modes {bad_modes} not kernel FAIL")
    ok &= failed == 0 and attempted > 0
    for line in lines:
        log(line)
    result.update(correct=bool(ok), metrics=metrics, device=device_out)
    if control:
        result["numbers"] = numbers
    result["compared"] = {k: {"value": numbers.get(k), "limit": v}
                          for k, v in limits.items()}
    return result

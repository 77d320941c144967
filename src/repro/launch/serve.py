"""Serving driver: TeleRAGServer + real LLM decode on local devices.

End-to-end RAG serving of batched requests through the unified serving
front-end: requests are submitted as typed ``RagRequest``s and the
server's decode hook runs the model inside each round frontier — *after*
the policy dispatched the (async) lookahead copy, so the decode steps
overlap the in-flight prefetch and the prefetch is dispatched exactly
once, by the policy.

The model is served at its published widths (``--reduced`` swaps in the
same-family tiny config, for rehearsing on the CPU).  Kernels resolve
``auto``: compiled Pallas on a TPU, the jnp oracles elsewhere.  Each
replica is one device: its page pool, KV slab and params live there.

Decode is **asynchronous and real**: the hook returns per-request
``DecodeEvent``s (observed steps + measured wall seconds), so each
request's generation windows on the event clock come from the decode
that actually ran.  The server runs per-request continuous batching.

The decode hook is a ``serving.DecodeRunner`` on the **paged KV
substrate**: each wave leases a block table over a shared page slab and
every step attends through ``kernels.ops.flash_decode_paged``
(``--dense-decode`` pins the dense ``[B, max_len]`` bucket path).
Either way the lease draws from the engine's shared HBM pool, so
prefetch pages and decode KV are accounted against the same ledger.

``build_index`` / ``build_server`` are the construction this driver and
``chip_smoke.py`` share.

  PYTHONPATH=src python -m repro.launch.serve --pipeline hyde --requests 8
  JAX_PLATFORMS=cpu PYTHONPATH=src python -m repro.launch.serve --reduced \\
      --vectors 20000 --clusters 64 --batch 4 --max-len 128
"""

from __future__ import annotations

import argparse
import functools
import os
import time
from typing import Optional, Sequence, Tuple

import numpy as np
import jax
from jax.sharding import SingleDeviceSharding

import repro.core as core
from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.core.ivf import IVFIndex
from repro.launch import env as launch_env
from repro.models import transformer as tf
from repro.obs import SystemClock, analyze, write_jsonl, write_trace
from repro.serving import (DecodeRunner, EngineConfig, KVCacheManager,
                           RagRequest, TeleRAGServer, make_traces,
                           summarize_latency)

DEFAULT_ARCH = "granite-moe-3b-a800m"

# The served shape: benchmarks/common.py's datastore, one decode wave per
# micro-batch.  chip_smoke.py serves it and tests/test_tpu_compile.py
# compiles the kernels for it.
BATCH = 16                  # decode rows per wave: the server's micro-batch
MAX_LEN = 1024              # KV tokens per sequence
MAX_STEPS = 32              # decode steps per wave
VECTORS, DIM = 320_000, 256
CLUSTERS, PAGE_SIZE, NPROBE = 256, 128, 64
BUFFER_PAGES = 1024         # prefetch share of each pool: < the datastore


def build_index(*, vectors: int, clusters: int,
                seed: int) -> Tuple[core.Datastore, IVFIndex]:
    """Seeded synthetic datastore of ``DIM``-wide vectors + its IVF index
    paged by ``PAGE_SIZE``."""
    store = core.synthetic_datastore(vectors, dim=DIM, seed=seed,
                                     num_topics=192)
    index = core.build_ivf(store, clusters, page_size=PAGE_SIZE,
                           kmeans_iters=5, train_sample=80_000, seed=seed)
    return store, index


def init_params_on(cfg: ArchConfig, seed: int, device: jax.Device):
    """Random weights from ``seed``, made by one jitted program directly
    on ``device`` (never materialized in host memory)."""
    init = jax.jit(functools.partial(tf.init_params, cfg),
                   out_shardings=SingleDeviceSharding(device))
    return init(jax.random.PRNGKey(seed))


def decode_runner(cfg: ArchConfig, params, *, batch: int,
                  max_len: int) -> DecodeRunner:
    """The decode hook: ``MAX_STEPS`` steps per wave over a KV slab of
    two micro-batches of ``max_len`` sequences."""
    return DecodeRunner(params, cfg, max_len=max_len, max_steps=MAX_STEPS,
                        slab_seqs=2 * batch)


def pool_pages(cfg: ArchConfig, page_nbytes: int, *, batch: int,
               max_len: int) -> int:
    """A replica's pool: ``BUFFER_PAGES`` prefetch pages plus the bytes
    of its largest decode lease (``batch`` sequences of ``max_len``)."""
    kv_bytes = KVCacheManager(cfg).nbytes(batch, max_len)
    return BUFFER_PAGES + -(-kv_bytes // page_nbytes)


def build_server(cfg: ArchConfig, index: IVFIndex, *,
                 devices: Sequence[jax.Device], batch: int, max_len: int,
                 nprobe: int, seed: int, params=None, scheduler=None,
                 dense_decode: bool = False,
                 ) -> Tuple[TeleRAGServer, DecodeRunner]:
    """One replica per device behind a ``TeleRAGServer``, each decoding
    ``cfg`` through a shared ``decode_runner``, each pool sized by
    ``pool_pages``.  ``params`` defaults to fresh weights from ``seed``
    on the first device."""
    if params is None:
        params = init_params_on(cfg, seed, devices[0])
    runner = decode_runner(cfg, params, batch=batch, max_len=max_len)
    # real serving: inject the wall clock — scheduler overhead and t_cc
    # calibration measure this machine (the library default is the
    # deterministic event clock)
    srv = TeleRAGServer(index, EngineConfig(
        nprobe=nprobe, top_k=3, buffer_pages=BUFFER_PAGES,
        pool_pages=pool_pages(cfg, index.paged.page_nbytes(), batch=batch,
                              max_len=max_len),
        lookahead_rank=min(2 * nprobe, index.num_clusters),
        kernel_mode="auto", cache_enabled=True, chips=1,
        paged_decode=not dense_decode, seed=seed), len(devices), cfg,
        scheduler=scheduler, micro_batch=batch, include_tail=True,
        decode_hook=runner, wall_clock=SystemClock(),
        devices=list(devices))
    runner.attach(srv)
    for eng in srv.engines:
        eng.calibrate_tcc()
    return srv, runner


def make_queries(store: core.Datastore, n: int, seed: int) -> np.ndarray:
    """``n`` unit query embeddings near random corpus vectors."""
    rng = np.random.default_rng(seed + 1)
    q = store.embeddings[rng.choice(store.num_vectors, n)]
    q = q + 0.05 * rng.standard_normal(q.shape).astype(np.float32)
    return q / np.linalg.norm(q, axis=-1, keepdims=True)


def main(argv: Optional[Sequence[str]] = None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=DEFAULT_ARCH)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the arch's same-family tiny config "
                         "(CPU rehearsal) instead of its published widths")
    ap.add_argument("--pipeline", default="hyde")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--batch", type=int, default=BATCH)
    ap.add_argument("--max-len", type=int, default=MAX_LEN)
    ap.add_argument("--vectors", type=int, default=VECTORS)
    ap.add_argument("--clusters", type=int, default=CLUSTERS)
    ap.add_argument("--nprobe", type=int, default=NPROBE)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--dense-decode", action="store_true",
                    help="decode on the dense [B, max_len] KV bucket path "
                         "instead of the paged block-table substrate")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the run's flight-recorder stream, "
                         "host-clock spans included, as Chrome/Perfetto "
                         "trace-event JSON (load in ui.perfetto.dev; see "
                         "docs/OBSERVABILITY.md)")
    ap.add_argument("--print-env", action="store_true",
                    help="print the recommended launch environment "
                         "(tcmalloc preload, XLA flags) and exit")
    args = ap.parse_args(argv)

    if args.print_env:
        launch_env.print_env()
        return
    launch_env.use_compile_cache()

    print(f"# building datastore ({args.vectors} x {DIM}d, "
          f"{args.clusters} clusters)")
    store, index = build_index(vectors=args.vectors, clusters=args.clusters,
                               seed=args.seed)
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    dev = jax.devices()[0]
    srv, runner = build_server(
        cfg, index, devices=[dev], batch=args.batch, max_len=args.max_len,
        nprobe=args.nprobe, seed=args.seed, dense_decode=args.dense_decode)
    eng = srv.engines[0]

    q = make_queries(store, args.requests, args.seed)
    traces = make_traces(args.pipeline, args.requests, seed=args.seed)
    if args.trace_out:
        # the trace then holds the host-clock lanes too
        srv.recorder.enable_host_spans(srv.wall)
    t0 = time.perf_counter()
    responses = srv.serve([RagRequest(q=q[i], trace=traces[i])
                           for i in range(args.requests)])
    wall = time.perf_counter() - t0
    for r in responses:
        hit = sum(rt.hits for rt in r.rounds)
        mis = sum(rt.misses for rt in r.rounds)
        print(f"req {r.request_id:3d} [{r.pipeline}] rounds="
              f"{len(r.rounds)} hit_rate={hit/max(hit+mis,1):.0%} "
              f"arrival->complete={r.latency_s*1e3:7.1f}ms "
              f"docs={[int(d[0]) for d in r.doc_ids[:1]]}")
    print(f"# {cfg.name} on {dev.platform}/{dev.device_kind}: "
          f"{len(responses)} requests in {wall:.1f}s host wall clock, "
          f"compilation included; "
          f"h2d={eng.buffer.stats.bytes_h2d/1e6:.1f}MB "
          f"cache_hit={eng.cache.hit_rate:.0%} "
          f"decode={'paged' if runner.paged else 'dense'} "
          f"(waves={runner.stats['paged_waves'] or runner.stats['dense_waves']})")
    print(f"# event-clock {summarize_latency(responses)}")
    print(srv.telemetry().summary())
    print(analyze(srv.recorder).summary())
    if args.trace_out:
        write_trace(srv.recorder, args.trace_out)
        # the lossless sibling stream: what tools/telint.py --trace and
        # tools/check_trace.py replay for happens-before invariants
        jl = os.path.splitext(args.trace_out)[0] + ".jsonl"
        write_jsonl(srv.recorder, jl)
        print(f"# trace written to {args.trace_out} (+ {jl}; "
              f"{len(srv.recorder.events)} events)")


if __name__ == "__main__":
    main()

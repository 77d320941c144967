#!/usr/bin/env python3
"""Smoke run of the served path on the chip, in one process.

Drives ``TeleRAGServer`` -> ``RetrievalRuntime`` -> lookahead prefetch
into the ``DevicePagePool`` -> fused ``probe_and_topk`` retrieval ->
``DecodeRunner`` paged decode through ``flash_decode_paged``, with
granite-moe-3b-a800m at its published widths (random weights made on
the device from ``--seed``) over a 320k x 256d datastore, and checks
what comes out: every request completes with doc ids, the kernels
resolved to ``kernel`` and agree with their jnp oracles on the run's own
KV slab and page pool, and the run held more than half of the chip.

  python chip_smoke.py              # one chip
  python chip_smoke.py --chips 4    # only the four-replica phase: four
                                    # one-chip replicas behind the
                                    # cache-aware router vs one replica

Exits non-zero, printing no result line, where JAX finds no TPU.  The
last line of standard output is the result:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Times printed are from one smoke run, not a benchmark.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import statistics
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

REQUESTS = 16              # per serve() call: hyde and iter, alternating
TOL = 2e-2                 # kernel vs oracle: bf16 operands, f32 sums


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def make_requests(store, first_id: int, n: int, seed: int):
    """``n`` requests alternating hyde and iter, ids first_id.. ."""
    from repro.launch.serve import make_queries
    from repro.serving import RagRequest, make_traces
    q = make_queries(store, first_id + n, seed)[first_id:]
    traces = (make_traces("hyde", first_id + n, seed=seed)[first_id:],
              make_traces("iter", first_id + n, seed=seed + 1)[first_id:])
    return [RagRequest(q=q[i], trace=dataclasses.replace(
                traces[i % 2][i], request_id=first_id + i))
            for i in range(n)]


def check_responses(responses, n_requests: int, num_vectors: int):
    """Every request completed, every round returned in-range doc ids;
    returns {request id: doc ids per round}."""
    import numpy as np
    from repro.serving import RequestState
    check(len(responses) == n_requests,
          f"{len(responses)} responses for {n_requests} requests")
    out = {}
    for r in responses:
        check(r.state == RequestState.COMPLETE,
              f"request {r.request_id} ended {r.state}")
        check(len(r.doc_ids) == len(r.rounds) >= 1,
              f"request {r.request_id}: {len(r.doc_ids)} doc-id sets "
              f"for {len(r.rounds)} rounds")
        ids = [np.asarray(d).ravel() for d in r.doc_ids]
        for d in ids:
            check(d.size > 0 and bool(((d >= 0) & (d < num_vectors)).all()),
                  f"request {r.request_id}: doc ids {d} out of range")
        out[r.request_id] = [tuple(int(x) for x in d) for d in ids]
    return out


def check_kernel_modes() -> dict:
    from repro.kernels import ops
    modes = ops.resolved_modes()
    for entry in ("flash_decode_paged", "probe_and_topk"):
        check(modes.get(entry) == "kernel",
              f"{entry} resolved to {modes.get(entry)!r}, not 'kernel'")
    return modes


def check_kernels_against_oracles(srv, runner, seed: int) -> None:
    """``flash_decode_paged`` and ``probe_and_topk``, compiled, against
    their jnp oracles at the run's shapes, on the KV the run decoded
    and the pages the run prefetched."""
    import jax
    import numpy as np
    from repro.kernels import ops
    from repro.launch.serve import BATCH, MAX_LEN, NPROBE

    rng = np.random.default_rng(seed)
    eng, kv = srv.engines[0], runner.kv(0)
    dev = eng.device
    k_pages, v_pages = kv.slab.layer(0)
    NP, ps, KVH, Dh = k_pages.shape
    G = runner.cfg.num_heads // KVH
    mb = MAX_LEN // ps
    table = rng.permutation(NP)[:BATCH * mb].reshape(BATCH, mb)
    lengths = rng.integers(1, MAX_LEN + 1, BATCH)
    put = lambda x: jax.device_put(x, dev)
    q = put(rng.standard_normal((BATCH, KVH, G, Dh)).astype(np.float32))
    args = (q, k_pages, v_pages, put(table.astype(np.int32)),
            put(lengths.astype(np.int32)))
    got = np.asarray(ops.flash_decode_paged(*args, mode="kernel"))
    want = np.asarray(ops.flash_decode_paged(*args, mode="ref"))
    err = float(np.max(np.abs(got - want)))
    log(f"# flash_decode_paged kernel vs oracle: max |diff| {err:.3g} "
        f"(B {BATCH}, KVH {KVH}, G {G}, Dh {Dh}, ps {ps}, max_len {MAX_LEN})")
    check(np.allclose(got, want, rtol=TOL, atol=TOL),
          f"flash_decode_paged differs from its oracle by {err}")

    pages, page_ids, page_cluster = eng.pool.device_view()
    resident = int(np.sum(np.asarray(page_cluster) >= 0))
    check(resident > 0, "the pool holds no resident pages after serving")
    index = srv.index
    qs = rng.standard_normal((BATCH, index.dim)).astype(np.float32)
    qs /= np.linalg.norm(qs, axis=-1, keepdims=True)
    cents = put(np.asarray(index.centroids, np.float32))
    kw = dict(nprobe=NPROBE, k=3)
    s_k, i_k = ops.probe_and_topk(put(qs), cents, pages, page_ids,
                                  page_cluster, mode="kernel", **kw)
    s_r, i_r = ops.probe_and_topk(put(qs), cents, pages, page_ids,
                                  page_cluster, mode="ref", **kw)
    s_k, i_k, s_r, i_r = map(np.asarray, (s_k, i_k, s_r, i_r))
    agree = float(np.mean(i_k == i_r))
    log(f"# probe_and_topk kernel vs oracle: ids agree {agree:.3f}, "
        f"max |score diff| {float(np.max(np.abs(s_k - s_r))):.3g} "
        f"({resident} resident pages of {pages.shape[0]})")
    check(np.allclose(s_k, s_r, rtol=TOL, atol=TOL),
          "probe_and_topk scores differ from its oracle")
    check(agree >= 0.9, f"probe_and_topk ids agree on only {agree:.3f}")
    # every id the kernel returned scores what the kernel says it does
    emb = index.paged
    flat_ids = np.asarray(emb.page_ids).ravel()
    flat_vecs = np.asarray(emb.pages).reshape(-1, emb.dim)
    row_of = {int(d): i for i, d in enumerate(flat_ids) if d >= 0}
    for b in range(BATCH):
        for s, d in zip(s_k[b], i_k[b]):
            if d >= 0:
                exact = float(flat_vecs[row_of[int(d)]] @ qs[b])
                check(abs(exact - float(s)) <= TOL,
                      f"doc {d}: kernel score {s} vs exact {exact}")


def device_report(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def on_device(tree, device) -> bool:
    import jax
    return all(leaf.devices() == {device} for leaf in jax.tree.leaves(tree))


def one_chip(args, store, index, cfg) -> None:
    import jax
    from repro.launch.serve import BATCH, MAX_LEN, NPROBE, build_server

    dev = jax.devices()[0]
    t0 = time.perf_counter()
    srv, runner = build_server(cfg, index, devices=[dev], batch=BATCH,
                               max_len=MAX_LEN, nprobe=NPROBE,
                               seed=args.seed)
    jax.block_until_ready(runner.kv(0).slab.k)
    log(f"# server built in {time.perf_counter() - t0:.1f}s "
        f"(weights, KV slab {runner.slab_seqs} x {MAX_LEN} tokens, pool "
        f"{srv.engines[0].pool.num_pages} pages)")

    served = {}
    for call, first in (("first (compiles)", 0), ("second", REQUESTS)):
        waves0 = len(runner.wave_step_seconds)
        t0 = time.perf_counter()
        responses = srv.serve(make_requests(store, first, REQUESTS,
                                            args.seed))
        wall = time.perf_counter() - t0
        served.update(check_responses(responses, REQUESTS,
                                      store.num_vectors))
        steps = runner.wave_step_seconds[waves0:]
        log(f"# serve call {call}: {len(responses)} requests completed in "
            f"{wall:.1f}s host wall clock, {len(steps)} decode waves")
    check(len(served) == 2 * REQUESTS, "request ids collided")
    log(f"# requests completed: {len(served)} of {2 * REQUESTS}")
    log(f"# decode step (one smoke run, not a benchmark; batch {BATCH}, "
        f"block_until_ready per wave): median "
        f"{statistics.median(steps) * 1e3:.2f} ms over the second call's "
        f"{len(steps)} waves")
    modes = check_kernel_modes()
    log(f"# resolved kernel modes: {json.dumps(modes, sort_keys=True)}")
    eng = srv.engines[0]
    log(f"# lookahead moved {eng.buffer.stats.bytes_h2d / 1e6:.1f} MB "
        f"host->device; cache hit rate {eng.cache.hit_rate:.3f}")
    check(eng.buffer.stats.bytes_h2d > 0, "lookahead moved no bytes")
    check_kernels_against_oracles(srv, runner, args.seed)


def four_chips(args, store, index, cfg) -> None:
    import jax
    from repro.core.schedulers import TeleRAGScheduler
    from repro.launch.serve import (BATCH, MAX_LEN, NPROBE, build_server,
                                    init_params_on)

    devices = jax.devices()[:4]
    params = init_params_on(cfg, args.seed, devices[0])
    kw = dict(batch=BATCH, max_len=MAX_LEN, nprobe=NPROBE, seed=args.seed,
              params=params)

    n = len(devices) * BATCH               # a micro-batch for each replica
    srv, runner = build_server(cfg, index, devices=devices[:1],
                               scheduler=TeleRAGScheduler(), **kw)
    single = check_responses(
        srv.serve(make_requests(store, 0, n, args.seed)), n,
        store.num_vectors)
    log(f"# one replica: {len(single)} requests completed")
    del srv, runner
    gc.collect()

    srv, runner = build_server(cfg, index, devices=devices,
                               scheduler=TeleRAGScheduler(), **kw)
    responses = srv.serve(make_requests(store, 0, n, args.seed))
    four = check_responses(responses, n, store.num_vectors)
    used = sorted({r.replica for r in responses})
    log(f"# four replicas: {len(four)} requests completed, routed to "
        f"replicas {used}")
    for r, (eng, dev) in enumerate(zip(srv.engines, devices)):
        check(on_device(eng.pool.device_view(), dev),
              f"replica {r}: pool not on {dev}")
        check(on_device((runner.kv(r).slab.k, runner.kv(r).slab.v), dev),
              f"replica {r}: KV slab not on {dev}")
        check(on_device(runner.replica_params(r), dev),
              f"replica {r}: params not on {dev}")
    log(f"# pool, KV slab and params of replica r sit on device r: "
        f"{[d.id for d in devices]}")
    check(len(used) > 1, f"the router used only replicas {used}")
    same = sum(four[i] == single[i] for i in single)
    log(f"# doc ids equal to the one-replica run for {same} of "
        f"{len(single)} requests")
    check(same == len(single), "doc ids differ from the one-replica run")
    check_kernel_modes()


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (jax found {devices[0].platform}); "
              f"nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found "
              f"{len(devices)} devices", file=sys.stderr)
        return 2

    from repro.configs import get_arch
    from repro.kernels import ops
    from repro.launch import env as launch_env
    from repro.launch import serve

    mode = ops.resolve_mode("auto")
    if mode != "kernel":
        print(f"chip_smoke: kernels resolve to {mode!r} on the chip "
              f"({ops.MODE_ENV_VAR}={os.environ.get(ops.MODE_ENV_VAR)!r})",
              file=sys.stderr)
        return 2
    cache = launch_env.use_compile_cache()
    compile_s, cache_hits = [], []
    # the compile event spans a persistent-cache read too: a warm cache
    # shows as fewer seconds over the same number of compiles
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **_: compile_s.append(secs)
        if event == "/jax/core/compile/backend_compile_duration" else None)
    jax.monitoring.register_event_listener(
        lambda event, **_: cache_hits.append(1)
        if event == "/jax/compilation_cache/cache_hits" else None)
    d = devices[0]
    log(f"# device: platform {d.platform}, kind {d.device_kind!r}, "
        f"count {len(devices)}; compile cache {cache}")

    cfg = get_arch(serve.DEFAULT_ARCH)
    mo = cfg.moe
    log(f"# arch {cfg.name} at published widths: {cfg.num_layers} layers, "
        f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads} "
        f"kv, head_dim {cfg.resolved_head_dim}, {mo.num_experts} experts "
        f"top-{mo.top_k} width {mo.d_ff_expert}, vocab {cfg.vocab_size}, "
        f"{cfg.param_count() / 1e9:.2f}B params")
    t0 = time.perf_counter()
    store, index = serve.build_index(vectors=serve.VECTORS,
                                     clusters=serve.CLUSTERS, seed=args.seed)
    log(f"# datastore {store.num_vectors} x {store.dim}d, "
        f"{index.num_clusters} clusters, {index.paged.total_pages} pages "
        f"of {index.paged.page_size}, built in "
        f"{time.perf_counter() - t0:.1f}s")

    if args.chips == 4:
        four_chips(args, store, index, cfg)
        used = devices[:4]
    else:
        one_chip(args, store, index, cfg)
        used = devices[:1]

    log(f"# compile seconds: {sum(compile_s):.1f} over {len(compile_s)} "
        f"backend compiles, {len(cache_hits)} read from the persistent "
        f"cache")
    for dev in used:
        stats = dev.memory_stats()
        peak, limit = stats["peak_bytes_in_use"], stats["bytes_limit"]
        log(f"# device {dev.id}: peak_bytes_in_use {peak} of bytes_limit "
            f"{limit} ({peak / limit:.3f})")
        check(peak > limit / 2, f"device {dev.id} peaked at {peak} bytes, "
                                f"not above half of {limit}")
    print(json.dumps({"ok": True, "device": device_report(used)}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

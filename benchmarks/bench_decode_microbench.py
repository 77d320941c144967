"""Per-kernel decode microbenchmark (MaxText-style).

Times each kernel on the decode hot path *in isolation*, one timed call
per decode step, and emits a JSON report — the per-kernel complement to
the end-to-end benches: when a serving number moves, this pins which
kernel moved it.

Kernels timed per step:

  * ``flash_decode``        — dense decode attention over [B, S] KV
  * ``flash_decode_paged``  — block-table decode attention over the
                              paged KV slab (same tokens, paged layout)
  * ``kv_append``           — one decode step's K/V scatter through the
                              block table (``KVCacheManager.append_paged``)
  * ``probe_topk_unfused``  — legacy retrieval chain: centroid probe ->
                              host-built page mask -> ``ivf_topk``
  * ``probe_topk_fused``    — the one-launch ``probe_and_topk`` kernel
  * ``serve_path_paged`` / ``serve_path_dense`` — the ACTUAL engine
    decode step: a ``serving.DecodeRunner`` wave (lease + full
    transformer step + sample) on the paged block-table substrate vs
    the dense bucket path, per decode step.  The paged row is verified
    to execute the paged kernels (``flash_decode_paged`` traced,
    ``append_paged`` accounted) — the row cannot silently fall back to
    dense.

Wall times are honest for the mode they ran in (ref on CPU is the
default; interpret mode is a correctness tool, not a perf proxy — the
report records the mode so downstream tooling never compares across
modes).  ``modeled_bytes`` is the analytic HBM traffic of each kernel
at the benched shapes, which IS comparable across modes and is what the
fused-vs-unfused assertions check.

Run:  PYTHONPATH=src python -m benchmarks.bench_decode_microbench [--smoke]
JSON: experiments/bench/decode_microbench.json
      (schema "telerag.decode_microbench/v1"; fields documented in
      docs/TELEMETRY.md and checked by ``validate_report``)
"""

from __future__ import annotations

import argparse
import json
import os
import time
from typing import Callable, Dict, List

import numpy as np
import jax
import jax.numpy as jnp

from types import SimpleNamespace

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import transformer as tf
from repro.obs import FlightRecorder, SystemClock
from repro.serving import DecodeRunner, EngineConfig
from repro.serving.kv_cache import KVCacheManager
from benchmarks.common import (emit, report_path, summarize_rows,
                               write_report)

SCHEMA = "telerag.decode_microbench/v1"

# every per-kernel record carries exactly these timing fields (us)
TIMING_FIELDS = ("wall_us_mean", "wall_us_p50", "wall_us_p99")


def _time_steps(fn: Callable[[int], jax.Array], steps: int,
                warmup: int = 1) -> List[float]:
    """One timed call per decode step; returns per-step seconds."""
    for s in range(warmup):
        jax.block_until_ready(fn(s))
    out = []
    for s in range(steps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(s))
        out.append(time.perf_counter() - t0)
    return out


def _record(name: str, secs: List[float], modeled_bytes: int) -> Dict:
    us = np.asarray(secs) * 1e6
    return {
        "name": name,
        "steps": len(secs),
        "wall_us_mean": round(float(us.mean()), 2),
        "wall_us_p50": round(float(np.percentile(us, 50)), 2),
        "wall_us_p99": round(float(np.percentile(us, 99)), 2),
        "modeled_bytes": int(modeled_bytes),
    }


def validate_report(report: Dict) -> None:
    """Schema guard for the JSON report (run by --smoke and by CI so the
    emitted fields cannot silently drift from docs/TELEMETRY.md)."""
    assert report.get("schema") == SCHEMA, report.get("schema")
    for key in ("mode", "backend", "steps", "shapes", "kernels"):
        assert key in report, f"missing {key}"
    assert isinstance(report["kernels"], list) and report["kernels"]
    names = set()
    for rec in report["kernels"]:
        for key in ("name", "steps", "modeled_bytes", *TIMING_FIELDS):
            assert key in rec, f"kernel record missing {key}: {rec}"
        for key in TIMING_FIELDS:
            assert rec[key] >= 0.0, (rec["name"], key, rec[key])
        assert rec["modeled_bytes"] > 0, rec["name"]
        names.add(rec["name"])
    fused = {r["name"]: r for r in report["kernels"]}
    if {"probe_topk_fused", "probe_topk_unfused"} <= names:
        assert (fused["probe_topk_fused"]["modeled_bytes"]
                <= fused["probe_topk_unfused"]["modeled_bytes"]), \
            "fused retrieval must not model more HBM traffic than unfused"


def _serve_path_records(*, B: int, steps: int, page_size: int,
                        mode: str) -> List[Dict]:
    """Time the ACTUAL engine decode step — a ``DecodeRunner`` wave
    (KV lease + full transformer serve step + sample per token) — in
    both modes, and assert the paged row really executed the paged
    substrate: ``flash_decode_paged`` must be traced by the paged
    runner's jit (and never by the dense one), and every paged step
    must have gone through ``append_paged`` accounting."""
    L, KVH, G, Dh = 2, 2, 2, 16
    cfg = ArchConfig(name="microbench-serve", family="dense",
                     source="bench", d_model=KVH * G * Dh, num_layers=L,
                     num_heads=KVH * G, num_kv_heads=KVH, head_dim=Dh,
                     d_ff=64, vocab_size=64)
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    members = [SimpleNamespace(request_id=i, tenant="shared")
               for i in range(B)]
    max_len = max(steps, page_size) + 1
    waves = 4

    traced = {"paged": 0}
    orig_paged = ops.flash_decode_paged

    def counting_paged(*a, **kw):
        traced["paged"] += 1
        return orig_paged(*a, **kw)

    records = []
    ops.flash_decode_paged = counting_paged
    try:
        for name, paged in (("serve_path_paged", True),
                            ("serve_path_dense", False)):
            runner = DecodeRunner(params, cfg, max_len=max_len,
                                  max_steps=steps, page_size=page_size,
                                  slab_seqs=B, paged=paged)
            runner.attach(SimpleNamespace(
                wall=SystemClock(), recorder=FlightRecorder(),
                micro_batch=None,
                engines=[SimpleNamespace(
                    cfg=EngineConfig(paged_decode=paged, kernel_mode=mode),
                    pool=None, device=None)]))
            before = traced["paged"]
            secs: List[float] = []
            for w in range(waves + 1):          # wave 0 is jit warmup
                t0 = time.perf_counter()
                runner(0, members, [steps] * B, w)
                dt = time.perf_counter() - t0
                if w:
                    secs.append(dt / max(steps, 1))
            if paged:
                assert traced["paged"] > before, \
                    "paged serve path never traced flash_decode_paged"
                assert runner.stats["paged_appends"] == (waves + 1) * steps
                assert runner.stats["dense_waves"] == 0
            else:
                assert traced["paged"] == before, \
                    "dense serve path traced the paged kernel"
                assert runner.stats["paged_waves"] == 0
                assert runner.stats["dense_steps"] == (waves + 1) * steps
            # per-step modeled traffic: k+v append write + full-capacity
            # KV read for attention, all layers (bf16 slab width)
            modeled = (2 * L * B * KVH * Dh * 2
                       + 2 * L * B * max_len * KVH * Dh * 2)
            records.append(_record(name, secs, modeled))
    finally:
        ops.flash_decode_paged = orig_paged
    return records


def run(*, B: int = 8, S: int = 1024, KVH: int = 8, G: int = 4,
        Dh: int = 128, page_size: int = 64, d: int = 256, Nc: int = 256,
        P: int = 256, ps_ret: int = 128, nprobe: int = 64, k: int = 8,
        steps: int = 16, mode: str = "auto", out: str = None) -> Dict:
    """Bench every decode-path kernel for ``steps`` decode steps at the
    given shapes and write the JSON report.  Attention shapes follow the
    serving defaults (GQA, fp32 math over bf16-width traffic); retrieval
    shapes follow benchmarks/common.py's 1/64-scale index."""
    resolved = ops.resolve_mode(mode)
    rng = np.random.default_rng(0)
    itemsize = 2                                     # bf16 KV / slab traffic

    # ---- attention operands (dense and paged views of the same tokens)
    q = jnp.asarray(rng.standard_normal((B, KVH, G, Dh)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), jnp.float32)
    pos = jnp.full((B,), S - 1, jnp.int32)
    mb = S // page_size
    kp = kc.reshape(B * mb, page_size, KVH, Dh)      # request-major pages
    vp = vc.reshape(B * mb, page_size, KVH, Dh)
    bt = jnp.arange(B * mb, dtype=jnp.int32).reshape(B, mb)
    lengths = jnp.full((B,), S, jnp.int32)

    # ---- retrieval operands (pool slab + centroids)
    qs = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    cents = jnp.asarray(rng.standard_normal((Nc, d)), jnp.float32)
    pages = jnp.asarray(rng.standard_normal((P, ps_ret, d)), jnp.float32)
    pids = jnp.arange(P * ps_ret, dtype=jnp.int32).reshape(P, ps_ret)
    page_cluster = jnp.asarray(rng.integers(0, Nc, P), jnp.int32)
    pc_host = np.asarray(page_cluster)

    # ---- paged KV manager for the append kernel (2 layers is enough to
    # exercise the stacked-layer scatter; bytes scale linearly in L)
    L = 2
    cfg = ArchConfig(name="microbench", family="dense", source="bench",
                     d_model=KVH * G * Dh, num_layers=L, num_heads=KVH * G,
                     num_kv_heads=KVH, head_dim=Dh, vocab_size=32)
    mgr = KVCacheManager(cfg, dtype=jnp.bfloat16)
    mgr.init_paged(num_pages=B * (steps // page_size + 2),
                   page_size=page_size)
    lease = mgr.acquire_paged(B, steps + 1)
    knew = jnp.asarray(rng.standard_normal((L, B, KVH, Dh)), jnp.bfloat16)
    vnew = jnp.asarray(rng.standard_normal((L, B, KVH, Dh)), jnp.bfloat16)

    def unfused(step):
        ps_, pi_ = ops.centroid_probe(cents, qs, nprobe, mode=mode)
        lut = np.zeros((B, Nc), bool)
        pi_h = np.asarray(pi_)
        fin = np.isfinite(np.asarray(ps_))
        for b in range(B):
            lut[b, pi_h[b][fin[b]]] = True
        mask = lut[:, pc_host]                       # [B, P] host-built
        return ops.ivf_topk(pages, pids, jnp.asarray(mask), qs, k, mode=mode)

    def append(step):
        mgr.append_paged(lease, knew, vnew)
        return mgr.slab.k

    kernels = [
        ("flash_decode_dense",
         lambda s: ops.flash_decode(q, kc, vc, pos, mode=mode),
         2 * B * S * KVH * Dh * itemsize + 2 * B * KVH * G * Dh * 4),
        ("flash_decode_paged",
         lambda s: ops.flash_decode_paged(q, kp, vp, bt, lengths, mode=mode),
         2 * B * S * KVH * Dh * itemsize + 2 * B * KVH * G * Dh * 4
         + B * mb * 4),                              # + block table
        ("kv_append", append,
         2 * 2 * L * B * KVH * Dh * itemsize),       # k+v write+readback
        ("probe_topk_unfused", unfused,
         # slab + centroids once, PLUS the [B, Nc] score round trip, the
         # host-built [B, P] mask upload, and the compacted-slab copy the
         # legacy path pays before ivf_topk can run
         P * ps_ret * d * itemsize + Nc * d * 4
         + 2 * 4 * B * Nc + B * P + 2 * P * ps_ret * d * itemsize),
        ("probe_topk_fused",
         lambda s: ops.probe_and_topk(qs, cents, pages, pids, page_cluster,
                                      nprobe=nprobe, k=k, mode=mode),
         P * ps_ret * d * itemsize + Nc * d * 4 + 2 * B * k * 8),
    ]

    records = []
    for name, fn, modeled in kernels:
        secs = _time_steps(fn, steps)
        rec = _record(name, secs, modeled)
        records.append(rec)
        emit(f"decode_microbench/{name}", rec["wall_us_mean"],
             f"p99={rec['wall_us_p99']};modeled_MB="
             f"{modeled / 1e6:.2f};mode={resolved}")

    # the end-to-end engine decode step (DecodeRunner wave), both modes
    for rec in _serve_path_records(B=B, steps=steps, page_size=page_size,
                                   mode=mode):
        records.append(rec)
        emit(f"decode_microbench/{rec['name']}", rec["wall_us_mean"],
             f"p99={rec['wall_us_p99']};modeled_MB="
             f"{rec['modeled_bytes'] / 1e6:.2f};mode={resolved}")

    report = {
        "schema": SCHEMA,
        "mode": resolved,
        "backend": jax.default_backend(),
        "steps": steps,
        "shapes": {"B": B, "S": S, "KVH": KVH, "G": G, "Dh": Dh,
                   "page_size": page_size, "d": d, "Nc": Nc, "P": P,
                   "ps_ret": ps_ret, "nprobe": nprobe, "k": k,
                   "num_layers": L},
        "kernels": records,
    }
    validate_report(report)
    # report-dir routed (untracked): regenerated timing JSON is a CI
    # artifact, never a commit — the schema itself is pinned by
    # tests/data/decode_microbench_pinned.json
    path = out or report_path("decode_microbench.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1)
    # the uniform telerag.bench/v1 report alongside the detailed one
    write_report("decode_microbench", metrics=summarize_rows(records),
                 rows=records, meta={"mode": resolved, "steps": steps})
    return report


def run_smoke() -> Dict:
    """CI-sized run: tiny shapes, ref mode, schema-validated."""
    return run(B=2, S=64, KVH=2, G=2, Dh=16, page_size=16, d=32, Nc=16,
               P=12, ps_ret=8, nprobe=4, k=3, steps=3, mode="ref")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny shapes + schema check (CI guard)")
    ap.add_argument("--steps", type=int, default=16)
    ap.add_argument("--mode", default="auto",
                    help="kernel mode (auto|ref|kernel|kernel_interpret)")
    ap.add_argument("--out", default=None, help="JSON output path")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    if args.smoke:
        run_smoke()
    else:
        run(steps=args.steps, mode=args.mode, out=args.out)


if __name__ == "__main__":
    main()

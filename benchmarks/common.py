"""Shared benchmark fixtures: a mid-scale datastore + engines.

Scale model: the paper's index is 21M vectors × 768d in 4096 clusters
(61 GB, nprobe 256 = 4√Nc). The CPU-budget version here keeps the same
*shape ratios* at 1/64 scale: 320k × 256d in 256 clusters, nprobe 64
(= 4√256), and the latency MODEL uses the paper-scale byte counts so
modeled numbers are paper-comparable (measured quantities — hit rates,
coverage, bytes moved, scheduling quality — are scale-honest).
"""

from __future__ import annotations

import functools
import json
import os
import platform
import time
from typing import Dict, List, Optional

import numpy as np

import repro.core as core
from repro.configs import get_arch
from repro.serving import (EngineConfig, RagRequest, TeleRAGEngine,
                           TeleRAGServer)

BENCH_DIR = os.path.join(os.path.dirname(__file__), "..", "experiments",
                         "bench")

NPROBE = 64           # 4 * sqrt(256)
TOP_K = 3
DIM = 256
N_VECTORS = 320_000
N_CLUSTERS = 256
PAGE_SIZE = 128

# paper-scale constants for the latency model (61 GB / 4096 clusters)
PAPER_CLUSTER_BYTES = 61e9 / 4096


@functools.lru_cache(maxsize=1)
def bench_store():
    return core.synthetic_datastore(N_VECTORS, dim=DIM, seed=0,
                                    num_topics=192)


@functools.lru_cache(maxsize=1)
def bench_index():
    t0 = time.time()
    idx = core.build_ivf(bench_store(), N_CLUSTERS, page_size=PAGE_SIZE,
                         kmeans_iters=5, train_sample=80_000)
    print(f"# built bench index in {time.time()-t0:.1f}s "
          f"(avg cluster {idx.paged.cluster_sizes.mean():.0f} vecs)")
    return idx


def bench_queries(n: int, seed: int = 1, jitter: float = 0.08) -> np.ndarray:
    store = bench_store()
    rng = np.random.default_rng(seed)
    q = store.embeddings[rng.choice(store.num_vectors, n)]
    q = q + jitter * rng.standard_normal(q.shape).astype(np.float32)
    return q / np.linalg.norm(q, axis=-1, keepdims=True)


def bench_cfg(mode: str = "telerag", *, buffer_pages: int = 640,
              budget_bytes=None, cache: bool = False,
              chips: int = 4, seed: int = 0) -> EngineConfig:
    return EngineConfig(
        nprobe=NPROBE, top_k=TOP_K, buffer_pages=buffer_pages,
        lookahead_rank=min(2 * NPROBE, N_CLUSTERS), mode=mode,
        cache_enabled=cache,
        prefetch_budget_bytes=budget_bytes, chips=chips, seed=seed)


def make_engine(mode: str = "telerag", *, buffer_pages: int = 640,
                budget_bytes=None, cache: bool = False, arch="llama3-8b",
                chips: int = 4, seed: int = 0) -> TeleRAGEngine:
    cfg = bench_cfg(mode, buffer_pages=buffer_pages,
                    budget_bytes=budget_bytes, cache=cache, chips=chips,
                    seed=seed)
    return TeleRAGEngine(bench_index(), cfg, get_arch(arch))


def make_server(mode: str = "telerag", *, replicas: int = 1,
                scheduler=None, micro_batch=None, buffer_pages: int = 640,
                budget_bytes=None, cache: bool = False, arch="llama3-8b",
                chips: int = 4, seed: int = 0,
                continuous: bool = False) -> TeleRAGServer:
    """A TeleRAGServer over the shared bench index (the serving
    front-end the benches drive instead of raw executors).
    ``continuous=True`` enables per-request continuous batching."""
    cfg = bench_cfg(mode, buffer_pages=buffer_pages,
                    budget_bytes=budget_bytes, cache=cache, chips=chips,
                    seed=seed)
    return TeleRAGServer(bench_index(), cfg, replicas, get_arch(arch),
                         scheduler=scheduler, micro_batch=micro_batch,
                         continuous=continuous)


def serve_requests(srv: TeleRAGServer, q, traces, arrivals=None):
    """Submit one request per (q row, trace) and drain the server."""
    return srv.serve([RagRequest(q=q[i], trace=traces[i],
                                 arrival_t=(0.0 if arrivals is None
                                            else float(arrivals[i])))
                      for i in range(len(traces))])


def slowest_replica_latency(resp, srv, micro_batch: int,
                            sched_s: float, modeled) -> float:
    """Modeled global-batch latency: replicas run their micro-batches
    serially, the slowest replica bounds the batch (Fig. 11/13/14)."""
    per_replica: Dict[int, float] = {}
    for r in resp:
        eng = srv.engines[r.replica]
        per_replica[r.replica] = (per_replica.get(r.replica, 0.0)
                                  + modeled(r, eng, "telerag") / micro_batch)
    return max(per_replica.values()) + sched_s


def paper_scale_tcc(hw=core.TPU_V5E) -> float:
    """Host per-cluster search time at PAPER datastore scale."""
    return core.host_cluster_search_seconds(PAPER_CLUSTER_BYTES, hw)


def write_csv(name: str, rows: List[Dict]) -> str:
    os.makedirs(BENCH_DIR, exist_ok=True)
    path = os.path.join(BENCH_DIR, f"{name}.csv")
    if rows:
        keys = list(rows[0].keys())
        with open(path, "w") as f:
            f.write(",".join(keys) + "\n")
            for r in rows:
                f.write(",".join(str(r[k]) for k in keys) + "\n")
    return path


def emit(name: str, us_per_call: float, derived: str) -> None:
    print(f"{name},{us_per_call:.1f},{derived}")


# ---------------------------------------------------------------------------
# Machine-readable bench reports (schema "telerag.bench/v1")
# ---------------------------------------------------------------------------

REPORT_SCHEMA = "telerag.bench/v1"
_report_dir: Optional[str] = None


def set_report_dir(path: Optional[str]) -> None:
    """Redirect ``write_report`` output (``benchmarks/run.py
    --report-dir``); None restores the default ``experiments/bench``."""
    global _report_dir
    _report_dir = path


def report_path(filename: str) -> str:
    """Resolve a bench output file against the active report dir
    (``--report-dir``, else ``experiments/bench`` — untracked either
    way: regenerated bench output is a CI artifact, not a commit)."""
    out_dir = _report_dir or BENCH_DIR
    os.makedirs(out_dir, exist_ok=True)
    return os.path.join(out_dir, filename)


def validate_report(report: Dict) -> None:
    """Schema guard for a ``telerag.bench/v1`` report (asserted by the
    bench smokes and tests/test_obs.py so the emitted JSON stays
    machine-consumable)."""
    assert report.get("schema") == REPORT_SCHEMA, report.get("schema")
    for key in ("bench", "host", "metrics", "rows"):
        assert key in report, f"missing {key}"
    assert isinstance(report["bench"], str) and report["bench"]
    assert isinstance(report["metrics"], dict)
    for k, v in report["metrics"].items():
        assert isinstance(k, str)
        assert isinstance(v, (int, float, str, bool)), (k, type(v))
    assert isinstance(report["rows"], list)
    for row in report["rows"]:
        assert isinstance(row, dict)


def summarize_rows(rows: List[Dict]) -> Dict:
    """Headline metrics from a bench's row table: the mean of every
    numeric column (``mean_<col>``) plus the row count — a uniform
    machine-readable summary for ``write_report``."""
    out: Dict = {"n_rows": len(rows)}
    if not rows:
        return out
    for k in rows[0]:
        vals = [r[k] for r in rows
                if isinstance(r.get(k), (int, float))
                and not isinstance(r.get(k), bool)]
        if len(vals) == len(rows):
            out[f"mean_{k}"] = float(np.mean(vals))
    return out


def write_report(name: str, *, metrics: Dict, rows: List[Dict] = (),
                 meta: Optional[Dict] = None) -> str:
    """Write one bench's machine-readable result as
    ``BENCH_<name>.json`` (schema ``telerag.bench/v1``): ``metrics`` is
    the bench's headline scalars, ``rows`` its per-configuration table
    (usually the same rows as ``write_csv``), ``meta`` free-form
    provenance.  Returns the path."""
    report = {
        "schema": REPORT_SCHEMA,
        "bench": name,
        "host": {"platform": platform.platform(),
                 "python": platform.python_version()},
        "metrics": {k: (float(v) if isinstance(v, (int, float))
                        and not isinstance(v, bool) else v)
                    for k, v in metrics.items()},
        "rows": [dict(r) for r in rows],
        "meta": dict(meta or {}),
    }
    validate_report(report)
    out_dir = _report_dir or BENCH_DIR
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    with open(path, "w") as f:
        json.dump(report, f, indent=1, default=float)
    print(f"# report: {path}")
    return path

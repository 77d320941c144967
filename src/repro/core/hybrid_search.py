"""Hybrid device/host IVF search + on-device merge (paper §4.1 steps 2–4).

* Device side: the prefetched slab is searched with the fused
  ``ivf_topk`` kernel, restricted to the probed clusters that are
  resident (mask LUT — no data movement).
* Host side: missed clusters are searched in numpy (the paper's
  multithreaded CPU path; one core here, wall-time is modeled upstream).
* Merge: only the host candidates' *scalar* scores+ids cross the link
  ("GPU sorting", §4.3 — transferring distances, not vectors), then one
  fused top-k on device.

Also provides the beyond-paper ``sharded_device_search``: the slab is
sharded over the ``model`` mesh axis, each shard computes a local top-k,
and candidates are all-gathered and merged — the distributed-datastore
mode sketched in paper §7.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import List, Optional, Sequence, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.datastore import PagedClusters
from repro.core.prefetch_buffer import PrefetchBuffer
from repro.kernels import ops
from repro.obs.recorder import NULL_SPAN, FlightRecorder


# ---------------------------------------------------------------------------
# Host search (numpy — runs on the host CPU by construction)
# ---------------------------------------------------------------------------


def host_search(paged: PagedClusters, clusters: Sequence[int],
                query: np.ndarray, k: int) -> Tuple[np.ndarray, np.ndarray]:
    """Search the given clusters on the host. Returns (scores, ids) desc."""
    scores: List[np.ndarray] = []
    ids: List[np.ndarray] = []
    for c in clusters:
        pages = paged.cluster_pages(int(c))          # [np, ps, d]
        pid = paged.cluster_page_ids(int(c))
        flat = pages.reshape(-1, paged.dim)
        fid = pid.reshape(-1)
        valid = fid >= 0
        s = flat @ query
        s[~valid] = -np.inf
        scores.append(s)
        ids.append(fid)
    if not scores:
        return (np.full(k, -np.inf, np.float32), np.full(k, -1, np.int32))
    s = np.concatenate(scores)
    i = np.concatenate(ids)
    if len(s) > k:
        part = np.argpartition(-s, k - 1)[:k]
    else:
        part = np.arange(len(s))
    order = part[np.argsort(-s[part])]
    out_s = np.full(k, -np.inf, np.float32)
    out_i = np.full(k, -1, np.int32)
    out_s[:len(order)] = s[order]
    out_i[:len(order)] = i[order]
    return out_s, out_i


# ---------------------------------------------------------------------------
# On-device merge ("GPU sorting")
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("k",))
def merge_topk(dev_s: jax.Array, dev_i: jax.Array,
               host_s: jax.Array, host_i: jax.Array, k: int,
               ) -> Tuple[jax.Array, jax.Array]:
    """Concat candidate lists and take global top-k per query (on device)."""
    s = jnp.concatenate([dev_s, host_s], axis=-1)
    i = jnp.concatenate([dev_i, host_i], axis=-1)
    top_s, idx = jax.lax.top_k(s, k)
    return top_s, jnp.take_along_axis(i, idx, axis=-1)


# ---------------------------------------------------------------------------
# Hybrid retrieval
# ---------------------------------------------------------------------------


@dataclass
class RetrievalResult:
    doc_ids: np.ndarray              # [B, k]
    scores: np.ndarray               # [B, k]
    hit_clusters: List[List[int]]    # per query: probed ∩ resident
    missed_clusters: List[List[int]] # per query: searched on host
    nprobe: int = 0

    @property
    def hit_rate(self) -> float:
        h = sum(len(x) for x in self.hit_clusters)
        m = sum(len(x) for x in self.missed_clusters)
        return h / max(h + m, 1)


def hybrid_retrieve(buffer: PrefetchBuffer, queries: np.ndarray,
                    probed_clusters: np.ndarray, *, k: int,
                    kernel_mode: str = "auto", fused: bool = False,
                    centroids: Optional[np.ndarray] = None,
                    recorder: Optional[FlightRecorder] = None,
                    ) -> RetrievalResult:
    """queries [B, d]; probed_clusters [B, nprobe] (ranked by q_out).

    Device searches every probed cluster that is resident; the host
    searches the rest; results merge on device.

    ``fused=True`` (requires ``centroids``) runs the device partition as
    ONE ``probe_and_topk`` launch over the pool's resident pages: the
    centroid probe, top-nprobe cluster admission and masked document
    top-k all happen in-kernel via the device page table
    (``page_cluster``), eliminating the host-built [B, Nc] LUT, the
    [B, num_pages] mask upload, and — in kernel mode — the [B, Nc]
    score-matrix round trip.  The admitted cluster set equals
    ``probed_clusters`` (same centroid scores, tie-free), so the host
    miss partition and telemetry are unchanged.

    ``recorder`` times the three parts as host spans
    (``telerag.retrieve.device`` / ``.host`` / ``.merge``) while its
    host spans are on.
    """
    span = (lambda name, **a: NULL_SPAN) if recorder is None \
        else recorder.span
    B, nprobe = probed_clusters.shape
    buffer.flush_invalidations()
    resident = buffer.resident_clusters()
    hit: List[List[int]] = []
    miss: List[List[int]] = []
    for b in range(B):
        cs = [int(c) for c in probed_clusters[b]]
        hit.append([c for c in cs if c in resident])
        miss.append([c for c in cs if c not in resident])

    with span("telerag.retrieve.device", queries=B):
        qd = jnp.asarray(queries, jnp.float32)
        if fused and centroids is not None:
            # one-launch device partition: probe + admission + top-k read
            # the pool pages in place through the device page table — a
            # page is searchable iff its cluster's centroid score reaches
            # the nprobe-th largest, which is exactly the probed set
            pages, page_ids, page_cluster = buffer.device_view()
            dev_s, dev_i = ops.probe_and_topk(
                qd, jnp.asarray(centroids, jnp.float32), pages, page_ids,
                page_cluster, nprobe=nprobe, k=k, mode=kernel_mode)
        else:
            # legacy two-launch partition — fused masked search over the
            # slab with *per-query* page masks built on host (exact
            # per-query IVF nprobe semantics; mask is page-level so the
            # traffic is num_pages bytes per query, tiny)
            Nc = buffer.paged.num_clusters
            luts = np.zeros((B, Nc), bool)
            for b in range(B):
                luts[b, hit[b]] = True
            pages, page_ids, _ = buffer.device_view()
            pc = buffer.slot_cluster                # host page-table mirror
            page_mask = np.zeros((B, pages.shape[0]), bool)
            valid_slots = np.flatnonzero(pc >= 0)
            page_mask[:, valid_slots] = luts[:, pc[valid_slots]]
            dev_s, dev_i = ops.ivf_topk(pages, page_ids,
                                        jnp.asarray(page_mask), qd, k,
                                        mode=kernel_mode)

    # host partition (scalar scores/ids only cross the link)
    with span("telerag.retrieve.host",
              clusters=sum(len(m) for m in miss)):
        host_results = [host_search(buffer.paged, miss[b], queries[b], k)
                        for b in range(B)]
        host_s = np.stack([r[0] for r in host_results])
        host_i = np.stack([r[1] for r in host_results])
    # the np.asarray waits for the device partition and the merge
    with span("telerag.retrieve.merge"):
        fs, fi = merge_topk(dev_s, dev_i, jnp.asarray(host_s),
                            jnp.asarray(host_i), k)
        doc_ids, scores = np.asarray(fi), np.asarray(fs)
    return RetrievalResult(doc_ids=doc_ids, scores=scores,
                           hit_clusters=hit, missed_clusters=miss,
                           nprobe=nprobe)


# ---------------------------------------------------------------------------
# Beyond-paper: datastore-sharded distributed search (paper §7)
# ---------------------------------------------------------------------------


def sharded_device_search(mesh, queries: jax.Array, pages: jax.Array,
                          page_ids: jax.Array, page_mask: jax.Array, *,
                          k: int, axis: str = "model",
                          ) -> Tuple[jax.Array, jax.Array]:
    """Slab sharded over ``axis`` pages-dim; local top-k then all-gather+merge.

    Collective cost: 2 * B * k * (4+4) bytes * axis_size — candidates only,
    never raw vectors; this is what makes datastore sharding viable at
    nprobe-scale slabs (roofline §Perf discusses the trade).
    """
    from jax.sharding import PartitionSpec as P

    def local(q, pg, pid, msk):
        s, i = ops.ivf_topk(pg, pid, msk, q, k, mode="ref")
        s_all = jax.lax.all_gather(s, axis, axis=1, tiled=True)   # [B, n*k]
        i_all = jax.lax.all_gather(i, axis, axis=1, tiled=True)
        top_s, idx = jax.lax.top_k(s_all, k)
        return top_s, jnp.take_along_axis(i_all, idx, axis=-1)

    fn = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(), P(axis), P(axis), P(axis)),
        out_specs=(P(), P()), check_vma=False)
    return fn(queries, pages, page_ids, page_mask)

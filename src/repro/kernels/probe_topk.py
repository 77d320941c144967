"""Pallas TPU kernel: ONE-LAUNCH fused IVF retrieval over resident pool
pages — centroid probe + per-query page masking + masked top-k.

This closes the substrate gap between ``memory/pool.py`` and the search
kernels: the pool already keeps prefetched cluster pages in place (block
tables, ``page_cluster`` slot map), yet the unfused path still (a) runs
the centroid probe as its own launch, materializing a ``[B, Nc]`` score
matrix in HBM, (b) builds a ``[B, P]`` page mask on the *host* and ships
it over the link, and (c) reshape-pads the ``[P, ps, d]`` slab into a
compacted flat copy for ``ivf_topk``.  Fused, the whole retrieval is one
grid:

  * **probe phase** (centroid tiles): queries stay VMEM-resident, the
    masked centroid scores accumulate into a ``[B, Nc]`` VMEM scratch —
    never touching HBM;
  * **threshold**: after the last centroid tile, the per-query
    top-``nprobe`` admission score is found by a vectorized binary
    search over the scratch (``lax.top_k`` at nprobe=256 is too wide to
    unroll in-kernel; the nprobe-th largest VALUE is enough, because a
    page is searchable iff its cluster's score reaches it).  The search
    converges to the exact nprobe-th score for any tie-free row (ties
    admit every tied cluster — a superset of ``top_k``'s arbitrary
    tie-break);
  * **search phase** (page tiles of the pool slab, read IN PLACE — no
    compaction copy): each tile's per-query page mask is derived
    on-device from ``page_cluster`` via a gather-free one-hot matmul
    against the scratch scores, then the same MXU inner-product +
    unrolled top-k merge as ``ivf_topk``.

Bytes moved vs the unfused path (modeled in bench_kernels): the slab is
read once either way, but the fused launch drops the score-matrix
round-trip (2·4·B·Nc), the host-built mask upload (B·P) and the slab
compaction copy (2·2·N·d).
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.ivf_topk import _tile_topk

NEG_INF = float("-inf")
# invalid-centroid sentinel must stay FINITE: the one-hot matmul that
# expands cluster scores to pages multiplies by 0.0, and -inf * 0 = nan
FINITE_NEG = -1.0e30
VALID_FLOOR = -1.0e29          # scores above this came from a real centroid
# f32 scores in full precision: the admitted clusters and the document
# scores then match the host's numpy search of the same vectors
HIGHEST = jax.lax.Precision.HIGHEST


def _kernel(q_ref, cent_ref, valid_ref, pages_ref, ids_ref, pc_ref,
            out_s_ref, out_i_ref, scores_s, tau_s, acc_s, acc_i, *,
            k: int, nprobe: int, cent_tile: int, page_tile: int,
            page_size: int, num_cent_tiles: int, num_page_tiles: int,
            search_iters: int = 48):
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        acc_s[...] = jnp.full_like(acc_s, NEG_INF)
        acc_i[...] = jnp.full_like(acc_i, -1)

    # ---- probe phase: masked centroid scores -> VMEM scratch --------------
    @pl.when(t < num_cent_tiles)
    def _probe():
        q = q_ref[...].astype(jnp.float32)             # [B, d]
        c = cent_ref[...].astype(jnp.float32)          # [ct, d]
        v = valid_ref[0]                               # [1, ct]
        s = jax.lax.dot_general(q, c, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
        scores_s[:, pl.dslice(t * cent_tile, cent_tile)] = jnp.where(
            v > 0, s, FINITE_NEG)

    # ---- threshold: nprobe-th largest score per query ---------------------
    @pl.when(t == num_cent_tiles - 1)
    def _threshold():
        s = scores_s[...]                              # [B, Nc_pad]
        valid = s > VALID_FLOOR
        hi = jnp.max(s, axis=1, keepdims=True)         # >= every valid score
        lo = jnp.min(jnp.where(valid, s, hi), axis=1, keepdims=True)

        def body(_, carry):
            lo, hi = carry
            mid = 0.5 * (lo + hi)
            cnt = jnp.sum(jnp.where(valid & (s >= mid), 1.0, 0.0),
                          axis=1, keepdims=True)
            ge = cnt >= nprobe                 # mid still admits >= nprobe
            return jnp.where(ge, mid, lo), jnp.where(ge, hi, mid)

        # invariant: count(s >= lo) >= nprobe (or every valid cluster when
        # nprobe exceeds the valid count); lo converges to the nprobe-th
        # largest value within f32 spacing
        lo, hi = jax.lax.fori_loop(0, search_iters, body, (lo, hi))
        tau_s[...] = lo

    # ---- search phase: masked top-k over pool page tiles IN PLACE ---------
    @pl.when(t >= num_cent_tiles)
    def _search():
        q = q_ref[...].astype(jnp.float32)             # [B, d]
        tile = pages_ref[...].astype(jnp.float32)      # [pt, ps, d]
        pc = pc_ref[0]                                 # [1, pt]

        # gather-free page mask: cluster score -> page via one-hot matmul
        # (the one-hot is built transposed, [Nc, pt], so the page row
        # broadcasts down sublanes instead of being relaid out)
        nc_pad = scores_s.shape[1]
        iota = jax.lax.broadcasted_iota(jnp.int32, (nc_pad, page_tile), 0)
        onehot = (pc == iota).astype(jnp.float32)
        cs = jax.lax.dot_general(scores_s[...], onehot,
                                 (((1,), (0,)), ((), ())),
                                 precision=HIGHEST,
                                 preferred_element_type=jnp.float32)  # [B,pt]
        allowed = ((cs >= tau_s[...]) & (cs > VALID_FLOOR)
                   & (pc >= 0))                        # [B, pt]

        flat = tile.reshape(page_tile * page_size, tile.shape[-1])
        s = jax.lax.dot_general(q, flat, (((1,), (1,)), ((), ())),
                                precision=HIGHEST,
                                preferred_element_type=jnp.float32)
        # page-major lane layout of the tile's vectors: page j owns lanes
        # [j*ps, (j+1)*ps) — built by concatenation, not a reshape
        fid = jnp.concatenate([ids_ref[pl.ds(j, 1), :]
                               for j in range(page_tile)], axis=1)
        vmask = jnp.concatenate(
            [jnp.broadcast_to(allowed[:, j:j + 1], (allowed.shape[0],
                                                    page_size))
             for j in range(page_tile)], axis=1) & (fid >= 0)
        s = jnp.where(vmask, s, NEG_INF)
        ts, ti = _tile_topk(s, jnp.broadcast_to(fid, s.shape), k)

        merged_s = jnp.concatenate([acc_s[...], ts], axis=1)
        merged_i = jnp.concatenate([acc_i[...], ti], axis=1)
        ms, mi = _tile_topk(merged_s, merged_i, k)
        acc_s[...] = ms
        acc_i[...] = mi

    @pl.when(t == num_cent_tiles + num_page_tiles - 1)
    def _flush():
        out_s_ref[...] = acc_s[...]
        out_i_ref[...] = acc_i[...]


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "cent_tile",
                                             "page_tile", "interpret"))
def probe_topk_fused(queries: jax.Array, centroids: jax.Array,
                     valid: jax.Array, pages: jax.Array, page_ids: jax.Array,
                     page_cluster: jax.Array, *, nprobe: int, k: int,
                     cent_tile: int = 512, page_tile: int = 8,
                     interpret: bool = False) -> Tuple[jax.Array, jax.Array]:
    """queries [B, d]; centroids [Nc, d] (Nc % cent_tile == 0); valid [Nc];
    pages [P, ps, d] / page_ids [P, ps] / page_cluster [P] — the pool's
    ``device_view`` read in place (P % page_tile == 0; ops.py picks the
    tiles).  Returns (scores [B, k] fp32, doc ids [B, k] int32): top-k
    over every pool page whose cluster lands in the query's top-nprobe
    probed clusters.
    """
    B, d = queries.shape
    Nc = centroids.shape[0]
    P, ps, _ = pages.shape
    assert Nc % cent_tile == 0, (Nc, cent_tile)
    assert P % page_tile == 0, (P, page_tile)
    nct = Nc // cent_tile
    npt = P // page_tile
    valid2 = valid.astype(jnp.int32).reshape(nct, 1, cent_tile)
    pc2 = page_cluster.reshape(npt, 1, page_tile)
    grid = (nct + npt,)
    # index maps clamp each input to its own phase's range; the out-of-
    # phase block load is redundant traffic, not a correctness issue
    cent_ix = lambda t: (jnp.minimum(t, nct - 1), 0)
    valid_ix = lambda t: (jnp.minimum(t, nct - 1), 0, 0)
    page_ix = lambda t: (jnp.clip(t - nct, 0, npt - 1), 0, 0)
    pid_ix = lambda t: (jnp.clip(t - nct, 0, npt - 1), 0)
    kern = functools.partial(
        _kernel, k=k, nprobe=max(1, min(nprobe, Nc)), cent_tile=cent_tile,
        page_tile=page_tile, page_size=ps, num_cent_tiles=nct,
        num_page_tiles=npt)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((B, d), lambda t: (0, 0)),               # queries
            pl.BlockSpec((cent_tile, d), cent_ix),                # centroids
            pl.BlockSpec((1, 1, cent_tile), valid_ix),            # valid
            pl.BlockSpec((page_tile, ps, d), page_ix),            # pool slab
            pl.BlockSpec((page_tile, ps), pid_ix),                # page ids
            pl.BlockSpec((1, 1, page_tile), page_ix),             # slot->cluster
        ],
        out_specs=(pl.BlockSpec((B, k), lambda t: (0, 0)),
                   pl.BlockSpec((B, k), lambda t: (0, 0))),
        out_shape=(jax.ShapeDtypeStruct((B, k), jnp.float32),
                   jax.ShapeDtypeStruct((B, k), jnp.int32)),
        scratch_shapes=[pltpu.VMEM((B, Nc), jnp.float32),
                        pltpu.VMEM((B, 1), jnp.float32),
                        pltpu.VMEM((B, k), jnp.float32),
                        pltpu.VMEM((B, k), jnp.int32)],
        interpret=interpret,
    )(queries, centroids, valid2, pages, page_ids, pc2)

"""Pure-jnp oracles for every Pallas kernel (the correctness contract).

Each kernel's tests sweep shapes/dtypes and assert_allclose against these.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


def ivf_topk_ref(pages: jax.Array, page_ids: jax.Array, page_mask: jax.Array,
                 queries: jax.Array, k: int) -> Tuple[jax.Array, jax.Array]:
    """Masked inner-product top-k over the prefetch slab.

    pages: [P, ps, d]; page_ids: [P, ps] (-1 = padding); page_mask: [P] or
    per-query [B, P] bool (clusters allowed for each query); queries [B, d].
    Returns (scores [B, k] fp32 desc, doc_ids [B, k] int32, -1 when empty).
    """
    P, ps, d = pages.shape
    flat = pages.reshape(P * ps, d).astype(jnp.float32)
    ids = page_ids.reshape(P * ps)
    if page_mask.ndim == 1:
        page_mask = page_mask[None, :]
    vmask = jnp.repeat(page_mask, ps, axis=1) & (ids >= 0)[None, :]  # [B?,N]
    scores = queries.astype(jnp.float32) @ flat.T               # [B, P*ps]
    scores = jnp.where(vmask, scores, -jnp.inf)
    top_s, top_i = jax.lax.top_k(scores, k)
    top_ids = jnp.where(jnp.isfinite(top_s), ids[top_i], -1)
    return top_s, top_ids


def centroid_probe_ref(centroids: jax.Array, queries: jax.Array,
                       valid: Optional[jax.Array] = None) -> jax.Array:
    """Masked centroid distances. centroids [Nc, d]; queries [B, d] -> [B, Nc]."""
    s = queries.astype(jnp.float32) @ centroids.astype(jnp.float32).T
    if valid is not None:
        s = jnp.where(valid[None, :], s, -jnp.inf)
    return s


def probe_and_topk_ref(queries: jax.Array, centroids: jax.Array,
                       valid: jax.Array, pages: jax.Array,
                       page_ids: jax.Array, page_cluster: jax.Array,
                       nprobe: int, k: int) -> Tuple[jax.Array, jax.Array]:
    """Fused-retrieval oracle: centroid probe -> top-nprobe cluster set
    -> per-query page mask over the pool slab -> masked top-k.  This IS
    the unfused composition (``lax.top_k`` selection, exact legacy
    hybrid-search semantics incl. tie-breaks); the Pallas kernel
    replicates it threshold-wise (ties at the nprobe-th score admit
    every tied cluster — identical on tie-free scores).

    queries [B, d]; centroids [Nc, d]; valid [Nc] bool; pages [P, ps, d];
    page_ids [P, ps]; page_cluster [P] (-1 = unsearchable slot).
    Returns (scores [B, k] fp32, doc ids [B, k] int32).
    """
    B = queries.shape[0]
    Nc = centroids.shape[0]
    s = centroid_probe_ref(centroids, queries, valid)          # [B, Nc]
    top_s, top_i = jax.lax.top_k(s, min(nprobe, Nc))
    lut = jnp.zeros((B, Nc), bool).at[
        jnp.arange(B)[:, None], top_i].set(jnp.isfinite(top_s))
    page_mask = jnp.where(page_cluster[None, :] >= 0,
                          lut[:, jnp.clip(page_cluster, 0)], False)  # [B, P]
    return ivf_topk_ref(pages, page_ids, page_mask, queries, k)


def flash_decode_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                     pos: jax.Array, window: int = 0) -> jax.Array:
    """Single-token decode attention oracle.

    q: [B, KVH, G, Dh]; k,v: [B, S, KVH, Dh]; pos: [B] (index of the new
    token; positions > pos are masked). window > 0 = sliding window.
    Returns [B, KVH, G, Dh] fp32.
    """
    B, S, KVH, Dh = k.shape
    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    kp = jnp.arange(S, dtype=jnp.int32)[None, None, None, :]
    qp = pos[:, None, None, None]
    mask = kp <= qp
    if window > 0:
        mask &= kp > qp - window
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))


def flash_decode_spliced_ref(q: jax.Array, k_pages: jax.Array,
                             v_pages: jax.Array, block_table: jax.Array,
                             lengths: jax.Array, page_delta: jax.Array,
                             page_valid: jax.Array, *,
                             rope_fraction: float = 1.0,
                             rope_theta: float = 10_000.0) -> jax.Array:
    """Paged decode-attention oracle over a block table that mixes fresh
    pages with **spliced** chunk-KV pages (reordered RoPE, TurboRAG).

    Spliced pages hold K rotated at chunk-local positions; RoPE rotations
    compose (``R(p + d) = R(d) @ R(p)``), so rotating a page's stored K
    by its constant layout offset ``page_delta[b, blk]`` reindexes it to
    the wave's global positions.  ``page_valid[b, blk]`` is the number of
    live tokens on the page (< ps only for a spliced chunk's partial last
    page); the dead tail slots are masked out of the softmax.  Fresh
    pages carry ``delta = 0`` and ``valid = ps``.

    q [B, KVH, G, Dh]; k_pages, v_pages [NP, ps, KVH, Dh]; block_table /
    page_delta / page_valid [B, MB] int32; lengths [B] int32 (the new
    token sits at layout position ``lengths - 1``).  Returns
    [B, KVH, G, Dh] fp32.
    """
    from repro.models.layers import apply_rope

    B, MB = block_table.shape
    NP, ps, KVH, Dh = k_pages.shape
    bt = jnp.maximum(block_table, 0)
    k = k_pages[bt]                                    # [B, MB, ps, KVH, Dh]
    v = v_pages[bt]
    k = apply_rope(k, jnp.broadcast_to(page_delta[:, :, None], (B, MB, ps)),
                   fraction=rope_fraction, theta=rope_theta)
    k = k.reshape(B, MB * ps, KVH, Dh)
    v = v.reshape(B, MB * ps, KVH, Dh)

    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bhgd,bshd->bhgs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    kp = jnp.arange(MB * ps, dtype=jnp.int32)          # layout positions
    live = kp[None, :] % ps < jnp.repeat(page_valid, ps, axis=1)   # [B, N]
    causal = kp[None, :] <= (lengths - 1)[:, None]
    mask = (live & causal)[:, None, None, :]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhgs,bshd->bhgd", p, v.astype(jnp.float32))


def flash_decode_paged_ref(q: jax.Array, k_pages: jax.Array,
                           v_pages: jax.Array, block_table: jax.Array,
                           lengths: jax.Array, window: int = 0) -> jax.Array:
    """Paged decode-attention oracle: gather the block table into a
    dense cache, then run the dense oracle with ``pos = lengths - 1``
    (lengths must be >= 1; -1 table entries are unallocated tail blocks,
    masked out by the position test either way).

    q [B, KVH, G, Dh]; k_pages, v_pages [NP, ps, KVH, Dh]; block_table
    [B, MB] int32; lengths [B] int32.  Returns [B, KVH, G, Dh] fp32.
    """
    B, MB = block_table.shape
    NP, ps, KVH, Dh = k_pages.shape
    bt = jnp.maximum(block_table, 0)
    k = k_pages[bt].reshape(B, MB * ps, KVH, Dh)
    v = v_pages[bt].reshape(B, MB * ps, KVH, Dh)
    return flash_decode_ref(q, k, v, lengths - 1, window)


def ssm_decode_update_ref(state: jax.Array, layer: jax.Array,
                          slots: jax.Array, x: jax.Array, dt: jax.Array,
                          B: jax.Array, C: jax.Array, A: jax.Array,
                          D: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """One Mamba-2 decode step of the state slab, f32.

    state [L, S, H, P, N]; layer int32 scalar; slots [B] int32 (each
    row's state slot); x [B, H, P]; dt [B, H] (after softplus); B, C
    [B, N]; A, D [H].  Per row: ``S <- exp(dt A) S + dt x (outer) B``,
    ``y = S C + D x``.  Returns (y [B, H, P] f32, state with the rows'
    slots of layer ``layer`` replaced)."""
    f32 = jnp.float32
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    S = state[layer, slots].astype(f32)                # [B, H, P, N]
    dA = jnp.exp(dt * A.astype(f32)[None, :])          # [B, H]
    S = (S * dA[..., None, None]
         + (dt[..., None] * x)[..., None] * B[:, None, None, :])
    y = jnp.sum(S * C[:, None, None, :], axis=-1) \
        + D.astype(f32)[None, :, None] * x
    return y, state.at[layer, slots].set(S.astype(state.dtype))

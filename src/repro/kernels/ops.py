"""Jit'd public wrappers around the Pallas kernels with oracle fallback.

ONE mode-dispatch layer for every kernel — resolution order:

  1. an explicit non-"auto" ``mode=`` argument;
  2. the ``REPRO_KERNEL_MODE`` environment variable (when the call said
     "auto" — one switch flips the whole serving stack, no per-kernel
     hardcoded defaults);
  3. backend auto-detect: real compiled kernel on TPU, pure-jnp oracle
     everywhere else (fast CPU path).

Every entry point records the mode it resolved to (``resolved_modes``),
so a run on the chip can show — and assert — that its kernels really
ran compiled rather than through an oracle.

Accepted modes (aliases in parentheses):
  * "auto"                      — the detection above
  * "kernel" ("tpu")            — pallas kernel compiled for the backend
  * "kernel_interpret" ("interpret") — pallas kernel body interpreted in
                                  Python (CPU validation; what the
                                  parity tests use)
  * "ref" ("oracle")            — pure-jnp oracle

Paged entry points (``flash_decode_paged``, ``probe_and_topk``,
``ssm_decode_update``) read the pool's pages or the state slab IN PLACE through block tables / slot-cluster maps — no
compaction copy between ``memory/pool.py`` and the kernels; the dense
forms keep their pad-and-flatten prep for callers that hold dense slabs.
"""

from __future__ import annotations

import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels import ref as ref_mod
from repro.kernels.centroid_probe import centroid_scores as _probe_kernel
from repro.kernels.flash_decode import flash_decode_paged as _flash_paged_kernel
from repro.kernels.ivf_topk import ivf_topk_flat as _ivf_kernel
from repro.kernels.probe_topk import probe_topk_fused as _probe_topk_kernel
from repro.kernels.ssm_decode import ssm_decode_update as _ssm_kernel

DEFAULT_MODE = "auto"
MODE_ENV_VAR = "REPRO_KERNEL_MODE"
_ALIASES = {
    "auto": "auto",
    "ref": "ref", "oracle": "ref",
    "kernel": "kernel", "tpu": "kernel", "compiled": "kernel",
    "kernel_interpret": "kernel_interpret", "interpret": "kernel_interpret",
}


# entry point -> execution plane it last resolved to (set at trace time)
_RESOLVED: Dict[str, str] = {}


def resolved_modes() -> Dict[str, str]:
    """The execution plane each kernel entry point last resolved to."""
    return dict(_RESOLVED)


def _resolve_for(entry: str, mode: Optional[str]) -> str:
    m = resolve_mode(mode)
    _RESOLVED[entry] = m
    return m


def resolve_mode(mode: Optional[str] = DEFAULT_MODE) -> str:
    """Resolve a requested mode to an execution plane ("ref" | "kernel"
    | "kernel_interpret"): explicit mode > ``REPRO_KERNEL_MODE`` env >
    backend auto-detect (TPU -> compiled kernel, else oracle)."""
    if mode is None:
        mode = "auto"
    if mode == "auto":
        mode = os.environ.get(MODE_ENV_VAR, "").strip().lower() or "auto"
    if mode not in _ALIASES:
        raise ValueError(
            f"unknown kernel mode {mode!r} (from {MODE_ENV_VAR}= or call "
            f"site); valid: {sorted(_ALIASES)}")
    resolved = _ALIASES[mode]
    if resolved == "auto":
        return "kernel" if jax.default_backend() == "tpu" else "ref"
    return resolved


def _interpret(m: str) -> bool:
    return m == "kernel_interpret"


def _pad_rows(x: jax.Array, multiple: int, fill=0):
    n = x.shape[0]
    pad = (-n) % multiple
    if pad == 0:
        return x
    widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, widths, constant_values=fill)


def _page_tile(n: int, want: int) -> int:
    """Pool pages per grid step: the largest multiple of 8 that divides
    ``n`` and is <= max(want, 8), else all ``n`` pages in one block
    (paged inputs are read in place, so the tile must divide instead of
    padding a copy, and a [tile, ps] block must be 8-row aligned or
    whole).  ``DevicePagePool`` pads its slab to a multiple of 8 rows."""
    for t in range(max(want, 8) // 8 * 8, 0, -8):
        if n % t == 0:
            return t
    return n


def ivf_topk(pages: jax.Array, page_ids: jax.Array, page_mask: jax.Array,
             queries: jax.Array, k: int, *, tile: int = 1024,
             mode: str = DEFAULT_MODE) -> Tuple[jax.Array, jax.Array]:
    """Search the prefetch slab. pages [P,ps,d]; page_mask [P] or per-query
    [B,P]; queries [B,d] -> (scores [B,k], ids [B,k])."""
    m = _resolve_for("ivf_topk", mode)
    if m == "ref":
        return ref_mod.ivf_topk_ref(pages, page_ids, page_mask, queries, k)
    B = queries.shape[0]
    P, ps, d = pages.shape
    flat = pages.reshape(P * ps, d)
    ids = page_ids.reshape(P * ps)
    if page_mask.ndim == 1:
        page_mask = jnp.broadcast_to(page_mask[None, :], (B, P))
    # tile must be a multiple of the page size and divide the padded slab
    tile = max(ps, (min(tile, P * ps) // ps) * ps)
    flat = _pad_rows(flat, tile)
    ids = _pad_rows(ids, tile, fill=-1)
    pad_pages = (flat.shape[0] - P * ps) // ps
    if pad_pages:
        page_mask = jnp.pad(page_mask, ((0, 0), (0, pad_pages)))
    return _ivf_kernel(queries, flat, ids, page_mask, k=k, page_size=ps,
                       tile=tile, interpret=_interpret(m))


def centroid_probe(centroids: jax.Array, queries: jax.Array, nprobe: int, *,
                   valid: Optional[jax.Array] = None, tile: int = 512,
                   mode: str = DEFAULT_MODE) -> Tuple[jax.Array, jax.Array]:
    """Coarse probe -> (scores [B,nprobe], cluster ids [B,nprobe])."""
    m = _resolve_for("centroid_probe", mode)
    Nc = centroids.shape[0]
    if valid is None:
        valid = jnp.ones((Nc,), bool)
    if m == "ref":
        s = ref_mod.centroid_probe_ref(centroids, queries, valid)
    else:
        tile = min(tile, Nc)
        cent = _pad_rows(centroids, tile)
        v = _pad_rows(valid, tile, fill=False)
        s = _probe_kernel(queries, cent, v, tile=tile,
                          interpret=_interpret(m))[:, :Nc]
    return jax.lax.top_k(s, nprobe)


def probe_and_topk(queries: jax.Array, centroids: jax.Array,
                   pages: jax.Array, page_ids: jax.Array,
                   page_cluster: jax.Array, *, nprobe: int, k: int,
                   valid: Optional[jax.Array] = None, cent_tile: int = 512,
                   page_tile: int = 8, mode: str = DEFAULT_MODE,
                   ) -> Tuple[jax.Array, jax.Array]:
    """ONE-launch fused retrieval over resident pool pages: centroid
    probe + top-nprobe cluster admission + masked top-k, reading the
    pool's ``device_view`` (pages [P,ps,d], page_ids [P,ps],
    page_cluster [P]) in place.  Replaces the ``centroid_probe`` ->
    host-built page mask -> ``ivf_topk``-over-compacted-slab chain on
    the serving hot path.  Returns (scores [B,k], doc ids [B,k])."""
    m = _resolve_for("probe_and_topk", mode)
    Nc = centroids.shape[0]
    nprobe = max(1, min(nprobe, Nc))
    if valid is None:
        valid = jnp.ones((Nc,), bool)
    if m == "ref":
        return ref_mod.probe_and_topk_ref(queries, centroids, valid, pages,
                                          page_ids, page_cluster, nprobe, k)
    ct = min(cent_tile, Nc)
    cent = _pad_rows(centroids, ct)
    v = _pad_rows(valid, ct, fill=False)
    P = pages.shape[0]
    pt = _page_tile(P, page_tile)
    return _probe_topk_kernel(queries, cent, v, pages, page_ids,
                              page_cluster, nprobe=nprobe, k=k, cent_tile=ct,
                              page_tile=pt, interpret=_interpret(m))


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array, pos: jax.Array, *,
                 window: int = 0, tile: int = 512,
                 mode: str = DEFAULT_MODE) -> jax.Array:
    """Decode attention [B,KVH,G,Dh] over dense KV [B,S,KVH,Dh] at
    per-row position ``pos``: the paged kernel over an identity block
    table, one page per S-tile (a free reshape of the dense cache)."""
    m = _resolve_for("flash_decode", mode)
    if m == "ref":
        return ref_mod.flash_decode_ref(q, k, v, pos, window)
    B, S = k.shape[:2]
    tile = min(tile, S)
    if S % tile:
        tile = S
    nt = S // tile
    pages = lambda x: x.reshape((B * nt, tile) + x.shape[2:])
    table = jnp.arange(B * nt, dtype=jnp.int32).reshape(B, nt)
    return _flash_paged_kernel(q, pages(k), pages(v), table,
                               pos.astype(jnp.int32) + 1, window=window,
                               interpret=_interpret(m))


def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_table: jax.Array, lengths: jax.Array, *,
                       window: int = 0,
                       mode: str = DEFAULT_MODE) -> jax.Array:
    """Decode attention [B,KVH,G,Dh] over paged KV [NP,ps,KVH,Dh]
    gathered through ``block_table`` [B,max_blocks] (-1 = unallocated)
    with per-request ``lengths`` [B] — the block-table form of
    ``flash_decode`` (identical numerics at ``pos = lengths - 1``)."""
    m = _resolve_for("flash_decode_paged", mode)
    if m == "ref":
        return ref_mod.flash_decode_paged_ref(q, k_pages, v_pages,
                                              block_table, lengths, window)
    return _flash_paged_kernel(q, k_pages, v_pages, block_table, lengths,
                               window=window, interpret=_interpret(m))


def flash_decode_spliced(q: jax.Array, k_pages: jax.Array,
                         v_pages: jax.Array, block_table: jax.Array,
                         lengths: jax.Array, page_delta: jax.Array,
                         page_valid: jax.Array, *,
                         rope_fraction: float = 1.0,
                         rope_theta: float = 10_000.0) -> jax.Array:
    """Paged decode attention over a block table mixing fresh pages with
    spliced chunk-KV pages: per-page reordered-RoPE reindexing
    (``page_delta`` [B,MB], the constant rotation offset per page) plus
    per-page live-token masking (``page_valid`` [B,MB], < ps only on a
    spliced chunk's partial last page).

    This is the jnp oracle and nothing else: the spliced form has no
    Pallas kernel, so it takes no ``mode`` and records ``"ref"``."""
    _RESOLVED["flash_decode_spliced"] = "ref"
    return ref_mod.flash_decode_spliced_ref(
        q, k_pages, v_pages, block_table, lengths, page_delta, page_valid,
        rope_fraction=rope_fraction, rope_theta=rope_theta)


def ssm_decode_update(state: jax.Array, layer: jax.Array, slots: jax.Array,
                      x: jax.Array, dt: jax.Array, B: jax.Array,
                      C: jax.Array, A: jax.Array, D: jax.Array, *,
                      mode: str = DEFAULT_MODE,
                      ) -> Tuple[jax.Array, jax.Array]:
    """One Mamba-2 decode step of the recurrent state slab
    [L, S, H, P, N] f32: row b's slot ``slots[b]`` of layer ``layer``
    is read and written in place.  x [B, H, P], dt [B, H] (after
    softplus), B, C [B, N], A, D [H].  Returns (y [B, H, P] f32, the
    updated slab)."""
    m = _resolve_for("ssm_decode_update", mode)
    if m == "ref":
        return ref_mod.ssm_decode_update_ref(state, layer, slots, x, dt, B,
                                             C, A, D)
    return _ssm_kernel(state, layer, slots, x, dt, B, C, A, D,
                       interpret=_interpret(m))

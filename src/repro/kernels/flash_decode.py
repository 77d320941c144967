"""Pallas TPU kernel: single-token flash-decode attention (GQA) over
paged KV.

Serving hot path: one new query token attends over the KV cache.
``flash_decode_paged`` gathers K/V pages straight through a
[B, max_blocks] block table (PagedAttention-style, scalar-prefetch index
maps), so KV leased page-wise from the shared ``DevicePagePool`` is
attended IN PLACE — no copy-out into a contiguous cache between the
memory subsystem and the kernel.  A dense [B, S, KVH, Dh] cache is the
special case of one page per S-tile and an identity block table
(``kernels.ops.flash_decode``).

TPU layout: a page [ps, KVH, Dh] is read as one [ps, KVH*Dh] block, so
the block's minor two dims are (ps, full width) — aligned to the
(8, 128) tiling whatever KVH is.  All heads of a page are scored in ONE
matmul against a block-diagonal query [KVH*G, KVH*Dh] (row (h, g)
carries q[h, g] in head h's Dh columns and zeros elsewhere); the
wrapper reads each head's output back off the diagonal.  The zeros add
exactly nothing, so the numerics are per-head attention; the extra
MXU work is KVH-fold on a memory-bound kernel.

Grid = (B, max_blocks); online-softmax state (m, l, acc) lives in VMEM
scratch across the block loop; block table and lengths ride the
scalar-prefetch channel (SMEM).  Blocks past a row's length re-use the
last live block's index (no new DMA) and skip their compute, so a step
costs O(length), not O(max_len).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = float("-inf")


def _paged_kernel(bt_ref, len_ref, q_ref, k_ref, v_ref, out_ref,
                  m_s, l_s, acc_s, *, page_size: int, max_blocks: int,
                  window: int, scale: float):
    """Online-softmax state machine over the request's block table (the
    DMA gather happens in the BlockSpec index map via the
    scalar-prefetched table)."""
    b = pl.program_id(0)
    t = pl.program_id(1)
    length = len_ref[b]                                # valid tokens, int32

    @pl.when(t == 0)
    def _init():
        m_s[...] = jnp.full_like(m_s, NEG_INF)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    @pl.when(t * page_size < length)
    def _step():
        q = q_ref[0]                                   # [M, D] block-diag
        k = k_ref[0]                                   # [ps, D]
        v = v_ref[0]
        s = jax.lax.dot_general(q, k.astype(jnp.float32),
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        # token position of this block's rows in the sequence
        kp = t * page_size + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        mask = kp < length
        if window > 0:
            mask &= kp >= length - window
        s = jnp.where(mask, s, NEG_INF)                # [M, ps]

        m_prev = m_s[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(m_new > NEG_INF, m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe), 0.0)
        corr = jnp.where(m_prev > NEG_INF, jnp.exp(m_prev - m_safe), 0.0)
        l_s[...] = l_s[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_s[...] = acc_s[...] * corr + jax.lax.dot_general(
            p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(t == max_blocks - 1)
    def _flush():
        out_ref[0] = acc_s[...] / jnp.maximum(l_s[...], 1e-20)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def flash_decode_paged(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                       block_table: jax.Array, lengths: jax.Array, *,
                       window: int = 0, interpret: bool = False) -> jax.Array:
    """Block-table decode attention over paged KV.

    q [B, KVH, G, Dh]; k_pages, v_pages [NP, ps, KVH, Dh] — the paged KV
    slab read IN PLACE (no contiguous materialization); block_table
    [B, max_blocks] int32 (page slot of each sequence block, -1 =
    unallocated tail); lengths [B] int32 valid tokens (>= 1).  Returns
    [B, KVH, G, Dh] fp32: attention of each query over its first
    ``lengths`` tokens (the last ``window`` of them when window > 0).
    """
    B, KVH, G, Dh = q.shape
    NP, ps, _, _ = k_pages.shape
    MB = block_table.shape[1]
    M, D = KVH * G, KVH * Dh
    scale = 1.0 / math.sqrt(Dh)
    kern = functools.partial(_paged_kernel, page_size=ps, max_blocks=MB,
                             window=window, scale=scale)
    # block-diagonal query: row (h, g) holds q[h, g] in head h's columns
    diag = jnp.eye(KVH, dtype=bool)[None, :, None, :, None]
    q_bd = jnp.where(diag, q.astype(jnp.float32)[:, :, :, None, :], 0.0)
    q_bd = q_bd.reshape(B, M, D)

    def kv_ix(b, t, bt, lens):
        # past the row's last live block, repeat that block's index: the
        # pipeline skips the re-fetch and the kernel skips the compute
        last = jnp.maximum(lens[b] - 1, 0) // ps
        return (jnp.maximum(bt[b, jnp.minimum(t, last)], 0), 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=[
            pl.BlockSpec((1, M, D), lambda b, t, bt, lens: (b, 0, 0)),
            pl.BlockSpec((1, ps, D), kv_ix),                         # k pages
            pl.BlockSpec((1, ps, D), kv_ix),                         # v pages
        ],
        out_specs=pl.BlockSpec((1, M, D), lambda b, t, bt, lens: (b, 0, 0)),
        scratch_shapes=[pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, 1), jnp.float32),
                        pltpu.VMEM((M, D), jnp.float32)],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, M, D), jnp.float32),
        interpret=interpret,
    )(block_table.astype(jnp.int32), lengths.astype(jnp.int32), q_bd,
      k_pages.reshape(NP, ps, D), v_pages.reshape(NP, ps, D))
    # head h's output sits in the diagonal block (row h, column h)
    out = jnp.diagonal(out.reshape(B, KVH, G, KVH, Dh), axis1=1, axis2=3)
    return jnp.moveaxis(out, -1, 1)                    # [B, KVH, G, Dh]

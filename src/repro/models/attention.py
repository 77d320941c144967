"""Grouped-query attention (covers MHA / GQA / MQA) with KV cache.

Three entry points share one scoring core (``layers.chunked_attention``):
  * ``attn_forward``   — full-sequence (train / prefill), returns new KV
  * ``attn_decode``    — one token against a pre-allocated KV cache
Window semantics: ``window=None/0`` is global causal; ``window=W`` is a
W-token sliding window (gemma2 local layers). ``window`` may be a traced
scalar so local/global layers share one scanned body.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import Maker, apply_rope, chunked_attention, softcap


def attn_params(mk: Maker, cfg: ArchConfig, prefix: str = "attn") -> dict:
    d, H, KVH, Dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "wq": mk(f"{prefix}.wq", (d, H, Dh), ("embed", "heads", None)),
        "wk": mk(f"{prefix}.wk", (d, KVH, Dh), ("embed", "kv_heads", None)),
        "wv": mk(f"{prefix}.wv", (d, KVH, Dh), ("embed", "kv_heads", None)),
        "wo": mk(f"{prefix}.wo", (H, Dh, d), ("heads", None, "embed")),
    }


def scale_query(q: jax.Array, cfg: ArchConfig) -> jax.Array:
    """Fold ``cfg.attention_multiplier`` into the query: the scoring
    cores scale scores by 1/sqrt(head_dim) themselves."""
    if cfg.attention_multiplier is None:
        return q
    return q * jnp.asarray(cfg.attention_multiplier
                           * math.sqrt(cfg.resolved_head_dim), q.dtype)


def _project_qkv(p: dict, x: jax.Array, cfg: ArchConfig, positions: jax.Array):
    H, KVH = cfg.num_heads, cfg.num_kv_heads
    G = H // KVH
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    q = scale_query(q, cfg)
    B, S = x.shape[:2]
    q = q.reshape(B, S, KVH, G, cfg.resolved_head_dim)
    return q, k, v


def attn_forward(p: dict, x: jax.Array, cfg: ArchConfig, *,
                 positions: jax.Array,
                 window: Optional[jax.Array | int] = None,
                 attn_chunk: int = 1024,
                 ) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """Full-sequence attention. Returns (out [B,S,d], (k, v) for caching)."""
    B, S, _ = x.shape
    q, k, v = _project_qkv(p, x, cfg, positions)
    out = chunked_attention(
        q, k, v,
        q_positions=positions, kv_positions=positions,
        window=window, softcap_val=cfg.attn_logit_softcap,
        chunk=min(attn_chunk, S))
    H = cfg.num_heads
    out = out.reshape(B, S, H, cfg.resolved_head_dim)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), (k, v)


def attn_decode(p: dict, x: jax.Array, cfg: ArchConfig, *,
                cache_k: jax.Array, cache_v: jax.Array,
                pos: jax.Array,
                window: Optional[jax.Array | int] = None,
                attn_chunk: int = 2048,
                kv_seq_spec=None,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One-token decode. x: [B,1,d]; cache_k/v: [B,Smax,KVH,Dh]; pos: [B] int32.

    Returns (out [B,1,d], new_cache_k, new_cache_v).
    """
    B, _, _ = x.shape
    Smax = cache_k.shape[1]
    positions = pos[:, None]                          # [B,1]
    H, KVH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    G = H // KVH
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    q = scale_query(q, cfg).reshape(B, 1, KVH, G, Dh)

    # scatter new k/v at pos (per-batch dynamic index)
    def put(cache, new):
        def one(c, n, i):
            return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (i, 0, 0))
        return jax.vmap(one)(cache, new, pos)
    cache_k = put(cache_k, k)
    cache_v = put(cache_v, v)

    out = _decode_attention(
        q, cache_k, cache_v, pos=pos, window=window,
        softcap_val=cfg.attn_logit_softcap,
        chunk=Smax if kv_seq_spec is not None else min(attn_chunk, Smax),
        kv_seq_spec=kv_seq_spec)
    out = out.reshape(B, 1, H, Dh)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def quantize_heads(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Per-(token, head) symmetric int8 quantization over the head dim.

    x: [..., Dh] -> (q int8 [..., Dh], scale bf16 [...]). Halves KV-cache
    HBM (the dominant decode roofline term); dequant fuses into the
    attention matmul on TPU.
    """
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.bfloat16)


def dequantize_heads(q: jax.Array, scale: jax.Array,
                     dtype=jnp.bfloat16) -> jax.Array:
    return (q.astype(jnp.float32)
            * scale.astype(jnp.float32)[..., None]).astype(dtype)


def attn_decode_quant(p: dict, x: jax.Array, cfg: ArchConfig, *,
                      cache_k: jax.Array, cache_v: jax.Array,
                      k_scale: jax.Array, v_scale: jax.Array,
                      pos: jax.Array,
                      window=None, attn_chunk: int = 0, kv_seq_spec=None):
    """attn_decode over an int8-quantized KV cache.

    cache_k/v: int8 [B,Smax,KVH,Dh]; k/v_scale: bf16 [B,Smax,KVH].
    Returns (out, ck, cv, ks, vs).
    """
    B = x.shape[0]
    Smax = cache_k.shape[1]
    KVH, H, Dh = cfg.num_kv_heads, cfg.num_heads, cfg.resolved_head_dim
    G = H // KVH
    positions = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    q = q.reshape(B, 1, KVH, G, Dh)
    kq, ks_new = quantize_heads(k)
    vq, vs_new = quantize_heads(v)

    def put(cache, new, nd):
        def one(c, n, i):
            idx = (i,) + (0,) * (c.ndim - 1)
            return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), idx)
        return jax.vmap(one)(cache, new, pos)
    cache_k = put(cache_k, kq, 3)
    cache_v = put(cache_v, vq, 3)
    k_scale = put(k_scale, ks_new, 2)
    v_scale = put(v_scale, vs_new, 2)

    kd = dequantize_heads(cache_k, k_scale)
    vd = dequantize_heads(cache_v, v_scale)
    out = _decode_attention(
        q, kd, vd, pos=pos, window=window,
        softcap_val=cfg.attn_logit_softcap,
        chunk=Smax if kv_seq_spec is not None else min(attn_chunk or Smax,
                                                       Smax),
        kv_seq_spec=kv_seq_spec)
    out = out.reshape(B, 1, H, Dh)
    return (jnp.einsum("bshk,hkd->bsd", out, p["wo"]),
            cache_k, cache_v, k_scale, v_scale)


def attn_decode_ring(p: dict, x: jax.Array, cfg: ArchConfig, *,
                     cache_k: jax.Array, cache_v: jax.Array,
                     pos: jax.Array, window: int,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Sliding-window decode over a RING cache of ``window`` slots.

    cache_k/v: [B, W, KVH, Dh] — slot(p) = p % W holds the most recent
    token at that residue, which is exactly the last W positions: the
    sliding-window KV cache needs W slots, not seq_len (gemma2 local
    layers: 4096 instead of 524288 — the split-cache serving optimization,
    DESIGN.md §5 / EXPERIMENTS.md §Perf).
    """
    B, _, _ = x.shape
    W = cache_k.shape[1]
    KVH, H, Dh = cfg.num_kv_heads, cfg.num_heads, cfg.resolved_head_dim
    G = H // KVH
    positions = pos[:, None]
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    q = apply_rope(q, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction, theta=cfg.rope_theta)
    q = q.reshape(B, 1, KVH, G, Dh)

    slot = jnp.mod(pos, W)

    def put(cache, new):
        def one(c, n, i):
            return jax.lax.dynamic_update_slice(c, n.astype(c.dtype), (i, 0, 0))
        return jax.vmap(one)(cache, new, slot)
    cache_k = put(cache_k, k)
    cache_v = put(cache_v, v)

    # absolute position stored in slot s: pos - ((pos - s) mod W)
    slots = jnp.arange(W, dtype=jnp.int32)[None, :]
    ks_pos = pos[:, None] - jnp.mod(pos[:, None] - slots, W)      # [B, W]
    valid = ks_pos >= 0

    scale = 1.0 / math.sqrt(Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", q.astype(cache_k.dtype) * scale,
                   cache_k, preferred_element_type=jnp.float32)
    s = softcap(s, cfg.attn_logit_softcap)
    s = jnp.where(valid[:, None, None, None, :], s, -jnp.inf)
    pattn = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bhgqd", pattn.astype(cache_v.dtype),
                     cache_v, preferred_element_type=jnp.float32)
    out = jnp.transpose(out, (0, 3, 1, 2, 4)).astype(x.dtype)
    out = out.reshape(B, 1, H, Dh)
    return jnp.einsum("bshk,hkd->bsd", out, p["wo"]), cache_k, cache_v


def ring_from_full(k_full: jax.Array, window: int) -> jax.Array:
    """Convert full-sequence K/V [.., B, S, KVH, Dh] (seq axis -3... axis=-3)
    to the ring layout [.., B, W, KVH, Dh] (prefill -> decode handoff)."""
    S = k_full.shape[-3]
    W = min(window, S)
    last = jax.lax.slice_in_dim(k_full, S - W, S, axis=k_full.ndim - 3)
    if W < window:
        pad = [(0, 0)] * k_full.ndim
        pad[k_full.ndim - 3] = (0, window - W)
        last = jnp.pad(last, pad)
        return last
    # position p lands in slot p % window: roll by (S - W) % W
    return jnp.roll(last, shift=(S - W) % W, axis=k_full.ndim - 3)


def _decode_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      pos: jax.Array, window, softcap_val,
                      chunk: int, kv_seq_spec=None) -> jax.Array:
    """Single-token attention over a [B,Smax,KVH,Dh] cache, chunked over KV.

    Unlike ``chunked_attention`` this supports *per-batch* query positions
    (continuous batching: every sequence is at a different decode offset).

    kv_seq_spec: PartitionSpec of the scores' KV axis for sequence-parallel
    decode (long-context: KV cache sharded over ``model``). Constraining
    the scores keeps each chip on its local KV shard — softmax and the
    p·V contraction then reduce with small psums instead of GSPMD
    all-gathering the multi-GB KV slice. Requires chunk == Smax.
    """
    B, _, KVH, G, Dh = q.shape
    Smax = k.shape[1]
    scale = 1.0 / math.sqrt(Dh)
    q32 = q.astype(jnp.float32) * scale              # [B,1,KVH,G,Dh]

    nchunks = max(Smax // chunk, 1)
    if Smax % nchunks:
        nchunks, chunk = 1, Smax
    else:
        chunk = Smax // nchunks
    k_c = jnp.moveaxis(k.reshape(B, nchunks, chunk, KVH, Dh), 1, 0)
    v_c = jnp.moveaxis(v.reshape(B, nchunks, chunk, KVH, Dh), 1, 0)
    base = jnp.arange(nchunks, dtype=jnp.int32) * chunk

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        kc, vc, b0 = xs
        s = jnp.einsum("bqhgd,bkhd->bhgqk", q32.astype(kc.dtype), kc,
                       preferred_element_type=jnp.float32)
        if kv_seq_spec is not None:
            s = jax.lax.with_sharding_constraint(s, kv_seq_spec)
        s = softcap(s, softcap_val)
        kp = (b0 + jnp.arange(chunk, dtype=jnp.int32))[None, None, None, None, :]
        qp = pos[:, None, None, None, None]
        mask = kp <= qp
        if window is not None:
            w = jnp.asarray(window, jnp.int32)
            mask &= jnp.where(w > 0, kp > qp - w, True)
        s = jnp.where(mask, s, -jnp.inf)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        p = jnp.where(mask, jnp.exp(s - m_safe[..., None]), 0.0)
        corr = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
        l_new = l_prev * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[..., None] + jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vc.dtype), vc,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, KVH, G, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, 1), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, 1, Dh), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (k_c, v_c, base))
    out = acc / jnp.maximum(l[..., None], 1e-20)
    return jnp.transpose(out, (0, 3, 1, 2, 4)).astype(q.dtype)

"""Operations and bytes each piece of work requires, from shapes alone.

These are the numerators of the roofline shares and of ``step_mfu``:
what the algorithm needs, not what an implementation happens to do, so
they read the same whatever kernel computes the work.
"""

from __future__ import annotations

import math
from typing import Sequence, Tuple

from benchmarks.chip.model import Shape


def layer_params(s: Shape) -> Tuple[int, int]:
    """(all, touched per token) parameters of one decoder layer: the
    attention projections, the router and experts (top-k of them touched)
    or the dense MLP, and the two RMSNorm weights."""
    attn = 2 * s.d * s.heads * s.head_dim + 2 * s.d * s.kv_heads * s.head_dim
    norms = 2 * s.d
    if s.moe:
        expert = 3 * s.d * s.ff
        router = s.d * s.experts
        return (attn + router + s.experts * expert + norms,
                attn + router + s.top_k * expert + norms)
    mlp = (3 if s.gated else 2) * s.d * s.ff
    return attn + mlp + norms, attn + mlp + norms


def param_count(s: Shape) -> int:
    """Every parameter held: layers, embedding, output head, final norm."""
    return s.layers * layer_params(s)[0] + 2 * s.vocab * s.d + s.d


def matmul_params_per_token(s: Shape) -> int:
    """Parameters a decoded token multiplies by: each layer's touched
    matrices and the output head (the embedding is a lookup, the norms
    are not matrix products)."""
    return s.layers * (layer_params(s)[1] - 2 * s.d) + s.d * s.vocab


def decode_token_flops(s: Shape, context: int) -> float:
    """FLOPs to decode one token that attends over ``context`` tokens
    (itself included): 2 per multiplied parameter, plus QK^T and PV."""
    attn = 4 * s.heads * s.head_dim * context
    return 2 * matmul_params_per_token(s) + s.layers * attn


def wave_flops(s: Shape, gens: Sequence[int]) -> float:
    """FLOPs of the live tokens of one wave: row j decodes ``gens[j]``
    tokens from position 0, token t attending over t + 1."""
    per_tok = 2 * matmul_params_per_token(s)
    attn = 4 * s.heads * s.head_dim * s.layers
    return sum(g * per_tok + attn * g * (g + 1) / 2 for g in gens)


def flash_decode_call(s: Shape, lengths: Sequence[int], page_size: int,
                      kv_bytes: int = 2, q_bytes: int = 2,
                      out_bytes: int = 4) -> Tuple[float, float]:
    """(FLOPs, bytes) one ``flash_decode_paged`` call requires: per row,
    QK^T and PV over its ``length`` tokens, reading the K and V pages up
    to that length, plus q in and the output out."""
    flops = sum(4 * s.heads * s.head_dim * n for n in lengths)
    kv_tok = 2 * s.kv_heads * s.head_dim * kv_bytes
    pages = sum(math.ceil(n / page_size) for n in lengths)
    qo = len(lengths) * s.heads * s.head_dim * (q_bytes + out_bytes)
    return flops, pages * page_size * kv_tok + qo


def probe_topk_call(queries: int, clusters: int, dim: int,
                    pages_per_query: Sequence[int], distinct_pages: int,
                    page_size: int, page_value_bytes: int = 2,
                    k: int = 3) -> Tuple[float, float]:
    """(FLOPs, bytes) one ``probe_and_topk`` call requires: every query
    scores every centroid (f32), then every vector on the resident pages
    of its probed clusters; each such page (values and int32 ids) and
    the centroids are read once; scores and ids of the top k go out."""
    flops = 2 * queries * clusters * dim + sum(
        2 * p * page_size * dim for p in pages_per_query)
    page_bytes = page_size * (dim * page_value_bytes + 4)
    nbytes = (clusters * dim * 4 + queries * dim * 4
              + distinct_pages * page_bytes + queries * k * 8)
    return flops, nbytes


def roofline(flops: float, nbytes: float, seconds: float, peak) -> Tuple[float, str]:
    """(share of the roofline in %, which bound applies): the least time
    the chip could take, the larger of FLOPs over peak FLOP/s and bytes
    over peak bandwidth, over the measured time."""
    t_flops = flops / peak.bf16_flops
    t_bytes = nbytes / peak.hbm_bytes_per_s
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound

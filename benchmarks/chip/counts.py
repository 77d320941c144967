"""Operations and bytes each piece of work requires, from shapes alone.

These are the numerators of the roofline shares and of ``step_mfu``:
what the algorithm needs, not what an implementation happens to do, so
they read the same whatever kernel computes the work.  A model's counts
come from its layout's ``Shape`` (``layouts/``): the parameters a decoded
token multiplies by and the attention FLOPs per context token; the
paged attention kernel's from the heads it is called with.
"""

from __future__ import annotations

import math
from typing import Protocol, Sequence, Tuple


class Shape(Protocol):
    """What the counts read of a layout's ``Shape``: the sizes paged
    attention is called with, and the model's own counts."""

    layers: int
    heads: int
    kv_heads: int
    head_dim: int

    def matmul_params_per_token(self) -> int: ...

    def attn_flops_per_context_token(self) -> int: ...


def decode_token_flops(s: Shape, context: int) -> float:
    """FLOPs to decode one token that attends over ``context`` tokens
    (itself included): 2 per multiplied parameter, plus QK^T and PV."""
    return (2 * s.matmul_params_per_token()
            + s.attn_flops_per_context_token() * context)


def wave_flops(s: Shape, gens: Sequence[int]) -> float:
    """FLOPs of the live tokens of one wave: row j decodes ``gens[j]``
    tokens from position 0, token t attending over t + 1."""
    per_tok = 2 * s.matmul_params_per_token()
    attn = s.attn_flops_per_context_token()
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

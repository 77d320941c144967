"""KV/state cache manager for the serving engine.

Allocates one decode cache per (batch, max_len) bucket and recycles it
across requests (zeroed logically via position resets — stale entries are
masked by per-sequence ``pos``). For SSM archs the "cache" is the O(1)
recurrent state, which must be explicitly zeroed between requests.

When constructed over a ``DevicePagePool`` the manager stops being a
memory island: every live lease charges its exact tensor bytes to the
replica's ``MemoryLedger`` (category ``"kv"``) and takes page slots out
of the same pool the prefetch buffer draws from, so generation state
and retrieval state compete for — and are accounted against — the same
HBM.  A recycled bucket keeps its pool lease (the bytes stay resident);
``acquire`` of a new bucket that the pool cannot fit raises
``PoolExhausted`` rather than silently overcommitting.

Leases are **tenant-tagged**: ``acquire(..., tenant=...)`` charges the
bucket's bytes to the owning request's tenant on the ledger
(``tenant:<name>`` keys now include KV, not just prefetch pages) and in
the pool's per-tenant occupancy; a recycled bucket is re-attributed to
whichever tenant reuses it.  ``ServerTelemetry.tenants`` surfaces the
per-tenant KV footprint.

**Paged mode** (``init_paged``/``acquire_paged``) replaces the
contiguous per-bucket cache with block-table leases over one shared KV
page slab: a ``PagedCacheLease`` is a [batch, max_blocks] table of slab
page slots plus per-sequence lengths — exactly the operands
``kernels.ops.flash_decode_paged`` gathers through in place
(PagedAttention-style), so decode attention reads leased pages with no
contiguous copy and no [B, max_len] over-allocation.  The same pool
byte accounting applies per lease.

A ``layer_types`` hybrid (Mamba-2 mixers beside GQA layers) pages the
K/V of its attention layers only, and its paged lease also holds one
**recurrent state row** per sequence: a slot of the manager's state
slabs (conv state in the model dtype, SSM state in f32, all Mamba-2
layers stacked), zeroed at acquire and freed with the pages.  Pages and
state rows are charged as one pool lease; running out of either raises
``PoolExhausted``.
"""

from __future__ import annotations

import functools
import itertools
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.memory.pool import DevicePagePool, PageLease, PoolExhausted
from repro.models import transformer as tf
from repro.obs.recorder import NULL_SPAN, KVEvent


@dataclass
class CacheLease:
    """One leased decode cache: the JAX cache pytree plus its bucket
    shape, exact byte footprint, (pool-backed) page lease, and the
    tenant whose requests the decode state serves (``"shared"`` = the
    untenanted sentinel)."""

    cache: dict
    batch: int
    max_len: int
    nbytes: int = 0
    page_lease: Optional[PageLease] = None
    tenant: str = "shared"


class KVCacheManager:
    """Decode-cache allocator: one cache per (batch, max_len) bucket,
    recycled across requests, with every live bucket's exact tensor
    bytes leased from the shared ``DevicePagePool`` (category ``"kv"``)
    when a pool is given."""

    def __init__(self, cfg: ArchConfig, dtype=jnp.bfloat16, *,
                 pool: Optional[DevicePagePool] = None):
        """``pool=None`` keeps the manager a standalone allocator (no
        ledger accounting, no admission pressure)."""
        self.cfg = cfg
        self.dtype = dtype
        self.pool = pool
        # the KV slab lives beside the pool it is accounted against
        self.device = pool.device if pool is not None else None
        self._pool_buckets: Dict[Tuple[int, int], Tuple[dict, Optional[PageLease]]] = {}
        self._nbytes_memo: Dict[Tuple[int, int], int] = {}
        self.slab: Optional["KVPageSlab"] = None   # init_paged() creates it
        # recurrent state rows held by paged leases (hybrid archs)
        self.stats = {"state_rows_in_use": 0, "state_rows_peak": 0}

    def _record(self, kind: str, batch: int, max_len: int, nbytes: int,
                tenant: str, *, lease_id: int = -1, pages: int = 0,
                length: int = 0, recycled: bool = False) -> None:
        """Trace through the pool's recorder lane (the manager has no
        lane of its own — KV state belongs to the pool's replica).
        Paged lease edges carry ``lease_id``/``pages`` (and appends the
        post-write ``length``) so the invariant checker can conserve
        pages per lease and pin the acquire→append→release order;
        dense edges carry ``recycled`` (acquire reused a released
        bucket) and ``kv.drop`` (a recycled bucket's bytes returned to
        the pool) so bucket recycling stays conservation-exact too."""
        rec = self.pool.recorder if self.pool is not None else None
        if rec is not None:
            rec.emit(KVEvent(t=rec.now, kind=kind,
                             replica=self.pool.replica_id, tenant=tenant,
                             batch=batch, max_len=max_len, nbytes=nbytes,
                             lease_id=lease_id, pages=pages, length=length,
                             recycled=recycled))

    def acquire(self, batch: int, max_len: int, *, fresh: bool = False,
                tenant: str = "shared") -> CacheLease:
        """Lease a decode cache for ``batch`` sequences of ``max_len``
        (recycled bucket when available, else a fresh pool-backed
        allocation; raises ``PoolExhausted`` when the pool cannot fit
        it).  ``fresh=True`` forces zeroed state.  ``tenant`` is the
        owning request's tenant: the bucket's pool lease carries it, so
        the ledger's ``tenant:<name>`` bytes (and the pool's per-tenant
        occupancy) include KV alongside prefetch pages — a recycled
        bucket is re-attributed to whoever reuses it."""
        key = (batch, max_len)
        nbytes = self.nbytes(batch, max_len)
        cache, page_lease = self._pool_buckets.pop(key, (None, None))
        recycled = cache is not None
        if cache is None:
            if self.pool is not None:
                page_lease = self.pool.lease_bytes(nbytes, "kv", tag=key,
                                                   tenant=tenant)
                if page_lease is None and self._pool_buckets:
                    # spill our own recycled buckets before giving up
                    self.drop_all()
                    page_lease = self.pool.lease_bytes(nbytes, "kv", tag=key,
                                                       tenant=tenant)
                if page_lease is None:
                    raise PoolExhausted(
                        f"kv cache {key} needs {nbytes} bytes; pool has "
                        f"{self.pool.reservable_pages()} reservable pages "
                        f"of {self.pool.page_nbytes} bytes",
                        bytes_needed=nbytes)
            try:
                cache = tf.init_cache(self.cfg, batch, max_len, self.dtype)
            except BaseException:
                # a failed allocation must hand its pool pages back —
                # otherwise every OOM here shrinks the pool forever
                # (telint TL001)
                if page_lease is not None and self.pool is not None:
                    self.pool.release(page_lease)
                raise
        else:
            if (page_lease is not None and self.pool is not None
                    and page_lease.tenant != tenant):
                # the recycled bytes now serve a different tenant — the
                # ledger must say so, or tenant KV bytes go stale
                self.pool.reattribute(page_lease, tenant)
            if fresh or tf.family_kind(self.cfg) != "attn":
                # recurrent state must not leak across requests;
                # attention caches are masked by pos so zeroing is
                # optional
                cache = jax.tree.map(lambda a: jnp.zeros_like(a), cache)
        self._record("kv.acquire", batch, max_len, nbytes, tenant,
                     recycled=recycled)
        return CacheLease(cache=cache, batch=batch, max_len=max_len,
                          nbytes=nbytes, page_lease=page_lease,
                          tenant=tenant)

    def release(self, lease: CacheLease) -> None:
        """Return the bucket for recycling (its pool lease stays live:
        the bytes remain resident until ``drop``/``drop_all``).  When a
        same-shaped bucket is already parked, keeping both would leak
        one pool lease forever — the incoming bucket's bytes go straight
        back to the pool instead (release + immediate drop in the
        trace, so the recycle balance stays conservation-exact)."""
        self._record("kv.release", lease.batch, lease.max_len,
                     lease.nbytes, lease.tenant)
        key = (lease.batch, lease.max_len)
        if key in self._pool_buckets:
            freed = lease.nbytes
            if lease.page_lease is not None and self.pool is not None:
                freed = lease.page_lease.nbytes
                self.pool.release(lease.page_lease)
            self._record("kv.drop", lease.batch, lease.max_len, freed,
                         lease.tenant)
            return
        self._pool_buckets[key] = (lease.cache, lease.page_lease)

    def drop(self, batch: int, max_len: int) -> int:
        """Free one recycled bucket back to the pool; returns its bytes.
        Emits ``kv.drop`` so the recycle pool's byte balance stays
        conservation-exact in the trace (a dense ``kv.release`` parks
        the bytes for reuse — only the drop actually returns them)."""
        cache, page_lease = self._pool_buckets.pop((batch, max_len),
                                                   (None, None))
        if cache is None:
            return 0
        freed = self.nbytes(batch, max_len)
        tenant = "shared"
        if page_lease is not None and self.pool is not None:
            tenant = page_lease.tenant
            freed = page_lease.nbytes
            self.pool.release(page_lease)
        self._record("kv.drop", batch, max_len, freed, tenant)
        return freed

    def drop_all(self) -> int:
        """Free every recycled bucket (replica teardown / pressure spill)."""
        freed = 0
        for batch, max_len in list(self._pool_buckets):
            freed += self.drop(batch, max_len)
        return freed

    def nbytes(self, batch: int, max_len: int) -> int:
        """Exact tensor bytes of one (batch, max_len) bucket — matches
        the ledger's ``"kv"`` charge to the byte."""
        key = (batch, max_len)
        if key not in self._nbytes_memo:     # eval_shape traces init_cache;
            shapes = jax.eval_shape(         # don't re-trace per acquire
                lambda: tf.init_cache(self.cfg, batch, max_len, self.dtype))
            self._nbytes_memo[key] = sum(s.size * s.dtype.itemsize
                                         for s in jax.tree.leaves(shapes))
        return self._nbytes_memo[key]

    # -- paged KV (block-table leases over a shared KV page slab) ----------

    def init_paged(self, num_pages: int, page_size: int = 16,
                   state_rows: int = 0) -> "KVPageSlab":
        """Allocate the manager's KV page slab (``paged_slab_shapes``):
        ``num_pages`` page slots of ``page_size`` tokens each, all
        attention layers stacked.  A ``layer_types`` hybrid also gets
        ``state_rows`` recurrent state rows (other archs take none)."""
        shapes = paged_slab_shapes(self.cfg, num_pages, page_size,
                                   state_rows, self.dtype)
        zeros = {k: jnp.zeros(a.shape, a.dtype, device=self.device)
                 for k, a in shapes.items()}
        self.slab = KVPageSlab(page_size=page_size,
                               free=list(range(num_pages)), **zeros)
        if "ssm" in zeros:
            self.slab.state_free = list(range(state_rows))
        return self.slab

    def paged_page_nbytes(self) -> int:
        """Exact bytes of one KV page slot (k+v, all layers)."""
        slab = self._require_slab()
        return 2 * slab.k.size // slab.k.shape[1] * slab.k.dtype.itemsize

    def state_row_nbytes(self) -> int:
        """Exact bytes of one recurrent state row (conv + SSM state, all
        Mamba-2 layers); 0 for an arch with none."""
        slab = self._require_slab()
        if slab.ssm is None:
            return 0
        rows = slab.ssm.shape[1]
        return (slab.conv.size * slab.conv.dtype.itemsize
                + slab.ssm.size * slab.ssm.dtype.itemsize) // rows

    def acquire_paged(self, batch: int, max_len: int, *,
                      tenant: str = "shared") -> "PagedCacheLease":
        """Lease a block-table decode cache: ceil(max_len/page_size)
        slab pages per sequence, handed back as a [batch, max_blocks]
        block table the paged kernels gather through — no contiguous
        [B, S] cache is ever materialized — plus, for an arch with
        recurrent state, one zeroed state row per sequence
        (``state_slots``).  Bytes are charged to the pool ledger
        (category ``"kv"``, tenant-tagged) exactly like the dense
        buckets; raises ``PoolExhausted`` when the slab's free pages or
        state rows, or the pool, cannot cover it."""
        slab = self._require_slab()
        ps = slab.page_size
        max_blocks = -(-max_len // ps)
        need = batch * max_blocks
        if len(slab.free) < need:
            raise PoolExhausted(
                f"kv page slab exhausted: need {need} pages for "
                f"({batch}, {max_len}), {len(slab.free)} free")
        recurrent = slab.ssm is not None
        if recurrent and len(slab.state_free) < batch:
            raise PoolExhausted(
                f"recurrent state slab exhausted: need {batch} rows, "
                f"{len(slab.state_free)} free")
        nbytes = (need * self.paged_page_nbytes()
                  + batch * self.state_row_nbytes())
        page_lease = None
        if self.pool is not None:
            page_lease = self.pool.lease_bytes(nbytes, "kv",
                                               tag=(batch, max_len),
                                               tenant=tenant)
            if page_lease is None and self._pool_buckets:
                self.drop_all()          # spill recycled dense buckets first
                page_lease = self.pool.lease_bytes(nbytes, "kv",
                                                   tag=(batch, max_len),
                                                   tenant=tenant)
            if page_lease is None:
                raise PoolExhausted(
                    f"paged kv cache ({batch}, {max_len}) needs {nbytes} "
                    f"bytes; pool has {self.pool.reservable_pages()} "
                    f"reservable pages of {self.pool.page_nbytes} bytes",
                    bytes_needed=nbytes)
        slots = [slab.free.pop() for _ in range(need)]
        bt = np.asarray(slots, np.int32).reshape(batch, max_blocks)
        rows = None
        if recurrent:
            rows = np.asarray([slab.state_free.pop() for _ in range(batch)],
                              np.int32)
            self._reset_state(rows)
        lease_id = next(_LEASE_IDS)
        self._record("kv.acquire", batch, max_len, nbytes, tenant,
                     lease_id=lease_id, pages=need)
        return PagedCacheLease(block_table=bt,
                               lengths=np.zeros(batch, np.int32),
                               batch=batch, max_len=max_len, nbytes=nbytes,
                               page_lease=page_lease, tenant=tenant,
                               lease_id=lease_id, owned_slots=tuple(slots),
                               state_slots=rows)

    def _reset_state(self, rows: np.ndarray) -> None:
        """Zero the state rows a lease takes (a new sequence starts from
        zero state), in place; the row count is padded to a power of two
        by repeating a row, so few shapes compile."""
        slab = self._require_slab()
        rec = self.pool.recorder if self.pool is not None else None
        span = NULL_SPAN if rec is None else rec.span(
            "telerag.kv.state_reset", rows=len(rows),
            bytes=len(rows) * self.state_row_nbytes())
        with span:
            n = 1 << (len(rows) - 1).bit_length()
            idx = np.concatenate([rows, np.full(n - len(rows), rows[0],
                                                np.int32)])
            Lm, S = slab.ssm.shape[:2]
            conv_idx = (np.arange(Lm, dtype=np.int32)[:, None] * S
                        + idx[None, :]).ravel()
            slab.conv, slab.ssm = _zero_rows(
                slab.conv, slab.ssm, jax.device_put(conv_idx, self.device),
                jax.device_put(idx, self.device))
        self.stats["state_rows_in_use"] += len(rows)
        self.stats["state_rows_peak"] = max(self.stats["state_rows_peak"],
                                            self.stats["state_rows_in_use"])

    def append_paged(self, lease: "PagedCacheLease",
                     k_new: Optional[jax.Array] = None,
                     v_new: Optional[jax.Array] = None) -> None:
        """Advance the lease by one decode step.  With ``k_new``/``v_new``
        (``[L, B, KVH, Dh]``) the step's K/V is written at each
        sequence's current length through the block table (donated
        in-place scatter — the slab is never copied).  Without them the
        scatter already happened inside the fused serve step
        (``transformer.serve_step_paged`` writes through the same block
        table in-jit) and this call is the accounting half: bounds
        check, length advance, and the ``kv.append`` trace edge the
        invariant checker orders between acquire and release."""
        slab = self._require_slab()
        ps = slab.page_size
        if int(lease.lengths.max(initial=0)) >= lease.max_len:
            raise ValueError(f"paged lease full at max_len={lease.max_len}")
        if k_new is not None:
            slots = lease.block_table[np.arange(lease.batch),
                                      lease.lengths // ps]
            offs = lease.lengths % ps
            slab.k, slab.v = _append_token(
                slab.k, slab.v, jnp.asarray(k_new), jnp.asarray(v_new),
                jnp.asarray(slots), jnp.asarray(offs, np.int32))
        # rebind, never `+=`: a step dispatched with the lease's tables()
        # may still read the old buffer (an upload to the CPU adopts an
        # aligned host buffer without a copy)
        lease.lengths = lease.lengths + 1
        self._record("kv.append", lease.batch, lease.max_len, 0,
                     lease.tenant, lease_id=lease.lease_id,
                     pages=lease.block_table.size,
                     length=int(lease.lengths.max(initial=0)))

    def splice_paged(self, lease: "PagedCacheLease",
                     row_chunks: List[List[Tuple[Tuple[int, ...], int]]],
                     ) -> int:
        """Attach precomputed chunk-KV pages to a fresh paged lease by
        **block-table edit** (TurboRAG-style reuse; no copy).

        ``row_chunks[i]`` lists row ``i``'s chunks as ``(slots,
        length)`` pairs — slab page slots already holding the chunk's
        K/V (written by ``ChunkKVCache.load``) and the chunk's token
        count.  Chunks splice at page boundaries, in order, AHEAD of the
        lease's own (fresh) pages: row ``i``'s table becomes ``[chunk
        pages..., fresh pages..., -1 padding]``, its length starts at
        the end of its spliced region (generation resumes at the next
        page boundary), and the lease's ``max_len`` grows by the widest
        spliced region so the append bounds check keeps holding.

        Per-page splice metadata for the reordered-RoPE attention
        (``serve_step_paged_spliced``) is materialized on the lease:
        ``page_delta[i, blk]`` — the RoPE rotation offset (the chunk's
        base layout position; stored K is roped chunk-locally, and
        rotations compose) — and ``page_valid[i, blk]`` — live tokens
        on the page (< page_size only on a chunk's partial last page;
        the dead tail is masked, and generation's own pages stay fully
        valid).

        The spliced slots are NOT added to ``owned_slots``: the lease
        only references them; ownership (and the pool's ``chunk_kv``
        byte charge) stays with the chunk residency, which the caller
        pins for the lease's lifetime.  Emits ``kv.splice`` (pages =
        spliced page count, length = post-splice max length) inside the
        lease's acquire→release window.  Returns the spliced page
        count (0 = nothing to splice; the lease is untouched)."""
        slab = self._require_slab()
        ps = slab.page_size
        if len(row_chunks) != lease.batch:
            raise ValueError(f"row_chunks has {len(row_chunks)} rows for a "
                             f"batch-{lease.batch} lease")
        if int(lease.lengths.max(initial=0)) > 0:
            raise ValueError("splice_paged must run on a fresh lease "
                             "(before any append)")
        if lease.state_slots is not None:
            raise ValueError("splice_paged cannot splice into a lease with "
                             "recurrent state: a chunk brings K/V pages, "
                             "not the state after it")
        n_blocks = [sum(len(slots) for slots, _ in row) for row in row_chunks]
        total = sum(n_blocks)
        if total == 0:
            return 0
        lead = max(n_blocks)
        B, MB = lease.block_table.shape
        bt = np.full((B, lead + MB), -1, np.int32)
        delta = np.zeros((B, lead + MB), np.int32)
        valid = np.full((B, lead + MB), ps, np.int32)
        for i, row in enumerate(row_chunks):
            b0 = 0
            for slots, length in row:
                npg = len(slots)
                if length <= 0 or npg != -(-length // ps):
                    raise ValueError(
                        f"chunk of {length} tokens needs "
                        f"{-(-max(length, 1) // ps)} pages, got {npg}")
                bt[i, b0:b0 + npg] = slots
                # stored K is roped at chunk-local positions p*ps + off;
                # the layout position is (b0 + p)*ps + off, so the
                # per-page rotation delta is the constant b0*ps
                delta[i, b0:b0 + npg] = b0 * ps
                valid[i, b0 + npg - 1] = length - (npg - 1) * ps
                b0 += npg
            bt[i, b0:b0 + MB] = lease.block_table[i]
        valid[bt < 0] = 0                  # padding columns attend nothing
        lease.block_table = bt
        lease.lengths = np.asarray([n * ps for n in n_blocks], np.int32)
        lease.page_delta = delta
        lease.page_valid = valid
        lease.spliced_pages = total
        lease.max_len = lead * ps + lease.max_len
        self._record("kv.splice", lease.batch, lease.max_len,
                     total * self.paged_page_nbytes(), lease.tenant,
                     lease_id=lease.lease_id, pages=total,
                     length=int(lease.lengths.max(initial=0)))
        return total

    def release_paged(self, lease: "PagedCacheLease") -> int:
        """Return the lease's **owned** slab pages to the free list and
        release its pool bytes; returns bytes freed.  Paged leases are
        per request batch — no recycling bucket (block tables are cheap
        to rebuild; the slab itself stays allocated).  Spliced chunk-KV
        pages in the block table are NOT owned: they belong to the
        ``ChunkKVCache``'s residency and go back to *warm* residency
        (the splicer unpins them), never to the slab free list here —
        freeing them would alias live chunk pages under future leases."""
        slab = self._require_slab()
        slab.free.extend(int(s) for s in lease.owned_slots)
        pages = len(lease.owned_slots)
        lease.owned_slots = ()
        if lease.state_slots is not None:
            slab.state_free.extend(int(s) for s in lease.state_slots)
            self.stats["state_rows_in_use"] -= len(lease.state_slots)
            lease.state_slots = lease.state_slots[:0]
        lease.block_table = np.full_like(lease.block_table, -1)
        self._record("kv.release", lease.batch, lease.max_len,
                     lease.nbytes, lease.tenant, lease_id=lease.lease_id,
                     pages=pages)
        if lease.page_lease is not None and self.pool is not None:
            self.pool.release(lease.page_lease)
            lease.page_lease = None
        return lease.nbytes

    def _require_slab(self) -> "KVPageSlab":
        if self.slab is None:
            raise RuntimeError("call init_paged(num_pages) before using "
                               "the paged KV API")
        return self.slab


# paged lease ids are process-global (not per manager): the invariant
# checker keys page conservation on (replica, lease_id), and one replica
# may host several managers
_LEASE_IDS = itertools.count()


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _append_token(k_slab, v_slab, k_new, v_new, slots, offs):
    """One donated scatter: k/v [L, NP, ps, KVH, Dh] <- new [L, B, KVH, Dh]
    at (slots[b], offs[b]) — the paged analogue of the dense cache's
    dynamic-update-slice write."""
    k_slab = k_slab.at[:, slots, offs].set(k_new.astype(k_slab.dtype))
    v_slab = v_slab.at[:, slots, offs].set(v_new.astype(v_slab.dtype))
    return k_slab, v_slab


def paged_slab_shapes(cfg: ArchConfig, num_pages: int, page_size: int,
                      state_rows: int = 0, dtype=jnp.bfloat16,
                      ) -> Dict[str, jax.ShapeDtypeStruct]:
    """The paged slab's arrays.  GQA archs: k/v [L, num_pages,
    page_size, KVH, Dh].  A ``layer_types`` hybrid: k/v [La, num_pages,
    page_size, KVH*Dh] over its La attention layers, and ``state_rows``
    recurrent state rows of its Lm Mamba-2 layers: conv [Lm*rows,
    (cw-1)*C] (row l*rows + r is layer l's row r) and ssm [Lm, rows, H,
    P, N] f32.  The hybrid's K/V and conv rows are laid out as rows of
    one token or one window, so the decode step's row scatters and the
    kernels read them in place."""
    kind = tf.family_kind(cfg)
    if kind not in ("attn", "hybrid") or not cfg.has_attention:
        raise ValueError(
            "paged KV supports plain GQA attention caches and layer_types "
            f"hybrids only (arch family {kind!r}, attn_kind "
            f"{cfg.attn_kind!r})")
    if cfg.attn_kind != "gqa":
        raise ValueError(f"paged KV needs GQA attention, not "
                         f"{cfg.attn_kind!r}")
    KVH, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    S = jax.ShapeDtypeStruct
    if kind == "attn":
        kv = S((cfg.num_layers, num_pages, page_size, KVH, Dh), dtype)
        return {"k": kv, "v": kv}
    if state_rows < 1:
        raise ValueError(f"{cfg.name} carries recurrent state: its paged "
                         "slab needs state_rows >= 1")
    kv = S((len(cfg.layers_of("attention")), num_pages, page_size,
            KVH * Dh), dtype)
    dense = jax.eval_shape(lambda: tf.init_cache(cfg, state_rows, 1, dtype))
    Lm, _, W, C = dense["conv"].shape
    return {"k": kv, "v": kv, "conv": S((Lm * state_rows, W * C), dtype),
            "ssm": dense["ssm"]}


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _zero_rows(conv, ssm, conv_rows, rows):
    """Zero state rows, in place (donated): ``conv_rows`` of the conv
    rows, ``rows`` of every layer of the SSM state."""
    return conv.at[conv_rows].set(0), ssm.at[:, rows].set(0)


@dataclass
class KVPageSlab:
    """The manager-owned paged KV arrays (all layers stacked) plus the
    host-side free list of page slots.  ``k[l]`` / ``v[l]`` are exactly
    the ``[NP, page_size, KVH, Dh]`` operands ``flash_decode_paged``
    reads in place.  A hybrid's slab also holds the recurrent state
    slabs ``conv`` and ``ssm`` (``paged_slab_shapes``) with their free
    list of rows (None and empty otherwise)."""

    k: jax.Array
    v: jax.Array
    page_size: int
    free: List[int] = field(default_factory=list)
    conv: Optional[jax.Array] = None
    ssm: Optional[jax.Array] = None
    state_free: List[int] = field(default_factory=list)

    @property
    def num_pages(self) -> int:
        """Total KV page slots in the slab (free + leased)."""
        return self.k.shape[1]

    def layer(self, l: int) -> Tuple[jax.Array, jax.Array]:
        """(k_pages, v_pages) for layer ``l`` — the paged-attention view."""
        return self.k[l], self.v[l]


@dataclass
class PagedCacheLease:
    """One leased block-table decode cache: ``block_table`` [B, MB]
    int32 (slab page slot per sequence block, -1 after release) and
    ``lengths`` [B] int32 (tokens written so far — what
    ``flash_decode_paged`` masks on), plus the same byte/tenant
    accounting as the dense ``CacheLease``."""

    block_table: np.ndarray
    lengths: np.ndarray
    batch: int
    max_len: int
    nbytes: int = 0
    page_lease: Optional[PageLease] = None
    tenant: str = "shared"
    lease_id: int = -1                 # globally unique (trace correlation)
    # slab slots this lease allocated (and will free): spliced chunk-KV
    # pages appear in block_table but never here — their ownership stays
    # with the ChunkKVCache residency
    owned_slots: Tuple[int, ...] = ()
    # splice metadata (None until splice_paged ran): per-block RoPE
    # rotation offset and live-token count for serve_step_paged_spliced
    page_delta: Optional[np.ndarray] = None
    page_valid: Optional[np.ndarray] = None
    spliced_pages: int = 0
    # recurrent state row of each sequence (hybrid archs; None otherwise)
    state_slots: Optional[np.ndarray] = None

    def tables(self) -> Tuple[np.ndarray, ...]:
        """The decode step's host-side table operands: (block_table,
        lengths) for ``serve_step_paged``, plus (page_delta, page_valid)
        for ``serve_step_paged_spliced`` once ``splice_paged`` spliced
        pages in, or plus (state_slots,) for
        ``serve_step_hybrid_paged``."""
        if self.spliced_pages:
            return (self.block_table, self.lengths, self.page_delta,
                    self.page_valid)
        if self.state_slots is not None:
            return self.block_table, self.lengths, self.state_slots
        return self.block_table, self.lengths

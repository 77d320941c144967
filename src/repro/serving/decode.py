"""The engine decode plumbing behind ``on_generate``: one reusable
decode-hook object for serve drivers, tests and benchmarks.

``DecodeRunner`` is the real-decode hook the serving front-end wires as
each runtime's ``on_generate``: at every round frontier it leases KV for
the wave, runs actual reduced-model decode steps while the wave's
lookahead copy is in flight, and returns per-request ``DecodeEvent``s —
async decode as the clock source.

By default (``EngineConfig.paged_decode=True``) decode runs on the
**paged substrate**: the wave's KV is a ``PagedCacheLease`` block table
over the manager's shared page slab (``acquire_paged``), every step goes
through ``transformer.serve_step_paged`` — which scatters the new K/V
through the block table in-jit and attends with
``kernels.ops.flash_decode_paged`` — and ``append_paged`` advances the
lease (emitting the ``kv.append`` trace edge the invariant checker
orders).  ``paged_decode=False`` pins the legacy dense ``[B, max_len]``
bucket path (``acquire``/``serve_step``).  Both paths release in
``finally`` (telint TL001) and tenant-tag the lease (TL004), so the
wave's decode state is pool/ledger-accounted either way.

``PoolExhausted`` from ``acquire_paged`` deliberately propagates: the
``RetrievalRuntime`` catches it at the round frontier, sheds what fits
and parks the rest ``PRESSURE_STALLED`` to rejoin on page-free — KV
pressure is an admission decision, not a hook crash.

Timing comes from an injected clock (``attach`` adopts the server's
``wall_clock``): launch drivers inject ``SystemClock`` for real
measurement; the library default is the deterministic event clock, which
is what lets tests pin paged==dense telemetry exactly.  While the server
recorder's host spans are on, the step loop (``telerag.decode.steps``),
each step's host work (``telerag.decode.dispatch``) and the token
read-back (``telerag.decode.readback``) are host-clock spans.  The
read-back is one batched transfer of the wave's ``[steps, rows]``
tokens after the last step (``stats["readback_syncs"]`` counts them).

A ``layer_types`` hybrid (Mamba-2 mixers beside GQA layers) decodes on
the paged path only, through ``transformer.serve_step_hybrid_paged``:
its lease also holds one recurrent state row per sequence, zeroed at
acquire (``stats["state_rows_reset"]`` counts them), and padding rows
update a scratch state row outside the free list.  It has no chunk-KV
splice: a spliced chunk brings pages, not the recurrent state after it.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.data.chunk_kv import ChunkKVStore
from repro.models import transformer as tf
from repro.serving.chunk_kv import ChunkKVCache
from repro.serving.kv_cache import KVCacheManager
from repro.serving.runtime import DecodeEvent
from repro.serving.sampler import sample


def supports_paged_decode(cfg: ArchConfig) -> bool:
    """True iff the arch can decode through block-table KV: plain
    global-causal GQA attention (the ``init_paged`` /
    ``serve_step_paged`` restriction), or a ``layer_types`` hybrid of
    Mamba-2 and such GQA layers (``serve_step_hybrid_paged``) —
    sliding-window, split-cache, MLA and other SSM families stay
    dense."""
    if tf.family_kind(cfg) == "hybrid":
        return cfg.attn_kind == "gqa"
    return (tf.family_kind(cfg) == "attn" and cfg.has_attention
            and cfg.attn_kind == "gqa" and not cfg.local_global_pattern
            and not cfg.sliding_window)


class DecodeRunner:
    """Reusable ``decode_hook(replica, records, gen_tokens, rnd)``:
    per-wave KV lease + real model decode steps, paged by default.

    Construct with the reduced arch's params, pass as the server's
    ``decode_hook``, then ``attach(server)`` so the runner can build one
    pool-backed ``KVCacheManager`` per replica engine (and adopt the
    server's wall clock and each engine's ``paged_decode`` /
    ``kernel_mode`` config)."""

    def __init__(self, params, cfg: ArchConfig, *, max_len: int = 128,
                 max_steps: int = 32, page_size: int = 16,
                 slab_seqs: int = 16,
                 paged: Optional[bool] = None,
                 chunk_store: Optional[ChunkKVStore] = None):
        """``paged=None`` defers to ``EngineConfig.paged_decode`` at
        ``attach`` time (ANDed with arch support); an explicit bool
        overrides the engine config.  ``slab_seqs`` sizes the paged KV
        slab: page slots for that many concurrent ``max_len``
        sequences.  ``chunk_store`` is the offline-built chunk-KV corpus
        (``data.chunk_kv.build_chunk_kv``): when given (and the engine
        enables ``chunk_kv``), each wave's previous-round retrieved docs
        are spliced into its paged lease from precomputed pages instead
        of being re-prefilled."""
        self.params = params
        self._params: Dict[int, object] = {}   # replica -> params on its device
        self.cfg = cfg
        self.max_len = max_len
        self.max_steps = max_steps
        self.page_size = page_size
        self.slab_seqs = slab_seqs
        self._paged_override = paged
        self.paged = bool(paged) and supports_paged_decode(cfg)
        self.chunk_store = chunk_store
        self.clock = None                      # attach() adopts server.wall
        self.recorder = None                   # attach(): server.recorder
        self._kv: Dict[int, KVCacheManager] = {}
        self._chunk: Dict[int, ChunkKVCache] = {}
        self._dense_step = None
        self._paged_step = None
        self._spliced_step = None
        self._rows = 0                         # attach(): server micro_batch
        self._pad_slot: Dict[int, int] = {}    # replica -> scratch KV page
        self._pad_state: Dict[int, int] = {}   # replica -> scratch state row
        # per-request generated tokens, per round: the differential
        # parity suite pins these exactly equal across paged/dense runs
        self.generated: Dict[int, List[Tuple[int, ...]]] = {}
        # measured seconds per decode step, one entry per wave that
        # stepped (first-call compilation lands in a shape's first wave)
        self.wave_step_seconds: List[float] = []
        self.stats = {"paged_waves": 0, "dense_waves": 0,
                      "paged_appends": 0, "dense_steps": 0,
                      "spliced_waves": 0, "readback_syncs": 0,
                      "state_rows_reset": 0}

    # -- wiring --------------------------------------------------------------
    def attach(self, server) -> "DecodeRunner":
        """Bind to a constructed ``TeleRAGServer``: one pool-backed KV
        manager per replica engine (paged mode also allocates the slab)
        and the params, both on the engine's device; clock from the
        server's ``wall_clock`` injection point."""
        self.clock = server.wall
        self.recorder = server.recorder        # host spans, when on
        # paged waves run at one row count (the server's micro-batch), so
        # the decode step compiles once instead of once per wave size
        self._rows = server.micro_batch or 0
        eng0 = server.engines[0]
        want = (eng0.cfg.paged_decode if self._paged_override is None
                else self._paged_override)
        self.paged = bool(want) and supports_paged_decode(self.cfg)
        recurrent = tf.family_kind(self.cfg) == "hybrid"
        if recurrent and not self.paged:
            raise ValueError(f"{self.cfg.name}: a hybrid with recurrent "
                             "state decodes on the paged path only")
        self._kernel_mode = eng0.cfg.kernel_mode
        want_chunk = (self.paged and eng0.cfg.chunk_kv
                      and self.chunk_store is not None)
        if want_chunk and recurrent:
            raise ValueError(
                f"{self.cfg.name}: chunk-KV splicing needs the recurrent "
                "state after each chunk, which a chunk store does not hold")
        self.chunk_docs = eng0.cfg.chunk_kv_docs
        for r, eng in enumerate(server.engines):
            kv = KVCacheManager(self.cfg, pool=eng.pool)
            self._params[r] = jax.device_put(self.params, eng.device)
            if self.paged:
                kv.init_paged(num_pages=self.slab_pages,
                              page_size=self.page_size,
                              state_rows=self.slab_seqs + 1)
                # one page outside the free list: padding rows write and
                # read only there, so they never touch a leased page
                self._pad_slot[r] = kv.slab.free.pop(0)
                if recurrent:           # and one state row, likewise
                    self._pad_state[r] = kv.slab.state_free.pop(0)
            self._kv[r] = kv
            if want_chunk:
                cache = ChunkKVCache(kv, self.chunk_store)
                self._chunk[r] = cache
                # the engine's spill chain and the policy's lookahead
                # prefetch reach chunk residency through this attr
                eng.chunk_kv = cache
        if self.paged and recurrent:
            cfg, mode = self.cfg, self._kernel_mode
            self._paged_step = jax.jit(
                lambda p, k, v, conv, ssm, bt, lens, slots, tok, live:
                    tf.serve_step_hybrid_paged(
                        p, k, v, conv, ssm, bt, lens, slots,
                        {"token": tok, "live_rows": live}, cfg,
                        kernel_mode=mode),
                donate_argnums=(1, 2, 3, 4))
        elif self.paged:
            cfg, mode = self.cfg, self._kernel_mode
            self._paged_step = jax.jit(
                lambda p, k, v, bt, lens, tok, live: tf.serve_step_paged(
                    p, k, v, bt, lens, {"token": tok, "live_rows": live},
                    cfg, kernel_mode=mode),
                donate_argnums=(1, 2))
            if want_chunk:
                self._spliced_step = jax.jit(
                    lambda p, k, v, bt, lens, dl, vd, tok, live:
                        tf.serve_step_paged_spliced(
                            p, k, v, bt, lens, dl, vd,
                            {"token": tok, "live_rows": live}, cfg),
                    donate_argnums=(1, 2))
        else:
            cfg = self.cfg
            self._dense_step = jax.jit(
                lambda p, c, i: tf.serve_step(p, c, i, cfg))
        return self

    @property
    def slab_pages(self) -> int:
        """KV slab page slots: ``slab_seqs`` sequences of ``max_len``
        plus the padding rows' scratch page (a hybrid's state slab holds
        ``slab_seqs`` rows plus a scratch row likewise)."""
        return self.slab_seqs * -(-self.max_len // self.page_size) + 1

    def kv(self, replica: int = 0) -> KVCacheManager:
        """The replica's KV manager (attach() must have run)."""
        return self._kv[replica]

    def replica_params(self, replica: int = 0):
        """The params the replica decodes with, on its engine's device."""
        return self._params[replica]

    def chunk(self, replica: int = 0) -> Optional[ChunkKVCache]:
        """The replica's chunk-KV residency cache (None when chunk-KV
        splicing is not enabled on this runner)."""
        return self._chunk.get(replica)

    # -- the hook ------------------------------------------------------------
    def __call__(self, replica: int, records, gen_tokens, rnd: int,
                 ) -> List[DecodeEvent]:
        """Decode this wave for real: ``steps`` tokens for the whole
        batch on leased KV, measured on the injected clock.  Returns
        one ``DecodeEvent`` per member (observed steps + seconds)."""
        if self.clock is None:
            raise RuntimeError("DecodeRunner.attach(server) before serving")
        n = len(records)
        steps = min(max(gen_tokens, default=0), self.max_steps)
        kv = self._kv[replica]
        tenant = records[0].tenant
        params = self._params[replica]
        if self.paged:
            row_docs = None
            chunk = self._chunk.get(replica)
            if chunk is not None:
                # each row's context = the docs its previous retrieval
                # round returned: splice their precomputed KV instead of
                # re-prefilling them (round 0 has nothing retrieved yet)
                row_docs = [
                    [int(d) for d in r.result.doc_ids[-1]][:self.chunk_docs]
                    if r.result.doc_ids else []
                    for r in records]
            toks, per_step = self._run_paged(replica, params, kv, n, steps,
                                             tenant, chunk=chunk,
                                             row_docs=row_docs)
        else:
            toks, per_step = self._run_dense(params, kv, n, steps, tenant)
        if steps:
            self.wave_step_seconds.append(per_step)
        with self.recorder.span("telerag.decode.readback", rows=n,
                                steps=len(toks), syncs=int(bool(toks))):
            # one transfer of the whole [steps, rows] wave, stacked on the
            # host: a device-side stack would compile once per wave length
            if toks:
                host = np.stack(jax.device_get(toks))
                self.stats["readback_syncs"] += 1
            else:
                host = np.zeros((0, n), np.int32)
            for r, row in zip(records, host.T.tolist()):
                self.generated.setdefault(r.request_id, []).append(
                    tuple(row))
        return [DecodeEvent(request_id=r.request_id,
                            tokens=min(g, steps) if g else 0,
                            seconds=per_step * (min(g, steps) if g else 0))
                for r, g in zip(records, gen_tokens)]

    def _padded_tables(self, replica: int, tables, n: int, rows: int):
        """The lease's (block_table, lengths[, page_delta, page_valid] or
        [, state_slots]) grown from ``n`` to ``rows`` rows: padding rows
        hold one token on the replica's scratch page (lengths 0, delta
        0, valid = ps) and their state in its scratch state row."""
        fill = (self._pad_slot[replica], 0) + (
            (self._pad_state[replica],) if replica in self._pad_state
            else (0, self.page_size))
        return tuple(np.concatenate([t, np.full((rows - n,) + t.shape[1:],
                                                f, t.dtype)])
                     for t, f in zip(tables, fill))

    def _run_paged(self, replica: int, params, kv: KVCacheManager, n: int,
                   steps: int,
                   tenant: str, *, chunk: Optional[ChunkKVCache] = None,
                   row_docs: Optional[List[List[int]]] = None):
        """Block-table decode: acquire_paged -> (serve_step_paged +
        append_paged) per step -> release_paged.  ``PoolExhausted``
        from the acquire propagates to the runtime's shed/park path.

        With a chunk cache and per-row doc ids, retrieved documents'
        precomputed KV pages are pinned and spliced into the fresh
        lease by block-table edit before the first step; the wave then
        decodes through ``serve_step_paged_spliced`` (reordered RoPE +
        partial-page masking).  Pins release back to warm residency in
        the same ``finally`` that frees the lease."""
        self.stats["paged_waves"] += 1
        lease = kv.acquire_paged(n, self.max_len, tenant=tenant)
        state = lease.state_slots is not None
        if state:
            self.stats["state_rows_reset"] += len(lease.state_slots)
        pinned: List[int] = []
        toks: List[jax.Array] = []
        try:
            if chunk is not None and row_docs and any(row_docs):
                row_chunks, pinned, _ = chunk.acquire_rows(row_docs,
                                                           tenant=tenant)
                if kv.splice_paged(lease, row_chunks):
                    self.stats["spliced_waves"] += 1
            rows = max(n, self._rows)
            tok = jnp.zeros((rows,), jnp.int32)
            # padding rows stay out of MoE expert capacity, so a wave's
            # tokens do not depend on the micro-batch it is padded to
            live = jax.device_put(np.int32(n), kv.device)
            span = self.recorder.span
            with span("telerag.decode.steps", rows=rows, steps=steps,
                      state_slots=len(lease.state_slots) if state else 0):
                t0 = self.clock.perf()
                for i in range(steps):
                    with span("telerag.decode.dispatch", step=i):
                        tables = lease.tables()
                        if rows > n:
                            tables = self._padded_tables(replica, tables,
                                                         n, rows)
                        tables = [jax.device_put(t, kv.device)
                                  for t in tables]
                        slab = kv.slab
                        if state:
                            (logits, slab.k, slab.v, slab.conv,
                             slab.ssm) = self._paged_step(
                                params, slab.k, slab.v, slab.conv, slab.ssm,
                                *tables, tok, live)
                        else:
                            step = (self._spliced_step if lease.spliced_pages
                                    else self._paged_step)
                            logits, slab.k, slab.v = step(
                                params, slab.k, slab.v, *tables, tok, live)
                        kv.append_paged(lease)  # scatter was fused in-jit
                        self.stats["paged_appends"] += 1
                        tok = sample(logits)
                        toks.append(tok)
                if toks:
                    jax.block_until_ready(toks[-1])
                per_step = (self.clock.perf() - t0) / max(steps, 1)
        finally:
            # a raising decode step must still free the block table —
            # leaked paged leases shrink the slab AND the shared pool
            # until admission starves (telint TL001); spliced chunks
            # unpin AFTER the table is gone (back to warm residency)
            kv.release_paged(lease)
            if chunk is not None:
                chunk.release_rows(pinned)
        return toks, per_step

    def _run_dense(self, params, kv: KVCacheManager, n: int, steps: int,
                   tenant: str):
        """The pinned legacy path: one dense [n, max_len] bucket."""
        self.stats["dense_waves"] += 1
        lease = kv.acquire(n, self.max_len, fresh=True, tenant=tenant)
        toks: List[jax.Array] = []
        try:
            tok = jnp.zeros((n,), jnp.int32)
            span = self.recorder.span
            with span("telerag.decode.steps", rows=n, steps=steps):
                t0 = self.clock.perf()
                for t in range(steps):
                    with span("telerag.decode.dispatch", step=t):
                        logits, lease.cache = self._dense_step(
                            params, lease.cache,
                            {"token": tok,
                             "pos": jnp.full((n,), t, jnp.int32)})
                        self.stats["dense_steps"] += 1
                        tok = sample(logits)
                        toks.append(tok)
                if toks:
                    jax.block_until_ready(toks[-1])
                per_step = (self.clock.perf() - t0) / max(steps, 1)
        finally:
            kv.release(lease)
        return toks, per_step

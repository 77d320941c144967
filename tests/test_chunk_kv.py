"""Chunk-KV splice: reordered-RoPE parity against re-prefill oracles,
residency refcount discipline, and the end-to-end serve path.

Parity is layered the way the subsystem is:

* **Kernel**: ``flash_decode_spliced_ref`` against (a) the dense
  ``flash_decode_ref`` on all-fresh and on aligned multi-chunk tables
  (stored K roped chunk-locally, the oracle roped at layout positions —
  the rotation-composition claim itself), and (b) a loopy numpy softmax
  oracle that only ever *gathers live tokens*, so partial-last-page
  masking is checked against an implementation with no masks at all.
* **Model**: ``serve_step_paged_spliced`` greedy decode against full
  re-prefill (``transformer.prefill`` over chunk tokens + generated
  tokens): logits within float32 tolerance, greedy tokens EXACTLY
  equal, including two chunks spliced in both orders in one batch.
  Ragged chunks are pinned by garbage-invariance: poisoning the dead
  tail of a partial last page must not move a single logit bit.
* **Serve**: a real ``TeleRAGServer`` run with a chunk store — splice
  hits, lookahead prefetch landing pages, miss fallback, retrieval
  parity with a chunk-less run, and a fully drained ledger + recorder
  stream (``must_drain=("kv", "chunk_kv")``).

The hypothesis sweeps (skipped when hypothesis is absent) randomize
page size, chunk lengths/orderings and step counts through the same
oracles.
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from hypothesis import HealthCheck, given, settings
from hypothesis import strategies as st

from repro.analysis import check_recorder
from repro.configs import get_arch
from repro.configs.base import ArchConfig
from repro.data.chunk_kv import (ChunkKVStore, build_chunk, build_chunk_kv,
                                 chunk_tokens, cluster_map_from_assignments,
                                 pages_from_cache)
from repro.kernels import ops, ref
from repro.memory.pool import DevicePagePool
from repro.models import transformer as tf
from repro.models.layers import apply_rope
from repro.obs.recorder import FlightRecorder
from repro.serving import (ChunkKVCache, DecodeRunner, EngineConfig,
                           KVCacheManager, RagRequest, RequestState,
                           TeleRAGServer, make_traces)
from tests.conftest import unit_queries

TINY = ArchConfig(name="tiny", family="dense", source="test",
                  d_model=64, num_layers=2, num_heads=4, num_kv_heads=2,
                  head_dim=16, vocab_size=64)
ARCH = get_arch("llama3-8b")
SERVE_CFG = ARCH.reduced()


@pytest.fixture(scope="module")
def tparams():
    return tf.init_params(TINY, jax.random.PRNGKey(1), dtype=jnp.float32)


@pytest.fixture(scope="module")
def serve_params():
    return tf.init_params(SERVE_CFG, jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Offline builder (data layer)
# ---------------------------------------------------------------------------


def test_chunk_tokens_deterministic_and_ragged():
    a = chunk_tokens(7, 64, seed=3)
    b = chunk_tokens(7, 64, seed=3)
    np.testing.assert_array_equal(a, b)          # pure fn of (seed, doc)
    assert 8 <= len(a) <= 24
    assert (a >= 0).all() and (a < 64).all()
    assert not np.array_equal(a, chunk_tokens(8, 64, seed=3)[:len(a)]) \
        or len(a) != len(chunk_tokens(8, 64, seed=3))
    assert not np.array_equal(chunk_tokens(7, 64, seed=4), a) \
        or len(chunk_tokens(7, 64, seed=4)) != len(a)
    lengths = {len(chunk_tokens(d, 64, seed=0)) for d in range(32)}
    assert len(lengths) > 3, "lengths must be ragged across docs"


def test_pages_from_cache_pads_and_bounds():
    rng = np.random.default_rng(0)
    k = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    v = rng.standard_normal((2, 16, 2, 8)).astype(np.float32)
    kp, vp = pages_from_cache(k, v, length=9, page_size=4)
    assert kp.shape == (2, 3, 4, 2, 8)           # ceil(9/4) pages
    np.testing.assert_array_equal(kp.reshape(2, 12, 2, 8)[:, :9], k[:, :9])
    assert (kp[:, 2, 1:] == 0).all(), "dead tail must be zero padding"
    assert (vp[:, 2, 1:] == 0).all()
    with pytest.raises(ValueError):
        pages_from_cache(k, v, length=17, page_size=4)


def test_build_chunk_matches_prefill_and_store_roundtrip(tparams, tmp_path):
    """The builder's pages are exactly one chunk-local prefill, cut to
    page geometry — and survive the .npz artifact roundtrip."""
    chunk = build_chunk(tparams, TINY, 5, page_size=4, seed=2)
    toks = chunk_tokens(5, TINY.vocab_size, seed=2)
    _, cache = tf.prefill(tparams, {"tokens": np.asarray(toks)[None]}, TINY)
    kp, vp = pages_from_cache(np.asarray(cache["k"][:, 0], np.float32),
                              np.asarray(cache["v"][:, 0], np.float32),
                              len(toks), 4)
    np.testing.assert_allclose(chunk.k, kp, rtol=1e-6)
    np.testing.assert_allclose(chunk.v, vp, rtol=1e-6)
    assert chunk.length == len(toks)

    store = build_chunk_kv(tparams, TINY, [5, 9], page_size=4, seed=2,
                           cluster_of=lambda d: d % 3)
    path = str(tmp_path / "chunks.npz")
    store.save(path)
    loaded = ChunkKVStore.load(path)
    assert loaded.page_size == 4 and len(loaded) == 2
    for d in (5, 9):
        np.testing.assert_array_equal(loaded.get(d).k, store.get(d).k)
        assert loaded.get(d).length == store.get(d).length
        assert loaded.get(d).cluster == d % 3
    assert loaded.docs_in_cluster(2) == [5]
    assert loaded.docs_in_cluster(0) == [9]


# ---------------------------------------------------------------------------
# RoPE composition + kernel-level splice parity
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fraction", [1.0, 0.5])
def test_rope_rotations_compose(fraction):
    """R(p + d) x == R(d) R(p) x — the identity the whole reordered-RoPE
    splice rests on (chunk-local K + one constant per-page delta)."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((5, 2, 16)), jnp.float32)
    p = jnp.asarray([0, 3, 4, 9, 17])
    d = jnp.asarray([8, 8, 8, 8, 8])
    once = apply_rope(x, p + d, fraction=fraction)
    twice = apply_rope(apply_rope(x, p, fraction=fraction), d,
                       fraction=fraction)
    np.testing.assert_allclose(np.asarray(once), np.asarray(twice),
                               rtol=1e-5, atol=1e-5)


def _rand_qkv(rng, B, S, KVH, G, Dh):
    q = jnp.asarray(rng.standard_normal((B, KVH, G, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((S, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((S, KVH, Dh)), jnp.float32)
    return q, k, v


def test_spliced_all_fresh_equals_dense_ref():
    """delta=0 / valid=ps degenerates to plain paged attention — and the
    ops entry point is that oracle: it takes no kernel mode."""
    rng = np.random.default_rng(4)
    B, S, KVH, G, Dh, ps = 2, 12, 2, 2, 16, 4
    q, k1, v1 = _rand_qkv(rng, B, S, KVH, G, Dh)
    k = jnp.stack([k1, k1[::-1]])                     # [B, S, KVH, Dh]
    v = jnp.stack([v1, v1[::-1]])
    kp = k.reshape(B * 3, ps, KVH, Dh)                # 3 pages per row
    vp = v.reshape(B * 3, ps, KVH, Dh)
    bt = jnp.arange(B * 3, dtype=jnp.int32).reshape(B, 3)
    lengths = jnp.asarray([S, S - 2], jnp.int32)
    delta = jnp.zeros((B, 3), jnp.int32)
    valid = jnp.full((B, 3), ps, jnp.int32)
    out = ref.flash_decode_spliced_ref(q, kp, vp, bt, lengths, delta, valid)
    want = ref.flash_decode_ref(q, k, v, lengths - 1, 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    out2 = ops.flash_decode_spliced(q, kp, vp, bt, lengths, delta, valid)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))
    assert ops.resolved_modes()["flash_decode_spliced"] == "ref"
    with pytest.raises(TypeError):
        ops.flash_decode_spliced(q, kp, vp, bt, lengths, delta, valid,
                                 mode="kernel")


def test_spliced_multi_chunk_delta_equals_layout_rope():
    """Two aligned chunks + fresh tokens: pages stored with CHUNK-LOCAL
    rope and reindexed by per-page delta must equal the dense oracle
    whose K was roped at layout positions outright."""
    rng = np.random.default_rng(5)
    KVH, G, Dh, ps = 2, 2, 16, 4
    lens = [8, 4]                        # chunk A: pages 0-1, B: page 2
    fresh = 2                            # 2 generated tokens on page 3
    S = sum(lens) + fresh                # layout positions 0..13
    q, raw_k, raw_v = _rand_qkv(rng, 1, S, KVH, G, Dh)
    layout = jnp.arange(S)
    dense_k = apply_rope(raw_k, layout)  # the re-prefill-at-layout oracle
    pages_k, pages_v, deltas = [], [], []
    base = 0
    for ln in lens:
        local = apply_rope(raw_k[base:base + ln], jnp.arange(ln))
        for p in range(ln // ps):
            pages_k.append(local[p * ps:(p + 1) * ps])
            pages_v.append(raw_v[base + p * ps:base + (p + 1) * ps])
            deltas.append(base)          # b0 * ps: constant per chunk
        base += ln
    pad = jnp.zeros((ps - fresh, KVH, Dh), jnp.float32)
    tail = apply_rope(raw_k[base:], layout[base:])    # fresh page, delta 0
    pages_k.append(jnp.concatenate([tail, pad]))
    pages_v.append(jnp.concatenate([raw_v[base:], pad]))
    deltas.append(0)
    kp, vp = jnp.stack(pages_k), jnp.stack(pages_v)
    bt = jnp.arange(4, dtype=jnp.int32)[None]
    out = ref.flash_decode_spliced_ref(
        q, kp, vp, bt, jnp.asarray([S], jnp.int32),
        jnp.asarray(deltas, jnp.int32)[None],
        jnp.full((1, 4), ps, jnp.int32))
    want = ref.flash_decode_ref(q, dense_k[None], raw_v[None],
                                jnp.asarray([S - 1]), 0)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def _loopy_spliced_oracle(q, k_pages, v_pages, bt, lengths, delta, valid,
                          ps):
    """Mask-free oracle: gather ONLY the live tokens per row, rotate by
    the page delta, plain softmax.  [B, KVH, G, Dh] fp32."""
    q = np.asarray(q, np.float32)
    B, KVH, G, Dh = q.shape
    out = np.zeros_like(q)
    for b in range(B):
        ks, vs = [], []
        for blk in range(bt.shape[1]):
            pg = int(bt[b, blk])
            if pg < 0:
                continue
            for off in range(int(valid[b, blk])):
                if blk * ps + off > int(lengths[b]) - 1:
                    continue
                kro = apply_rope(jnp.asarray(k_pages[pg, off])[None],
                                 jnp.asarray([int(delta[b, blk])]))
                ks.append(np.asarray(kro, np.float32)[0])
                vs.append(np.asarray(v_pages[pg, off], np.float32))
        K, V = np.stack(ks), np.stack(vs)             # [N, KVH, Dh]
        for h in range(KVH):
            s = q[b, h] @ K[:, h].T / np.sqrt(Dh)     # [G, N]
            w = np.exp(s - s.max(-1, keepdims=True))
            w /= w.sum(-1, keepdims=True)
            out[b, h] = w @ V[:, h]
    return out


def _ragged_case(rng, lens, ps, fresh, KVH=2, G=2, Dh=16):
    """Build a spliced table for ragged chunk ``lens`` + ``fresh``
    generated tokens; returns (q, kp, vp, bt, lengths, delta, valid)."""
    n_pages = [-(-ln // ps) for ln in lens]
    MB = sum(n_pages) + max(1, -(-fresh // ps))
    q = jnp.asarray(rng.standard_normal((1, KVH, G, Dh)), jnp.float32)
    pages_k, pages_v, delta, valid = [], [], [], []
    b0 = 0
    for ln, npg in zip(lens, n_pages):
        raw = jnp.asarray(rng.standard_normal((npg * ps, KVH, Dh)),
                          jnp.float32)
        local = apply_rope(raw, jnp.arange(npg * ps))
        for p in range(npg):
            pages_k.append(local[p * ps:(p + 1) * ps])
            pages_v.append(raw[p * ps:(p + 1) * ps])
            delta.append(b0 * ps)
            valid.append(ps if p < npg - 1 else ln - (npg - 1) * ps)
        b0 += npg
    layout0 = b0 * ps                                 # generation resumes
    for p in range(MB - sum(n_pages)):
        raw = jnp.asarray(rng.standard_normal((ps, KVH, Dh)), jnp.float32)
        pos = jnp.arange(layout0 + p * ps, layout0 + (p + 1) * ps)
        pages_k.append(apply_rope(raw, pos))
        pages_v.append(raw)
        delta.append(0)
        valid.append(ps)
    kp, vp = jnp.stack(pages_k), jnp.stack(pages_v)
    bt = np.arange(MB, dtype=np.int32)[None]
    lengths = np.asarray([layout0 + fresh], np.int32)
    return (q, kp, vp, bt, lengths, np.asarray(delta, np.int32)[None],
            np.asarray(valid, np.int32)[None])


@pytest.mark.parametrize("lens,ps,fresh", [
    ([5], 4, 3),            # partial last page, holes at layout 5..7
    ([9, 3], 4, 1),         # two ragged chunks, two partial pages
    ([3, 5, 2], 2, 2),      # three chunks crossing page_size=2 oddly
])
def test_spliced_ragged_vs_loopy_oracle(lens, ps, fresh):
    rng = np.random.default_rng(sum(lens) * 31 + ps)
    q, kp, vp, bt, lengths, delta, valid = _ragged_case(rng, lens, ps, fresh)
    out = ref.flash_decode_spliced_ref(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
        jnp.asarray(delta), jnp.asarray(valid))
    want = _loopy_spliced_oracle(q, kp, vp, bt, lengths, delta, valid, ps)
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-5, atol=1e-5)


def test_spliced_hole_slots_are_garbage_invariant():
    """Poisoning the dead tail of a partial last page (and padding
    columns) must not move any output bit — the masks, not luck."""
    rng = np.random.default_rng(9)
    q, kp, vp, bt, lengths, delta, valid = _ragged_case(rng, [5], 4, 3)
    clean = ref.flash_decode_spliced_ref(
        q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
        jnp.asarray(delta), jnp.asarray(valid))
    kp2 = kp.at[1, 1:].set(1e9)          # chunk's page 1 holds 1 live token
    vp2 = vp.at[1, 1:].set(-1e9)
    dirty = ref.flash_decode_spliced_ref(
        q, kp2, vp2, jnp.asarray(bt), jnp.asarray(lengths),
        jnp.asarray(delta), jnp.asarray(valid))
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))


def test_hypothesis_spliced_kernel_vs_loopy_oracle():
    """Randomized ragged sweep of the kernel oracle pair."""

    @settings(max_examples=20, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(ps=st.sampled_from([2, 4]),
           lens=st.lists(st.integers(1, 9), min_size=0, max_size=3),
           fresh=st.integers(1, 5), seed=st.integers(0, 2**16))
    def check(ps, lens, fresh, seed):
        rng = np.random.default_rng(seed)
        q, kp, vp, bt, lengths, delta, valid = _ragged_case(
            rng, lens, ps, fresh)
        out = ref.flash_decode_spliced_ref(
            q, kp, vp, jnp.asarray(bt), jnp.asarray(lengths),
            jnp.asarray(delta), jnp.asarray(valid))
        want = _loopy_spliced_oracle(q, kp, vp, bt, lengths, delta, valid,
                                     ps)
        np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4,
                                   atol=1e-4)

    check()


# ---------------------------------------------------------------------------
# Model-level: spliced decode vs full re-prefill oracle
# ---------------------------------------------------------------------------


def _splice_env(tparams, row_docs, doc_lens, *, ps=4, max_len=8,
                num_pages=64, seed=0):
    """Manager + store + a spliced lease over explicit per-doc lengths
    (min_len == max_len pins each doc's chunk_tokens length)."""
    mgr = KVCacheManager(TINY, dtype=jnp.float32)
    mgr.init_paged(num_pages=num_pages, page_size=ps)
    store = ChunkKVStore(page_size=ps, seed=seed)
    for d, ln in doc_lens.items():
        store.add(d, build_chunk(tparams, TINY, d, page_size=ps, seed=seed,
                                 min_len=ln, max_len=ln))
    cache = ChunkKVCache(mgr, store)
    row_chunks, pinned, misses = cache.acquire_rows(row_docs)
    lease = mgr.acquire_paged(len(row_docs), max_len)
    mgr.splice_paged(lease, row_chunks)
    return mgr, cache, lease, pinned, misses


def _spliced_greedy(params, cfg, mgr, lease, steps):
    """Greedy decode through serve_step_paged_spliced; returns
    (per-step logits [B, V], per-step tokens [B])."""
    logits_seq, toks = [], []
    tok = jnp.zeros((lease.batch,), jnp.int32)
    for _ in range(steps):
        bt, lens, dl, vd = map(jnp.asarray, lease.tables())
        logits, mgr.slab.k, mgr.slab.v = tf.serve_step_paged_spliced(
            params, mgr.slab.k, mgr.slab.v, bt, lens, dl, vd,
            {"token": tok}, cfg)
        mgr.append_paged(lease)
        logits_seq.append(np.asarray(logits))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(np.asarray(tok))
    return logits_seq, toks


def _prefill_oracle(params, cfg, ctx_tokens, steps):
    """Full re-prefill greedy oracle: each step re-prefills context +
    everything generated so far and reads the last-position logits."""
    seq = [int(t) for t in ctx_tokens] + [0]   # BOS-like 0 = first token
    logits_seq, toks = [], []
    for _ in range(steps):
        lg, _ = tf.prefill(params, {"tokens": np.asarray(seq, np.int32)[None]},
                           cfg)
        last = np.asarray(lg)                  # [1, V] last-token logits
        nxt = int(np.argmax(last, -1)[0])
        logits_seq.append(last)
        toks.append(nxt)
        seq.append(nxt)
    return logits_seq, toks


def test_spliced_decode_matches_full_reprefill_single_chunk(tparams):
    """One page-aligned chunk spliced at layout 0: greedy tokens EXACT,
    logits within float32 tolerance of re-prefilling everything."""
    mgr, cache, lease, pinned, _ = _splice_env(tparams, [[7]], {7: 8})
    assert lease.spliced_pages == 2 and list(lease.lengths) == [8]
    got_logits, got_toks = _spliced_greedy(tparams, TINY, mgr, lease, 4)
    ctx = chunk_tokens(7, TINY.vocab_size, seed=0, min_len=8, max_len=8)
    want_logits, want_toks = _prefill_oracle(tparams, TINY, ctx, 4)
    assert [int(t[0]) for t in got_toks] == want_toks
    for g, w in zip(got_logits, want_logits):
        np.testing.assert_allclose(g, w, rtol=2e-4, atol=2e-4)
    mgr.release_paged(lease)
    cache.release_rows(pinned)
    assert cache.resident_pages() == 2 and cache.pinned_pages() == 0


def _assembled_dense_oracle(params, cfg, store, order, steps, *, ps=4):
    """Exact multi-chunk oracle: a DENSE cache assembled from each
    chunk's own independent prefill pages, rotated to their layout
    offset (rope composition), then plain ``serve_step`` greedy decode.
    This is the semantic contract of the splice — for several chunks it
    deliberately differs from re-prefilling the concatenation, whose
    layer>0 hidden states mix the chunks (the TurboRAG independent-
    chunk approximation); for ONE chunk the two oracles coincide."""
    L, KVH, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    ks, vs = [], []
    base = 0
    for d in order:
        c = store.get(d)
        assert c.length % ps == 0, "aligned chunks only (no holes)"
        k = jnp.asarray(c.k.reshape(L, -1, KVH, Dh), jnp.float32)
        v = jnp.asarray(c.v.reshape(L, -1, KVH, Dh), jnp.float32)
        ks.append(apply_rope(k, jnp.full((k.shape[1],), base)))
        vs.append(v)
        base += c.length
    cache = tf.init_cache(cfg, 1, base + steps, jnp.float32)
    cache["k"] = cache["k"].at[:, 0, :base].set(jnp.concatenate(ks, 1))
    cache["v"] = cache["v"].at[:, 0, :base].set(jnp.concatenate(vs, 1))
    logits_seq, toks = [], []
    tok = jnp.zeros((1,), jnp.int32)
    for t in range(steps):
        logits, cache = tf.serve_step(
            params, cache, {"token": tok,
                            "pos": jnp.full((1,), base + t, jnp.int32)}, cfg)
        logits_seq.append(np.asarray(logits))
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        toks.append(int(tok[0]))
    return logits_seq, toks


def test_spliced_decode_multi_chunk_orderings_match_oracle(tparams):
    """Two chunks spliced [A, B] in row 0 and [B, A] in row 1 of ONE
    batch: each row must match the assembled-rotated-cache oracle for
    its own order (order changes the context, deltas differ per row,
    parity must hold for both rows simultaneously)."""
    mgr, cache, lease, pinned, _ = _splice_env(
        tparams, [[3, 5], [5, 3]], {3: 8, 5: 4})
    assert lease.spliced_pages == 6 and list(lease.lengths) == [12, 12]
    # row 0: chunk 5 sits at base block 2 -> delta 8; row 1: chunk 3 at
    # base block 1 -> delta 4
    assert list(lease.page_delta[0][:3]) == [0, 0, 8]
    assert list(lease.page_delta[1][:3]) == [0, 4, 4]
    got_logits, got_toks = _spliced_greedy(tparams, TINY, mgr, lease, 3)
    for row, order in enumerate(([3, 5], [5, 3])):
        want_logits, want_toks = _assembled_dense_oracle(
            tparams, TINY, cache.store, order, 3)
        assert [int(t[row]) for t in got_toks] == want_toks, f"row {row}"
        for g, w in zip(got_logits, want_logits):
            np.testing.assert_allclose(g[row][None], w, rtol=2e-4,
                                       atol=2e-4)
    # the two orders are genuinely different contexts
    assert not np.allclose(got_logits[0][0], got_logits[0][1], atol=1e-3)
    mgr.release_paged(lease)
    cache.release_rows(pinned)


def test_spliced_decode_ragged_chunk_garbage_invariant(tparams):
    """A ragged chunk (partial last page) decoded end-to-end: poisoning
    the page's dead tail in the slab changes nothing."""
    mgr, cache, lease, pinned, _ = _splice_env(tparams, [[11]], {11: 5})
    assert lease.spliced_pages == 2
    assert list(lease.lengths) == [8], "resume at next page boundary"
    assert lease.page_valid[0][1] == 1
    hole_slot = int(lease.block_table[0, 1])
    k0, v0 = mgr.slab.k, mgr.slab.v
    bt, lens, dl, vd = map(jnp.asarray, lease.tables())
    tok = jnp.zeros((1,), jnp.int32)
    clean, _, _ = tf.serve_step_paged_spliced(
        tparams, k0, v0, bt, lens, dl, vd, {"token": tok}, TINY)
    dirty, _, _ = tf.serve_step_paged_spliced(
        tparams, k0.at[:, hole_slot, 1:].set(1e9),
        v0.at[:, hole_slot, 1:].set(-1e9), bt, lens, dl, vd,
        {"token": tok}, TINY)
    np.testing.assert_array_equal(np.asarray(clean), np.asarray(dirty))
    mgr.release_paged(lease)
    cache.release_rows(pinned)


def test_hypothesis_spliced_decode_vs_oracles(tparams):
    """Randomized aligned multi-chunk orderings: greedy tokens exact
    and logits within tolerance of the assembled-cache oracle (which
    for a single chunk IS full re-prefill)."""

    @settings(max_examples=8, deadline=None,
              suppress_health_check=list(HealthCheck))
    @given(n_pages=st.lists(st.integers(1, 3), min_size=1, max_size=3),
           perm_seed=st.integers(0, 5), steps=st.integers(1, 3))
    def check(n_pages, perm_seed, steps):
        ps = 4
        doc_lens = {10 + i: n * ps for i, n in enumerate(n_pages)}
        order = list(doc_lens)
        np.random.default_rng(perm_seed).shuffle(order)
        mgr, cache, lease, pinned, _ = _splice_env(
            tparams, [order], doc_lens, ps=ps)
        got_logits, got_toks = _spliced_greedy(tparams, TINY, mgr, lease,
                                               steps)
        want_logits, want_toks = _assembled_dense_oracle(
            tparams, TINY, cache.store, order, steps, ps=ps)
        assert [int(t[0]) for t in got_toks] == want_toks
        for g, w in zip(got_logits, want_logits):
            np.testing.assert_allclose(g, w, rtol=5e-4, atol=5e-4)
        if len(order) == 1:
            ctx = chunk_tokens(order[0], TINY.vocab_size, seed=0,
                               min_len=doc_lens[order[0]],
                               max_len=doc_lens[order[0]])
            rl, rt = _prefill_oracle(tparams, TINY, ctx, steps)
            assert rt == want_toks       # one chunk: oracles coincide
        mgr.release_paged(lease)
        cache.release_rows(pinned)

    check()


# ---------------------------------------------------------------------------
# Splice mechanics on the manager (block-table edit discipline)
# ---------------------------------------------------------------------------


def test_splice_paged_edits_table_and_keeps_ownership(tparams):
    mgr, cache, lease, pinned, _ = _splice_env(tparams, [[7], []], {7: 8})
    free_before = set(mgr.slab.free)
    owned = set(lease.owned_slots)
    chunk_slots = set(int(s) for s in lease.block_table[0, :2])
    assert chunk_slots.isdisjoint(owned)
    assert lease.max_len == 8 + 8                 # lead pages widen bounds
    assert list(lease.lengths) == [8, 0]          # row 1 spliced nothing
    assert (lease.page_valid[0, :2] == [4, 4]).all()
    assert (lease.page_valid[lease.block_table < 0] == 0).all()
    mgr.release_paged(lease)
    # ONLY the owned slots return to the free list; the chunk's pages
    # stay with the residency (freeing them would alias live pages)
    assert set(mgr.slab.free) == free_before | owned
    assert chunk_slots.isdisjoint(mgr.slab.free)
    cache.release_rows(pinned)
    assert cache.resident_pages() == 2            # warm, not freed


def test_splice_paged_rejects_bad_rows(tparams):
    mgr = KVCacheManager(TINY, dtype=jnp.float32)
    mgr.init_paged(num_pages=16, page_size=4)
    lease = mgr.acquire_paged(2, 8)
    with pytest.raises(ValueError):               # row count mismatch
        mgr.splice_paged(lease, [[]])
    with pytest.raises(ValueError):               # page count vs length
        mgr.splice_paged(lease, [[((1, 2), 3)], []])
    assert mgr.splice_paged(lease, [[], []]) == 0
    assert lease.spliced_pages == 0 and lease.page_delta is None
    z = np.zeros((TINY.num_layers, 2, TINY.num_kv_heads,
                  TINY.resolved_head_dim), np.float32)
    mgr.append_paged(lease, z, z)
    with pytest.raises(ValueError):               # not fresh anymore
        mgr.splice_paged(lease, [[((1,), 4)], []])
    mgr.release_paged(lease)


# ---------------------------------------------------------------------------
# ChunkKVCache residency: refcounts, LRU, accounting
# ---------------------------------------------------------------------------


def _pool_cache(small_index, tparams, *, slab_pages=32, pool_pages=128,
                docs=(1, 2, 3), lens=(5, 8, 9), cluster_of=None):
    pool = DevicePagePool(small_index.paged, pool_pages)
    pool.recorder = FlightRecorder()
    pool.replica_id = 0
    mgr = KVCacheManager(TINY, dtype=jnp.float32, pool=pool)
    mgr.init_paged(num_pages=slab_pages, page_size=4)
    store = ChunkKVStore(page_size=4)
    for d, ln in zip(docs, lens):
        store.add(d, build_chunk(tparams, TINY, d, page_size=4,
                                 min_len=ln, max_len=ln,
                                 cluster=(-1 if cluster_of is None
                                          else cluster_of(d))))
    return pool, mgr, ChunkKVCache(mgr, store)


def test_residency_lifecycle_refcounts_and_ledger(small_index, tparams):
    pool, mgr, cache = _pool_cache(small_index, tparams)
    free0 = len(mgr.slab.free)
    res = cache.load(1, tenant="acme")            # 5 tokens -> 2 pages
    assert res.slots and len(mgr.slab.free) == free0 - 2
    assert pool.ledger.bytes_of("chunk_kv") == 2 * mgr.paged_page_nbytes()
    assert pool.tenant_bytes("acme", owner="chunk_kv") > 0
    assert cache.load(1, tenant="acme") is res    # idempotent re-load
    assert cache.stats.loads == 1
    cache.pin(1)
    cache.pin(1)
    with pytest.raises(ValueError):
        cache.evict(1)                            # pinned -> protected
    with pytest.raises(RuntimeError):
        cache.drain()
    cache.unpin(1)
    cache.unpin(1)
    with pytest.raises(ValueError):
        cache.unpin(1)                            # not pinned anymore
    with pytest.raises(KeyError):
        cache.pin(99)                             # pin-before-load
    assert cache.evict(1) == 2
    assert len(mgr.slab.free) == free0
    assert pool.ledger.bytes_of("chunk_kv") == 0
    assert cache.load(77) is None                 # store miss -> fallback
    rep = check_recorder(pool.recorder, drained=True,
                         must_drain=("chunk_kv",))
    assert rep.ok, rep.summary()
    assert rep.stats["chunk_loads"] == 1


def test_evict_cold_is_lru_and_skips_pinned(small_index, tparams):
    _, mgr, cache = _pool_cache(small_index, tparams)
    for d in (1, 2, 3):
        cache.load(d)
    cache.load(1)                                 # refresh 1 -> 2 is LRU
    cache.pin(2)                                  # ... but 2 is pinned
    cache.evict_cold(pages_hint=1)
    assert 3 not in cache.resident                # next-coldest unpinned
    assert 1 in cache.resident and 2 in cache.resident
    cache.unpin(2)
    assert cache.drain() == 2 + 2                 # docs 1 and 2, 2 pages each
    assert not cache.resident and cache.stats.evictions == 3


def test_page_size_mismatch_rejected(tparams):
    mgr = KVCacheManager(TINY, dtype=jnp.float32)
    mgr.init_paged(num_pages=8, page_size=4)
    with pytest.raises(ValueError):
        ChunkKVCache(mgr, ChunkKVStore(page_size=8))


def test_acquire_rows_stats_and_backfill(small_index, tparams):
    _, mgr, cache = _pool_cache(small_index, tparams)
    rows, pinned, misses = cache.acquire_rows([[1, 99], [2]])
    assert [len(r) for r in rows] == [1, 1] and misses == [[99], []]
    assert cache.stats.hits == 2 and cache.stats.misses == 1
    assert cache.stats.prefill_tokens_avoided == 5 + 8
    assert cache.stats.spliced_pages == 2 + 2
    assert sorted(pinned) == [1, 2] and cache.pinned_pages() == 4
    cache.release_rows(pinned)
    assert cache.pinned_pages() == 0
    # miss-path backfill: prefill once now, hit forever after
    assert cache.backfill(99, tparams, TINY, min_len=6, max_len=6)
    assert cache.backfill(99, tparams, TINY) is None   # already there
    assert cache.stats.backfills == 1
    rows2, pinned2, misses2 = cache.acquire_rows([[99]])
    assert misses2 == [[]] and len(rows2[0]) == 1
    cache.release_rows(pinned2)
    cache.drain()


def test_prefetch_clusters_budget_and_room(small_index, tparams):
    _, mgr, cache = _pool_cache(small_index, tparams,
                                cluster_of=lambda d: d % 2)
    landed = cache.prefetch_clusters([1], budget_pages=2)   # docs 1, 3
    assert landed == 2                       # doc 1 (2 pages) hits budget
    assert cache.stats.prefetched_pages == 2
    assert 1 in cache.resident and 3 not in cache.resident
    assert cache.prefetch_clusters([0]) == 2                # doc 2
    cache.drain()
    # a slab too small for the chunk stops the burst instead of raising
    _, mgr2, cache2 = _pool_cache(small_index, tparams, slab_pages=1,
                                  cluster_of=lambda d: d % 2)
    assert cache2.prefetch_clusters([1]) == 0


def test_chunk_load_under_pool_pressure_evicts_cold(small_index, tparams):
    """When the POOL (not the slab) is the constraint, loading spills
    cold residency first and only then reports no-room."""
    pool, mgr, cache = _pool_cache(small_index, tparams, slab_pages=32,
                                   pool_pages=1)
    assert cache.load(1) is not None         # the one pool page
    assert pool.ledger.bytes_of("chunk_kv") > 0
    assert cache.load(2) is not None         # evicts 1 to make room
    assert 1 not in cache.resident and cache.stats.evictions == 1
    cache.pin(2)
    assert cache.load(3) is None             # pinned -> nothing to spill
    cache.unpin(2)
    cache.drain()
    assert pool.ledger.bytes_of("chunk_kv") == 0


# ---------------------------------------------------------------------------
# End-to-end serve: splice + lookahead prefetch on a real server
# ---------------------------------------------------------------------------


def _serve_chunk(small_index, q, traces, *, params, store, micro_batch=3,
                 max_steps=4, page_size=4, slab_seqs=None):
    n = len(traces)
    runner = DecodeRunner(params, SERVE_CFG, max_len=24,
                          max_steps=max_steps, page_size=page_size,
                          slab_seqs=slab_seqs if slab_seqs is not None
                          else n + 8, chunk_store=store)
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        lookahead_rank=16, kernel_mode="ref", chips=8, seed=7,
        paged_decode=True, chunk_kv=store is not None), 1, ARCH,
        micro_batch=micro_batch, include_tail=True, decode_hook=runner)
    runner.attach(srv)
    resp = srv.serve([RagRequest(q=q[i], trace=traces[i], arrival_t=0.0)
                      for i in range(n)])
    return runner, srv, resp


def _round_docs(resp):
    return [[sorted(int(x) for x in d) for d in r.doc_ids] for r in resp]


@pytest.mark.slow
def test_serve_splices_prefetches_and_drains(small_store, small_index, rng,
                                             serve_params):
    """The whole tentpole on a live server: retrieval docs resolve to
    precomputed pages, waves decode through the spliced step, lookahead
    lands pages ahead of the splice, retrieval is unchanged vs the
    chunk-less run, and everything drains to zero."""
    q = unit_queries(small_store, rng, 4)
    traces = make_traces("iter", 4, seed=11)
    r0, s0, resp0 = _serve_chunk(small_index, q, traces,
                                 params=serve_params, store=None)
    assert all(r.state == RequestState.COMPLETE for r in resp0)
    docs = sorted({int(d) for r in resp0 for rd in r.doc_ids for d in rd})
    assert docs, "no retrieval rounds ran"
    store = build_chunk_kv(
        serve_params, SERVE_CFG, docs, page_size=4, seed=3, min_len=6,
        max_len=8,
        cluster_of=cluster_map_from_assignments(small_index.assignments))
    r1, s1, resp1 = _serve_chunk(small_index, q, traces,
                                 params=serve_params, store=store)
    assert all(r.state == RequestState.COMPLETE for r in resp1)
    ck = r1.chunk(0)
    assert ck is not None, "chunk cache never attached"
    st = ck.stats
    assert st.hits > 0 and st.spliced_pages > 0
    assert st.prefill_tokens_avoided >= st.hits * 6
    assert r1.stats["spliced_waves"] > 0
    assert st.hits / (st.hits + st.misses) == 1.0, \
        "every retrieved doc was built offline; all splices must hit"
    # lookahead prefetch landed pages ahead of the splice
    assert st.prefetched_pages > 0
    # retrieval itself is untouched by splicing
    assert _round_docs(resp1) == _round_docs(resp0)
    # teardown: warm residency + kv buckets drain to a zero ledger
    for runner, srv in ((r0, s0), (r1, s1)):
        chunk = runner.chunk(0)
        if chunk is not None:
            chunk.drain()
        runner.kv(0).drop_all()
        eng = srv.engines[0]
        assert eng.ledger.bytes_of("kv") == 0
        assert eng.ledger.bytes_of("chunk_kv") == 0
    rep = check_recorder(s1.recorder, drained=True,
                         must_drain=("kv", "chunk_kv"))
    assert rep.ok, rep.summary()
    assert rep.stats["chunk_loads"] > 0
    kinds = {getattr(e, "kind", "") for e in s1.recorder.events}
    assert "kv.splice" in kinds and "chunk.pin" in kinds


@pytest.mark.slow
def test_serve_partial_store_mixes_hits_and_misses(small_store, small_index,
                                                   rng, serve_params):
    """Half-coverage store: misses fall back to the plain path (no
    crash, requests complete) and the hit-rate telemetry reflects it."""
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("iter", 3, seed=5)
    r0, _, resp0 = _serve_chunk(small_index, q, traces,
                                params=serve_params, store=None)
    docs = sorted({int(d) for r in resp0 for rd in r.doc_ids for d in rd})
    store = build_chunk_kv(serve_params, SERVE_CFG, docs[:len(docs) // 2],
                           page_size=4, seed=3, min_len=6, max_len=8)
    r1, s1, resp1 = _serve_chunk(small_index, q, traces,
                                 params=serve_params, store=store)
    assert all(r.state == RequestState.COMPLETE for r in resp1)
    st = r1.chunk(0).stats
    assert st.misses > 0, "half the docs are not in the store"
    if st.hits:
        assert r1.stats["spliced_waves"] > 0
    tel = s1.telemetry()
    ch = tel.replicas[0].chunk_kv
    assert ch and ch["misses"] == st.misses
    r1.chunk(0).drain()
    r1.kv(0).drop_all()
    r0.kv(0).drop_all()

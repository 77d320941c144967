"""A Mamba-2 + GQA hybrid on the paged serve path, at a tiny size.

The tiny hybrid (``benchmarks/chip/testdata/tiny-hybrid.json``: mixers
[mamba, attention, mamba, mamba], NoPE attention, every muP scalar not
1, a tied head) is served through ``TeleRAGServer`` and the paged
``DecodeRunner`` and compared, logit by logit, with the plain float32
reference ``benchmarks/chip/references/hybrid.py`` on the same seeded
weights.  Beside it: the lease that holds recurrent state rows next to
KV pages, the ``ssm_decode_update`` kernel against its oracle, the
refusals of the chunk-KV splice, and the tied head's embedding scale
kept apart from Gemma's.
"""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from benchmarks.chip import cell
from benchmarks.chip.layouts import hybrid as layout
from benchmarks.chip.references import hybrid as reference
from repro.configs import get_arch
from repro.data.chunk_kv import ChunkKVStore
from repro.kernels import ops, ref
from repro.models import transformer as tf
from repro.obs import SYSTEM_CLOCK
from repro.serving import (DecodeRunner, EngineConfig, KVCacheManager,
                           RagRequest, TeleRAGServer, make_traces)
from repro.memory.pool import PoolExhausted
from tests.conftest import unit_queries

CONFIG = cell.load_json(os.path.join(cell.HERE, "testdata",
                                     "tiny-hybrid.json"))
ARCH = layout.arch_config(CONFIG)
SEED = 2**33 + 12
# Served bf16 against the float32 reference: the widest logit error over
# every position of a 3-row wave of 24 steps reads 5.7e-4 to 8.5e-4 on
# four seeds (2**33 + 12 to 15), against logits of std 0.010 and at most
# 0.05 in magnitude; bf16 keeps 8 bits, so one rounding of the largest
# logit is 2e-4, and 40 or so roundings lie on a logit's path.  The fp8
# control (3 bits) reads 5.9e-3 or more there.  2e-3 lies between.
LOGIT_TOL = 2e-3


@pytest.fixture(scope="module")
def params():
    return layout.program_params(CONFIG, SEED, jax.devices()[0])


def serve(small_index, q, traces, params, *, micro_batch):
    """One served run of the tiny hybrid, host spans on; returns
    (runner, per-step logits [steps, rows, V] of every wave, the
    recorder's host spans)."""
    runner = DecodeRunner(params, ARCH, max_len=64, max_steps=24,
                          page_size=16, slab_seqs=len(traces) + 2)
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        lookahead_rank=16, kernel_mode="ref", chips=8, seed=7,
        paged_decode=True), 1, ARCH, micro_batch=micro_batch,
        include_tail=True, decode_hook=runner, continuous=True)
    runner.attach(srv)
    srv.recorder.enable_host_spans(SYSTEM_CLOCK)
    inner, logits = runner._paged_step, []

    def step(*args):
        out = inner(*args)
        logits.append(np.asarray(out[0], np.float32))
        return out

    runner._paged_step = step
    srv.serve([RagRequest(q=q[i], trace=traces[i], arrival_t=0.0)
               for i in range(len(traces))])
    assert runner.stats["paged_waves"] >= 1
    assert runner.kv(0).stats["state_rows_in_use"] == 0
    return runner, np.stack(logits), srv.recorder.host_spans


def one_wave(small_store, small_index, rng, params, micro_batch):
    q = unit_queries(small_store, rng, 3)
    traces = make_traces("hyde", 3, seed=5)
    runner, logits, spans = serve(small_index, q, traces, params,
                                  micro_batch=micro_batch)
    rows = [runner.generated[t.request_id][0] for t in traces]
    return rows, logits, spans


def test_served_hybrid_matches_the_reference_at_every_position(
        small_store, small_index, rng, params):
    rows, logits, spans = one_wave(small_store, small_index, rng, params, 4)
    steps = len(rows[0])
    assert steps == logits.shape[0] >= 16
    inputs = [np.concatenate([[0], r[:-1]]).astype(np.int32) for r in rows]
    want = reference.Hidden(CONFIG, SEED, inputs).logits()
    ctl = reference.Hidden(CONFIG, SEED, inputs, quant="fp8").logits()
    for j, (w, c) in enumerate(zip(want, ctl)):
        got = logits[:, j]
        assert np.max(np.abs(got - w)) <= LOGIT_TOL, j
        assert np.max(np.abs(c - w)) > LOGIT_TOL, j
    # the wave's three state rows were zeroed under their own span
    reset = [h for h in spans if h.name == "telerag.kv.state_reset"]
    loops = [h for h in spans if h.name == "telerag.decode.steps"]
    assert [h.args["rows"] for h in reset] == [3]
    assert reset[0].args["bytes"] > 0
    assert [h.args["state_slots"] for h in loops] == [3]


def test_padding_a_wave_leaves_its_tokens_unchanged(small_store,
                                                     small_index, params):
    alone, _, _ = one_wave(small_store, small_index,
                           np.random.default_rng(0), params, 3)
    padded, logits, _ = one_wave(small_store, small_index,
                                 np.random.default_rng(0), params, 16)
    assert logits.shape[1] == 16
    assert padded == alone


def test_state_rows_are_zeroed_on_acquire_and_freed_on_release():
    kv = KVCacheManager(ARCH)
    slab = kv.init_paged(num_pages=16, page_size=16, state_rows=5)
    slab.conv = jnp.ones_like(slab.conv)
    slab.ssm = jnp.ones_like(slab.ssm)
    lease = kv.acquire_paged(2, 32)
    rows = sorted(int(r) for r in lease.state_slots)
    assert len(rows) == 2 and set(rows).isdisjoint(slab.state_free)
    Lm, S = slab.ssm.shape[:2]
    conv = np.asarray(slab.conv).reshape(Lm, S, -1)
    ssm = np.asarray(slab.ssm)
    for r in range(S):
        want = 0.0 if r in rows else 1.0
        assert (conv[:, r] == want).all() and (ssm[:, r] == want).all(), r
    assert kv.stats == {"state_rows_in_use": 2, "state_rows_peak": 2}
    assert lease.nbytes == (2 * 2 * kv.paged_page_nbytes()
                            + 2 * kv.state_row_nbytes())
    assert kv.state_row_nbytes() == (conv[:, 0].size * 2
                                     + ssm[:, 0].size * 4)
    kv.release_paged(lease)
    assert sorted(slab.state_free) == list(range(S))
    assert kv.stats == {"state_rows_in_use": 0, "state_rows_peak": 2}


def test_running_out_of_state_rows_is_pool_exhausted():
    kv = KVCacheManager(ARCH)
    kv.init_paged(num_pages=64, page_size=16, state_rows=3)
    held = kv.acquire_paged(2, 16)
    with pytest.raises(PoolExhausted, match="state"):
        kv.acquire_paged(2, 16)
    kv.release_paged(held)
    kv.release_paged(kv.acquire_paged(3, 16))
    with pytest.raises(ValueError, match="state_rows"):
        KVCacheManager(ARCH).init_paged(num_pages=4, page_size=16)


def test_the_dense_bucket_counts_state_beside_kv():
    kv = KVCacheManager(ARCH)
    kv.init_paged(num_pages=9, page_size=16, state_rows=3)
    assert kv.nbytes(3, 64) == 3 * (4 * kv.paged_page_nbytes()
                                    + kv.state_row_nbytes())


SSM_CASES = [
    (3, 5, 8, 16, 16, 4, 0),
    (2, 6, 16, 8, 128, 3, 0),
    (2, 4, 64, 64, 128, 2, 0),    # granite-4.0-h-micro's heads, two blocks
    (1, 3, 192, 64, 128, 2, 0),   # three blocks or more
    (2, 5, 32, 8, 64, 3, 0),      # P of 8, N under a vreg's lanes
    (2, 4, 24, 16, 32, 2, 0),     # P of 16
    (2, 3, 16, 64, 64, 2, 0),     # N under a vreg's lanes
    (2, 6, 64, 64, 128, 3, 4),    # padding rows share the scratch slot
]


@pytest.mark.parametrize(
    "L,S,H,P,N,B,pad", SSM_CASES,
    ids=["-".join(map(str, c[:6])) + (f"-pad{c[6]}" if c[6] else "")
         for c in SSM_CASES])
def test_ssm_decode_update_matches_its_oracle_in_place(L, S, H, P, N, B,
                                                       pad):
    """B live rows on distinct slots, and ``pad`` padding rows that all
    write the last slot, as a wave's padding rows write the scratch
    slot: the live rows' y and state are the oracle's, and the slots
    no row names are left as they were."""
    rng = np.random.default_rng(L * 100 + H)
    f = lambda *shape: jnp.asarray(rng.standard_normal(shape), jnp.float32)
    R = B + pad
    state, x, b, c, d = f(L, S, H, P, N), f(R, H, P), f(R, N), f(R, N), f(H)
    dt = jnp.asarray(rng.random((R, H)), jnp.float32)
    a = -jnp.asarray(rng.random(H) + 0.5, jnp.float32)
    scratch = S - 1
    slots = np.concatenate([rng.permutation(S - bool(pad))[:B],
                            np.full(pad, scratch)])
    order = rng.permutation(R)
    live = np.argsort(order)[:B]          # rows of the live slots
    slots = jnp.asarray(slots[order], jnp.int32)
    layer = jnp.int32(L - 1)
    y0, s0 = ref.ssm_decode_update_ref(state, layer, slots[live], x[live],
                                       dt[live], b[live], c[live], a, d)
    y1, s1 = ops.ssm_decode_update(state, layer, slots, x, dt, b, c, a, d,
                                   mode="kernel_interpret")
    np.testing.assert_allclose(np.asarray(y1)[live], np.asarray(y0),
                               rtol=1e-5, atol=1e-5)
    held = np.ones((L, S), bool)
    if pad:
        held[L - 1, scratch] = False
    np.testing.assert_allclose(np.asarray(s1)[held], np.asarray(s0)[held],
                               rtol=1e-6, atol=1e-6)
    untouched = held.copy()
    untouched[L - 1, np.asarray(slots)[live]] = False
    assert np.array_equal(np.asarray(s1)[untouched],
                          np.asarray(state)[untouched])
    assert not np.allclose(np.asarray(s1)[L - 1, np.asarray(slots)[live]],
                           np.asarray(state)[L - 1, np.asarray(slots)[live]])


def test_paged_step_matches_the_full_sequence_forward():
    """Token by token through the paged step (kernels in interpret mode)
    against the program's chunked full-sequence forward, in float32."""
    cfg = get_arch("granite-4.0-h-micro").reduced()
    p = tf.init_params(cfg, jax.random.PRNGKey(3), dtype=jnp.float32)
    B, T = 3, 10
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, T), 0,
                              cfg.vocab_size)
    x, _, _ = tf.forward(p, toks, cfg)
    want = np.asarray(tf.unembed(p, x, cfg))
    kv = KVCacheManager(cfg, dtype=jnp.float32)
    slab = kv.init_paged(num_pages=12, page_size=4, state_rows=4)
    lease = kv.acquire_paged(B, 16)
    got = []
    for t in range(T):
        bt, lens, slots = lease.tables()
        lg, slab.k, slab.v, slab.conv, slab.ssm = tf.serve_step_hybrid_paged(
            p, slab.k, slab.v, slab.conv, slab.ssm, bt, lens, slots,
            {"token": toks[:, t]}, cfg, kernel_mode="kernel_interpret")
        kv.append_paged(lease)
        got.append(np.asarray(lg))
    np.testing.assert_allclose(np.stack(got, 1), want, atol=1e-5, rtol=1e-4)


def test_chunk_kv_splicing_refuses_recurrent_state(small_index, params):
    kv = KVCacheManager(ARCH)
    kv.init_paged(num_pages=8, page_size=16, state_rows=2)
    lease = kv.acquire_paged(1, 16)
    with pytest.raises(ValueError, match="recurrent state"):
        kv.splice_paged(lease, [[((0,), 4)]])
    bt, lens, _ = lease.tables()
    with pytest.raises(ValueError, match="recurrent"):
        tf.serve_step_paged_spliced(
            params, kv.slab.k, kv.slab.v, bt, lens, np.zeros_like(bt),
            np.full_like(bt, 16), {"token": jnp.zeros((1,), jnp.int32)},
            ARCH)
    runner = DecodeRunner(params, ARCH, max_len=32, max_steps=4,
                          page_size=16, slab_seqs=2,
                          chunk_store=ChunkKVStore(page_size=16))
    srv = TeleRAGServer(small_index, EngineConfig(
        nprobe=8, top_k=3, buffer_pages=256, pool_pages=4096,
        kernel_mode="ref", chips=8, paged_decode=True, chunk_kv=True),
        1, ARCH, micro_batch=2, decode_hook=runner)
    with pytest.raises(ValueError, match="chunk-KV splicing"):
        runner.attach(srv)


def test_a_tied_head_no_longer_scales_the_embedding():
    """Gemma keeps its sqrt(d) embedding scale through its own flag; a
    tied head alone (granite's) takes the muP multiplier instead."""
    gemma = get_arch("gemma2-27b").reduced()
    assert gemma.tie_embeddings and gemma.embed_scale_sqrt_d
    p = {"embed": jnp.ones((8, gemma.d_model), jnp.float32)}
    tok = jnp.zeros((1,), jnp.int32)
    assert float(tf.embed_tokens(p, tok, gemma)[0, 0]) == pytest.approx(
        gemma.d_model ** 0.5)
    p = {"embed": jnp.ones((8, ARCH.d_model), jnp.float32)}
    assert float(tf.embed_tokens(p, tok, ARCH)[0, 0]) == 12.0


# Gemma2-27b reduced, seed 7, tokens from PRNGKey(1): logits of the full
# forward, as the program gave them before the tied head was parted from
# the sqrt(d) scale.
GEMMA_LOGITS = {(0, 0): (0.63671875, 0.029541015625, 0.4765625),
                (1, 11): (-0.263671875, 0.091796875, -0.333984375)}


def test_gemma2_logits_are_pinned():
    cfg = get_arch("gemma2-27b").reduced()
    p = tf.init_params(cfg, jax.random.PRNGKey(7))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 12), 0,
                              cfg.vocab_size)
    x, _, _ = tf.forward(p, toks, cfg)
    lg = np.asarray(tf.unembed(p, x, cfg).astype(jnp.float32))
    for (b, t), want in GEMMA_LOGITS.items():
        np.testing.assert_allclose(lg[b, t, :3], want, rtol=1e-6), (b, t)

"""Paged-block substrate: block-table KV leases over the shared pool,
fused one-launch retrieval on the engine path, and the launch-env
hygiene module."""

import os

import numpy as np
import jax.numpy as jnp
import pytest

import repro.core as core
from repro.configs.base import ArchConfig
from repro.core.hybrid_search import hybrid_retrieve
from repro.core.ivf import probe
from repro.core.prefetch_buffer import PrefetchBuffer
from repro.kernels import ops, ref
from repro.launch import env as launch_env
from repro.memory.pool import DevicePagePool, PoolExhausted
from repro.serving import EngineConfig, KVCacheManager, TeleRAGEngine
from tests.conftest import unit_queries


def tiny_cfg(num_layers=2, kvh=2, g=2, dh=16):
    return ArchConfig(name="tiny", family="dense", source="test",
                      d_model=kvh * g * dh, num_layers=num_layers,
                      num_heads=kvh * g, num_kv_heads=kvh, head_dim=dh,
                      vocab_size=64)


# ---------------------------------------------------------------------------
# KVCacheManager paged leases
# ---------------------------------------------------------------------------


def test_acquire_paged_block_table_and_release():
    mgr = KVCacheManager(tiny_cfg(), dtype=jnp.float32)
    slab = mgr.init_paged(num_pages=24, page_size=4)
    lease = mgr.acquire_paged(batch=3, max_len=10)
    assert lease.block_table.shape == (3, 3)          # ceil(10/4) blocks
    assert (lease.block_table >= 0).all()
    assert len(set(lease.block_table.reshape(-1).tolist())) == 9  # distinct
    assert len(slab.free) == 24 - 9
    assert (lease.lengths == 0).all()
    freed = mgr.release_paged(lease)
    assert freed == lease.nbytes
    assert len(slab.free) == 24
    assert (lease.block_table == -1).all()


def test_append_paged_then_attention_matches_dense():
    """Tokens written through the block table + flash_decode_paged ==
    dense flash_decode over the same tokens, every layer."""
    cfg = tiny_cfg()
    L, KVH, Dh = cfg.num_layers, cfg.num_kv_heads, cfg.resolved_head_dim
    B, G, steps = 3, 2, 7
    mgr = KVCacheManager(cfg, dtype=jnp.float32)
    slab = mgr.init_paged(num_pages=16, page_size=4)
    lease = mgr.acquire_paged(B, steps + 1)
    rng = np.random.default_rng(5)
    ks = rng.standard_normal((steps, L, B, KVH, Dh)).astype(np.float32)
    vs = rng.standard_normal((steps, L, B, KVH, Dh)).astype(np.float32)
    for t in range(steps):
        mgr.append_paged(lease, ks[t], vs[t])
    assert (lease.lengths == steps).all()
    q = jnp.asarray(rng.standard_normal((B, KVH, G, Dh)), jnp.float32)
    bt, lens = map(jnp.asarray, lease.tables())
    for l in range(L):
        kp, vp = slab.layer(l)
        out_p = ops.flash_decode_paged(q, kp, vp, bt, lens,
                                       mode="kernel_interpret")
        dense_k = jnp.asarray(np.transpose(ks[:, l], (1, 0, 2, 3)))
        dense_v = jnp.asarray(np.transpose(vs[:, l], (1, 0, 2, 3)))
        out_d = ref.flash_decode_ref(q, dense_k, dense_v, lens - 1, 0)
        np.testing.assert_allclose(np.asarray(out_p), np.asarray(out_d),
                                   rtol=1e-5, atol=1e-5)


def test_paged_pool_accounting_and_exhaustion(small_index):
    pool = DevicePagePool(small_index.paged, 64)
    mgr = KVCacheManager(tiny_cfg(), dtype=jnp.float32, pool=pool)
    mgr.init_paged(num_pages=16, page_size=4)
    lease = mgr.acquire_paged(2, 8, tenant="acme")
    # exact bytes on the ledger, tenant-attributed
    assert lease.nbytes == 2 * 2 * mgr.paged_page_nbytes()
    assert pool.ledger.bytes_of("kv") == lease.nbytes
    assert pool.ledger.tenant_bytes("acme") == lease.nbytes
    # slab exhaustion raises, never overcommits
    with pytest.raises(PoolExhausted):
        mgr.acquire_paged(100, 1000)
    mgr.release_paged(lease)
    assert pool.ledger.bytes_of("kv") == 0


def test_paged_rejects_non_attention_archs():
    cfg = ArchConfig(name="ssm", family="ssm", source="test", d_model=32,
                     num_layers=2, num_heads=2, num_kv_heads=2,
                     vocab_size=64, attn_kind="none")
    mgr = KVCacheManager(cfg)
    with pytest.raises(ValueError):
        mgr.init_paged(8)


def test_append_paged_full_lease_raises():
    mgr = KVCacheManager(tiny_cfg(), dtype=jnp.float32)
    mgr.init_paged(num_pages=8, page_size=4)
    lease = mgr.acquire_paged(1, 4)
    cfg = tiny_cfg()
    z = np.zeros((cfg.num_layers, 1, cfg.num_kv_heads,
                  cfg.resolved_head_dim), np.float32)
    for _ in range(4):
        mgr.append_paged(lease, z, z)
    with pytest.raises(ValueError):
        mgr.append_paged(lease, z, z)


# ---------------------------------------------------------------------------
# Fused retrieval on the engine path
# ---------------------------------------------------------------------------


def test_hybrid_retrieve_fused_matches_legacy(small_store, small_index, rng):
    """One-launch probe_and_topk on the device partition returns the
    same documents as the legacy host-mask chain (same probe scores,
    tie-free data) — hit/miss telemetry identical."""
    buf = PrefetchBuffer(small_index.paged, num_pages=128)
    buf.load_clusters(range(0, 40))                  # partial residency
    q = unit_queries(small_store, rng, 5)
    ranked = probe(q, small_index, 12)
    legacy = hybrid_retrieve(buf, q, ranked, k=4, kernel_mode="ref",
                             fused=False)
    fused = hybrid_retrieve(buf, q, ranked, k=4, kernel_mode="ref",
                            fused=True, centroids=small_index.centroids)
    np.testing.assert_array_equal(fused.doc_ids, legacy.doc_ids)
    np.testing.assert_allclose(fused.scores, legacy.scores, rtol=1e-5)
    assert fused.hit_clusters == legacy.hit_clusters
    assert fused.missed_clusters == legacy.missed_clusters


def test_engine_fused_flag_equivalence(small_index, small_store, rng):
    """EngineConfig.fused_retrieval=True (the default) and False produce
    identical retrievals through the full policy path."""
    q = unit_queries(small_store, rng, 4)
    outs = {}
    for fused in (True, False):
        cfg = EngineConfig(nprobe=12, top_k=4, buffer_pages=128,
                           kernel_mode="ref", fused_retrieval=fused)
        eng = TeleRAGEngine(small_index, cfg)
        eng.lookahead(q, gen_tokens=[8] * len(q))
        outs[fused] = eng.retrieve(q)
    np.testing.assert_array_equal(outs[True].doc_ids, outs[False].doc_ids)
    np.testing.assert_allclose(outs[True].scores, outs[False].scores,
                               rtol=1e-5)


# ---------------------------------------------------------------------------
# Launch env hygiene
# ---------------------------------------------------------------------------


def test_recommended_env_and_shell_snippet():
    env = launch_env.recommended_env(host_device_count=4)
    assert env["TF_CPP_MIN_LOG_LEVEL"] == "4"
    assert "--xla_step_marker_location=1" in env["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    if "LD_PRELOAD" in env:
        assert os.path.exists(env["LD_PRELOAD"])
    snippet = launch_env.render_shell()
    for key in env:
        if key != "XLA_FLAGS":
            continue
        assert f'export {key}=' in snippet


def test_env_validate_reports_divergence(monkeypatch):
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "0")
    diffs = {k for k, _, _ in launch_env.validate()}
    assert "TF_CPP_MIN_LOG_LEVEL" in diffs
    monkeypatch.setenv("TF_CPP_MIN_LOG_LEVEL", "4")
    monkeypatch.setenv(
        "TCMALLOC_LARGE_ALLOC_REPORT_THRESHOLD",
        str(launch_env.LARGE_ALLOC_THRESHOLD))
    # flag-wise containment: extra operator flags are not a divergence
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_dump_to=/tmp/x --xla_step_marker_location=1")
    diffs = {k for k, _, _ in launch_env.validate()}
    assert "XLA_FLAGS" not in diffs
    assert "TF_CPP_MIN_LOG_LEVEL" not in diffs


def test_decode_microbench_pinned_fixture_still_validates():
    """The committed schema fixture (tests/data) is the contract: the
    regenerated JSON itself is untracked bench output (--report-dir /
    CI artifact), so THIS is what pins the schema across PRs."""
    import json
    import os

    from benchmarks.bench_decode_microbench import validate_report
    path = os.path.join(os.path.dirname(__file__), "data",
                        "decode_microbench_pinned.json")
    with open(path) as f:
        report = json.load(f)
    validate_report(report)


def test_decode_microbench_smoke_schema():
    """The microbench JSON must validate against its schema guard."""
    from benchmarks.bench_decode_microbench import run_smoke, validate_report
    report = run_smoke()
    validate_report(report)
    assert report["schema"] == "telerag.decode_microbench/v1"
    names = {r["name"] for r in report["kernels"]}
    assert {"flash_decode_dense", "flash_decode_paged", "kv_append",
            "probe_topk_unfused", "probe_topk_fused"} <= names

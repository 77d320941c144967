"""Pallas kernel parity vs pure-jnp oracles (interpret mode), swept over
shapes and dtypes as required for every kernel."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels import ops, ref


@pytest.mark.parametrize("P,ps,d,B,k", [
    (12, 64, 128, 3, 5),
    (4, 32, 96, 1, 3),
    (16, 128, 256, 8, 16),
    (7, 16, 64, 2, 4),          # odd page count -> padding path
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ivf_topk_parity(P, ps, d, B, k, dtype):
    rng = np.random.default_rng(P * 1000 + B)
    pages = jnp.asarray(rng.standard_normal((P, ps, d)), dtype)
    ids = jnp.asarray(rng.permutation(P * ps).reshape(P, ps), jnp.int32)
    ids = ids.at[1, ps // 2:].set(-1)             # padded tail
    mask = jnp.asarray(rng.random((B, P)) > 0.3)  # per-query page masks
    q = jnp.asarray(rng.standard_normal((B, d)), dtype)
    s_ref, i_ref = ref.ivf_topk_ref(pages, ids, mask, q, k)
    s_k, i_k = ops.ivf_topk(pages, ids, mask, q, k, tile=max(ps * 2, 64),
                            mode="kernel_interpret")
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_ref),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-4)
    # ids must match wherever scores are distinct; compare via score lookup
    np.testing.assert_array_equal(np.asarray(i_k >= 0), np.asarray(i_ref >= 0))


def test_ivf_topk_shared_mask_broadcast():
    rng = np.random.default_rng(0)
    pages = jnp.asarray(rng.standard_normal((6, 32, 64)), jnp.float32)
    ids = jnp.arange(6 * 32, dtype=jnp.int32).reshape(6, 32)
    mask1 = jnp.asarray(rng.random(6) > 0.4)
    q = jnp.asarray(rng.standard_normal((4, 64)), jnp.float32)
    s1, i1 = ops.ivf_topk(pages, ids, mask1, q, 4, mode="kernel_interpret")
    s2, i2 = ops.ivf_topk(pages, ids, jnp.broadcast_to(mask1, (4, 6)), q, 4,
                          mode="kernel_interpret")
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


@pytest.mark.parametrize("Nc,d,B,nprobe", [(128, 128, 3, 16), (96, 64, 1, 8)])
def test_centroid_probe_parity(Nc, d, B, nprobe):
    rng = np.random.default_rng(Nc)
    cents = jnp.asarray(rng.standard_normal((Nc, d)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    valid = jnp.asarray(rng.random(Nc) > 0.2)
    sp, ip = ops.centroid_probe(cents, q, nprobe, valid=valid,
                                tile=32, mode="kernel_interpret")
    sr, ir = ops.centroid_probe(cents, q, nprobe, valid=valid, mode="ref")
    np.testing.assert_allclose(np.asarray(sp), np.asarray(sr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ip), np.asarray(ir))


@pytest.mark.parametrize("B,S,KVH,G,Dh,window", [
    (2, 256, 4, 3, 64, 0),
    (2, 256, 4, 3, 64, 50),
    (1, 128, 1, 8, 32, 0),      # MQA
    (3, 64, 2, 1, 128, 16),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_parity(B, S, KVH, G, Dh, window, dtype):
    rng = np.random.default_rng(S + window)
    q = jnp.asarray(rng.standard_normal((B, KVH, G, Dh)), dtype)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), dtype)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), dtype)
    pos = jnp.asarray(rng.integers(1, S, B), jnp.int32)
    o_ref = ref.flash_decode_ref(q, k, v, pos, window)
    o_k = ops.flash_decode(q, k, v, pos, window=window, tile=64,
                           mode="kernel_interpret")
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_ref),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("B,KVH,G,Dh,ps,MB,window", [
    (3, 2, 4, 32, 16, 5, 0),
    (3, 2, 4, 32, 16, 5, 20),
    (1, 1, 8, 64, 8, 3, 0),      # MQA
    (2, 4, 1, 64, 32, 2, 10),
])
@pytest.mark.parametrize("mode", ["ref", "kernel_interpret"])
def test_flash_decode_paged_parity(B, KVH, G, Dh, ps, MB, window, mode):
    """Paged == dense over ragged block tables, incl. partially filled
    last blocks and unallocated (-1) tail entries."""
    rng = np.random.default_rng(B * 100 + ps + window)
    NP = B * MB + 4                             # slab bigger than needed
    q = jnp.asarray(rng.standard_normal((B, KVH, G, Dh)), jnp.float32)
    kp = jnp.asarray(rng.standard_normal((NP, ps, KVH, Dh)), jnp.float32)
    vp = jnp.asarray(rng.standard_normal((NP, ps, KVH, Dh)), jnp.float32)
    # non-contiguous slots per request; lengths hit partial last blocks
    perm = rng.permutation(NP)[:B * MB].reshape(B, MB)
    lengths = rng.integers(1, MB * ps + 1, B)
    bt = perm.copy()
    for b in range(B):
        bt[b, -(-int(lengths[b]) // ps):] = -1  # unallocated tail
    bt = jnp.asarray(bt, jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    out = ops.flash_decode_paged(q, kp, vp, bt, lengths, window=window,
                                 mode=mode)
    # oracle: gather the table into a dense cache, dense kernel at
    # pos = lengths - 1
    dense_k = kp[jnp.maximum(bt, 0)].reshape(B, MB * ps, KVH, Dh)
    dense_v = vp[jnp.maximum(bt, 0)].reshape(B, MB * ps, KVH, Dh)
    o_ref = ref.flash_decode_ref(q, dense_k, dense_v, lengths - 1, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(o_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("B,d,Nc,P,ps,nprobe,k", [
    (4, 64, 24, 18, 8, 7, 5),
    (1, 32, 16, 6, 16, 3, 4),
    (6, 128, 32, 24, 4, 16, 8),
])
@pytest.mark.parametrize("mode", ["ref", "kernel_interpret"])
def test_probe_and_topk_matches_composition(B, d, Nc, P, ps, nprobe, k, mode):
    """Fused one-launch retrieval == centroid_probe -> page mask ->
    ivf_topk on random page tables (incl. unsearchable -1 slots and
    padded page tails)."""
    rng = np.random.default_rng(B * 31 + Nc)
    qs = jnp.asarray(rng.standard_normal((B, d)), jnp.float32)
    cents = jnp.asarray(rng.standard_normal((Nc, d)), jnp.float32)
    pages = jnp.asarray(rng.standard_normal((P, ps, d)), jnp.float32)
    pids = jnp.asarray(rng.permutation(P * ps).reshape(P, ps), jnp.int32)
    pids = pids.at[0, ps // 2:].set(-1)                 # padded page tail
    pc = jnp.asarray(rng.integers(-1, Nc, P), jnp.int32)  # -1 = unsearchable
    s_f, i_f = ops.probe_and_topk(qs, cents, pages, pids, pc, nprobe=nprobe,
                                  k=k, cent_tile=8, page_tile=2, mode=mode)
    # unfused composition via the public ops
    ps_, pi_ = ops.centroid_probe(cents, qs, nprobe, mode="ref")
    lut = np.zeros((B, Nc), bool)
    for b in range(B):
        lut[b, np.asarray(pi_)[b][np.isfinite(np.asarray(ps_)[b])]] = True
    pcn = np.asarray(pc)
    mask = np.zeros((B, P), bool)
    mask[:, pcn >= 0] = lut[:, pcn[pcn >= 0]]
    s_u, i_u = ops.ivf_topk(pages, pids, jnp.asarray(mask), qs, k, mode="ref")
    np.testing.assert_array_equal(np.asarray(i_f), np.asarray(i_u))
    np.testing.assert_allclose(np.asarray(s_f), np.asarray(s_u),
                               rtol=1e-5, atol=1e-5)


def test_resolve_mode_env_and_aliases(monkeypatch):
    """ONE dispatch layer: explicit mode > REPRO_KERNEL_MODE env >
    backend autodetect; aliases resolve; unknown modes raise."""
    monkeypatch.delenv(ops.MODE_ENV_VAR, raising=False)
    auto = ops.resolve_mode("auto")
    assert auto == ("kernel" if jax.default_backend() == "tpu" else "ref")
    assert ops.resolve_mode(None) == auto
    # aliases
    assert ops.resolve_mode("tpu") == "kernel"
    assert ops.resolve_mode("compiled") == "kernel"
    assert ops.resolve_mode("oracle") == "ref"
    assert ops.resolve_mode("interpret") == "kernel_interpret"
    # env only applies when the call says "auto"
    monkeypatch.setenv(ops.MODE_ENV_VAR, "interpret")
    assert ops.resolve_mode("auto") == "kernel_interpret"
    assert ops.resolve_mode("ref") == "ref"
    monkeypatch.setenv(ops.MODE_ENV_VAR, "bogus")
    with pytest.raises(ValueError):
        ops.resolve_mode("auto")
    with pytest.raises(ValueError):
        ops.resolve_mode("not-a-mode")


def test_env_mode_flips_whole_stack(monkeypatch):
    """REPRO_KERNEL_MODE=interpret routes a default-mode op through the
    pallas interpreter — same numbers as the oracle."""
    rng = np.random.default_rng(3)
    cents = jnp.asarray(rng.standard_normal((32, 16)), jnp.float32)
    q = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    monkeypatch.setenv(ops.MODE_ENV_VAR, "interpret")
    si, ii = ops.centroid_probe(cents, q, 4)
    # the entry point reports the plane it ran on
    assert ops.resolved_modes()["centroid_probe"] == "kernel_interpret"
    monkeypatch.delenv(ops.MODE_ENV_VAR)
    sr, ir = ops.centroid_probe(cents, q, 4, mode="ref")
    assert ops.resolved_modes()["centroid_probe"] == "ref"
    np.testing.assert_allclose(np.asarray(si), np.asarray(sr), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(ii), np.asarray(ir))


def test_flash_decode_matches_model_decode_attention():
    """Kernel semantics == the pure-JAX decode attention used by serve_step."""
    from repro.models.attention import _decode_attention
    rng = np.random.default_rng(7)
    B, S, KVH, G, Dh = 2, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, 1, KVH, G, Dh)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((B, S, KVH, Dh)), jnp.float32)
    pos = jnp.asarray([60, 127], jnp.int32)
    a = _decode_attention(q, k, v, pos=pos, window=None, softcap_val=None,
                          chunk=S)
    b = ops.flash_decode(q[:, 0] / np.sqrt(1.0), k, v, pos, window=0,
                         tile=32, mode="kernel_interpret")
    np.testing.assert_allclose(np.asarray(a[:, 0]), np.asarray(b),
                               rtol=1e-4, atol=1e-4)

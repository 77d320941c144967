"""Compile-only checks of the serve path's Pallas kernels for a TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a described (not attached) v5e chip at ``repro.launch.serve``'s served
shape, the one ``chip_smoke.py`` runs, so a block shape or memory space the chip's compiler refuses is
caught here without a chip.  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_arch
from repro.core.datastore import page_nbytes
from repro.kernels import ops
from repro.launch import serve
from repro.memory.pool import device_rows

CFG = get_arch(serve.DEFAULT_ARCH)
B = serve.BATCH


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:          # no TPU compiler in this install
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _shape(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_decode_paged_compiles_for_v5e(one_chip):
    runner = serve.decode_runner(CFG, None, batch=B, max_len=serve.MAX_LEN)
    KVH, Dh = CFG.num_kv_heads, CFG.resolved_head_dim
    G = CFG.num_heads // KVH
    ps = runner.page_size
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    fn = jax.jit(lambda q, k, v, bt, lens: ops.flash_decode_paged(
        q, k, v, bt, lens, mode="kernel"))
    kv = s((runner.slab_pages, ps, KVH, Dh), jnp.bfloat16)
    compiled = fn.lower(s((B, KVH, G, Dh), jnp.float32), kv, kv,
                        s((B, -(-serve.MAX_LEN // ps)), jnp.int32),
                        s((B,), jnp.int32)).compile()
    _assert_kernel(compiled)


@pytest.mark.parametrize("queries", [B, 5])
def test_probe_and_topk_compiles_for_v5e(one_chip, queries):
    ps, dim = serve.PAGE_SIZE, serve.DIM
    rows = device_rows(serve.pool_pages(CFG, page_nbytes(ps, dim), batch=B,
                                        max_len=serve.MAX_LEN))
    s = lambda shape, dt: _shape(one_chip, shape, dt)
    fn = jax.jit(lambda q, c, p, i, pc: ops.probe_and_topk(
        q, c, p, i, pc, nprobe=serve.NPROBE, k=3, mode="kernel"))
    compiled = fn.lower(s((queries, dim), jnp.float32),
                        s((serve.CLUSTERS, dim), jnp.float32),
                        s((rows, ps, dim), jnp.bfloat16),
                        s((rows, ps), jnp.int32),
                        s((rows,), jnp.int32)).compile()
    _assert_kernel(compiled)

"""Compile-only checks of the serve path's Pallas kernels for a TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for
a described (not attached) v5e chip at ``repro.launch.serve``'s served
shape, the one ``chip_smoke.py`` runs, and the SSM state update at
granite-4.0-h-micro's, so a block shape or memory space the chip's
compiler refuses is caught here without a chip.  The topology is described inside a fixture,
never at import, so every test worker collects the same tests and only
the worker that runs this file loads the TPU library.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
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


def test_ssm_decode_update_compiles_in_place_for_v5e(one_chip):
    """granite-4.0-h-micro's state slab (36 Mamba-2 layers, 32 sequences
    and the scratch slot, 64 heads of 64 x 128 f32: 2.5 GB) under a wave
    of 16 rows, donated: the kernel updates it in place, so the compiled
    program aliases it to the output and holds no copy of it."""
    arch = get_arch("granite-4.0-h-micro")
    P, N = arch.ssm.head_dim, arch.ssm.state_dim
    H = arch.d_model * arch.ssm.expand // P
    slab = (len(arch.layers_of("mamba")), 33, H, P, N)
    rows = 16
    s = lambda shape, dt=jnp.float32: _shape(one_chip, shape, dt)
    fn = jax.jit(lambda st, layer, slots, x, dt, b, c, a, d:
                 ops.ssm_decode_update(st, layer, slots, x, dt, b, c, a, d,
                                       mode="kernel"), donate_argnums=0)
    compiled = fn.lower(s(slab), s((), jnp.int32), s((rows,), jnp.int32),
                        s((rows, H, P)), s((rows, H)), s((rows, N)),
                        s((rows, N)), s((H,)), s((H,))).compile()
    _assert_kernel(compiled)
    text = compiled.as_text()
    shape = "f32[" + ",".join(map(str, slab)) + "]"
    copies = [ln for ln in text.splitlines()
              if re.search(r" copy(-start)?\(", ln) and shape in ln]
    assert not copies, copies
    assert "input_output_alias={ {1}: (0, {}" in text
    assert compiled.memory_analysis().alias_size_in_bytes \
        >= np.prod(slab) * 4

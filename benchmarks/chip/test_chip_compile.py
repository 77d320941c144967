"""Compile rehearsal of the benchmark's cells for a described TPU v5e:
the 768-wide ``probe_and_topk`` at every retrieval batch size the cells
meet, over the pool the harness builds, and each configuration's decode
step at published widths, with its weights.

Nothing runs.  The topology is described inside a fixture, never at
import, so every test worker collects the same tests and only the
worker that runs this file loads the TPU library.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.chip import cell

HERE = os.path.dirname(os.path.abspath(__file__))


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


def config(name: str) -> dict:
    return cell.load_json(os.path.join(HERE, "configs", f"{name}.json"))


def pool_rows(cfg: dict) -> int:
    """Rows of the device pool ``harness.build`` makes for ``cfg``."""
    from repro.core.datastore import page_nbytes
    from repro.memory.pool import device_rows
    from repro.serving import KVCacheManager
    sv, ds = cfg["serving"], cfg["datastore"]
    arch = cell.load_layout(cfg).arch_config(cfg)
    kv = KVCacheManager(arch).nbytes(sv["micro_batch"], sv["max_len"])
    per = page_nbytes(ds["page_size"], ds["dim"])
    return device_rows(ds["buffer_pages"] + -(-kv // per))


@pytest.mark.parametrize("name", ["granite-moe-3b", "granite-20b-stage"])
@pytest.mark.parametrize("queries", [1, 7, 16])
def test_probe_and_topk_768_compiles_for_v5e(one_chip, name, queries):
    from repro.kernels import ops
    cfg = config(name)
    ds = cfg["datastore"]
    rows, ps, dim = pool_rows(cfg), ds["page_size"], ds["dim"]
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    fn = jax.jit(lambda q, c, p, i, pc: ops.probe_and_topk(
        q, c, p, i, pc, nprobe=ds["nprobe"], k=ds["top_k"], mode="kernel"))
    compiled = fn.lower(s((queries, dim), jnp.float32),
                        s((ds["clusters"], dim), jnp.float32),
                        s((rows, ps, dim), jnp.bfloat16),
                        s((rows, ps), jnp.int32),
                        s((rows,), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def decode_step_compiles(one_chip, name: str) -> None:
    from repro.models import transformer as tf
    cfg = config(name)
    layout = cell.load_layout(cfg)
    arch, sv = layout.arch_config(cfg), cfg["serving"]
    B, ps = sv["micro_batch"], sv["kv_page_size"]
    blocks = -(-sv["max_len"] // ps)
    slab = (arch.num_layers, sv["slab_seqs"] * blocks + 1, ps,
            arch.num_kv_heads, arch.resolved_head_dim)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    params = jax.tree.map(lambda x: s(x.shape, x.dtype),
                          layout.param_shapes(cfg))
    step = jax.jit(lambda p, k, v, bt, lens, tok, live: tf.serve_step_paged(
        p, k, v, bt, lens, {"token": tok, "live_rows": live}, arch,
        kernel_mode="kernel"), donate_argnums=(1, 2))
    compiled = step.lower(params, s(slab, jnp.bfloat16), s(slab, jnp.bfloat16),
                          s((B, blocks), jnp.int32), s((B,), jnp.int32),
                          s((B,), jnp.int32), s((), jnp.int32)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5e9, f"{held} bytes on a 16 GB chip"


def test_granite_20b_stage_decode_step_compiles_for_v5e(one_chip):
    decode_step_compiles(one_chip, "granite-20b-stage")


def test_granite_moe_3b_decode_step_compiles_for_v5e(one_chip):
    decode_step_compiles(one_chip, "granite-moe-3b")

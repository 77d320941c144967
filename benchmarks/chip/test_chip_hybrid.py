"""The benchmark's ``hybrid`` layout on the CPU: granite-4.0-h-micro's
counts against hand-worked values, the layout's tree against the served
model's, the reference's weights against the served ones, a tiny run of
the cell's path that comes out correct while its controls do not, the
SSM kernel's count and its two metrics on a made-up trace, and a compile
rehearsal of the published-width decode step for a described v5e.

The topology is described inside a fixture, never at import, so every
test worker collects the same tests and only the worker that runs this
file loads the TPU library.
"""

from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from benchmarks.chip import cell, check, counts, harness, ssm_counts, weights
from benchmarks.chip import xplane
from benchmarks.chip.peaks import peak_for
from benchmarks.chip.probe import Wave

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 12


def config(name: str) -> dict:
    d = "testdata" if name.startswith("tiny") else "configs"
    return cell.load_json(os.path.join(HERE, d, f"{name}.json"))


def test_granite_4_h_micro_counts():
    cfg = config("granite-4.0-h-micro")
    s = cell.load_layout(cfg).Shape.from_config(cfg)
    assert (s.layers, s.mamba_layers, s.attention_layers) == (40, 36, 4)
    # in/out projections 25,821,184 + conv, A, dt bias, D, gated norm
    # 26,048 + MLP 50,331,648 + norms 4,096
    assert s.layer_params("mamba") == (76_182_976, 76_152_832)
    # q, k, v, o 10,485,760 + MLP + norms
    assert s.layer_params("attention") == (60_821_504, 60_817_408)
    assert s.param_count() == 3_191_396_096
    assert s.matmul_params_per_token() == 3_190_292_480
    assert counts.decode_token_flops(s, 1) == 6_380_617_728
    assert cell.load_layout(cfg).arch_config(cfg).param_count() \
        == s.param_count()


def test_the_published_config_at_the_top_level_is_the_model_run():
    cfg = config("granite-4.0-h-micro")
    layout = cell.load_layout(cfg)
    assert cfg["model"].items() <= cfg.items()
    cfg["attention_multiplier"] = None
    with pytest.raises(ValueError, match="attention_multiplier"):
        layout.Shape.from_config(cfg)


def test_ssm_decode_call_count():
    # 2 rows, 2 heads of 8, state 16: per row 5*256 + 3*16 + 2*2 FLOPs;
    # per row 2*1024 B of state (f32) and (32 + 32 + 2)*4 B in and out,
    # plus A and D
    assert ssm_counts.ssm_decode_call(2, 2, 8, 16) == (2_664.0, 4_640.0)


def test_layout_makes_the_served_model_tree():
    from repro.models import transformer as tf
    for name in ("tiny-hybrid", "granite-4.0-h-micro"):
        cfg = config(name)
        layout = cell.load_layout(cfg)
        arch = layout.arch_config(cfg)
        want = jax.eval_shape(lambda: tf.init_params(arch,
                                                     jax.random.PRNGKey(0)))
        got = layout.param_shapes(cfg)
        assert jax.tree.structure(got) == jax.tree.structure(want)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_reference_redraws_the_served_weights():
    from benchmarks.chip.layouts import hybrid as layout
    cfg = config("tiny-hybrid")
    s = layout.Shape.from_config(cfg)
    tree = layout.program_params(cfg, 2**40 + 3, jax.devices()[0])
    key = weights.seed_key(2**40 + 3)
    for name, (shp, fan_in) in layout.leaf_specs(s).items():
        node = tree
        for part in name.split("."):
            node = node[part]
        if not name.startswith("layers."):
            layers = [(0, node)]
        else:
            kind = name.split(".")[1]
            layers = [(l, node[i]) for i, l in enumerate(
                l for l, t in enumerate(s.layer_types) if t == kind)]
        for layer, got in layers:
            want = layout.draw(weights.leaf_key(key, name, layer), name,
                               shp, fan_in)
            assert bool(jnp.array_equal(got, want)), (name, layer)
    a = np.asarray(tree["layers"]["mamba"]["mamba"]["a_log"], np.float32)
    assert (np.exp(a) >= 0.99).all() and (np.exp(a) <= 16.1).all()


def tiny_run(tmp_path, **kw) -> dict:
    bench = cell.load_benchmark()
    c = cell.Cell(name="tiny-hybrid.tiny", chips=1, config=config(
        "tiny-hybrid"), traffic=cell.load_json(os.path.join(
            HERE, "testdata", "tiny-traffic.json")),
        end_to_end=bench["end_to_end"], per_layer=bench["per_layer"])
    return harness.run_cell(c, seed=SEED, seconds=1.0, trace=False,
                            device=jax.devices()[0],
                            t_start=time.perf_counter(),
                            trace_dir=str(tmp_path / "trace"), **kw)


def test_tiny_hybrid_run_is_correct_and_its_controls_are_not(tmp_path):
    res = tiny_run(tmp_path, control=True)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    n = res["numbers"]
    limits = {k: v["limit"] for k, v in res["compared"].items()}
    assert n["decode_positions"] > 0
    for k in check.COMPARED:
        ok, _ = check.verdict({k: n[f"control_{k}"]}, {k: limits[k]})
        assert not ok, (k, n)
    assert set(res["metrics"]) == {"tokens_per_s", "latency_p50_s",
                                   "latency_p90_s", "setup_s"}


def _window(ops, waves, shape):
    return harness.Window(
        shape=shape, t0=0.0, t1=1.0, drains=[], waves=waves, retrievals=[],
        latencies=[], wave_seconds=[], bytes_h2d=0,
        cluster_pages=np.zeros(1), page_size=16, kv_page_size=16,
        setup_s=0.0, peak=peak_for("TPU v5 lite"),
        trace=xplane.Trace(ops={0: ops}, spans=[]),
        trace_window=(0.0, 1e6), traced_waves=waves)


def test_the_ssm_metrics_read_the_kernel_in_the_trace():
    cfg = config("granite-4.0-h-micro")
    s = cell.load_layout(cfg).Shape.from_config(cfg)
    wave = Wave(t0=0, t1=1, rows=16, live=3, steps=2, gens=[2, 1, 2],
                request_ids=[1, 2, 3], token_index=[0, 0, 0])
    calls = 2 * s.mamba_layers
    ops = ([(f"%ssm_decode_update.{i} = custom-call()", 1e3 * i,
             1e3 * i + 500.0) for i in range(calls)]
           + [("%fusion.1 = fusion()", 0.0, 1e5)])
    w = _window(ops, [wave], s)
    roof = cell.load_reader("ssm_decode_update_roofline")(w)
    flops, nbytes = ssm_counts.ssm_decode_call(16, 64, 64, 128)
    want, bound = counts.roofline(calls * flops, calls * nbytes,
                                  calls * 500e-9, w.peak)
    assert bound == "memory" and roof == pytest.approx(want)
    share = cell.load_reader("decode.ssm_busy_share")(w)
    assert share == pytest.approx(calls * 500.0 / 1e5)
    # a program without the kernel, or a shape without Mamba-2 layers,
    # reads nothing
    bare = _window(ops[-1:], [wave], s)
    for name in ("ssm_decode_update_roofline", "decode.ssm_busy_share"):
        assert cell.load_reader(name)(bare) is None
    dense = config("granite-20b-stage")
    w = _window(ops, [wave], cell.load_layout(dense).Shape.from_config(dense))
    assert cell.load_reader("ssm_decode_update_roofline")(w) is None


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


def test_granite_4_h_micro_decode_step_compiles_for_v5e(one_chip):
    from repro.models import transformer as tf
    from repro.serving.kv_cache import paged_slab_shapes
    cfg = config("granite-4.0-h-micro")
    layout = cell.load_layout(cfg)
    arch, sv = layout.arch_config(cfg), cfg["serving"]
    B, ps = sv["micro_batch"], sv["kv_page_size"]
    blocks = -(-sv["max_len"] // ps)
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    slabs = {k: s(a.shape, a.dtype) for k, a in paged_slab_shapes(
        arch, sv["slab_seqs"] * blocks + 1, ps, sv["slab_seqs"] + 1).items()}
    params = jax.tree.map(lambda x: s(x.shape, x.dtype),
                          layout.param_shapes(cfg))
    step = jax.jit(
        lambda p, k, v, conv, ssm, bt, lens, slots, tok, live:
            tf.serve_step_hybrid_paged(
                p, k, v, conv, ssm, bt, lens, slots,
                {"token": tok, "live_rows": live}, arch,
                kernel_mode="kernel"), donate_argnums=(1, 2, 3, 4))
    i32 = jnp.int32
    compiled = step.lower(params, slabs["k"], slabs["v"], slabs["conv"],
                          slabs["ssm"], s((B, blocks), i32), s((B,), i32),
                          s((B,), i32), s((B,), i32), s((), i32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert "ssm_decode_update" in text and "flash_decode_paged" in text
    mem = compiled.memory_analysis()
    held = mem.argument_size_in_bytes + mem.temp_size_in_bytes
    assert held < 15.5e9, f"{held} bytes on a 16 GB chip"

"""Sharding rules, compression, elastic scaling, roofline parsing."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AxisType, Mesh, PartitionSpec as P

import repro.distributed as dist
from repro.configs import get_arch
from repro.launch.roofline import collective_bytes, model_flops_for


def fake_mesh(shape=(2, 2), axes=("data", "model")):
    # abstract mesh over fake devices (no jax device init needed for specs)
    devs = np.array(jax.devices() * (int(np.prod(shape)) // len(jax.devices())
                                     + 1))[:int(np.prod(shape))]
    return Mesh(devs.reshape(shape), axes,
                axis_types=(AxisType.Auto,) * len(axes))


def test_spec_for_divisibility_and_duplicates():
    mesh = fake_mesh()
    rules = dist.RULES_DEFAULT
    # divisible dims shard
    assert dist.spec_for(("vocab", "embed"), (512, 64), mesh, rules) == \
        P("model")
    # non-divisible dim replicates (kv_heads=1 under TP)
    assert dist.spec_for(("embed", "kv_heads", None), (64, 1, 128), mesh,
                         rules) == P()
    # duplicate mesh axis: first dim claims it, second drops
    lc = dist.RULES_LONG_CONTEXT
    spec = dist.spec_for(("layers", "batch", "kv_seq", "kv_heads", None),
                         (4, 4, 64, 2, 16), mesh, lc)
    assert spec == P(None, "data", "model")


def test_param_shardings_cover_all_archs():
    mesh = fake_mesh()
    for arch in ("llama3-8b", "gemma2-27b", "zamba2-2.7b", "rwkv6-3b",
                 "arctic-480b", "musicgen-large"):
        cfg = get_arch(arch).reduced()
        tree = dist.param_shardings(cfg, mesh)
        from repro.models import transformer as tf
        shapes = jax.eval_shape(lambda: tf.init_params(cfg,
                                                       jax.random.PRNGKey(0)))
        assert jax.tree.structure(tree) == jax.tree.structure(shapes)


@pytest.mark.slow
def test_manual_dp_step_with_compression():
    cfg = get_arch("llama3-8b").reduced()
    from repro.training import OptConfig, init_training
    from repro.training.train_loop import make_manual_dp_train_step
    from repro.distributed import init_error_feedback
    mesh = fake_mesh((1,), ("data",))
    opt = OptConfig(lr=1e-3)
    params, opt_state = init_training(cfg, opt, jax.random.PRNGKey(0))
    err = init_error_feedback(params)
    from repro.data import DataConfig, TokenStream
    data = TokenStream(cfg, DataConfig(global_batch=2, seq_len=16, seed=0))
    batch = {k: jnp.asarray(v) for k, v in data.next_batch().items()}
    step = make_manual_dp_train_step(cfg, opt, mesh, compress=True,
                                     attn_chunk=16)
    with mesh:
        p2, o2, e2, m = step(params, opt_state, err, batch)
    assert np.isfinite(float(m["loss"]))
    # error feedback is non-trivial (quantization residue exists)
    assert any(float(jnp.max(jnp.abs(l))) > 0 for l in jax.tree.leaves(e2))


def test_collective_bytes_parser():
    hlo = """
  %ag = bf16[16,4096]{1,0} all-gather(bf16[1,4096]{1,0} %p0), replica_groups={}
  %ar.1 = f32[128]{0} all-reduce(f32[128]{0} %x), to_apply=%sum
  %t = (f32[8,8]{1,0}, f32[8,8]{1,0}) all-to-all(f32[8,8]{1,0} %a, f32[8,8]{1,0} %b)
  %rs = bf16[2,64]{1,0} reduce-scatter(bf16[16,64]{1,0} %y), dimensions={0}
  %cp-start = bf16[4]{0} collective-permute-start(bf16[4]{0} %z)
  %notacoll = f32[4]{0} add(f32[4]{0} %z, f32[4]{0} %z)
"""
    out = collective_bytes(hlo)
    assert out["all-gather"] == 16 * 4096 * 2
    assert out["all-reduce"] == 128 * 4
    assert out["all-to-all"] == 2 * 8 * 8 * 4
    assert out["reduce-scatter"] == 2 * 64 * 2
    assert out["collective-permute"] == 4 * 2


def test_cost_analysis_is_per_device():
    """Pin down the per-device semantics the roofline relies on."""
    mesh = fake_mesh((1, 1))
    w = jnp.ones((256, 256), jnp.float32)
    x = jnp.ones((64, 256), jnp.float32)
    c = jax.jit(lambda a, b: a @ b.T).lower(x, w).compile()
    flops = c.cost_analysis()["flops"]
    assert flops == pytest.approx(2 * 64 * 256 * 256, rel=0.01)


def test_model_flops_convention():
    cfg = get_arch("llama3-8b")
    n = cfg.active_param_count()
    assert model_flops_for(cfg, "train_step", 4096, 256) == 6.0 * n * 4096 * 256
    assert model_flops_for(cfg, "serve_step", 32768, 128) == 2.0 * n * 128


def test_elastic_rerun_after_resize():
    from repro.distributed import ElasticRun
    run = ElasticRun(global_batch=32)
    s1 = run.resize(0, {0, 1, 2, 3})
    assert sum(b - a for a, b in s1.values()) == 32
    s2 = run.resize(5, {0, 1, 3})            # node 2 died
    assert set(s2) == {0, 1, 3}
    assert sum(b - a for a, b in s2.values()) == 32
    assert len(run.history) == 2

"""The on-chip benchmark's harness, on the CPU: loading cells and model
layouts by name, the traffic generator, the FLOP and byte counts against
hand-worked values, the weights a layout makes and the reference
redraws, the trace reduction on a trace recorded on the chip, and the
command's refusal without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import cell, counts, weights, xplane
from benchmarks.chip.peaks import peak_for
from benchmarks.chip.traffic import Traffic, windows

HERE = os.path.dirname(os.path.abspath(__file__))
FIXTURE = os.path.join(HERE, "fixtures", "decode_steps.xplane.pb")


@pytest.fixture(scope="module")
def bench():
    return cell.load_benchmark()


def test_every_cell_loads_by_name(bench):
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    for w in bench["workloads"]:
        c = cell.load_cell(w["name"], bench)
        assert c.chips == w["chips"] == 1
        assert c.config["name"] == w["config"]
        assert c.traffic["name"] == w["traffic"]
        cell.load_layout(c.config).arch_config(c.config)
        Traffic.from_dict(c.traffic)
        for trace in (False, True):
            for m in c.metrics(trace):
                assert callable(cell.load_reader(m["name"]))
    for n in names:
        assert os.path.exists(os.path.join(HERE, "metrics", f"{n}.py"))
    for c in bench["configs"]:
        assert cell.load_json(os.path.join(cell.ROOT, c["file"]))["name"] \
            == c["name"]


def test_each_reduced_key_is_a_cut_or_a_departure(bench):
    for c in bench["configs"]:
        f = cell.load_json(os.path.join(cell.ROOT, c["file"]))
        assert f["reduced"] == c["reduced"]
        assert set(f["reduced"]) == set(f["cuts"]) | set(f["departures"])
        assert not set(f["cuts"]) & set(f["departures"])
        assert set(f["reduced"]) <= set(f["model"])


def test_unknown_workload_is_an_error(bench):
    with pytest.raises(KeyError):
        cell.load_cell("no-such.cell", bench)


CONFIG_FILES = sorted(
    os.path.join(d, f) for d in ("configs", "testdata")
    for f in os.listdir(os.path.join(HERE, d))
    if f.endswith(".json") and f != "tiny-traffic.json")


@pytest.mark.parametrize("path", CONFIG_FILES)
def test_every_configuration_resolves_through_its_named_layout(path):
    cfg = cell.load_json(os.path.join(HERE, path))
    layout = cell.load_layout(cfg)
    assert layout.__name__ == f"benchmarks.chip.layouts.{cfg['layout']}"
    s = layout.Shape.from_config(cfg)
    arch = layout.arch_config(cfg)
    assert (arch.num_layers, arch.num_heads, arch.num_kv_heads) == (
        s.layers, s.heads, s.kv_heads)
    leaves = jax.tree.leaves(layout.param_shapes(cfg))
    assert sum(x.size for x in leaves) == s.param_count()


@pytest.mark.parametrize("name", ["no-such-layout", None])
def test_an_unknown_or_missing_layout_is_an_error(name):
    cfg = cell.load_json(os.path.join(HERE, "testdata", "tiny-dense.json"))
    if name is None:
        del cfg["layout"]
    else:
        cfg["layout"] = name
    with pytest.raises(KeyError, match="none of .*'decoder'"):
        cell.load_layout(cfg)


@pytest.mark.parametrize("name", ["multiround", "hyde"])
def test_traffic_is_the_same_work_for_every_seed(name):
    t = Traffic.from_dict(cell.load_json(
        os.path.join(HERE, "traffic", f"{name}.json")))
    plans = t.plans(max_len=1024)
    assert plans == t.plans(max_len=1024)
    assert len(plans) == t.clients == 16
    for _, stages, _ in plans:
        assert all(w <= 1024 for w in windows(stages))
        assert sum(k == "retrieve" for k, _, _ in stages) >= 1
    if name == "multiround":
        counts_ = {p: sum(q == p for q, _, _ in plans) for p in t.pipelines}
        assert counts_ == {"irg": 6, "iter": 5, "flare": 5}


def test_a_window_longer_than_max_len_is_cut():
    from benchmarks.chip.traffic import capped
    st = [("generate", 700, 1), ("judge", 500, 1), ("retrieve", 0, 1),
          ("generate", 30, 1)]
    assert windows(capped(st, 1024)) == [1024, 30]


def test_requests_differ_by_seed_in_order_and_queries_only():
    from benchmarks.chip.traffic import make_requests
    t = Traffic.from_dict(cell.load_json(
        os.path.join(HERE, "traffic", "multiround.json")))
    emb = np.random.default_rng(0).standard_normal((500, 8)).astype(np.float32)
    a = make_requests(t, emb, seed=2**33 + 1, drain=0, max_len=1024)
    b = make_requests(t, emb, seed=2**33 + 2, drain=0, max_len=1024)
    key = lambda r: (r.trace.pipeline, [(s.kind, s.gen_tokens)
                                        for s in r.trace.stages])
    assert sorted(map(str, map(key, a))) == sorted(map(str, map(key, b)))
    assert not np.allclose(a[0].q, b[0].q)
    again = make_requests(t, emb, seed=2**33 + 1, drain=0, max_len=1024)
    assert [key(r) for r in a] == [key(r) for r in again]
    ids = [r.trace.request_id for r in a] + [
        r.trace.request_id
        for r in make_requests(t, emb, seed=1, drain=1, max_len=1024)]
    assert len(set(ids)) == len(ids)


# -- counts, against values worked by hand ------------------------------------

def shape(name):
    cfg = cell.load_json(os.path.join(HERE, "configs", f"{name}.json"))
    return cell.load_layout(cfg).Shape.from_config(cfg)


def test_granite_moe_counts():
    s = shape("granite-moe-3b")
    # attention 6,291,456 + router 61,440 + 40 experts 94,371,840 + norms
    assert s.layer_params() == (100_727_808, 25_230_336)
    assert s.param_count() == 3_374_295_552
    assert s.matmul_params_per_token() == 882_774_528
    assert counts.decode_token_flops(s, 1) == 1_765_745_664


def test_granite_20b_stage_counts():
    s = shape("granite-20b-stage")
    assert s.layer_params()[0] == 379_072_512
    assert s.param_count() == 5_531_928_576
    assert s.matmul_params_per_token() == 5_229_772_800
    assert counts.decode_token_flops(s, 1) == 10_459_865_088


@pytest.mark.parametrize("name", ["granite-moe-3b", "granite-20b-stage"])
def test_param_count_is_the_weights_made(name):
    cfg = cell.load_json(os.path.join(HERE, "configs", f"{name}.json"))
    layout = cell.load_layout(cfg)
    leaves = jax.tree.leaves(layout.param_shapes(cfg))
    assert sum(x.size for x in leaves) == layout.Shape.from_config(
        cfg).param_count()
    assert all(x.dtype == jnp.bfloat16 for x in leaves)


def test_wave_flops_counts_each_token_context():
    s = shape("granite-moe-3b")
    assert counts.wave_flops(s, [2, 1]) == (
        2 * counts.decode_token_flops(s, 1) + counts.decode_token_flops(s, 2))


def test_flash_decode_call_count():
    s = shape("granite-moe-3b")
    # 4*24*64*(1+17) FLOPs; 3 pages of 16 tokens x 2048 B, q+out 2x1536x6
    assert counts.flash_decode_call(s, [1, 17], 16) == (110_592, 116_736)


def test_probe_topk_call_count():
    f, b = counts.probe_topk_call(2, 256, 768, [10, 0], 10, 128)
    assert f == 2 * 2 * 256 * 768 + 2 * 10 * 128 * 768 == 2_752_512
    assert b == 786_432 + 6_144 + 1_971_200 + 48 == 2_763_824


def test_roofline_share_and_bound():
    p = peak_for("TPU v5 lite")
    share, bound = counts.roofline(197e9, 1.0, 2e-3, p)
    assert bound == "compute" and share == pytest.approx(50.0)
    share, bound = counts.roofline(1.0, 819e6, 4e-3, p)
    assert bound == "memory" and share == pytest.approx(25.0)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peak_for("cpu")


def test_reference_redraws_the_served_weights():
    from benchmarks.chip.references import decoder as ref
    cfg = cell.load_json(os.path.join(HERE, "testdata", "tiny-moe.json"))
    layout = cell.load_layout(cfg)
    s = layout.Shape.from_config(cfg)
    tree = layout.program_params(cfg, 2**40 + 3, jax.devices()[0])
    key = weights.seed_key(2**40 + 3)
    for name, (shp, fan_in) in ref.leaf_specs(s).items():
        node = tree
        for part in name.split("."):
            node = node[part]
        for layer in range(s.layers if name.startswith("layers.") else 1):
            got = node[layer] if name.startswith("layers.") else node
            want = weights.leaf_values(weights.leaf_key(key, name, layer),
                                       shp, fan_in)
            assert bool(jnp.array_equal(got, want)), (name, layer)


# crc32 of each leaf's bf16 bits at seed 2**40 + 3, as the weights were
# made before the layouts were named in the configuration files
PINNED_LEAVES = {
    "tiny-moe": {
        "['embed']": 3335536517, "['final_norm']": 2641948502,
        "['layers']['attn']['wk']": 2042051119,
        "['layers']['attn']['wo']": 2610886845,
        "['layers']['attn']['wq']": 2815202024,
        "['layers']['attn']['wv']": 3287094584,
        "['layers']['attn_norm']": 580096979,
        "['layers']['mlp']['router']": 896475838,
        "['layers']['mlp']['w_down']": 998746818,
        "['layers']['mlp']['w_gate']": 378712331,
        "['layers']['mlp']['w_up']": 2595723472,
        "['layers']['mlp_norm']": 1178708074, "['unembed']": 3944596257},
    "tiny-dense": {
        "['embed']": 3335536517, "['final_norm']": 2641948502,
        "['layers']['attn']['wk']": 1689732409,
        "['layers']['attn']['wo']": 2610886845,
        "['layers']['attn']['wq']": 2815202024,
        "['layers']['attn']['wv']": 1828347581,
        "['layers']['attn_norm']": 580096979,
        "['layers']['mlp']['w_down']": 4073857534,
        "['layers']['mlp']['w_up']": 2595723472,
        "['layers']['mlp_norm']": 1178708074, "['unembed']": 3944596257}}


@pytest.mark.parametrize("name", sorted(PINNED_LEAVES))
def test_layout_makes_the_pinned_weights(name):
    cfg = cell.load_json(os.path.join(HERE, "testdata", f"{name}.json"))
    tree = cell.load_layout(cfg).program_params(cfg, 2**40 + 3,
                                                jax.devices()[0])
    got = {jax.tree_util.keystr(p): zlib.crc32(
        np.asarray(v).view(np.uint16).tobytes())
        for p, v in jax.tree_util.tree_flatten_with_path(tree)[0]}
    assert got == PINNED_LEAVES[name]


# -- trace reduction ----------------------------------------------------------

def test_union_busy_and_gaps():
    ops = [("%a.1 = f32[] add()", 0, 10), ("%b = f32[] fusion(%k.2)", 5, 20),
           ("%k.2 = f32[] custom-call()", 30, 40),
           ("%while.3 = (f32[]) while(%t)", 30, 50),
           ("%c = f32[] copy()", 45, 50)]
    spans = [("bench.drain", 0, 100), ("bench.retrieve", 18, 35)]
    assert xplane.union([(0, 10), (5, 20), (30, 40)]) == [(0, 20), (30, 40)]
    assert xplane.busy_ns(ops, (0, 100)) == 40
    assert xplane.busy_ns(ops, (8, 32)) == 14
    assert xplane.kernel_ns(ops, (0, 100), ("k",)) == (10, 1)
    gaps = xplane.idle_gaps(ops, (0, 100), spans)
    assert [(g[1], g[2]) for g in gaps] == [(20, 30), (50, 100)]
    assert gaps[0][0] == "bench.retrieve" and gaps[1][0] == "bench.drain"
    top = xplane.breakdown(ops, (0, 100), spans)["device_ops"]
    assert [n for n, _ in top] == ["b", "a.1", "k.2", "c"]


def test_reduction_of_a_trace_recorded_on_the_chip():
    t = xplane.load(FIXTURE)
    assert 0 in t.ops and t.ops[0]
    lo, hi = t.window()
    ops = t.ops[0]
    busy = xplane.busy_ns(ops, (lo, hi))
    assert 0 < busy <= hi - lo
    ns, calls = xplane.kernel_ns(ops, (lo, hi), ("flash_decode_paged",))
    assert calls > 0 and 0 < ns <= busy
    gaps = xplane.idle_gaps(ops, (lo, hi), t.spans)
    assert sum(e - s for _, s, e in gaps) == pytest.approx(hi - lo - busy)
    assert {g[0] for g in gaps} <= {n for n, _, _ in t.spans} | {
        "outside bench spans"}
    br = xplane.breakdown(ops, (lo, hi), t.spans)
    assert 0 < len(br["device_ops"]) <= 10 and 0 < len(br["idle_gaps"]) <= 10


# -- the command --------------------------------------------------------------

def test_run_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload",
         "granite-20b-stage.hyde", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=cell.ROOT, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    for line in p.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)

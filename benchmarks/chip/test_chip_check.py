"""The comparison that decides ``correct``, on the CPU at a size a test
run holds: a sound run of the served path is correct, its control (the
reference one precision lower in the program's place) is not, and each
fault the cells can have, planted in the timed path underneath the
harness, makes ``correct`` come out false.

The harness runs as ``run.py`` runs it, with the look for a chip left
out.  The cells run on one chip, so there is no exchange between chips
to leave out.
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import numpy as np
import pytest

from benchmarks.chip import cell, check, harness

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 2**33 + 12


def tiny_cell(config: str) -> cell.Cell:
    bench = cell.load_benchmark()
    td = os.path.join(HERE, "testdata")
    return cell.Cell(name=f"{config}.tiny", chips=1,
                     config=cell.load_json(os.path.join(td, f"{config}.json")),
                     traffic=cell.load_json(os.path.join(td,
                                                         "tiny-traffic.json")),
                     end_to_end=bench["end_to_end"],
                     per_layer=bench["per_layer"])


def run(config: str, tmp_path, **kw) -> dict:
    return harness.run_cell(tiny_cell(config), seed=SEED, seconds=1.0,
                            trace=False, device=jax.devices()[0],
                            t_start=time.perf_counter(),
                            trace_dir=str(tmp_path / "trace"), **kw)


# The numbers whose control fails its limit in the harness's own run.  At
# the 22 positions tiny-dense decodes, its fp8 control's widest gap reads
# 0.073, under the limit 0.1: there only the retrieval control fails.
CONTROL_FAILS = {"tiny-moe": {"logit_gap", "retrieval_score_gap"},
                 "tiny-dense": {"retrieval_score_gap"}}


@pytest.mark.parametrize("config", ["tiny-moe", "tiny-dense"])
def test_sound_run_is_correct_and_its_control_is_not(config, tmp_path):
    res = run(config, tmp_path, control=True)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    n = res["numbers"]
    limits = {k: v["limit"] for k, v in res["compared"].items()}
    assert set(limits) == set(check.COMPARED)
    ok, _ = check.verdict({k: n[f"control_{k}"] for k in limits}, limits)
    assert not ok
    failed = {k for k in limits
              if not check.verdict({k: n[f"control_{k}"]},
                                   {k: limits[k]})[0]}
    assert CONTROL_FAILS[config] <= failed, n
    assert set(res["metrics"]) == {"tokens_per_s", "latency_p50_s",
                                   "latency_p90_s", "setup_s"}
    assert list(res)[-1] == "compared"


@pytest.mark.parametrize("config", ["tiny-moe", "tiny-dense"])
def test_the_decode_control_rounds_attention_too(config):
    """Over 4 x 64 positions a seed: each decode control fails the
    configuration's ``logit_gap`` limit, and the control that also rounds
    attention's inputs lies further from the reference's logits than the
    one that rounds only the products with weights.  At this size the
    widest gap does not order the two: a few near-ties decide it, and
    either control can read the larger."""
    from benchmarks.chip.references import decoder as ref
    cfg = tiny_cell(config).config
    vocab = cfg["model"]["vocab_size"]
    for seed in (SEED, SEED + 1, SEED + 2):
        rng = np.random.default_rng(seed)
        inputs = [rng.integers(0, vocab, 64, dtype=np.int32)
                  for _ in range(4)]
        want = np.concatenate(ref.Hidden(cfg, seed, inputs).logits())
        err = {}
        for quant in ref.CONTROLS:
            got = np.concatenate(ref.Hidden(cfg, seed, inputs,
                                            quant=quant).logits())
            at = want[np.arange(len(want)), got.argmax(-1)]
            assert np.max(want.max(-1) - at) > \
                cfg["check"]["logit_gap_limit"], (quant, seed)
            err[quant] = np.mean(np.abs(got - want))
        assert err["fp8"] > err["fp8_weights"], (seed, err)


def test_a_file_compares_exactly_the_fixed_numbers():
    cfg = tiny_cell("tiny-dense").config
    assert check.limits_of(cfg) == {"logit_gap": 0.1,
                                    "retrieval_score_gap": 1e-05}
    left_out = {**cfg, "check": {k: v for k, v in cfg["check"].items()
                                 if k != "logit_gap_limit"}}
    with pytest.raises(ValueError, match="logit_gap_limit"):
        check.limits_of(left_out)
    added = {**cfg, "check": {**cfg["check"], "decode_positions_limit": 1}}
    with pytest.raises(ValueError, match="decode_positions_limit"):
        check.limits_of(added)


def test_a_limit_not_set_from_readings_fails_every_run():
    """granite-moe-3b has no ``logit_gap`` limit that separates its
    control: even a gap of 0 does not come out correct by its file."""
    cfg = cell.load_json(os.path.join(HERE, "configs",
                                      "granite-moe-3b.json"))
    limits = check.limits_of(cfg)
    assert limits["logit_gap"] is None
    ok, lines = check.verdict({"logit_gap": 0.0, "retrieval_score_gap": 0.0},
                              limits)
    assert not ok and lines[0].endswith("FAIL")


def _state_unchanged(monkeypatch):
    """The decode step returns the KV slab it was given."""
    from repro.models import transformer as tf

    def after_build(srv, runner):
        cfg, mode = runner.cfg, runner._kernel_mode
        runner._paged_step = jax.jit(
            lambda p, k, v, bt, lens, tok, live: (tf.serve_step_paged(
                p, k, v, bt, lens, {"token": tok, "live_rows": live}, cfg,
                kernel_mode=mode)[0], k, v))
    return after_build


def _half_batch(monkeypatch):
    """Retrieval searches half of the batch; the rest get its answers."""
    from repro.serving.policies import TeleRAGPolicy
    inner = TeleRAGPolicy.retrieve

    def retrieve(self, engine, q_out, **kw):
        half = max(1, len(q_out) // 2)
        res = inner(self, engine, q_out[:half], **kw)
        take = np.arange(len(q_out)) % half
        return dataclasses.replace(
            res, doc_ids=res.doc_ids[take], scores=res.scores[take],
            hit_clusters=[res.hit_clusters[i] for i in take],
            missed_clusters=[res.missed_clusters[i] for i in take])
    monkeypatch.setattr(TeleRAGPolicy, "retrieve", retrieve)


def _token_altered(monkeypatch):
    """Each sampled token is one past the greedy choice."""
    from repro.serving import decode
    inner = decode.sample
    monkeypatch.setattr(decode, "sample", lambda logits: (
        (inner(logits) + 1) % logits.shape[-1]).astype(np.int32))


def _answer_altered(monkeypatch):
    """One doc id of every retrieval is replaced by its neighbour."""
    from repro.serving.policies import TeleRAGPolicy
    inner = TeleRAGPolicy.retrieve

    def retrieve(self, engine, q_out, **kw):
        res = inner(self, engine, q_out, **kw)
        ids = np.array(res.doc_ids)
        ids[0, 0] = (ids[0, 0] + 1) % engine.index.assignments.shape[0]
        return dataclasses.replace(res, doc_ids=ids)
    monkeypatch.setattr(TeleRAGPolicy, "retrieve", retrieve)


FAULTS = {"state_unchanged": _state_unchanged, "half_batch": _half_batch,
          "token_altered": _token_altered, "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_fault_in_the_timed_path_is_not_correct(fault, monkeypatch,
                                                  tmp_path):
    after_build = FAULTS[fault](monkeypatch)
    res = run("tiny-moe", tmp_path, after_build=after_build)
    assert not res["correct"], res["compared"]


def _edge_corpus():
    """Six one-vector clusters scored by q = e0: cluster 3's centroid
    (0.3) rounds in bf16 onto cluster 2's (0.30078125), the third of an
    nprobe of 3, and its vector outscores cluster 2's."""
    from benchmarks.chip.datastore import Corpus
    cen = np.zeros((6, 4), np.float32)
    cen[:, 0] = [0.9, 0.6, 0.30078125, 0.3, 0.1, -0.5]
    vec = np.zeros((6, 4), np.float32)
    vec[:, 0] = [0.5, 0.375, 0.25, 0.4375, 0.875, 0.9375]
    q = np.zeros((1, 4), np.float32)
    q[0, 0] = 1.0
    return Corpus(vectors=vec, centroids=cen,
                  assignment=np.arange(6, dtype=np.int32)), q


# served ids (top-2), by the probe they come from
EDGE_ANSWERS = {"exact_probe": [0, 1], "edge_cluster_taken": [0, 3],
                "far_cluster_taken": [4, 0], "best_doc_left_out": [1, 3]}


@pytest.mark.parametrize("edge,answer,ok", [
    ("bf16", "exact_probe", True), ("bf16", "edge_cluster_taken", True),
    ("bf16", "far_cluster_taken", False),
    ("bf16", "best_doc_left_out", False),
    ("highest", "exact_probe", True),
    ("highest", "edge_cluster_taken", False)])
def test_an_answer_at_the_probe_edge(edge, answer, ok):
    from benchmarks.chip.references import ivf
    corpus, q = _edge_corpus()
    ref = ivf.search(corpus, q, nprobe=3, k=2, edge_precision=edge)
    assert ref.ids.tolist() == [[0, 1]]
    ids = np.asarray([EDGE_ANSWERS[answer]])
    scores = corpus.vectors[ids[0], 0][None, :]
    gap = float(np.max(ivf.answer_gap(corpus, q, ids, scores, ref)))
    assert (gap <= 1e-5) == ok, gap

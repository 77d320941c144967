"""The comparison that decides ``correct``: what the timed path produced,
against the plain references.

* Decode: a sample of the window's completed requests drawn from the
  seed, the one with the longest decode stage always in it.  Each of its
  decode stages started at position 0 from token 0; the reference runs
  over those inputs and reads, at every position, how far the logit of
  the token the server produced lies below its own best
  (``logit_gap``).  Greedy decoding makes that 0 up to rounding.
* Retrieval: every round of the window.  The reference searches the
  round's rewritten query; ``retrieval_score_gap`` is the widest gap
  between a served score and what its doc id really scores, or, rank by
  rank, by which a served score lies outside what the reference's probe
  gives, where clusters at the probe's edge may be taken or left as
  ``check.probe_edge_precision`` rounds (``references/ivf.py``).  A
  missing id, or one from a cluster no such probe takes, reads infinite.

Every configuration compares the same numbers, ``COMPARED``; its file
gives each a limit, ``check.<name>_limit``.  A limit of null is one not
yet set from readings: no run of that file comes out correct.

The controls (``control=True``) put the reference in the program's place
one precision lower: float8 weights and the inputs of every product,
attention's among them, for the bf16 decoder; one bf16 pass for the
float32 search.
The benchmark's own runs do not run them.
"""

from __future__ import annotations

import importlib
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.chip.references import ivf
from benchmarks.chip.traffic import seed_rng

COMPARED = ("logit_gap", "retrieval_score_gap")


def decode_sample(generated: Dict[int, list], window_waves, seed: int,
                  n_requests: int) -> List[Tuple[np.ndarray, np.ndarray]]:
    """(inputs, served tokens) of each decode stage of the sampled
    requests, longest first, with exact duplicates and prefixes of a
    longer one merged into it."""
    stages: Dict[int, List[np.ndarray]] = {}
    for w in window_waves:
        for rid, g, idx in zip(w.request_ids, w.gens, w.token_index):
            if g > 0:
                toks = np.asarray(generated[rid][idx][:g], np.int32)
                stages.setdefault(rid, []).append(toks)
    if not stages:
        return []
    rids = sorted(stages)
    longest = max(rids, key=lambda r: max(len(t) for t in stages[r]))
    rng = seed_rng(seed, 2)
    others = [r for r in rids if r != longest]
    pick = [longest] + list(rng.choice(others, min(len(others),
                                                   n_requests - 1),
                                       replace=False))
    seqs = sorted((t for r in pick for t in stages[int(r)]), key=len,
                  reverse=True)
    kept: List[np.ndarray] = []
    for t in seqs:
        if not any(len(k) >= len(t) and np.array_equal(k[:len(t)], t)
                   for k in kept):
            kept.append(t)
    return [(np.concatenate([[0], t[:-1]]).astype(np.int32), t)
            for t in kept]


def decode_gaps(cfg: dict, seed: int, seqs, control: bool = False) -> dict:
    """``logit_gap`` of the served tokens (and of the control's argmax
    tokens with ``control``), each the widest over every position."""
    ref_mod = importlib.import_module(
        f"benchmarks.chip.references.{cfg['reference']}")
    inputs = [i for i, _ in seqs]
    ref = ref_mod.Hidden(cfg, seed, inputs)
    got = ref.read([t for _, t in seqs])
    out = {"logit_gap": float(max(np.max(r["best"] - r["target"])
                                  for r in got)),
           "decode_positions": int(sum(len(i) for i in inputs)),
           "decode_sequences": len(seqs)}
    if control:
        ctl = ref_mod.Hidden(cfg, seed, inputs, quant="fp8")
        picks = [r["argmax"] for r in ctl.read([t for _, t in seqs])]
        del ctl
        at = ref.read(picks)
        out["control_logit_gap"] = float(max(np.max(r["best"] - r["target"])
                                             for r in at))
    return out


def retrieval_gaps(corpus, ds: dict, rounds, edge_precision: str,
                   control: bool = False) -> dict:
    """``retrieval_score_gap`` over the served rounds (and the control's
    with ``control``), the probe's edge as wide as ``edge_precision``
    rounds."""
    if not rounds:
        return {}
    q = np.concatenate([r.queries for r in rounds])
    ids = np.concatenate([r.doc_ids for r in rounds]).astype(np.int64)
    got = np.concatenate([r.scores for r in rounds])
    kw = dict(nprobe=int(ds["nprobe"]), k=int(ds["top_k"]))
    ref = ivf.search(corpus, q, edge_precision=edge_precision, **kw)
    gap = ivf.answer_gap(corpus, q, ids, got, ref)
    exact = np.abs(got.astype(np.float64) - ref.scores)
    edge = ref.possible & ~ref.sure
    out = {"retrieval_score_gap": float(np.max(gap)),
           "retrieval_queries": int(len(q)),
           "retrieval_ids_equal": float(np.mean(ids == ref.ids)),
           # answers off the exact probe's by more than rounding: the
           # program's probe took another cluster at its edge
           "retrieval_edge_flips": int(np.sum(np.any(exact > 1e-5, axis=1))),
           "retrieval_exact_probe_gap": float(np.max(exact)),
           "retrieval_edge_clusters": float(np.mean(np.sum(edge, axis=1))),
           # reference answers from clusters at the edge of the probe
           "retrieval_edge_answers": int(np.sum(ref.rank >= kw["nprobe"] - 2))}
    if control:
        ctl = ivf.search(corpus, q, precision="bf16", **kw)
        out["control_retrieval_score_gap"] = float(np.max(ivf.answer_gap(
            corpus, q, ctl.ids, ctl.scores, ref)))
    return out


def limits_of(cfg: dict) -> Dict[str, Optional[float]]:
    """The limit of each of ``COMPARED`` from the file's ``check``: a
    file that leaves one out, or gives a limit to another number, is an
    error."""
    chk = cfg["check"]
    want = {f"{name}_limit" for name in COMPARED}
    have = {k for k in chk if k.endswith("_limit")}
    if have != want:
        raise ValueError(f"{cfg['name']}: check limits {sorted(have)}, "
                         f"want exactly {sorted(want)}")
    return {name: chk[f"{name}_limit"] for name in COMPARED}


def verdict(numbers: dict, limits: dict) -> Tuple[bool, List[str]]:
    """``correct`` and one line per number compared: name, value, limit.
    A limit of None, none set from readings, fails."""
    lines, ok = [], True
    for name, limit in limits.items():
        val = numbers.get(name)
        good = (limit is not None and val is not None and np.isfinite(val)
                and val <= limit)
        ok &= bool(good)
        lines.append(f"{name} {val!r} limit {limit!r} "
                     f"{'ok' if good else 'FAIL'}")
    return ok, lines

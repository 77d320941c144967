"""The one traffic generator: reads a traffic file's parameters and makes
the requests of each drain from the seed.

Stage plans are a copy of ``serving/trace.py``'s ``make_trace`` (the
paper's pipeline structures, geometric generation lengths) and query
embeddings a copy of ``launch/serve.py``'s ``make_queries``, kept here
so that a change to the program does not change the yardstick.

Every drain submits the same ``clients`` stage plans, drawn once from the
file's ``plan_seed``; the run's seed draws their order and the query
embeddings.  So every seed serves the same amount of decode and
retrieval work, and seeds differ in content, not in size.
"""

from __future__ import annotations

import dataclasses
from typing import List, Sequence

import numpy as np

# rewrite strength per pipeline (copied from core/overlap.py)
REWRITE_SIGMA = {"hyde": 0.0375, "subq": 0.0550, "iter": 0.0100,
                 "irg": 0.0200, "flare": 0.0275, "self_rag": 0.0}


def _geo(rng: np.random.Generator, mean: float, lo: int = 4) -> int:
    return int(max(lo, rng.geometric(1.0 / max(mean, 1.0))))


def stage_plan(pipeline: str, rng: np.random.Generator,
               length_scale: float) -> List[tuple]:
    """One request's stages as (kind, gen_tokens, num_queries)."""
    s = lambda m: _geo(rng, m * length_scale)
    st: List[tuple] = []
    if pipeline == "hyde":
        st = [("generate", s(128), 1), ("retrieve", 0, 1),
              ("generate", s(96), 1)]
    elif pipeline == "subq":
        nq = int(rng.integers(3, 5))
        st = [("generate", s(24) * nq, 1), ("retrieve", 0, nq),
              ("generate", s(128), 1)]
    elif pipeline == "iter":
        for _ in range(int(rng.integers(2, 4))):
            st += [("generate", s(32), 1), ("retrieve", 0, 1),
                   ("generate", s(64), 1), ("judge", s(8), 1)]
    elif pipeline == "irg":
        for _ in range(3):
            st += [("retrieve", 0, 1), ("generate", s(48), 1)]
    elif pipeline == "flare":
        for _ in range(int(rng.integers(2, 5))):
            st += [("generate", s(28), 1), ("retrieve", 0, 1)]
        st.append(("generate", s(48), 1))
    elif pipeline == "self_rag":
        st = [("judge", s(8), 1), ("retrieve", 0, 1),
              ("generate", s(96), 1), ("judge", s(16), 1)]
    else:
        raise KeyError(pipeline)
    prompt = _geo(rng, 48 * length_scale, lo=8)
    return st, prompt


def windows(stages: Sequence[tuple]) -> List[int]:
    """Decode tokens between retrievals (and after the last one)."""
    out, acc = [], 0
    for kind, g, _ in stages:
        if kind == "retrieve":
            out.append(acc)
            acc = 0
        else:
            acc += g
    return out + [acc]


def capped(stages: Sequence[tuple], max_len: int) -> List[tuple]:
    """Stages with each decode window cut to ``max_len`` tokens: a wave
    decodes from position 0, so a row's KV holds only what it decoded.
    The cut falls on the window's last stages."""
    out, acc = [], 0
    for kind, g, nq in stages:
        if kind == "retrieve":
            acc = 0
            out.append((kind, g, nq))
            continue
        g = min(g, max_len - acc)
        acc += g
        out.append((kind, g, nq))
    return out


@dataclasses.dataclass(frozen=True)
class Traffic:
    """A traffic file's parameters."""

    name: str
    clients: int
    pipelines: tuple
    length_scale: float
    plan_seed: int
    query_noise: float

    @classmethod
    def from_dict(cls, d: dict) -> "Traffic":
        if d.get("loop") != "closed":
            raise ValueError(f"traffic {d.get('name')!r}: only a closed "
                             f"loop is implemented, not {d.get('loop')!r}")
        return cls(name=d["name"], clients=int(d["clients"]),
                   pipelines=tuple(d["pipelines"]),
                   length_scale=float(d["length_scale"]),
                   plan_seed=int(d["plan_seed"]),
                   query_noise=float(d["query_noise"]))

    def plans(self, max_len: int) -> List[tuple]:
        """The drain's fixed (pipeline, stages, prompt tokens) plans:
        pipelines in equal turns, lengths from ``plan_seed``."""
        rng = np.random.default_rng(self.plan_seed)
        out = []
        for i in range(self.clients):
            p = self.pipelines[i % len(self.pipelines)]
            st, prompt = stage_plan(p, rng, self.length_scale)
            out.append((p, capped(st, max_len), prompt))
        return out


def seed_rng(seed: int, *stream: int) -> np.random.Generator:
    """A generator for (seed, stream...): any whole seed, 64 bits wide."""
    return np.random.default_rng([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF,
                                  *stream])


WARM_TOKENS = 4           # decode tokens per stage of a warm-up drain


def make_requests(traffic: Traffic, embeddings: np.ndarray, *, seed: int,
                  drain: int, max_len: int, warm: bool = False):
    """The ``clients`` requests of one drain: the fixed plans in an order
    drawn from (seed, drain), each with a query near a random corpus
    vector.  Request ids are unique across the run's drains.  A ``warm``
    drain draws from another stream and cuts every decode stage to
    ``WARM_TOKENS``: it runs every program the window runs, at the same
    shapes, in a fraction of the time."""
    from repro.serving import RagRequest
    from repro.serving.trace import RequestTrace, StageTrace

    rng = seed_rng(seed, 0 if warm else 1, drain)
    plans = traffic.plans(max_len)
    order = rng.permutation(len(plans))
    n = len(plans)
    pick = rng.choice(embeddings.shape[0], n)
    q = embeddings[pick] + traffic.query_noise * rng.standard_normal(
        (n, embeddings.shape[1])).astype(np.float32)
    q /= np.linalg.norm(q, axis=-1, keepdims=True)
    base = (drain + (1 << 20 if warm else 0)) * n
    out = []
    for i, j in enumerate(order):
        pipeline, stages, prompt = plans[j]
        trace = RequestTrace(
            pipeline=pipeline, request_id=base + i,
            stages=[StageTrace(k, gen_tokens=min(g, WARM_TOKENS) if warm
                               else g, num_queries=nq)
                    for k, g, nq in stages],
            rewrite_sigma=REWRITE_SIGMA[pipeline], prompt_tokens=prompt)
        out.append(RagRequest(q=q[i], trace=trace))
    return out

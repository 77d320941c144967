"""The benchmark's instruments around the served path.

``Probe`` stands between the server and its decode hook and wraps each
engine's ``retrieve`` and ``lookahead_ex``: it times every call on the
host clock, writes ``bench.decode_wave``, ``bench.retrieve`` and
``bench.lookahead`` spans into the profiler's trace, counts what each
call carried, and stamps each request with the time of the last call
that worked for it.  Nothing in the program is
changed: the hook it calls is the program's ``DecodeRunner``.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np


@dataclasses.dataclass
class Wave:
    """One call of the decode hook."""

    t0: float
    t1: float
    rows: int                  # rows the step runs (the micro-batch)
    live: int                  # rows that belong to requests
    steps: int                 # decode steps run
    gens: List[int]            # each live row's own tokens this wave
    request_ids: List[int]
    token_index: List[int]     # each row's index into runner.generated


@dataclasses.dataclass
class Retrieval:
    """One call of an engine's ``retrieve``."""

    t0: float
    t1: float
    queries: np.ndarray        # [B, dim] the rewritten queries searched
    doc_ids: np.ndarray        # [B, k]
    scores: np.ndarray         # [B, k]
    hit_clusters: List[List[int]]
    missed_clusters: List[List[int]]
    request_ids: List[int]


class Probe:
    """Decode hook wrapper plus ``retrieve`` wrapper; see module doc."""

    def __init__(self, runner, rows: int, clock=time.perf_counter):
        self.runner = runner
        self.rows = rows               # the server's micro-batch
        self.clock = clock
        self.waves: List[Wave] = []
        self.retrievals: List[Retrieval] = []
        self.last_touch: Dict[int, float] = {}
        self._retrieving: List[int] = []
        # called with "wave" or "retrieve" after every such call
        self.after_call: Optional[Callable[[str], None]] = None

    # -- the decode hook -----------------------------------------------------
    def __call__(self, replica: int, records, gen_tokens, rnd: int):
        n = len(records)
        steps = min(max(gen_tokens, default=0), self.runner.max_steps)
        ids = [r.request_id for r in records]
        index = [len(self.runner.generated.get(i, ())) for i in ids]
        # rows with a retrieval left hand their rewritten queries to the
        # engine's retrieve right after this hook, in this order
        self._retrieving = [
            r.request_id for r in records if r.next_round < len(r.plan)
            for _ in range(r.plan[r.next_round][1])]
        with jax.profiler.TraceAnnotation("bench.decode_wave"):
            t0 = self.clock()
            events = self.runner(replica, records, gen_tokens, rnd)
            t1 = self.clock()
        self.waves.append(Wave(
            t0=t0, t1=t1, rows=max(n, self.rows), live=n,
            steps=steps, gens=[min(g, steps) for g in gen_tokens],
            request_ids=ids, token_index=index))
        for i in ids:
            self.last_touch[i] = t1
        if self.after_call is not None:
            self.after_call("wave")
        return events

    # -- retrieval -----------------------------------------------------------
    def wrap(self, engine) -> None:
        """Route ``engine.retrieve`` and ``engine.lookahead_ex`` through
        this probe."""
        inner = engine.retrieve

        def retrieve(q_out, **kw):
            with jax.profiler.TraceAnnotation("bench.retrieve"):
                t0 = self.clock()
                res = inner(q_out, **kw)
                t1 = self.clock()
            ids = (self._retrieving if len(self._retrieving) == len(q_out)
                   else [-1] * len(q_out))
            self._retrieving = []
            self.retrievals.append(Retrieval(
                t0=t0, t1=t1, queries=np.array(q_out, np.float32),
                doc_ids=np.array(res.doc_ids), scores=np.array(res.scores),
                hit_clusters=[list(map(int, h)) for h in res.hit_clusters],
                missed_clusters=[list(map(int, m))
                                 for m in res.missed_clusters],
                request_ids=list(ids)))
            for i in ids:
                if i >= 0:
                    self.last_touch[i] = t1
            if self.after_call is not None:
                self.after_call("retrieve")
            return res

        def lookahead_ex(*args, **kw):
            with jax.profiler.TraceAnnotation("bench.lookahead"):
                return prefetch(*args, **kw)

        prefetch = engine.lookahead_ex
        engine.retrieve = retrieve
        engine.lookahead_ex = lookahead_ex

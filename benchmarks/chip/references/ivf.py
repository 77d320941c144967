"""Plain reference of the IVF search a configuration states: each query
scores every centroid, the ``nprobe`` best clusters are probed, and the
``top_k`` vectors of those clusters by inner product are the answer.
Vectors are held at the page precision the file states (bf16), scores
are float32 at ``HIGHEST`` precision.

Which clusters make the probe is decided by centroid scores, and the
configuration's ``check.probe_edge_precision`` names the precision that
decision may be taken at.  A cluster is at the probe's edge where its
score at that precision, or the rounding of any float32 sum, could move
it across the ``nprobe``-th: some probes within rounding take it and
some do not.  ``search`` returns the top-k of the exact probe, and, rank
by rank, the top-k of the clusters every such probe takes (``lo``) and
of those some such probe may take (``hi``).  With ``"highest"`` the edge
holds only what float32 sums can round across.

``precision="bf16"`` is the control: queries, centroids and vectors
rounded to bf16, one pass, float32 sums.  (XLA's three-pass ``HIGH`` is
no step down here: the pages hold bf16 values, so it rounds only the
query's third bf16 part away.)
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64                # queries per device call
F32_EPS = 2.0 ** -24      # unit roundoff of float32


def _bf16(x):
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def _dot(a, b, precision: str):
    """a [B, d] . b [N, d]^T at the named precision."""
    ein = functools.partial(jnp.einsum, "bd,nd->bn", precision=HIGHEST)
    if precision == "highest":
        return ein(a, b)
    if precision == "bf16":
        return ein(_bf16(a), _bf16(b))
    raise ValueError(f"unknown precision {precision!r}")


def _edge(q, centroids, cs, nprobe: int, edge_precision: str):
    """(sure, possible) [B, Nc]: the clusters in every top-``nprobe`` of
    centroid scores within rounding of ``cs``, and those in some.  The
    rounding is the gap to the scores at ``edge_precision`` and that of
    the three float32 sums involved, each at most d eps sum_i |q_i c_i|."""
    slack = 3 * q.shape[1] * F32_EPS * _dot(jnp.abs(q), jnp.abs(centroids),
                                            "highest")
    if edge_precision != "highest":
        slack += jnp.abs(_dot(q, centroids, edge_precision) - cs)
    lo, hi = cs - slack, cs + slack
    # others that may score at least as high as c's lowest (c counts once)
    rivals = jnp.sum(hi[:, None, :] >= lo[:, :, None], axis=-1) - 1
    # others that score above c's highest in every such ranking
    beaten_by = jnp.sum(lo[:, None, :] > hi[:, :, None], axis=-1)
    return rivals < nprobe, beaten_by < nprobe


@functools.partial(jax.jit, static_argnames=("nprobe", "k", "precision",
                                             "edge_precision"))
def _search(q, centroids, vectors, assignment, *, nprobe, k, precision,
            edge_precision):
    cs = _dot(q, centroids, precision)                       # [B, Nc]
    _, probed = jax.lax.top_k(cs, nprobe)
    rank = jnp.full(cs.shape, nprobe, jnp.int32)
    rank = rank.at[jnp.arange(q.shape[0])[:, None], probed].set(
        jnp.arange(nprobe, dtype=jnp.int32)[None, :])
    sure, possible = _edge(q, centroids, cs, nprobe, edge_precision)
    ds = _dot(q, vectors, precision)                         # [B, N]
    s, i = jax.lax.top_k(
        jnp.where(rank[:, assignment] < nprobe, ds, -jnp.inf), k)
    lo, _ = jax.lax.top_k(jnp.where(sure[:, assignment], ds, -jnp.inf), k)
    hi, _ = jax.lax.top_k(jnp.where(possible[:, assignment], ds, -jnp.inf), k)
    return s, i, rank, lo, hi, sure, possible


@dataclasses.dataclass
class Answer:
    """The reference's answers to a batch of queries."""

    scores: np.ndarray       # [B, k] top-k of the exact probe
    ids: np.ndarray          # [B, k]
    rank: np.ndarray         # [B, k] probe rank of each id's cluster
    lo: np.ndarray           # [B, k] top-k of the clusters every probe takes
    hi: np.ndarray           # [B, k] top-k of those some probe may take
    sure: np.ndarray         # [B, Nc] bool
    possible: np.ndarray     # [B, Nc] bool


def search(corpus, q: np.ndarray, *, nprobe: int, k: int,
           precision: str = "highest",
           edge_precision: str = "highest") -> Answer:
    """The answers to queries ``q`` [B, d] over a ``datastore.Corpus``,
    with the probe's edge as wide as ``edge_precision`` rounds."""
    vec = jnp.asarray(corpus.page_values())
    cen = jnp.asarray(corpus.centroids)
    asg = jnp.asarray(corpus.assignment)
    parts = []
    for lo in range(0, len(q), CHUNK):
        part = np.asarray(q[lo:lo + CHUNK], np.float32)
        n = len(part)
        part = np.pad(part, ((0, CHUNK - n), (0, 0)))
        out = _search(jnp.asarray(part), cen, vec, asg, nprobe=nprobe, k=k,
                      precision=precision,
                      edge_precision=edge_precision)
        parts.append([np.asarray(x)[:n] for x in out])
    s, i, rank, lo_s, hi_s, sure, possible = (np.concatenate(x)
                                              for x in zip(*parts))
    return Answer(scores=s, ids=i,
                  rank=np.take_along_axis(rank, corpus.assignment[i], axis=1),
                  lo=lo_s, hi=hi_s, sure=sure, possible=possible)


def scores_of(corpus, q: np.ndarray, ids: np.ndarray) -> np.ndarray:
    """The exact score of each given id for its query (float64 on the
    host): what an answer says it scored must be this."""
    vals = corpus.page_values()
    safe = np.where(ids >= 0, ids, 0)
    s = np.einsum("bd,bkd->bk", q.astype(np.float64),
                  vals[safe].astype(np.float64))
    return np.where(ids >= 0, s, -np.inf)


def answer_gap(corpus, q: np.ndarray, ids: np.ndarray, scores: np.ndarray,
               ref: Answer) -> np.ndarray:
    """[B, k] how far each answer lies from one the reference allows:
    the gap between the score it says and its doc's exact score, or, rank
    by rank, outside the band between ``lo`` and ``hi``; infinite for a
    doc in a cluster that no probe within rounding takes."""
    got = scores.astype(np.float64)
    said = np.abs(got - scores_of(corpus, q, ids))
    band = np.maximum(got - ref.hi, ref.lo - got).clip(min=0.0)
    safe = np.where(ids >= 0, ids, 0)
    taken = np.take_along_axis(ref.possible, corpus.assignment[safe], axis=1)
    gap = np.where(taken & (ids >= 0), np.maximum(said, band), np.inf)
    return np.where(np.isfinite(gap), gap, np.inf)

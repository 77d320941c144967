"""A configuration file's datastore and IVF index, made from the seed.

The vectors (topic centers plus noise on the unit sphere, as
``core.synthetic_datastore`` makes them), the spherical k-means centroids
and the assignment are the benchmark's own, computed on the device; the
program is handed the paged layout it serves from
(``core.datastore.build_paged_clusters``).  The reference search in
``references/ivf.py`` reads the same vectors, centroids and assignment.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

from benchmarks.chip.weights import seed_key

HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass
class Corpus:
    """What the benchmark made: vectors, centroids, assignment."""

    vectors: np.ndarray          # [N, dim] float32, unit rows
    centroids: np.ndarray        # [Nc, dim] float32, unit rows
    assignment: np.ndarray       # [N] int32

    def page_values(self) -> np.ndarray:
        """The vectors at the page precision the configuration states."""
        return self.vectors.astype(ml_dtypes.bfloat16).astype(np.float32)


@functools.partial(jax.jit, static_argnames=("n", "dim", "topics"))
def _vectors(key, *, n: int, dim: int, topics: int, noise: float):
    kc, kt, kn = jax.random.split(key, 3)
    centers = jax.random.normal(kc, (topics, dim), jnp.float32)
    centers /= jnp.linalg.norm(centers, axis=-1, keepdims=True)
    topic = jax.random.randint(kt, (n,), 0, topics)
    emb = centers[topic] + noise * jax.random.normal(kn, (n, dim), jnp.float32)
    return emb / jnp.maximum(jnp.linalg.norm(emb, axis=-1, keepdims=True),
                             1e-9)


@jax.jit
def _assign(points, centroids):
    def body(_, p):
        s = jnp.einsum("nd,cd->nc", p, centroids, precision=HIGHEST)
        return None, jnp.argmax(s, axis=-1).astype(jnp.int32)
    n = points.shape[0]
    chunk = next(c for c in (8192, 4096, 2048, 1024, 512, 256, 128, 64, 32,
                             16, 8, 4, 2, 1) if n % c == 0)
    _, a = jax.lax.scan(body, None, points.reshape(n // chunk, chunk, -1))
    return a.reshape(n)


@jax.jit
def _update(points, centroids, assign):
    one = jax.nn.one_hot(assign, centroids.shape[0], dtype=jnp.float32)
    sums = jnp.einsum("nc,nd->cd", one, points, precision=HIGHEST)
    counts = jnp.sum(one, axis=0)[:, None]
    new = jnp.where(counts > 0, sums / jnp.maximum(counts, 1.0), centroids)
    return new / jnp.maximum(jnp.linalg.norm(new, axis=-1, keepdims=True),
                             1e-9)


def make_corpus(ds: dict, seed: int) -> Corpus:
    """Vectors, k-means centroids and assignment for (datastore, seed)."""
    key = jax.random.fold_in(seed_key(seed), 0x0DA7A)
    kv, ks = jax.random.split(key)
    pts = _vectors(kv, n=int(ds["vectors"]), dim=int(ds["dim"]),
                   topics=int(ds["topics"]), noise=float(ds["noise"]))
    n, nc = pts.shape[0], int(ds["clusters"])
    pick = jax.random.permutation(ks, n)[:int(ds["kmeans_sample"])]
    train = pts[pick]
    cent = train[:nc]
    for _ in range(int(ds["kmeans_iters"])):
        cent = _update(train, cent, _assign(train, cent))
    assign = _assign(pts, cent)
    return Corpus(vectors=np.asarray(pts), centroids=np.asarray(cent),
                  assignment=np.asarray(assign))


def program_index(corpus: Corpus, ds: dict):
    """The program's ``IVFIndex`` over the benchmark's corpus."""
    from repro.core.datastore import Datastore, build_paged_clusters
    from repro.core.ivf import IVFIndex

    paged = build_paged_clusters(Datastore(embeddings=corpus.vectors),
                                 corpus.assignment, corpus.centroids.shape[0],
                                 int(ds["page_size"]))
    return IVFIndex(centroids=corpus.centroids.copy(),
                    assignments=corpus.assignment.copy(), paged=paged)

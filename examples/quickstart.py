"""Quickstart: one RAG query through TeleRAG's lookahead retrieval.

Builds a small synthetic datastore + IVF index, then runs the paper's
§4.1 flow end to end:
  1. probe the *input* query and prefetch its clusters (async dispatch)
  2. run real LLM decode steps (reduced llama) — the generation window
     that hides the transfer
  3. rewrite -> probe -> hybrid search (device hits + host misses)
  4. merge on device and show the retrieved documents

Run: PYTHONPATH=src python examples/quickstart.py
"""

import time

import numpy as np
import jax
import jax.numpy as jnp

import repro.core as core
from repro.configs import get_arch
from repro.models import transformer as tf
from repro.serving import EngineConfig, TeleRAGEngine, sample


def main():
    print("== building datastore ==")
    store = core.synthetic_datastore(40_000, dim=128, seed=0)
    index = core.build_ivf(store, 64, page_size=64, kmeans_iters=4)
    print(f"{store.num_vectors} vectors, {index.num_clusters} clusters, "
          f"{store.nbytes()/1e6:.0f} MB host-resident")

    eng = TeleRAGEngine(index, EngineConfig(
        nprobe=16, top_k=3, buffer_pages=192, lookahead_rank=32),
        get_arch("llama3-8b"))

    # the user query (embedding) — q_in
    rng = np.random.default_rng(7)
    q_in = store.embeddings[rng.choice(store.num_vectors, 1)]
    q_in += 0.05 * rng.standard_normal(q_in.shape).astype(np.float32)
    q_in /= np.linalg.norm(q_in, axis=-1, keepdims=True)

    print("\n== 1. lookahead prefetch (async dispatch) ==")
    t0 = time.time()
    nbytes, nfetch = eng.lookahead(q_in, gen_tokens=[24])
    print(f"planned {nfetch} clusters / {nbytes/1e6:.2f} MB "
          f"(dispatch {1e3*(time.time()-t0):.1f} ms — returns immediately)")

    print("\n== 2. pre-retrieval generation overlaps the transfer ==")
    cfg = get_arch("llama3-8b").reduced()
    params = tf.init_params(cfg, jax.random.PRNGKey(0))
    cache = tf.init_cache(cfg, 1, 64)
    step = jax.jit(lambda p, c, i: tf.serve_step(p, c, i, cfg))
    tok = jnp.zeros((1,), jnp.int32)
    t0 = time.time()
    for t in range(24):
        logits, cache = step(params, cache,
                             {"token": tok,
                              "pos": jnp.asarray([t], jnp.int32)})
        tok = sample(logits)
    print(f"generated 24 tokens in {time.time()-t0:.2f}s (reduced llama)")

    print("\n== 3./4. rewrite -> hybrid retrieval -> merge ==")
    q_out = core.synthetic_rewrite(q_in, 0.04, rng)
    res = eng.retrieve(q_out)
    print(f"cluster hit rate: {res.hit_rate:.0%} "
          f"(device searched {len(res.hit_clusters[0])} clusters, "
          f"host searched {len(res.missed_clusters[0])})")
    print(f"top-3 documents: {res.doc_ids[0].tolist()} "
          f"scores {np.round(res.scores[0], 3).tolist()}")

    # verify against exhaustive search over the probed clusters
    ranked = core.probe(q_out, index, 16)[0]
    mask = np.isin(index.assignments, ranked)
    sims = store.embeddings[mask] @ q_out[0]
    ids = np.where(mask)[0]
    expect = ids[np.argsort(-sims)[:3]]
    assert set(expect.tolist()) == set(res.doc_ids[0].tolist())
    print("verified: identical to exhaustive search over probed clusters")


if __name__ == "__main__":
    main()

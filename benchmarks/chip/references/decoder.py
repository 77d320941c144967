"""Plain float32 reference of the layout ``decoder`` (``layouts/decoder.py``):
token embedding, then per layer RMSNorm, grouped-query attention with
rotary positions (rotate-half, theta from the file), RMSNorm and either
a top-k softmax mixture of gated experts (renormalized over the k
chosen, no token dropped) or a dense MLP; a final RMSNorm and the output
head.

It imports nothing of the program.  Weights are drawn from the seed by
``weights.leaf_values``, one layer at a time, and every product runs at
``HIGHEST`` precision.  ``quant="fp8"`` is the control, the precision
below the bf16 the model is served in: every weight matrix and the input
of every product rounded to float8 e4m3, the arithmetic still float32.
Weights take one scale per output channel; the inputs of a product with
a weight, and attention's queries, keys and values, one per token;
attention's probabilities one per row.  ``quant="fp8_weights"`` rounds
the weights and the inputs of their products only, leaving attention's
own products in float32: the weaker control, kept to show what the
attention rounding adds.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.layouts.decoder import Shape, leaf_specs
from benchmarks.chip.weights import leaf_key, leaf_values, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 128                 # sequences pad to a multiple of this
CONTROLS = ("fp8", "fp8_weights")

# the contraction axes of each weight: fp8 scales are per output channel
_IN_AXES = {"embed": (1,), "unembed": (0,), "layers.attn.wq": (0,),
            "layers.attn.wk": (0,), "layers.attn.wv": (0,),
            "layers.attn.wo": (0, 1), "layers.mlp.router": (0,)}


def _in_axes(name: str, s: Shape):
    if name in _IN_AXES:
        return _IN_AXES[name]
    return (1,) if s.moe else (0,)        # experts are [E, in, out]


def _fp8(x: jax.Array, axes) -> jax.Array:
    """x rounded to float8 e4m3, scaled so its largest magnitude along
    ``axes`` maps to the format's largest finite value."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True)
                        / 448.0, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _inputs(x: jax.Array, quant: bool, axes=(-1,)) -> jax.Array:
    """A matrix product's input, per token at the control's precision."""
    return _fp8(x, axes) if quant else x


def _weights(key, s: Shape, names: Sequence[str], layer: int,
             quant: Optional[str]) -> Dict[str, jax.Array]:
    specs = leaf_specs(s)
    out = {}
    for name in names:
        shape, fan_in = specs[name]
        w = leaf_values(leaf_key(key, name, layer), shape, fan_in
                        ).astype(jnp.float32)
        if fan_in is None:
            w = 1.0 + w                  # RMSNorm weight
        elif quant in CONTROLS:
            w = _fp8(w, _in_axes(name, s))
        elif quant is not None:
            raise ValueError(f"unknown control precision {quant!r}")
        out[name.rsplit(".", 1)[-1]] = w
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, Dh] at positions 0..T-1, rotate-half."""
    T, _, dh = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = jnp.arange(T, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act(name):
    return {"silu": jax.nn.silu,
            "gelu": functools.partial(jax.nn.gelu, approximate=True)}[name]


@functools.partial(jax.jit, static_argnames=("s", "quant", "attn"))
def _layer(x, w, s: Shape, quant: bool, attn: bool):
    """One decoder layer over one sequence x [T, d]; ``quant`` rounds the
    inputs of products with weights, ``attn`` attention's own."""
    T = x.shape[0]
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    a = _inputs(_rms(x, w["attn_norm"], s.eps), quant)
    q = _rope(ein("td,dhk->thk", a, w["wq"]), s.rope_theta)
    k = _rope(ein("td,dhk->thk", a, w["wk"]), s.rope_theta)
    v = ein("td,dhk->thk", a, w["wv"])
    q, k, v = (_inputs(t, attn, (1, 2)) for t in (q, k, v))
    g = s.heads // s.kv_heads
    q = q.reshape(T, s.kv_heads, g, s.head_dim)
    sc = ein("tcgk,uck->cgtu", q, k) / np.sqrt(s.head_dim)
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = _inputs(jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1),
                attn)
    o = ein("cgtu,uck->tcgk", p, v).reshape(
        T, s.heads, s.head_dim)
    x = x + ein("thk,hkd->td", _inputs(o, quant, (1, 2)), w["wo"])
    m = _inputs(_rms(x, w["mlp_norm"], s.eps), quant)
    act = _act(s.act)
    if s.moe:
        probs = jax.nn.softmax(ein("td,de->te", m, w["router"]), axis=-1)
        top_p, top_e = jax.lax.top_k(probs, s.top_k)
        top_p = top_p / jnp.sum(top_p, -1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(top_e, s.experts) * top_p[..., None],
                       axis=1)                                   # [T, E]
        h = act(ein("td,edf->tef", m, w["w_gate"])) * ein(
            "td,edf->tef", m, w["w_up"])
        y = ein("te,tef,efd->td", gate, _inputs(h, quant), w["w_down"])
    else:
        h = ein("td,df->tf", m, w["w_up"])
        h = act(ein("td,df->tf", m, w["w_gate"])) * h if s.gated else act(h)
        y = ein("tf,fd->td", _inputs(h, quant), w["w_down"])
    return x + y


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, norm, unembed, targets, eps, quant: bool):
    """Per position: the best logit, the target's logit, the argmax."""
    lg = jnp.einsum("td,dv->tv", _inputs(_rms(x, norm, eps), quant),
                    unembed, precision=HIGHEST)
    at = jnp.take_along_axis(lg, targets[:, None], axis=1)[:, 0]
    return jnp.max(lg, -1), at, jnp.argmax(lg, -1).astype(jnp.int32)


class Hidden:
    """The final hidden states of some sequences, with the head weights
    they are read out through."""

    def __init__(self, cfg: dict, seed: int, inputs: List[np.ndarray],
                 quant: Optional[str] = None):
        s = Shape.from_config(cfg)
        key = seed_key(seed)
        T = -(-max(len(t) for t in inputs) // PAD) * PAD
        toks = [np.pad(np.asarray(t, np.int32), (0, T - len(t)))
                for t in inputs]
        emb = _weights(key, s, ["embed"], 0, quant)["embed"]
        xs = [emb[jnp.asarray(t)] for t in toks]
        del emb
        names = [n for n in leaf_specs(s) if n.startswith("layers.")]
        for l in range(s.layers):
            w = _weights(key, s, names, l, quant)
            xs = [_layer(x, w, s, quant is not None, quant == "fp8")
                  for x in xs]
            del w
        self.shape, self.xs, self.lengths = s, xs, [len(t) for t in inputs]
        self.quant = quant is not None
        self.top = _weights(key, s, ["final_norm", "unembed"], 0, quant)

    def read(self, targets: List[np.ndarray]) -> List[dict]:
        """Per sequence and position: the best logit, the logit of the
        given target token, and the argmax."""
        out = []
        for x, n, tgt in zip(self.xs, self.lengths, targets):
            t = np.zeros(x.shape[0], np.int32)
            t[:n] = np.asarray(tgt, np.int32)[:n]
            best, at, arg = _head(x, self.top["final_norm"],
                                  self.top["unembed"], jnp.asarray(t),
                                  self.shape.eps, self.quant)
            out.append({"best": np.asarray(best)[:n],
                        "target": np.asarray(at)[:n],
                        "argmax": np.asarray(arg)[:n]})
        return out

    def logits(self) -> List[np.ndarray]:
        """Full logits [len, vocab] per sequence (small sizes only)."""
        return [np.asarray(jnp.einsum(
            "td,dv->tv", _inputs(_rms(x, self.top["final_norm"],
                                      self.shape.eps), self.quant),
            self.top["unembed"], precision=HIGHEST))[:n]
            for x, n in zip(self.xs, self.lengths)]

"""Plain float32 reference of the layout ``hybrid`` (``layouts/hybrid.py``):
the token embedding times the embedding multiplier, then per layer an
RMSNorm and its mixer, added to the residual stream times the residual
multiplier, then an RMSNorm and the gated SiLU MLP, added likewise; a
final RMSNorm and the tied head, logits divided by the logits scaling.

* Mamba-2 mixer: the in-projection to [z | x | B | C | dt], the causal
  depthwise conv over [x | B | C] (window ``mamba_d_conv``, with bias)
  and SiLU, ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``, then a
  sequential scan over positions: ``S <- exp(dt A) S + dt x B^T``,
  ``y = S C + D x``; the gated RMSNorm ``norm(y * silu(z))`` and the
  out-projection.
* Attention: causal grouped-query attention with no positional encoding,
  scores scaled by the attention multiplier.

It imports nothing of the program.  Weights are redrawn from the seed by
the layout's ``draw`` (``weights.leaf_values`` and the three Mamba-2
leaves it draws as Mamba-2 initializes them), one layer at a time, and
every product runs at ``HIGHEST`` precision.  ``quant="fp8"`` is the
control, the precision below the bf16 the model is served in: every
weight matrix and the input of every product rounded to float8 e4m3,
the arithmetic still float32.  Weights take one scale per output
channel (the conv weight one per channel); the inputs of a product with
a weight, attention's queries, keys and values, and the SSM's x, B, C,
dt and the state it reads out, one per token (and head); attention's
probabilities one per row.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.layouts.hybrid import Shape, draw, leaf_specs
from benchmarks.chip.weights import leaf_key, seed_key

HIGHEST = jax.lax.Precision.HIGHEST
PAD = 128                 # sequences pad to a multiple of this
CONTROLS = ("fp8",)

# the contraction axes of each weight: fp8 scales are per output channel
_IN_AXES = {"embed": (1,), "w_in": (0,), "w_out": (0,), "conv_w": (0,),
            "wq": (0,), "wk": (0,), "wv": (0,), "wo": (0, 1),
            "w_up": (0,), "w_gate": (0,), "w_down": (0,)}


def _fp8(x: jax.Array, axes) -> jax.Array:
    """x rounded to float8 e4m3, scaled so its largest magnitude along
    ``axes`` maps to the format's largest finite value."""
    scale = jnp.maximum(jnp.max(jnp.abs(x), axis=axes, keepdims=True)
                        / 448.0, 1e-30)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _inputs(x: jax.Array, quant: bool, axes=(-1,)) -> jax.Array:
    """A product's input, per token at the control's precision."""
    return _fp8(x, axes) if quant else x


def _weights(key, s: Shape, names: Sequence[str], layer: int,
             quant: Optional[str]) -> Dict[str, jax.Array]:
    specs = leaf_specs(s)
    out = {}
    for name in names:
        shape, fan_in = specs[name]
        w = draw(leaf_key(key, name, layer), name, shape, fan_in
                 ).astype(jnp.float32)
        leaf = name.rsplit(".", 1)[-1]
        if fan_in is None:
            w = 1.0 + w                  # RMSNorm weight
        elif quant in CONTROLS and leaf in _IN_AXES:
            w = _fp8(w, _IN_AXES[leaf])
        elif quant is not None and quant not in CONTROLS:
            raise ValueError(f"unknown control precision {quant!r}")
        out[leaf] = w
    return out


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _mlp(x, w, s: Shape, quant: bool):
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    m = _inputs(_rms(x, w["mlp_norm"], s.eps), quant)
    h = jax.nn.silu(ein("td,df->tf", m, w["w_gate"])) * ein(
        "td,df->tf", m, w["w_up"])
    return x + s.residual_multiplier * ein("tf,fd->td", _inputs(h, quant),
                                           w["w_down"])


@functools.partial(jax.jit, static_argnames=("s", "quant"))
def _mamba_layer(x, w, s: Shape, quant: bool):
    """One Mamba-2 layer over one sequence x [T, d]."""
    T = x.shape[0]
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    H, P, N, di = s.ssm_heads, s.ssm_head_dim, s.ssm_state, s.d_inner
    a = _inputs(_rms(x, w["norm"], s.eps), quant)
    proj = ein("td,de->te", a, w["w_in"])
    z, xbc, dt = proj[:, :di], proj[:, di:2 * di + 2 * N], proj[:, 2 * di
                                                                + 2 * N:]
    # causal depthwise conv: out[t] = sum_k w[k] xbc[t - (K - 1) + k]
    K = s.conv
    xin = _inputs(xbc, quant)
    pad = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1])), xin])
    conv = sum(pad[k:k + T] * w["conv_w"][k] for k in range(K))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs = xbc[:, :di].reshape(T, H, P)
    Bm, Cm = xbc[:, di:di + N], xbc[:, di + N:]
    dt = jax.nn.softplus(dt + w["dt_bias"])                  # [T, H]
    A = -jnp.exp(w["a_log"])

    def step(S, inp):
        xt, bt, ct, dtt = inp                                # [H,P],[N],[N],[H]
        u = _inputs(dtt[:, None] * xt, quant, (1,))          # per head
        S = jnp.exp(dtt * A)[:, None, None] * S \
            + u[:, :, None] * _inputs(bt, quant)[None, None, :]
        y = jnp.sum(_inputs(S, quant, (1, 2)) * _inputs(ct, quant)[None,
                                                                  None, :],
                    axis=-1) + w["d_skip"][:, None] * xt
        return S, y

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (xs, Bm, Cm, dt))
    y = _rms(y.reshape(T, di) * jax.nn.silu(z), w["out_norm"], s.eps)
    x = x + s.residual_multiplier * ein("te,ed->td", _inputs(y, quant),
                                        w["w_out"])
    return _mlp(x, w, s, quant)


@functools.partial(jax.jit, static_argnames=("s", "quant", "attn"))
def _attention_layer(x, w, s: Shape, quant: bool, attn: bool):
    """One attention layer over one sequence x [T, d]; ``quant`` rounds
    the inputs of products with weights, ``attn`` attention's own."""
    T = x.shape[0]
    ein = functools.partial(jnp.einsum, precision=HIGHEST)
    a = _inputs(_rms(x, w["attn_norm"], s.eps), quant)
    q = ein("td,dhk->thk", a, w["wq"])
    k = ein("td,dhk->thk", a, w["wk"])
    v = ein("td,dhk->thk", a, w["wv"])
    q, k, v = (_inputs(t, attn, (1, 2)) for t in (q, k, v))
    g = s.heads // s.kv_heads
    q = q.reshape(T, s.kv_heads, g, s.head_dim)
    sc = ein("tcgk,uck->cgtu", q, k) * s.attention_multiplier
    causal = jnp.tril(jnp.ones((T, T), bool))
    p = _inputs(jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1),
                attn)
    o = ein("cgtu,uck->tcgk", p, v).reshape(T, s.heads, s.head_dim)
    x = x + s.residual_multiplier * ein("thk,hkd->td",
                                        _inputs(o, quant, (1, 2)), w["wo"])
    return _mlp(x, w, s, quant)


@functools.partial(jax.jit, static_argnames=("quant",))
def _head(x, norm, embed, targets, eps, scaling, quant: bool):
    """Per position: the best logit, the target's logit, the argmax."""
    lg = jnp.einsum("td,vd->tv", _inputs(_rms(x, norm, eps), quant),
                    embed, precision=HIGHEST) / scaling
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
        xs = [emb[jnp.asarray(t)] * s.embedding_multiplier for t in toks]
        del emb
        names = {t: [n for n in leaf_specs(s) if n.startswith(f"layers.{t}.")]
                 for t in ("mamba", "attention")}
        for l, t in enumerate(s.layer_types):
            w = _weights(key, s, names[t], l, quant)
            if t == "mamba":
                xs = [_mamba_layer(x, w, s, quant is not None) for x in xs]
            else:
                xs = [_attention_layer(x, w, s, quant is not None,
                                       quant is not None) for x in xs]
            del w
        self.shape, self.xs, self.lengths = s, xs, [len(t) for t in inputs]
        self.quant = quant is not None
        self.top = _weights(key, s, ["final_norm", "embed"], 0, quant)

    def _args(self):
        return self.top["final_norm"], self.top["embed"]

    def read(self, targets: List[np.ndarray]) -> List[dict]:
        """Per sequence and position: the best logit, the logit of the
        given target token, and the argmax."""
        out = []
        for x, n, tgt in zip(self.xs, self.lengths, targets):
            t = np.zeros(x.shape[0], np.int32)
            t[:n] = np.asarray(tgt, np.int32)[:n]
            best, at, arg = _head(x, *self._args(), jnp.asarray(t),
                                  self.shape.eps, self.shape.logits_scaling,
                                  self.quant)
            out.append({"best": np.asarray(best)[:n],
                        "target": np.asarray(at)[:n],
                        "argmax": np.asarray(arg)[:n]})
        return out

    def logits(self) -> List[np.ndarray]:
        """Full logits [len, vocab] per sequence (small sizes only)."""
        norm, embed = self._args()
        return [np.asarray(jnp.einsum(
            "td,vd->tv", _inputs(_rms(x, norm, self.shape.eps), self.quant),
            embed, precision=HIGHEST) / self.shape.logits_scaling)[:n]
            for x, n in zip(self.xs, self.lengths)]

"""The layout ``decoder``: a grouped-query-attention decoder with rotary
positions, RMSNorm and either a dense MLP (gated or not) or a top-k
mixture of gated experts with no shared expert; an untied output head
and no muP multipliers.

A configuration file names it with ``"layout": "decoder"``.  It gives the
configuration's sizes (``Shape``) with the counts the metrics take from
them, the served model's ``ArchConfig``, and the served model's
parameter tree made from the seed (``weights.py``).
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import leaf_key, leaf_values, seed_key

ACTIVATIONS = {"silu": "silu", "gelu_pytorch_tanh": "gelu"}


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a decoder as a configuration file states them."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    act: str
    gated: bool
    eps: float
    rope_theta: float
    experts: int = 0
    top_k: int = 0

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        m = cfg["model"]
        for key, want in (("tie_word_embeddings", False),
                          ("embedding_multiplier", 1.0),
                          ("residual_multiplier", 1.0),
                          ("logits_scaling", 1.0)):
            if m.get(key, want) != want:
                raise ValueError(f"{cfg['name']}: {key}={m[key]!r} is not "
                                 f"what the served model runs")
        hd = int(m["head_dim"])
        if abs(m.get("attention_multiplier", hd ** -0.5) - hd ** -0.5) > 1e-12:
            raise ValueError(f"{cfg['name']}: attention_multiplier must be "
                             f"1/sqrt(head_dim)")
        return cls(layers=int(m["num_hidden_layers"]), d=int(m["hidden_size"]),
                   heads=int(m["num_attention_heads"]),
                   kv_heads=int(m["num_key_value_heads"]), head_dim=hd,
                   ff=int(m["intermediate_size"]), vocab=int(m["vocab_size"]),
                   act=ACTIVATIONS[m["hidden_act"]], gated=bool(m["mlp_gated"]),
                   eps=float(m["rms_norm_eps"]),
                   rope_theta=float(m["rope_theta"]),
                   experts=int(m.get("num_local_experts", 0)),
                   top_k=int(m.get("num_experts_per_tok", 0)))

    @property
    def moe(self) -> bool:
        return self.experts > 0

    # -- the counts the metrics take from the shape (``counts.py``) --------

    def layer_params(self) -> Tuple[int, int]:
        """(all, touched per token) parameters of one layer: the attention
        projections, the router and experts (top-k of them touched) or
        the dense MLP, and the two RMSNorm weights."""
        d = self.d
        attn = 2 * d * self.heads * self.head_dim \
            + 2 * d * self.kv_heads * self.head_dim
        norms = 2 * d
        if self.moe:
            expert = 3 * d * self.ff
            router = d * self.experts
            return (attn + router + self.experts * expert + norms,
                    attn + router + self.top_k * expert + norms)
        mlp = (3 if self.gated else 2) * d * self.ff
        return attn + mlp + norms, attn + mlp + norms

    def param_count(self) -> int:
        """Every parameter held: layers, embedding, output head, final
        norm."""
        return self.layers * self.layer_params()[0] + 2 * self.vocab * self.d \
            + self.d

    def matmul_params_per_token(self) -> int:
        """Parameters a decoded token multiplies by: each layer's touched
        matrices and the output head (the embedding is a lookup, the
        norms are not matrix products)."""
        return self.layers * (self.layer_params()[1] - 2 * self.d) \
            + self.d * self.vocab

    def attn_flops_per_context_token(self) -> int:
        """FLOPs a decoded token spends on each token it attends over, in
        all layers: QK^T and PV."""
        return 4 * self.heads * self.head_dim * self.layers


def leaf_specs(s: Shape) -> Dict[str, Tuple[Tuple[int, ...], Optional[int]]]:
    """name -> (per-layer shape, fan-in); fan-in None marks an RMSNorm
    weight.  Names under ``layers.`` exist once per layer."""
    d, H, KVH, Dh, F = s.d, s.heads, s.kv_heads, s.head_dim, s.ff
    out = {"embed": ((s.vocab, d), d), "final_norm": ((d,), None),
           "unembed": ((d, s.vocab), d),
           "layers.attn_norm": ((d,), None),
           "layers.attn.wq": ((d, H, Dh), d),
           "layers.attn.wk": ((d, KVH, Dh), d),
           "layers.attn.wv": ((d, KVH, Dh), d),
           "layers.attn.wo": ((H, Dh, d), H * Dh),
           "layers.mlp_norm": ((d,), None)}
    if s.moe:
        E = s.experts
        out.update({"layers.mlp.router": ((d, E), d),
                    "layers.mlp.w_up": ((E, d, F), d),
                    "layers.mlp.w_gate": ((E, d, F), d),
                    "layers.mlp.w_down": ((E, F, d), F)})
    else:
        out.update({"layers.mlp.w_up": ((d, F), d),
                    "layers.mlp.w_down": ((F, d), F)})
        if s.gated:
            out["layers.mlp.w_gate"] = ((d, F), d)
    return out


def arch_config(cfg: dict):
    """The served model's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, MoEConfig

    s = Shape.from_config(cfg)
    moe = None
    if s.moe:
        moe = MoEConfig(num_experts=s.experts, top_k=s.top_k,
                        d_ff_expert=s.ff,
                        capacity_factor=float(cfg["moe_capacity_factor"]))
    return ArchConfig(
        name=f"bench-{cfg['name']}", family="moe" if s.moe else "dense",
        source=cfg["source"], num_layers=s.layers, d_model=s.d,
        num_heads=s.heads, num_kv_heads=s.kv_heads, head_dim=s.head_dim,
        d_ff=s.ff, vocab_size=s.vocab, attn_kind="gqa", moe=moe,
        mlp_act=s.act, mlp_gated=s.gated, norm_eps=s.eps,
        rope_theta=s.rope_theta, tie_embeddings=False, dtype="bfloat16")


def _make_tree(s: Shape, key: jax.Array) -> dict:
    tree: dict = {}
    for name, (shape, fan_in) in leaf_specs(s).items():
        if name.startswith("layers."):
            keys = jnp.stack([leaf_key(key, name, l) for l in range(s.layers)])
            val = jax.vmap(lambda k: leaf_values(k, shape, fan_in))(keys)
        else:
            val = leaf_values(leaf_key(key, name), shape, fan_in)
        node = tree
        *path, last = name.split(".")
        for p in path:
            node = node.setdefault(p, {})
        node[last] = val
    return tree


def program_params(cfg: dict, seed: int, device) -> dict:
    """The served model's parameter tree, made on ``device`` in one
    jitted call from ``seed``."""
    from jax.sharding import SingleDeviceSharding

    s = Shape.from_config(cfg)
    make = jax.jit(lambda k: _make_tree(s, k),
                   out_shardings=SingleDeviceSharding(device))
    return make(seed_key(seed))


def param_shapes(cfg: dict) -> dict:
    """The parameter tree as ShapeDtypeStructs (for compiling without
    weights)."""
    s = Shape.from_config(cfg)
    return jax.eval_shape(lambda k: _make_tree(s, k), seed_key(0))

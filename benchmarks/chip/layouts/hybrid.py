"""The layout ``hybrid``: granite-4.0-h's decoder (``granitemoehybrid``
without experts).  Each layer is a Mamba-2 mixer or a grouped-query
attention layer with no positional encoding, as ``layer_types`` says,
and every mixer is followed by a gated MLP; RMSNorm before each, the
muP scalars (embedding, residual, attention, logits) and a tied head.

The Mamba-2 mixer (one group): an in-projection to [z | x | B | C | dt],
a causal depthwise conv with bias over [x | B | C] and SiLU, step sizes
``softplus(dt + dt_bias)``, decays ``exp(dt A)`` with ``A = -exp(A_log)``,
the state recurrence ``S <- exp(dt A) S + dt x B^T``, ``y = S C + D x``,
a gated RMSNorm ``norm(y * silu(z))`` and an out-projection.

A configuration file names it with ``"layout": "hybrid"``.  It gives the
configuration's sizes (``Shape``) with the counts the metrics take from
them, the served model's ``ArchConfig``, and the served model's
parameter tree made from the seed (``weights.py``); ``draw`` is the one
place a leaf's values are made, shared with ``references/hybrid.py``.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from benchmarks.chip.weights import leaf_key, leaf_values, seed_key

KINDS = ("mamba", "attention")
# what the served model runs, for every key the layout does not read
FIXED = {"model_type": "granitemoehybrid", "hidden_act": "silu",
         "normalization_function": "rmsnorm",
         "position_embedding_type": "nope", "tie_word_embeddings": True,
         "attention_bias": False, "mamba_proj_bias": False,
         "mamba_conv_bias": True, "mamba_n_groups": 1,
         "num_local_experts": 0, "num_experts_per_tok": 0}


@dataclasses.dataclass(frozen=True)
class Shape:
    """The sizes of a hybrid as a configuration file states them."""

    layers: int
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    layer_types: Tuple[str, ...]
    ssm_heads: int
    ssm_head_dim: int
    ssm_state: int
    conv: int
    embedding_multiplier: float
    residual_multiplier: float
    attention_multiplier: float
    logits_scaling: float

    @classmethod
    def from_config(cls, cfg: dict) -> "Shape":
        m = cfg["model"]
        # a published file also states its source's config at its top
        # level, key for key; the run takes ``model``, so the two agree
        for key, v in m.items():
            if key in cfg and cfg[key] != v:
                raise ValueError(f"{cfg['name']}: {key} is {v!r} in model "
                                 f"and {cfg[key]!r} at the top level")
        for key, want in FIXED.items():
            if m.get(key) != want:
                raise ValueError(f"{cfg['name']}: {key}={m.get(key)!r} is "
                                 f"not what the served model runs ({want!r})")
        d = int(m["hidden_size"])
        types = tuple(m["layer_types"])
        if len(types) != int(m["num_hidden_layers"]) or set(types) - set(KINDS):
            raise ValueError(f"{cfg['name']}: layer_types {types!r}")
        if int(m["mamba_n_heads"]) * int(m["mamba_d_head"]) != \
                int(m["mamba_expand"]) * d:
            raise ValueError(f"{cfg['name']}: mamba heads x head size is "
                             "not expand x hidden_size")
        if int(m["shared_intermediate_size"]) != int(m["intermediate_size"]):
            raise ValueError(f"{cfg['name']}: the MLP has one width")
        heads = int(m["num_attention_heads"])
        return cls(layers=len(types), d=d, heads=heads,
                   kv_heads=int(m["num_key_value_heads"]),
                   head_dim=d // heads, ff=int(m["intermediate_size"]),
                   vocab=int(m["vocab_size"]), eps=float(m["rms_norm_eps"]),
                   layer_types=types, ssm_heads=int(m["mamba_n_heads"]),
                   ssm_head_dim=int(m["mamba_d_head"]),
                   ssm_state=int(m["mamba_d_state"]),
                   conv=int(m["mamba_d_conv"]),
                   embedding_multiplier=float(m["embedding_multiplier"]),
                   residual_multiplier=float(m["residual_multiplier"]),
                   attention_multiplier=float(m["attention_multiplier"]),
                   logits_scaling=float(m["logits_scaling"]))

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.ssm_state

    @property
    def mamba_layers(self) -> int:
        return self.layer_types.count("mamba")

    @property
    def attention_layers(self) -> int:
        return self.layer_types.count("attention")

    # -- the counts the metrics take from the shape (``counts.py``) --------

    def layer_params(self, kind: str) -> Tuple[int, int]:
        """(all, matrix) parameters of one layer of ``kind``: its mixer,
        the gated MLP and the two RMSNorm weights; the matrix ones are
        those a decoded token multiplies by."""
        d, mlp = self.d, 3 * self.d * self.ff
        if kind == "attention":
            attn = 2 * d * self.heads * self.head_dim \
                + 2 * d * self.kv_heads * self.head_dim
            return attn + mlp + 2 * d, attn + mlp
        H, di, C = self.ssm_heads, self.d_inner, self.conv_channels
        proj = d * (2 * di + 2 * self.ssm_state + H) + di * d
        # conv weight and bias, A_log, dt_bias, D, the gated norm
        rest = self.conv * C + C + 3 * H + di
        return proj + rest + mlp + 2 * d, proj + mlp

    def param_count(self) -> int:
        """Every parameter held: layers, the tied embedding, final norm."""
        return sum(self.layer_params(t)[0] for t in self.layer_types) \
            + self.vocab * self.d + self.d

    def matmul_params_per_token(self) -> int:
        """Parameters a decoded token multiplies by: each layer's
        matrices and the tied output head.  The conv, the SSM recurrence
        and attention over the context are not weight products."""
        return sum(self.layer_params(t)[1] for t in self.layer_types) \
            + self.d * self.vocab

    def attn_flops_per_context_token(self) -> int:
        """FLOPs a decoded token spends on each token it attends over, in
        the attention layers: QK^T and PV."""
        return 4 * self.heads * self.head_dim * self.attention_layers


def leaf_specs(s: Shape) -> Dict[str, Tuple[Tuple[int, ...], Optional[int]]]:
    """name -> (per-layer shape, fan-in); fan-in None marks an RMSNorm
    weight.  Names under ``layers.mamba.`` and ``layers.attention.``
    exist once per layer of that kind."""
    d, H, KVH, Dh, F = s.d, s.heads, s.kv_heads, s.head_dim, s.ff
    N, Hm, C = s.ssm_state, s.ssm_heads, s.conv_channels
    di = s.d_inner
    mlp = lambda k: {f"layers.{k}.mlp_norm": ((d,), None),
                     f"layers.{k}.mlp.w_up": ((d, F), d),
                     f"layers.{k}.mlp.w_gate": ((d, F), d),
                     f"layers.{k}.mlp.w_down": ((F, d), F)}
    # the embedding's std is 1 / (multiplier sqrt(d)): the multiplied
    # embedding a token enters the residual stream with has norm about 1
    emb_fan_in = round(d * s.embedding_multiplier ** 2)
    return {"embed": ((s.vocab, d), emb_fan_in), "final_norm": ((d,), None),
            "layers.mamba.norm": ((d,), None),
            "layers.mamba.mamba.w_in": ((d, 2 * di + 2 * N + Hm), d),
            "layers.mamba.mamba.conv_w": ((s.conv, C), s.conv),
            "layers.mamba.mamba.conv_b": ((C,), s.conv),
            "layers.mamba.mamba.a_log": ((Hm,), 1),
            "layers.mamba.mamba.dt_bias": ((Hm,), 1),
            "layers.mamba.mamba.d_skip": ((Hm,), 1),
            "layers.mamba.mamba.out_norm": ((di,), None),
            "layers.mamba.mamba.w_out": ((di, d), di),
            **mlp("mamba"),
            "layers.attention.attn_norm": ((d,), None),
            "layers.attention.attn.wq": ((d, H, Dh), d),
            "layers.attention.attn.wk": ((d, KVH, Dh), d),
            "layers.attention.attn.wv": ((d, KVH, Dh), d),
            "layers.attention.attn.wo": ((H, Dh, d), H * Dh),
            **mlp("attention")}


def draw(k: jax.Array, name: str, shape, fan_in) -> jax.Array:
    """One layer's leaf as served (bf16), from its key
    ``weights.leaf_key(seed key, name, layer of the whole stack)``.
    Most leaves are ``weights.leaf_values``; three are drawn as Mamba-2
    initializes them: ``a_log`` is log U(1, 16), so A = -exp(a_log) lies
    in [-16, -1]; ``dt_bias`` is the inverse softplus of a step size
    log-uniform in [0.001, 0.1]; ``d_skip`` is 1 + 0.1 N(0, 1)."""
    leaf = name.rsplit(".", 1)[-1]
    if leaf == "a_log":
        v = jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    elif leaf == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        v = dt + jnp.log(-jnp.expm1(-dt))
    elif leaf == "d_skip":
        v = 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)
    else:
        return leaf_values(k, shape, fan_in)
    return v.astype(jnp.bfloat16)


def arch_config(cfg: dict):
    """The served model's ``ArchConfig`` for a configuration file."""
    from repro.configs.base import ArchConfig, SSMConfig

    s = Shape.from_config(cfg)
    m = cfg["model"]
    return ArchConfig(
        name=f"bench-{cfg['name']}", family="hybrid", source=cfg["source"],
        num_layers=s.layers, d_model=s.d, num_heads=s.heads,
        num_kv_heads=s.kv_heads, head_dim=s.head_dim, d_ff=s.ff,
        vocab_size=s.vocab, attn_kind="gqa", rope_fraction=0.0,
        attention_multiplier=s.attention_multiplier,
        ssm=SSMConfig(kind="mamba2", state_dim=s.ssm_state,
                      head_dim=s.ssm_head_dim, conv_width=s.conv,
                      expand=int(m["mamba_expand"]),
                      chunk_size=int(m["mamba_chunk_size"])),
        layer_types=s.layer_types, mlp_act="silu", mlp_gated=True,
        norm_eps=s.eps, tie_embeddings=True,
        embedding_multiplier=s.embedding_multiplier,
        residual_multiplier=s.residual_multiplier,
        logits_scaling=s.logits_scaling, dtype="bfloat16")


def _make_tree(s: Shape, key: jax.Array) -> dict:
    tree: dict = {}
    index = {t: [i for i, u in enumerate(s.layer_types) if u == t]
             for t in KINDS}
    for name, (shape, fan_in) in leaf_specs(s).items():
        if name.startswith("layers."):
            keys = jnp.stack([leaf_key(key, name, l)
                              for l in index[name.split(".")[1]]])
            val = jax.vmap(lambda k: draw(k, name, shape, fan_in))(keys)
        else:
            val = draw(leaf_key(key, name), name, shape, fan_in)
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

"""Mixture-of-Experts layer: GShard-style capacity dispatch via einsums.

TPU-idiomatic formulation: token→expert routing becomes two einsums with a
[groups, tokens, experts, capacity] dispatch tensor, which GSPMD shards
cleanly with experts on the ``model`` mesh axis (expert parallelism) and
groups on the ``data`` axes. Arctic's *dense residual* MLP runs in
parallel and is summed into the expert output.

Capacity semantics: each group of ``T`` tokens gets per-expert capacity
``C = ceil(T * top_k * capacity_factor / E)``; overflow tokens lose that
expert (standard GShard token dropping) but keep their other top-k picks.
"""

from __future__ import annotations

import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import Maker, activation, mlp_forward, mlp_params


def moe_params(mk: Maker, cfg: ArchConfig, prefix: str = "moe") -> dict:
    mo = cfg.moe
    d, E, F = cfg.d_model, mo.num_experts, mo.d_ff_expert
    p = {
        "router": mk(f"{prefix}.router", (d, E), ("embed", None),
                     scale=1.0 / math.sqrt(d)),
        "w_up": mk(f"{prefix}.w_up", (E, d, F), ("experts", "embed", "mlp")),
        "w_gate": mk(f"{prefix}.w_gate", (E, d, F), ("experts", "embed", "mlp")),
        "w_down": mk(f"{prefix}.w_down", (E, F, d), ("experts", "mlp", "embed")),
    }
    if mo.dense_residual_d_ff:
        p["dense"] = mlp_params(mk, d, mo.dense_residual_d_ff, gated=True,
                                prefix=f"{prefix}.dense")
    return p


def moe_forward(p: dict, x: jax.Array, cfg: ArchConfig,
                live_rows: Optional[jax.Array] = None,
                ) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar).

    Tokens are dispatched in *subgroups* of ``group_size`` tokens: per-group
    capacity is C = ceil(Tg·K·cf/E), so both the dispatch tensor
    [G, Tg, E, C] (≈ T_total·E·C_g elements) and the dispatch-einsum FLOPs
    (ratio Tg·cf/(3·ff) of the expert FLOPs) are bounded by the group
    size, independent of sequence length. This keeps high-top-k/small-ff
    configs (granite-moe: K=8 of E=40, ff=512) from blowing up, where
    sequence-sized GShard groups would need C≈T/3.

    ``live_rows`` (traced int scalar) marks rows ``>= live_rows`` as
    padding: they take no expert capacity, and capacity is that of the
    live tokens alone, so the live rows' output equals ``moe_forward`` on
    ``x[:live_rows]``.  Padding must fit one dispatch group.
    """
    mo = cfg.moe
    B, S, d = x.shape
    E, K = mo.num_experts, mo.top_k
    T = B * S
    Tg = min(mo.group_size, T)
    while T % Tg:
        Tg -= 1
    G = T // Tg
    def capacity(t: int) -> int:
        return min(max(1, math.ceil(t * K * mo.capacity_factor / E)), t)

    C = capacity(Tg)

    xg = x.reshape(G, Tg, d)
    logits = jnp.einsum("gtd,de->gte", xg, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                      # [G,T,E]

    # top-k selection, renormalized over the selected experts
    top_p, top_e = jax.lax.top_k(probs, K)                        # [G,T,K]
    top_p = top_p / jnp.maximum(jnp.sum(top_p, -1, keepdims=True), 1e-9)
    sel = jax.nn.one_hot(top_e, E, dtype=jnp.float32)             # [G,T,K,E]
    gate = jnp.einsum("gtk,gtke->gte", top_p, sel)                # [G,T,E]
    sel_any = jnp.max(sel, axis=2)                                # [G,T,E] 0/1
    cap = C
    if live_rows is not None:
        if G != 1:
            raise ValueError(f"live_rows needs one dispatch group; {T} "
                             f"tokens exceed group_size {mo.group_size}")
        live = jnp.arange(B) < live_rows                          # [B]
        sel_any = sel_any * jnp.repeat(live, S)[None, :, None]
        caps = jnp.asarray([capacity(t) for t in range(T + 1)], jnp.int32)
        cap = caps[jnp.clip(live_rows, 0, B) * S]

    # position of each token within each expert's capacity buffer
    pos_in_e = jnp.cumsum(sel_any, axis=1) - sel_any              # [G,T,E]
    keep = sel_any * (pos_in_e < cap)
    onehot_c = jax.nn.one_hot(pos_in_e.astype(jnp.int32), C,
                              dtype=jnp.float32)                  # [G,T,E,C]
    dispatch = (keep[..., None] * onehot_c).astype(x.dtype)
    combine = (gate[..., None] * onehot_c * keep[..., None]).astype(x.dtype)

    xin = jnp.einsum("gtec,gtd->gecd", dispatch, xg)
    up = jnp.einsum("gecd,edf->gecf", xin, p["w_up"])
    gt = jnp.einsum("gecd,edf->gecf", xin, p["w_gate"])
    h = activation(cfg.mlp_act)(gt) * up
    out_e = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    out = jnp.einsum("gtec,gecd->gtd", combine, out_e).reshape(B, S, d)

    # GShard load-balancing loss
    frac_tokens = jnp.mean(sel_any, axis=(0, 1))                  # [E]
    frac_probs = jnp.mean(probs, axis=(0, 1))                     # [E]
    aux = E * jnp.sum(frac_tokens * frac_probs) * mo.aux_loss_weight

    if mo.dense_residual_d_ff:
        out = out + mlp_forward(p["dense"], x, cfg.mlp_act, gated=True)
    return out, aux

"""Generic decoder assembler: one code path drives all eleven archs.

Layers are *stacked* along a leading ``layers`` dim and driven by
``jax.lax.scan`` so the HLO stays O(1) in depth (compile-time critical for
the 80-cell dry-run sweep). Per-layer heterogeneity (gemma2's local/global
alternation) is expressed as scanned per-layer scalars, not Python
branches. Zamba2's shared attention block lives outside the scan and is
applied between groups with per-group LoRA deltas.

Entry points (all pure functions of pytrees — pjit-ready):
  loss_fn(params, batch)                -> (loss, metrics)
  prefill(params, inputs)               -> (last_logits, cache)
  serve_step(params, cache, inputs)     -> (logits, new_cache)
Param/axes/shape trees are built through the same builders (see
``layers.Maker``) so sharding specs always match the param structure.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as attn_mod
from repro.models import mamba2 as mamba_mod
from repro.models import mla as mla_mod
from repro.models import moe as moe_mod
from repro.models import rwkv6 as rwkv_mod
from repro.models.layers import (AxesMaker, InitMaker, Maker, apply_rope,
                                 cross_entropy_loss, mlp_forward, mlp_params,
                                 rms_norm, softcap)

Params = Dict[str, Any]


def family_kind(cfg: ArchConfig) -> str:
    if cfg.layer_types is not None:
        return "hybrid"
    if cfg.ssm is not None and cfg.ssm.kind == "rwkv6":
        return "rwkv6"
    if cfg.shared_attn_every:
        return "zamba2"
    return "attn"


# ---------------------------------------------------------------------------
# Parameter construction
# ---------------------------------------------------------------------------


def _layer_builder(cfg: ArchConfig):
    kind = family_kind(cfg)

    def build(mk: Maker) -> Params:
        d = cfg.d_model
        if kind == "rwkv6":
            return {
                "tm_norm": mk("tm_norm", (d,), ("embed",)),
                "tm": rwkv_mod.rwkv6_params(mk, cfg),
                "cm_norm": mk("cm_norm", (d,), ("embed",)),
            }
        if kind == "zamba2":
            return {
                "norm": mk("norm", (d,), ("embed",)),
                "mamba": mamba_mod.mamba2_params(mk, cfg),
            }
        p: Params = {"attn_norm": mk("attn_norm", (d,), ("embed",))}
        if cfg.attn_kind == "mla":
            p["attn"] = mla_mod.mla_params(mk, cfg)
        else:
            p["attn"] = attn_mod.attn_params(mk, cfg)
        p["mlp_norm"] = mk("mlp_norm", (d,), ("embed",))
        if cfg.moe is not None:
            p["mlp"] = moe_mod.moe_params(mk, cfg)
        else:
            p["mlp"] = mlp_params(mk, d, cfg.d_ff, cfg.mlp_gated)
        return p

    return build


def _hybrid_builders(cfg: ArchConfig) -> Dict[str, Any]:
    """A ``layer_types`` hybrid's layer of each mixer kind: the mixer,
    then the MLP, each after its RMSNorm."""
    d = cfg.d_model
    unknown = set(cfg.layer_types) - {"mamba", "attention"}
    if unknown:
        raise ValueError(f"unknown layer types {sorted(unknown)}")

    def tail(mk: Maker) -> Params:
        return {"mlp_norm": mk("mlp_norm", (d,), ("embed",)),
                "mlp": mlp_params(mk, d, cfg.d_ff, cfg.mlp_gated)}

    return {
        "mamba": lambda mk: {"norm": mk("norm", (d,), ("embed",)),
                             "mamba": mamba_mod.mamba2_params(mk, cfg),
                             **tail(mk)},
        "attention": lambda mk: {"attn_norm": mk("attn_norm", (d,), ("embed",)),
                                 "attn": attn_mod.attn_params(mk, cfg),
                                 **tail(mk)},
    }


def layer_runs(cfg: ArchConfig) -> Tuple[Tuple[str, int, int], ...]:
    """A hybrid's layers as runs of one mixer kind, in order: (kind,
    index of the run's first layer in that kind's stack, length)."""
    runs: list = []
    seen = {"mamba": 0, "attention": 0}
    for t in cfg.layer_types:
        if runs and runs[-1][0] == t:
            runs[-1][2] += 1
        else:
            runs.append([t, seen[t], 1])
        seen[t] += 1
    return tuple(tuple(r) for r in runs)


def _shared_block_builder(cfg: ArchConfig):
    """Zamba2 shared attention(+MLP) block and per-group LoRA deltas."""

    def build_shared(mk: Maker) -> Params:
        d = cfg.d_model
        return {
            "attn_norm": mk("shared.attn_norm", (d,), ("embed",)),
            "attn": attn_mod.attn_params(mk, cfg, prefix="shared.attn"),
            "mlp_norm": mk("shared.mlp_norm", (d,), ("embed",)),
            "mlp": mlp_params(mk, d, cfg.d_ff, cfg.mlp_gated, prefix="shared.mlp"),
        }

    def build_lora(mk: Maker) -> Params:
        d, r = cfg.d_model, cfg.shared_attn_lora_rank
        H, KVH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
        return {
            "qa": mk("lora.qa", (d, r), ("embed", None)),
            "qb": mk("lora.qb", (r, H * Dh), (None, "heads_flat"), scale=0.01),
            "va": mk("lora.va", (d, r), ("embed", None)),
            "vb": mk("lora.vb", (r, KVH * Dh), (None, "heads_flat"), scale=0.01),
        }

    return build_shared, build_lora


def _top_builder(cfg: ArchConfig):
    def build(mk: Maker) -> Params:
        d, V = cfg.d_model, cfg.vocab_size
        p: Params = {"final_norm": mk("final_norm", (d,), ("embed",))}
        if cfg.frontend is not None and cfg.frontend.kind == "encodec_stub":
            nc = cfg.frontend.num_codebooks
            p["embed"] = mk("embed", (nc, V, d), (None, "vocab", "embed"), scale=0.02)
            p["unembed"] = mk("unembed", (nc, d, V), (None, "embed", "vocab"))
        else:
            p["embed"] = mk("embed", (V, d), ("vocab", "embed"), scale=0.02)
            if not cfg.tie_embeddings:
                p["unembed"] = mk("unembed", (d, V), ("embed", "vocab"))
        if cfg.frontend is not None and cfg.frontend.kind == "vit_stub":
            p["vit_proj"] = mk("vit_proj", (cfg.frontend.embed_dim, d),
                               (None, "embed"))
        return p

    return build


def zamba2_groups(cfg: ArchConfig) -> Tuple[int, int]:
    per = cfg.shared_attn_every
    assert cfg.num_layers % per == 0, "zamba2 layers must divide group size"
    return cfg.num_layers // per, per


def init_params(cfg: ArchConfig, key: jax.Array,
                dtype=jnp.bfloat16) -> Params:
    """Materialize random-init params (use under jax.eval_shape for AOT)."""
    layer_build = _layer_builder(cfg)
    kind = family_kind(cfg)
    mk = lambda k: InitMaker(k, dtype=dtype)
    top = _top_builder(cfg)(mk(jax.random.fold_in(key, 0)))

    if kind == "hybrid":
        layers = {}
        for j, (t, build) in enumerate(_hybrid_builders(cfg).items()):
            keys = jax.random.split(jax.random.fold_in(key, 4 + j),
                                    len(cfg.layers_of(t)))
            layers[t] = jax.vmap(lambda k, b=build: b(mk(k)))(keys)
    elif kind == "zamba2":
        G, per = zamba2_groups(cfg)
        keys = jax.random.split(jax.random.fold_in(key, 1), G * per)
        layers = jax.vmap(lambda k: layer_build(mk(k)))(keys)
        layers = jax.tree.map(lambda a: a.reshape((G, per) + a.shape[1:]), layers)
        build_shared, build_lora = _shared_block_builder(cfg)
        top["shared"] = build_shared(mk(jax.random.fold_in(key, 2)))
        lkeys = jax.random.split(jax.random.fold_in(key, 3), G)
        top["lora"] = jax.vmap(lambda k: build_lora(mk(k)))(lkeys)
    else:
        keys = jax.random.split(jax.random.fold_in(key, 1), cfg.num_layers)
        layers = jax.vmap(lambda k: layer_build(mk(k)))(keys)
    top["layers"] = layers
    return top


def param_axes(cfg: ArchConfig) -> Params:
    """Logical-axis tree structurally matching ``init_params`` output."""
    mk = AxesMaker()
    kind = family_kind(cfg)
    top = _top_builder(cfg)(mk)
    stacked = lambda tree: jax.tree.map(lambda ax: ("layers",) + ax, tree,
                                        is_leaf=lambda x: isinstance(x, tuple))
    if kind == "hybrid":
        top["layers"] = {t: stacked(build(mk))
                         for t, build in _hybrid_builders(cfg).items()}
        return top
    layer_axes = _layer_builder(cfg)(mk)
    if kind == "zamba2":
        layer_axes = jax.tree.map(lambda ax: ("layers", "layers") + ax, layer_axes,
                                  is_leaf=lambda x: isinstance(x, tuple))
        build_shared, build_lora = _shared_block_builder(cfg)
        top["shared"] = build_shared(mk)
        top["lora"] = stacked(build_lora(mk))
    else:
        layer_axes = stacked(layer_axes)
    top["layers"] = layer_axes
    return top


# ---------------------------------------------------------------------------
# Per-layer static metadata (scanned alongside params)
# ---------------------------------------------------------------------------


def layer_windows(cfg: ArchConfig) -> jnp.ndarray:
    """Per-layer attention window (0 = global). gemma2 alternates local/global."""
    L = cfg.num_layers
    if cfg.local_global_pattern and cfg.sliding_window:
        w = [(cfg.sliding_window if i % 2 == 0 else 0) for i in range(L)]
    elif cfg.sliding_window:
        w = [cfg.sliding_window] * L
    else:
        w = [0] * L
    return jnp.asarray(w, jnp.int32)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------


def embed_tokens(params: Params, tokens: jax.Array, cfg: ArchConfig) -> jax.Array:
    if cfg.frontend is not None and cfg.frontend.kind == "encodec_stub":
        # tokens: [..., num_codebooks]; sum codebook embeddings
        nc = cfg.frontend.num_codebooks
        embs = [jnp.take(params["embed"][c], tokens[..., c], axis=0)
                for c in range(nc)]
        x = functools.reduce(jnp.add, embs)
    else:
        x = jnp.take(params["embed"], tokens, axis=0)
    if cfg.embed_scale_sqrt_d:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def unembed(params: Params, x: jax.Array, cfg: ArchConfig) -> jax.Array:
    """x: [..., d] -> logits [..., V] (or [..., nc, V] for audio)."""
    if cfg.frontend is not None and cfg.frontend.kind == "encodec_stub":
        logits = jnp.einsum("...d,cdv->...cv", x, params["unembed"])
    elif cfg.tie_embeddings:
        logits = jnp.einsum("...d,vd->...v", x, params["embed"])
    else:
        logits = jnp.einsum("...d,dv->...v", x, params["unembed"])
    if cfg.logits_scaling != 1.0:
        logits = logits / jnp.asarray(cfg.logits_scaling, logits.dtype)
    return softcap(logits, cfg.final_logit_softcap)


def _residual(h: jax.Array, y: jax.Array, cfg: ArchConfig) -> jax.Array:
    """h plus a block's output, scaled by the muP residual multiplier."""
    if cfg.residual_multiplier != 1.0:
        y = y * jnp.asarray(cfg.residual_multiplier, y.dtype)
    return h + y


def _mlp_residual(lp: Params, h: jax.Array, cfg: ArchConfig,
                  live_rows=None) -> jax.Array:
    """The MLP (or MoE) block after its RMSNorm, added to h."""
    m_in = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
    if cfg.moe is not None:
        m_out, _ = moe_mod.moe_forward(lp["mlp"], m_in, cfg,
                                       live_rows=live_rows)
    else:
        m_out = mlp_forward(lp["mlp"], m_in, cfg.mlp_act, cfg.mlp_gated)
    return _residual(h, m_out, cfg)


# ---------------------------------------------------------------------------
# Full-sequence forward (train / prefill)
# ---------------------------------------------------------------------------


def _grouped(tree, g: int):
    """Reshape stacked layer params [L, ...] -> [L//g, g, ...]."""
    return jax.tree.map(lambda a: a.reshape((a.shape[0] // g, g) + a.shape[1:]),
                        tree)


def _best_group(L: int, target: int) -> int:
    g = min(target, L)
    while L % g:
        g -= 1
    return g


def forward(params: Params, tokens: jax.Array, cfg: ArchConfig, *,
            image_embeds: Optional[jax.Array] = None,
            attn_chunk: int = 1024,
            remat: bool = False,
            remat_group: int = 4,
            act_spec=None,
            want_cache: bool = False,
            ) -> Tuple[jax.Array, jax.Array, Optional[Params]]:
    """Returns (hidden [B,S,d] after final norm, aux_loss, cache|None).

    remat=True uses *grouped* rematerialization: layers are scanned in
    groups of ``remat_group`` with jax.checkpoint at group boundaries, so
    saved residuals are L/g activations instead of per-layer scan
    residuals. ``act_spec`` (a PartitionSpec) additionally shards the
    saved residual stream — Megatron-style activation TP over d_model —
    which divides saved-activation HBM by the model-axis size.
    """
    kind = family_kind(cfg)

    def constrain(h):
        if act_spec is not None:
            return jax.lax.with_sharding_constraint(h, act_spec)
        return h

    x = embed_tokens(params, tokens, cfg)
    if image_embeds is not None:
        prefix = jnp.einsum("bpe,ed->bpd", image_embeds.astype(x.dtype),
                            params["vit_proj"])
        x = jnp.concatenate([prefix, x], axis=1)
    x = constrain(x)
    B, S, _ = x.shape
    positions = jnp.arange(S, dtype=jnp.int32)

    if kind == "attn":
        windows = layer_windows(cfg)

        def body(carry, xs):
            h, aux = carry
            lp, win = xs
            a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                a_out, kv = mla_mod.mla_forward(lp["attn"], a_in, cfg,
                                                positions=positions,
                                                attn_chunk=attn_chunk)
            else:
                a_out, kv = attn_mod.attn_forward(lp["attn"], a_in, cfg,
                                                  positions=positions, window=win,
                                                  attn_chunk=attn_chunk)
            h = h + a_out
            m_in = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if cfg.moe is not None:
                m_out, a = moe_mod.moe_forward(lp["mlp"], m_in, cfg)
                aux = aux + a
            else:
                m_out = mlp_forward(lp["mlp"], m_in, cfg.mlp_act, cfg.mlp_gated)
            h = constrain(h + m_out)
            return (h, aux), kv if want_cache else None

        carry0 = (x, jnp.zeros((), jnp.float32))
        if remat and not want_cache:
            g = _best_group(cfg.num_layers, remat_group)

            def group_body(carry, xs):
                glp, gwin = xs
                return jax.lax.scan(body, carry, (glp, gwin))

            (x, aux), kvs = jax.lax.scan(
                jax.checkpoint(group_body), carry0,
                (_grouped(params["layers"], g), windows.reshape(-1, g)))
        else:
            f = jax.checkpoint(body) if remat else body
            (x, aux), kvs = jax.lax.scan(f, carry0,
                                         (params["layers"], windows))
        cache = None
        if want_cache:
            if cfg.attn_kind == "mla":
                cache = {"ckv": kvs[0], "kpe": kvs[1]}
            else:
                cache = {"k": kvs[0], "v": kvs[1]}
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, aux, cache

    if kind == "hybrid":
        d_in, Hm, P, N = mamba_mod.mamba2_dims(cfg)
        cw = cfg.ssm.conv_width

        def mamba_body(h, lp):
            m_in = rms_norm(h, lp["norm"], cfg.norm_eps)
            y, co, so = mamba_mod.mamba2_forward(
                lp["mamba"], m_in, cfg,
                conv_in=jnp.zeros((B, cw - 1, d_in + 2 * N), h.dtype),
                state_in=jnp.zeros((B, Hm, P, N), jnp.float32))
            h = constrain(_mlp_residual(lp, _residual(h, y, cfg), cfg))
            return h, (co, so) if want_cache else None

        def attn_body(h, lp):
            a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            a_out, kv = attn_mod.attn_forward(lp["attn"], a_in, cfg,
                                              positions=positions, window=0,
                                              attn_chunk=attn_chunk)
            h = constrain(_mlp_residual(lp, _residual(h, a_out, cfg), cfg))
            return h, kv if want_cache else None

        bodies = {"mamba": mamba_body, "attention": attn_body}
        states: Dict[str, list] = {"mamba": [], "attention": []}
        for t, i0, n in layer_runs(cfg):
            f = jax.checkpoint(bodies[t]) if remat else bodies[t]
            x, ys = jax.lax.scan(f, x, jax.tree.map(
                lambda a: a[i0:i0 + n], params["layers"][t]))
            states[t].append(ys)
        cache = None
        if want_cache:
            cat = lambda ys: jax.tree.map(lambda *a: jnp.concatenate(a),
                                          *ys)
            (k, v), (conv, ssm) = cat(states["attention"]), cat(states["mamba"])
            cache = {"k": k, "v": v, "conv": conv, "ssm": ssm}
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros((), jnp.float32), cache

    if kind == "rwkv6":
        K = cfg.ssm.head_dim
        H = cfg.d_model // K

        def body(h, lp):
            s1 = jnp.zeros((B, cfg.d_model), h.dtype)
            st = jnp.zeros((B, H, K, K), jnp.float32)
            tm_in = rms_norm(h, lp["tm_norm"], cfg.norm_eps)
            y, s1o, sto = rwkv_mod.rwkv6_time_mix(lp["tm"], tm_in, cfg,
                                                  shift_in=s1, state_in=st)
            h = h + y
            cm_in = rms_norm(h, lp["cm_norm"], cfg.norm_eps)
            y2, s2o = rwkv_mod.rwkv6_channel_mix(lp["tm"], cm_in,
                                                 jnp.zeros((B, cfg.d_model), h.dtype))
            h = constrain(h + y2)
            return h, (s1o, sto, s2o) if want_cache else None

        if remat and not want_cache:
            g = _best_group(cfg.num_layers, remat_group)

            def group_body(h, glp):
                return jax.lax.scan(body, h, glp)

            x, states = jax.lax.scan(jax.checkpoint(group_body), x,
                                     _grouped(params["layers"], g))
        else:
            f = jax.checkpoint(body) if remat else body
            x, states = jax.lax.scan(f, x, params["layers"])
        cache = None
        if want_cache:
            cache = {"shift1": states[0], "wkv": states[1], "shift2": states[2]}
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        return x, jnp.zeros((), jnp.float32), cache

    # ---- zamba2 hybrid -----------------------------------------------------
    G, per = zamba2_groups(cfg)
    d_in, Hm, P, N = mamba_mod.mamba2_dims(cfg)
    cw = cfg.ssm.conv_width
    shared = params["shared"]

    def shared_apply(h, lora):
        dd, HH, DD = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
        KVH = cfg.num_kv_heads
        ap = dict(shared["attn"])
        ap["wq"] = ap["wq"] + jnp.einsum("dr,re->de", lora["qa"],
                                         lora["qb"]).reshape(dd, HH, DD)
        ap["wv"] = ap["wv"] + jnp.einsum("dr,re->de", lora["va"],
                                         lora["vb"]).reshape(dd, KVH, DD)
        a_in = rms_norm(h, shared["attn_norm"], cfg.norm_eps)
        a_out, kv = attn_mod.attn_forward(ap, a_in, cfg, positions=positions,
                                          window=0, attn_chunk=attn_chunk)
        h = h + a_out
        m_in = rms_norm(h, shared["mlp_norm"], cfg.norm_eps)
        h = h + mlp_forward(shared["mlp"], m_in, cfg.mlp_act, cfg.mlp_gated)
        return h, kv

    def group_body(carry, xs):
        h = carry
        glp, lora = xs
        h, kv = shared_apply(h, lora)

        def mamba_body(hh, lp):
            m_in = rms_norm(hh, lp["norm"], cfg.norm_eps)
            ci = jnp.zeros((B, cw - 1, d_in + 2 * N), hh.dtype)
            si = jnp.zeros((B, Hm, P, N), jnp.float32)
            y, co, so = mamba_mod.mamba2_forward(lp["mamba"], m_in, cfg,
                                                 conv_in=ci, state_in=si)
            return hh + y, (co, so) if want_cache else None

        h, mstates = jax.lax.scan(mamba_body, h, glp)
        return constrain(h), (kv, mstates) if want_cache else None

    f = jax.checkpoint(group_body) if remat else group_body
    x, ys = jax.lax.scan(f, x, (params["layers"], params["lora"]))
    cache = None
    if want_cache:
        (k, v), (conv, ssd) = ys
        cache = {"shared_k": k, "shared_v": v, "conv": conv, "ssd": ssd}
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, jnp.zeros((), jnp.float32), cache


# ---------------------------------------------------------------------------
# Loss (sequence-chunked to avoid materializing [B,S,V] logits)
# ---------------------------------------------------------------------------


def loss_fn(params: Params, batch: Dict[str, jax.Array], cfg: ArchConfig, *,
            attn_chunk: int = 1024, remat: bool = True,
            remat_group: int = 4, act_spec=None,
            loss_chunk: int = 512) -> Tuple[jax.Array, Dict[str, jax.Array]]:
    tokens = batch["tokens"]
    labels = batch["labels"]
    mask = batch.get("loss_mask")
    x, aux, _ = forward(params, tokens, cfg,
                        image_embeds=batch.get("image_embeds"),
                        attn_chunk=attn_chunk, remat=remat,
                        remat_group=remat_group, act_spec=act_spec)
    if batch.get("image_embeds") is not None:
        x = x[:, batch["image_embeds"].shape[1]:, :]   # loss on text positions

    B, S = x.shape[0], x.shape[1]
    nch = max(S // loss_chunk, 1)
    while S % nch:            # largest divisor <= S//loss_chunk, so the
        nch -= 1              # [B, S/nch, V] logits chunk stays bounded
    xs = jnp.moveaxis(x.reshape(B, nch, S // nch, -1), 1, 0)
    ls = jnp.moveaxis(labels.reshape((B, nch, S // nch) + labels.shape[2:]), 1, 0)
    ms = (jnp.moveaxis(mask.reshape(B, nch, S // nch), 1, 0)
          if mask is not None else None)

    def chunk_loss(carry, xs_):
        if ms is None:
            xc, lc = xs_
            mc = jnp.ones(lc.shape[:2], jnp.float32)
        else:
            xc, lc, mc = xs_
        logits = unembed(params, xc, cfg).astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lc[..., None], axis=-1)[..., 0]
        nll = logz - gold
        if nll.ndim == 3:          # audio: extra codebook dim
            nll = jnp.mean(nll, axis=-1)
        tot, cnt = carry
        return (tot + jnp.sum(nll * mc), cnt + jnp.sum(mc)), None

    args = (xs, ls) if ms is None else (xs, ls, ms)
    # checkpoint: backward recomputes each chunk's [B,chunk,V] logits
    # instead of saving them per scan step (the dominant train-memory term)
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(chunk_loss),
                                 (jnp.zeros(()), jnp.zeros(())), args)
    loss = tot / jnp.maximum(cnt, 1.0) + aux
    return loss, {"ce": tot / jnp.maximum(cnt, 1.0), "aux": aux,
                  "tokens": cnt}


# ---------------------------------------------------------------------------
# KV / state cache
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, maker=jnp.zeros,
               kv_quant: bool = False) -> Params:
    """kv_quant=True stores attention K/V int8 with per-(token, head)
    bf16 scales — halves the decode memory-roofline term (§Perf). The
    gemma2 split cache quantizes the full-length global layers; the
    window-sized local rings stay bf16 (negligible size)."""
    kind = family_kind(cfg)
    L, B, S = cfg.num_layers, batch, max_len
    if kind == "attn":
        if cfg.attn_kind == "mla":
            m = cfg.mla
            return {"ckv": maker((L, B, S, m.kv_lora_rank), dtype),
                    "kpe": maker((L, B, S, m.qk_rope_head_dim), dtype)}
        KVH, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
        kv_dt = jnp.int8 if kv_quant else dtype
        if cfg.local_global_pattern and cfg.sliding_window:
            # split cache: local layers need only `window` ring slots
            assert L % 2 == 0, "local/global alternation expects even L"
            W = min(cfg.sliding_window, max_len)
            Lp = L // 2
            out = {"k_local": maker((Lp, B, W, KVH, Dh), dtype),
                   "v_local": maker((Lp, B, W, KVH, Dh), dtype),
                   "k_global": maker((Lp, B, S, KVH, Dh), kv_dt),
                   "v_global": maker((Lp, B, S, KVH, Dh), kv_dt)}
            if kv_quant:
                out["k_global_scale"] = maker((Lp, B, S, KVH), jnp.bfloat16)
                out["v_global_scale"] = maker((Lp, B, S, KVH), jnp.bfloat16)
            return out
        out = {"k": maker((L, B, S, KVH, Dh), kv_dt),
               "v": maker((L, B, S, KVH, Dh), kv_dt)}
        if kv_quant:
            out["k_scale"] = maker((L, B, S, KVH), jnp.bfloat16)
            out["v_scale"] = maker((L, B, S, KVH), jnp.bfloat16)
        return out
    if kind == "hybrid":
        d_in, Hm, P, N = mamba_mod.mamba2_dims(cfg)
        La, Lm = len(cfg.layers_of("attention")), len(cfg.layers_of("mamba"))
        KVH, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
        return {"k": maker((La, B, S, KVH, Dh), dtype),
                "v": maker((La, B, S, KVH, Dh), dtype),
                "conv": maker((Lm, B, cfg.ssm.conv_width - 1, d_in + 2 * N),
                              dtype),
                "ssm": maker((Lm, B, Hm, P, N), jnp.float32)}
    if kind == "rwkv6":
        K = cfg.ssm.head_dim
        H = cfg.d_model // K
        return {"shift1": maker((L, B, cfg.d_model), dtype),
                "wkv": maker((L, B, H, K, K), jnp.float32),
                "shift2": maker((L, B, cfg.d_model), dtype)}
    G, per = zamba2_groups(cfg)
    d_in, Hm, P, N = mamba_mod.mamba2_dims(cfg)
    cw = cfg.ssm.conv_width
    KVH, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    return {"shared_k": maker((G, B, S, KVH, Dh), dtype),
            "shared_v": maker((G, B, S, KVH, Dh), dtype),
            "conv": maker((G, per, B, cw - 1, d_in + 2 * N), dtype),
            "ssd": maker((G, per, B, Hm, P, N), jnp.float32)}


def cache_axes(cfg: ArchConfig, kv_quant: bool = False) -> Params:
    """Logical axes for cache leaves (mirrors init_cache structure)."""
    kind = family_kind(cfg)
    if kind == "attn":
        if cfg.attn_kind == "mla":
            return {"ckv": ("layers", "batch", "kv_seq", None),
                    "kpe": ("layers", "batch", "kv_seq", None)}
        kv = ("layers", "batch", "kv_seq", "kv_heads", None)
        sc = ("layers", "batch", "kv_seq", "kv_heads")
        if cfg.local_global_pattern and cfg.sliding_window:
            out = {"k_local": kv, "v_local": kv,
                   "k_global": kv, "v_global": kv}
            if kv_quant:
                out["k_global_scale"] = sc
                out["v_global_scale"] = sc
            return out
        out = {"k": kv, "v": kv}
        if kv_quant:
            out["k_scale"] = sc
            out["v_scale"] = sc
        return out
    if kind == "hybrid":
        kv = ("layers", "batch", "kv_seq", "kv_heads", None)
        return {"k": kv, "v": kv,
                "conv": ("layers", "batch", None, "heads_flat"),
                "ssm": ("layers", "batch", "heads_flat", None, None)}
    if kind == "rwkv6":
        return {"shift1": ("layers", "batch", "embed"),
                "wkv": ("layers", "batch", "heads_flat", None, None),
                "shift2": ("layers", "batch", "embed")}
    return {"shared_k": ("layers", "batch", "kv_seq", "kv_heads", None),
            "shared_v": ("layers", "batch", "kv_seq", "kv_heads", None),
            "conv": ("layers", "layers", "batch", None, "heads_flat"),
            "ssd": ("layers", "layers", "batch", "heads_flat", None, None)}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------


def prefill(params: Params, inputs: Dict[str, jax.Array], cfg: ArchConfig, *,
            attn_chunk: int = 1024) -> Tuple[jax.Array, Params]:
    """Full-prompt forward; returns (last-token logits, cache at prompt len)."""
    x, _, cache = forward(params, inputs["tokens"], cfg,
                          image_embeds=inputs.get("image_embeds"),
                          attn_chunk=attn_chunk, want_cache=True)
    if (family_kind(cfg) == "attn" and cfg.local_global_pattern
            and cfg.sliding_window):
        # split handoff: even layers are local (ring of W slots)
        W = cfg.sliding_window
        cache = {
            "k_local": attn_mod.ring_from_full(cache["k"][0::2], W),
            "v_local": attn_mod.ring_from_full(cache["v"][0::2], W),
            "k_global": cache["k"][1::2],
            "v_global": cache["v"][1::2],
        }
    logits = unembed(params, x[:, -1, :], cfg)
    return logits, cache


# ---------------------------------------------------------------------------
# Decode (serve_step)
# ---------------------------------------------------------------------------


def serve_step(params: Params, cache: Params, inputs: Dict[str, jax.Array],
               cfg: ArchConfig, *, attn_chunk: int = 0,
               seq_axis: Optional[str] = None,
               kv_quant: bool = False,
               ) -> Tuple[jax.Array, Params]:
    """One decode step for the whole batch.

    inputs: token [B] (audio: [B, nc]), pos [B] — per-sequence positions
    (continuous batching). attn_chunk=0 => single-pass attention over the
    cache (best for sharded KV; chunking matters only for prefill).
    seq_axis: mesh axis the KV cache's seq dim is sharded over (long-
    context sequence-parallel decode); threads sharding constraints into
    the attention so scores stay KV-local with small psum reductions.
    """
    import jax.sharding as jsh
    kind = family_kind(cfg)
    if kind == "hybrid":
        raise ValueError(f"{cfg.name}: a layer_types hybrid decodes on the "
                         "paged path only (serve_step_hybrid_paged)")
    tok = inputs["token"]
    pos = inputs["pos"]
    kv_spec5 = (jsh.PartitionSpec(None, None, None, None, seq_axis)
                if seq_axis else None)
    kv_spec3 = (jsh.PartitionSpec(None, None, seq_axis)
                if seq_axis else None)
    x = embed_tokens(params, tok[:, None] if tok.ndim == 1 else tok[:, None, :],
                     cfg)
    B = x.shape[0]

    if (kind == "attn" and cfg.local_global_pattern and cfg.sliding_window):
        # gemma2: pair scan (local ring layer + global layer), split cache
        L = cfg.num_layers
        W = cache["k_local"].shape[2]
        Smax = cache["k_global"].shape[2]
        chunk = attn_chunk or Smax
        pair_params = _grouped(params["layers"], 2)

        def mlp_apply(lp, h):
            m_in = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            return h + mlp_forward(lp["mlp"], m_in, cfg.mlp_act,
                                   cfg.mlp_gated)

        def body(carry, xs):
            h, c = carry
            plp, pi = xs
            lp_loc = jax.tree.map(lambda a: a[0], plp)
            lp_glb = jax.tree.map(lambda a: a[1], plp)
            # local (ring) layer
            a_in = rms_norm(h, lp_loc["attn_norm"], cfg.norm_eps)
            kl = jax.lax.dynamic_index_in_dim(c["k_local"], pi, keepdims=False)
            vl = jax.lax.dynamic_index_in_dim(c["v_local"], pi, keepdims=False)
            a_out, kl, vl = attn_mod.attn_decode_ring(
                lp_loc["attn"], a_in, cfg, cache_k=kl, cache_v=vl, pos=pos,
                window=W)
            c = dict(c,
                     k_local=jax.lax.dynamic_update_index_in_dim(
                         c["k_local"], kl, pi, 0),
                     v_local=jax.lax.dynamic_update_index_in_dim(
                         c["v_local"], vl, pi, 0))
            h = mlp_apply(lp_loc, h + a_out)
            # global layer
            a_in = rms_norm(h, lp_glb["attn_norm"], cfg.norm_eps)
            kg = jax.lax.dynamic_index_in_dim(c["k_global"], pi, keepdims=False)
            vg = jax.lax.dynamic_index_in_dim(c["v_global"], pi, keepdims=False)
            if kv_quant:
                ks = jax.lax.dynamic_index_in_dim(c["k_global_scale"], pi,
                                                  keepdims=False)
                vs = jax.lax.dynamic_index_in_dim(c["v_global_scale"], pi,
                                                  keepdims=False)
                a_out, kg, vg, ks, vs = attn_mod.attn_decode_quant(
                    lp_glb["attn"], a_in, cfg, cache_k=kg, cache_v=vg,
                    k_scale=ks, v_scale=vs, pos=pos, window=0,
                    attn_chunk=chunk, kv_seq_spec=kv_spec5)
                c = dict(c,
                         k_global_scale=jax.lax.dynamic_update_index_in_dim(
                             c["k_global_scale"], ks, pi, 0),
                         v_global_scale=jax.lax.dynamic_update_index_in_dim(
                             c["v_global_scale"], vs, pi, 0))
            else:
                a_out, kg, vg = attn_mod.attn_decode(
                    lp_glb["attn"], a_in, cfg, cache_k=kg, cache_v=vg,
                    pos=pos, window=0, attn_chunk=chunk,
                    kv_seq_spec=kv_spec5)
            c = dict(c,
                     k_global=jax.lax.dynamic_update_index_in_dim(
                         c["k_global"], kg, pi, 0),
                     v_global=jax.lax.dynamic_update_index_in_dim(
                         c["v_global"], vg, pi, 0))
            h = mlp_apply(lp_glb, h + a_out)
            return (h, c), None

        (x, cache), _ = jax.lax.scan(
            body, (x, cache),
            (pair_params, jnp.arange(L // 2, dtype=jnp.int32)))
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = unembed(params, x[:, 0, :], cfg)
        return logits, cache

    if kind == "attn":
        windows = layer_windows(cfg)
        Smax = (cache["ckv"] if cfg.attn_kind == "mla" else cache["k"]).shape[2]
        chunk = attn_chunk or Smax
        L = cfg.num_layers

        # Cache rides in the scan CARRY and is updated with
        # dynamic_update_index_in_dim at the layer index: XLA recognizes
        # the in-place update inside the while loop, so the (possibly
        # hundreds of GB) stacked cache is single-buffered — scanning it
        # as xs/ys would double-buffer it in temp space.
        def body(carry, xs):
            h, cache_c = carry
            lp, win, li = xs
            a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
            if cfg.attn_kind == "mla":
                ckv = jax.lax.dynamic_index_in_dim(cache_c["ckv"], li,
                                                   keepdims=False)
                kpe = jax.lax.dynamic_index_in_dim(cache_c["kpe"], li,
                                                   keepdims=False)
                a_out, ckv, kpe = mla_mod.mla_decode(lp["attn"], a_in, cfg,
                                                     cache_ckv=ckv,
                                                     cache_kpe=kpe, pos=pos,
                                                     kv_seq_spec=kv_spec3)
                cache_c = {
                    "ckv": jax.lax.dynamic_update_index_in_dim(
                        cache_c["ckv"], ckv, li, 0),
                    "kpe": jax.lax.dynamic_update_index_in_dim(
                        cache_c["kpe"], kpe, li, 0),
                }
            else:
                ck = jax.lax.dynamic_index_in_dim(cache_c["k"], li,
                                                  keepdims=False)
                cv = jax.lax.dynamic_index_in_dim(cache_c["v"], li,
                                                  keepdims=False)
                if kv_quant:
                    ks = jax.lax.dynamic_index_in_dim(cache_c["k_scale"], li,
                                                      keepdims=False)
                    vs = jax.lax.dynamic_index_in_dim(cache_c["v_scale"], li,
                                                      keepdims=False)
                    a_out, ck, cv, ks, vs = attn_mod.attn_decode_quant(
                        lp["attn"], a_in, cfg, cache_k=ck, cache_v=cv,
                        k_scale=ks, v_scale=vs, pos=pos, window=win,
                        attn_chunk=chunk, kv_seq_spec=kv_spec5)
                    cache_c = dict(
                        cache_c,
                        k_scale=jax.lax.dynamic_update_index_in_dim(
                            cache_c["k_scale"], ks, li, 0),
                        v_scale=jax.lax.dynamic_update_index_in_dim(
                            cache_c["v_scale"], vs, li, 0))
                else:
                    a_out, ck, cv = attn_mod.attn_decode(
                        lp["attn"], a_in, cfg, cache_k=ck, cache_v=cv,
                        pos=pos, window=win, attn_chunk=chunk,
                        kv_seq_spec=kv_spec5)
                cache_c = dict(
                    cache_c,
                    k=jax.lax.dynamic_update_index_in_dim(
                        cache_c["k"], ck, li, 0),
                    v=jax.lax.dynamic_update_index_in_dim(
                        cache_c["v"], cv, li, 0))
            h = h + a_out
            m_in = rms_norm(h, lp["mlp_norm"], cfg.norm_eps)
            if cfg.moe is not None:
                m_out, _ = moe_mod.moe_forward(lp["mlp"], m_in, cfg)
            else:
                m_out = mlp_forward(lp["mlp"], m_in, cfg.mlp_act, cfg.mlp_gated)
            return (h + m_out, cache_c), None

        (x, cache), _ = jax.lax.scan(
            body, (x, cache),
            (params["layers"], windows, jnp.arange(L, dtype=jnp.int32)))

    elif kind == "rwkv6":
        def body(h, xs):
            lp, s1, st, s2 = xs
            h2 = h[:, 0, :]
            tm_in = rms_norm(h2, lp["tm_norm"], cfg.norm_eps)
            y, s1o, sto = rwkv_mod.rwkv6_time_mix_step(lp["tm"], tm_in, cfg,
                                                       shift_in=s1, state_in=st)
            h2 = h2 + y
            cm_in = rms_norm(h2, lp["cm_norm"], cfg.norm_eps)
            y2, s2o = rwkv_mod.rwkv6_channel_mix(lp["tm"], cm_in, s2)
            h2 = h2 + y2
            return h2[:, None, :], (s1o, sto, s2o)

        x, new = jax.lax.scan(body, x, (params["layers"], cache["shift1"],
                                        cache["wkv"], cache["shift2"]))
        cache = {"shift1": new[0], "wkv": new[1], "shift2": new[2]}

    else:  # zamba2
        G, per = zamba2_groups(cfg)
        shared = params["shared"]
        Smax = cache["shared_k"].shape[2]

        def group_body(h, xs):
            glp, lora, ck, cv, conv, ssd = xs
            dd, HH, DD = cfg.d_model, cfg.num_heads, cfg.resolved_head_dim
            KVH = cfg.num_kv_heads
            ap = dict(shared["attn"])
            ap["wq"] = ap["wq"] + jnp.einsum("dr,re->de", lora["qa"],
                                             lora["qb"]).reshape(dd, HH, DD)
            ap["wv"] = ap["wv"] + jnp.einsum("dr,re->de", lora["va"],
                                             lora["vb"]).reshape(dd, KVH, DD)
            a_in = rms_norm(h, shared["attn_norm"], cfg.norm_eps)
            a_out, ck, cv = attn_mod.attn_decode(ap, a_in, cfg, cache_k=ck,
                                                 cache_v=cv, pos=pos, window=0,
                                                 attn_chunk=attn_chunk or Smax,
                                                 kv_seq_spec=kv_spec5)
            h = h + a_out
            m_in = rms_norm(h, shared["mlp_norm"], cfg.norm_eps)
            h = h + mlp_forward(shared["mlp"], m_in, cfg.mlp_act, cfg.mlp_gated)

            def mamba_body(hh, xs2):
                lp, ci, si = xs2
                m_in2 = rms_norm(hh[:, 0, :], lp["norm"], cfg.norm_eps)
                y, co, so = mamba_mod.mamba2_step(lp["mamba"], m_in2, cfg,
                                                  conv_in=ci, state_in=si)
                return (hh[:, 0, :] + y)[:, None, :], (co, so)

            h, (co, so) = jax.lax.scan(mamba_body, h, (glp, conv, ssd))
            return h, (ck, cv, co, so)

        x, new = jax.lax.scan(group_body, x,
                              (params["layers"], params["lora"],
                               cache["shared_k"], cache["shared_v"],
                               cache["conv"], cache["ssd"]))
        cache = {"shared_k": new[0], "shared_v": new[1],
                 "conv": new[2], "ssd": new[3]}

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x[:, 0, :], cfg)
    return logits, cache


def _attn_qkv(ap: Params, a_in: jax.Array, cfg: ArchConfig,
              positions: jax.Array):
    """A decode step's attention projections of ``a_in`` [B,1,d], roped
    at ``positions`` (not at all for NoPE), the query scaled for
    ``attention_multiplier``: (q [B,KVH,G,Dh], k, v [B,KVH,Dh])."""
    B = a_in.shape[0]
    H, KVH, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = jnp.einsum("bsd,dhk->bshk", a_in, ap["wq"])
    k = jnp.einsum("bsd,dhk->bshk", a_in, ap["wk"])
    v = jnp.einsum("bsd,dhk->bshk", a_in, ap["wv"])
    q = apply_rope(q, positions, fraction=cfg.rope_fraction,
                   theta=cfg.rope_theta)
    k = apply_rope(k, positions, fraction=cfg.rope_fraction,
                   theta=cfg.rope_theta)
    q = attn_mod.scale_query(q, cfg)
    return q[:, 0].reshape(B, KVH, H // KVH, Dh), k[:, 0], v[:, 0]


def _attn_out(ap: Params, out: jax.Array, a_in: jax.Array) -> jax.Array:
    """Attention output [B,KVH,G,Dh] projected back to [B,1,d]."""
    B, KVH, G, Dh = out.shape
    out = out.reshape(B, 1, KVH * G, Dh).astype(a_in.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, ap["wo"])


def _paged_gqa_step(params: Params, k_slab: jax.Array, v_slab: jax.Array,
                    block_table: jax.Array, lengths: jax.Array,
                    inputs: Dict[str, jax.Array], cfg: ArchConfig, attend,
                    ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """The layer scan of ``serve_step_paged`` and its spliced form;
    ``attend(q, k_pages, v_pages)`` is their attention."""
    tok = inputs["token"]
    x = embed_tokens(params, tok[:, None], cfg)
    L = cfg.num_layers
    ps = k_slab.shape[2]
    positions = lengths[:, None]                       # new token's position
    slot = jnp.take_along_axis(block_table,
                               (lengths // ps)[:, None], axis=1)[:, 0]
    off = lengths % ps

    # same carry/in-place-update discipline as the dense serve_step: the
    # slab rides the scan carry and each layer's page view is updated
    # with dynamic_update_index_in_dim so XLA single-buffers it
    def body(carry, xs):
        h, ks, vs = carry
        lp, li = xs
        a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(lp["attn"], a_in, cfg, positions)
        kl = jax.lax.dynamic_index_in_dim(ks, li, keepdims=False)
        vl = jax.lax.dynamic_index_in_dim(vs, li, keepdims=False)
        kl = kl.at[slot, off].set(k.astype(kl.dtype))
        vl = vl.at[slot, off].set(v.astype(vl.dtype))
        ks = jax.lax.dynamic_update_index_in_dim(ks, kl, li, 0)
        vs = jax.lax.dynamic_update_index_in_dim(vs, vl, li, 0)
        a_out = _attn_out(lp["attn"], attend(q, kl, vl), a_in)
        h = _mlp_residual(lp, _residual(h, a_out, cfg), cfg,
                          live_rows=inputs.get("live_rows"))
        return (h, ks, vs), None

    (x, k_slab, v_slab), _ = jax.lax.scan(
        body, (x, k_slab, v_slab),
        (params["layers"], jnp.arange(L, dtype=jnp.int32)))
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x[:, 0, :], cfg)
    return logits, k_slab, v_slab


def _require_paged_gqa(cfg: ArchConfig, entry: str) -> None:
    if family_kind(cfg) == "hybrid":
        raise ValueError(
            f"{entry}: {cfg.name} carries recurrent (Mamba-2) state, which "
            "a block table cannot hold or splice; its paged step is "
            "serve_step_hybrid_paged")
    if (family_kind(cfg) != "attn" or cfg.attn_kind != "gqa"
            or cfg.local_global_pattern or cfg.sliding_window):
        raise ValueError(
            f"{entry} supports plain global-causal GQA archs only "
            f"(family {family_kind(cfg)!r}, attn_kind {cfg.attn_kind!r})")


def serve_step_paged(params: Params, k_slab: jax.Array, v_slab: jax.Array,
                     block_table: jax.Array, lengths: jax.Array,
                     inputs: Dict[str, jax.Array], cfg: ArchConfig, *,
                     kernel_mode: Optional[str] = None,
                     ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One decode step over **paged** (block-table) KV — the serving
    path's PagedAttention form of ``serve_step``.

    k_slab/v_slab: the ``KVPageSlab`` arrays [L, NP, ps, KVH, Dh] (all
    layers stacked); block_table: [B, max_blocks] int32 slab page slots
    (a ``PagedCacheLease.tables()`` operand); lengths: [B] int32
    tokens already written per sequence — the new token is scattered at
    position ``lengths`` through the block table (the in-jit half of
    ``KVCacheManager.append_paged``; the caller advances the lease's
    host-side lengths afterwards) and attended in place with
    ``kernels.ops.flash_decode_paged``.  inputs: token [B], and
    optionally live_rows (int32 scalar): rows from it on are padding,
    which an MoE layer leaves out of expert capacity.

    Returns (logits [B, V], k_slab, v_slab).  Plain global-causal GQA
    attention archs only (the same restriction as
    ``KVCacheManager.init_paged``); a ``layer_types`` hybrid decodes
    through ``serve_step_hybrid_paged``; sliding-window / split-cache /
    MLA / other SSM families stay on the dense ``serve_step``.
    """
    from repro.kernels import ops as kernel_ops

    _require_paged_gqa(cfg, "serve_step_paged")
    mode = kernel_ops.DEFAULT_MODE if kernel_mode is None else kernel_mode
    lens1 = lengths + 1
    return _paged_gqa_step(
        params, k_slab, v_slab, block_table, lengths, inputs, cfg,
        lambda q, kl, vl: kernel_ops.flash_decode_paged(
            q, kl, vl, block_table, lens1, mode=mode))


def serve_step_paged_spliced(params: Params, k_slab: jax.Array,
                             v_slab: jax.Array, block_table: jax.Array,
                             lengths: jax.Array, page_delta: jax.Array,
                             page_valid: jax.Array,
                             inputs: Dict[str, jax.Array], cfg: ArchConfig,
                             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``serve_step_paged`` over a block table that mixes fresh pages
    with **spliced** chunk-KV pages (reordered RoPE per TurboRAG).

    Spliced pages hold K/V prefillled offline at chunk-local positions
    0..C-1 and attach by block-table edit; at attention time each page's
    stored K is rotated by its constant layout offset ``page_delta[b,
    blk]`` (chunks splice at page boundaries, so the offset is uniform
    across a page) and the dead tail of a chunk's partial last page is
    masked via ``page_valid[b, blk]`` live-token counts.  Fresh pages
    carry ``delta = 0`` and ``valid = ps`` — with an all-fresh table this
    is numerically ``serve_step_paged``.  The new token is roped and
    scattered at layout position ``lengths``, and ``inputs`` read, exactly
    as in the unspliced form.  Same plain global-causal GQA restriction:
    a model with recurrent state is refused, since a spliced chunk
    brings K/V pages but no state.  Attention runs through the jnp
    oracle ``ops.flash_decode_spliced`` (no kernel yet).
    """
    from repro.kernels import ops as kernel_ops

    _require_paged_gqa(cfg, "serve_step_paged_spliced")
    lens1 = lengths + 1
    return _paged_gqa_step(
        params, k_slab, v_slab, block_table, lengths, inputs, cfg,
        lambda q, kl, vl: kernel_ops.flash_decode_spliced(
            q, kl, vl, block_table, lens1, page_delta, page_valid,
            rope_fraction=cfg.rope_fraction, rope_theta=cfg.rope_theta))


def serve_step_hybrid_paged(params: Params, k_slab: jax.Array,
                            v_slab: jax.Array, conv_slab: jax.Array,
                            ssm_slab: jax.Array, block_table: jax.Array,
                            lengths: jax.Array, state_slots: jax.Array,
                            inputs: Dict[str, jax.Array], cfg: ArchConfig,
                            *, kernel_mode: Optional[str] = None,
                            ) -> Tuple[jax.Array, ...]:
    """One decode step of a ``layer_types`` hybrid (Mamba-2 mixers and
    GQA layers, each followed by the MLP) over the paged KV of its
    attention layers and the state slabs of its Mamba-2 layers.

    k_slab/v_slab [La, NP, ps, KVH*Dh]: the attention layers' pages,
    written and attended through ``block_table``/``lengths`` as in
    ``serve_step_paged``.  The slabs are carried as rows of one token
    (written by a one-index row scatter) and read as one page axis of
    La * NP pages, layer j's table offset by j * NP, so
    ``flash_decode_paged`` attends the slab in place.  conv_slab
    [Lm*S, (cw-1)*C] and ssm_slab [Lm, S, H, P, N] f32
    (``kv_cache.paged_slab_shapes``): row b's recurrent state is slot
    ``state_slots[b]`` (``mamba2_step_paged``; the SSM update
    ``ssm_decode_update`` works on the slab in place).
    Each run of same-kind layers is one scan over layer indices that
    carries the whole slabs, so no layer's slice is copied in or out.

    Returns (logits [B, V], k_slab, v_slab, conv_slab, ssm_slab)."""
    from repro.kernels import ops as kernel_ops

    if family_kind(cfg) != "hybrid" or cfg.attn_kind != "gqa":
        raise ValueError("serve_step_hybrid_paged takes a layer_types "
                         f"hybrid with GQA layers, not {cfg.name!r}")
    mode = kernel_ops.DEFAULT_MODE if kernel_mode is None else kernel_mode
    tok = inputs["token"]
    x = embed_tokens(params, tok[:, None], cfg)
    kv_shape = k_slab.shape
    La, NP, ps = kv_shape[:3]
    positions = lengths[:, None]
    # the new token's K/V row in layer 0's pages; layer j's is j*NP*ps on
    row = jnp.take_along_axis(block_table, (lengths // ps)[:, None],
                              axis=1)[:, 0] * ps + lengths % ps
    lens1 = lengths + 1
    KVH, Dh = cfg.num_kv_heads, cfg.resolved_head_dim
    pages = lambda rows: rows.reshape(La * NP, ps, KVH, Dh)
    at = lambda stack, j: jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, j, keepdims=False), stack)

    def attention_layer(carry, j):
        h, kr, vr, conv, ssm = carry
        lp = at(params["layers"]["attention"], j)
        a_in = rms_norm(h, lp["attn_norm"], cfg.norm_eps)
        q, k, v = _attn_qkv(lp["attn"], a_in, cfg, positions)
        r = row + j * NP * ps
        kr = kr.at[r].set(k.reshape(k.shape[0], -1).astype(kr.dtype))
        vr = vr.at[r].set(v.reshape(v.shape[0], -1).astype(vr.dtype))
        out = kernel_ops.flash_decode_paged(q, pages(kr), pages(vr),
                                            block_table + j * NP, lens1,
                                            mode=mode)
        h = _residual(h, _attn_out(lp["attn"], out, a_in), cfg)
        return (_mlp_residual(lp, h, cfg), kr, vr, conv, ssm), None

    def mamba_layer(carry, j):
        h, kr, vr, conv, ssm = carry
        lp = at(params["layers"]["mamba"], j)
        m_in = rms_norm(h[:, 0], lp["norm"], cfg.norm_eps)
        y, conv, ssm = mamba_mod.mamba2_step_paged(
            lp["mamba"], m_in, cfg, conv_rows=conv, ssm_slab=ssm, layer=j,
            slots=state_slots, kernel_mode=mode)
        h = _residual(h, y[:, None], cfg)
        return (_mlp_residual(lp, h, cfg), kr, vr, conv, ssm), None

    bodies = {"mamba": mamba_layer, "attention": attention_layer}
    carry = (x, k_slab.reshape(-1, kv_shape[3]),
             v_slab.reshape(-1, kv_shape[3]), conv_slab, ssm_slab)
    for t, i0, n in layer_runs(cfg):
        carry, _ = jax.lax.scan(bodies[t], carry,
                                jnp.arange(i0, i0 + n, dtype=jnp.int32))
    x, k_rows, v_rows, conv_slab, ssm_slab = carry
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = unembed(params, x[:, 0, :], cfg)
    return (logits, k_rows.reshape(kv_shape), v_rows.reshape(kv_shape),
            conv_slab, ssm_slab)

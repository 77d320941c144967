"""Mamba2 (SSD) block, TPU-adapted chunked form (arXiv:2405.21060 lineage).

Per-head scalar decay makes the sequence mixing 1-semiseparable: within a
chunk it is an attention-like masked einsum with decay ratios <= 1; across
chunks state is carried by a scan. Decode is the exact O(1) recurrence.

Recurrence (head h, P = head channels, N = state dim, ngroups = 1):
  a_t   = exp(dt_t * A_h)                      (A_h < 0)
  S_t   = a_t S_{t-1} + (dt_t x_t) ⊗ B_t       S: [P, N]
  y_t   = S_t C_t + D_h x_t
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import Maker, rms_norm


def mamba2_dims(cfg: ArchConfig):
    s = cfg.ssm
    d_in = s.expand * cfg.d_model
    n_heads = d_in // s.head_dim
    return d_in, n_heads, s.head_dim, s.state_dim


def mamba2_params(mk: Maker, cfg: ArchConfig, prefix: str = "mamba") -> dict:
    d = cfg.d_model
    d_in, H, P, N = mamba2_dims(cfg)
    cw = cfg.ssm.conv_width
    return {
        # fused in-projection: [z | x | B | C | dt]
        "w_in": mk(f"{prefix}.w_in", (d, 2 * d_in + 2 * N + H),
                   ("embed", "heads_flat")),
        "conv_w": mk(f"{prefix}.conv_w", (cw, d_in + 2 * N),
                     (None, "heads_flat"), scale=0.5),
        "conv_b": mk(f"{prefix}.conv_bias", (d_in + 2 * N,), ("heads_flat",)),
        "a_log": mk(f"{prefix}.a_log", (H,), ("heads_flat",), scale=0.5),
        "dt_bias": mk(f"{prefix}.dt_bias", (H,), ("heads_flat",), scale=0.5),
        "d_skip": mk(f"{prefix}.d_skip", (H,), ("heads_flat",), scale=1.0),
        "out_norm": mk(f"{prefix}.out_norm", (d_in,), ("heads_flat",)),
        "w_out": mk(f"{prefix}.w_out", (d_in, d), ("heads_flat", "embed")),
    }


def _split_in(cfg: ArchConfig, proj: jax.Array):
    d_in, H, P, N = mamba2_dims(cfg)
    z = proj[..., :d_in]
    xbc = proj[..., d_in:2 * d_in + 2 * N]
    dt = proj[..., 2 * d_in + 2 * N:]
    return z, xbc, dt


def _conv(p: dict, xbc: jax.Array, conv_in: jax.Array):
    """Causal depthwise conv over seq. xbc: [B,S,ch]; conv_in: [B,cw-1,ch]."""
    cw = p["conv_w"].shape[0]
    full = jnp.concatenate([conv_in.astype(xbc.dtype), xbc], axis=1)
    out = jnp.zeros_like(xbc)
    S = xbc.shape[1]
    for i in range(cw):
        out = out + full[:, i:i + S, :] * p["conv_w"][i]
    conv_out = full[:, -(cw - 1):, :] if cw > 1 else conv_in
    return jax.nn.silu(out + p["conv_b"]), conv_out


def mamba2_forward(p: dict, x: jax.Array, cfg: ArchConfig, *,
                   conv_in: jax.Array, state_in: jax.Array,
                   ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence SSD via chunked scan.

    x: [B,S,d]; conv_in: [B,cw-1,d_in+2N]; state_in: [B,H,P,N].
    Returns (y [B,S,d], conv_out, state_out).
    """
    B, S, d = x.shape
    d_in, H, P, N = mamba2_dims(cfg)
    C = min(cfg.ssm.chunk_size, S)
    if S % C:
        C = S
    NC = S // C

    proj = jnp.einsum("bsd,de->bse", x, p["w_in"])
    z, xbc, dt = _split_in(cfg, proj)
    xbc, conv_out = _conv(p, xbc, conv_in)
    xc = xbc[..., :d_in].reshape(B, S, H, P)
    Bm = xbc[..., d_in:d_in + N]                                  # [B,S,N]
    Cm = xbc[..., d_in + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                  # [H] < 0
    la = dt * a[None, None, :]                                    # log-decay [B,S,H]

    xc32, B32, C32 = (t.astype(jnp.float32) for t in (xc, Bm, Cm))
    ch4 = lambda t: jnp.moveaxis(t.reshape(B, NC, C, *t.shape[2:]), 1, 0)
    x_c, B_c, C_c, dt_c, la_c = ch4(xc32), ch4(B32), ch4(C32), ch4(dt), ch4(la)

    def chunk_body(S_in, xs):
        xcc, Bc, Cc, dtc, lac = xs      # [B,C,H,P], [B,C,N], [B,C,N], [B,C,H], [B,C,H]
        cum = jnp.cumsum(lac, axis=1)                             # Σ_{s<=t}
        # intra: y_t = Σ_{j<=t} exp(cum_t - cum_j) dt_j (C_t·B_j) x_j
        ratio = jnp.clip(cum[:, :, None] - cum[:, None, :, :], -60.0, 0.0)
        L = jnp.exp(ratio)                                        # [B,C,C,H]
        G = jnp.einsum("btn,bjn->btj", Cc, Bc)                    # [B,C,C]
        M = G[..., None] * L * dtc[:, None, :, :]                 # [B,t,j,H]
        tri = jnp.tril(jnp.ones((C, C), bool))[None, :, :, None]
        M = jnp.where(tri, M, 0.0)
        y = jnp.einsum("btjh,bjhp->bthp", M, xcc)
        # inter: y_t += exp(cum_t) S_in C_t
        y += jnp.einsum("bth,bhpn,btn->bthp", jnp.exp(cum), S_in, Cc)
        # state update
        dec_end = jnp.exp(cum[:, -1])                             # [B,H]
        w = jnp.exp(jnp.clip(cum[:, -1][:, None] - cum, -60.0, 0.0)) * dtc
        S_out = S_in * dec_end[..., None, None] + jnp.einsum(
            "bth,bthp,btn->bhpn", w, xcc, Bc)
        return S_out, y

    # checkpoint: intra-chunk [B,C,C,H] masks recompute in backward
    state_out, y_c = jax.lax.scan(jax.checkpoint(chunk_body),
                                  state_in.astype(jnp.float32),
                                  (x_c, B_c, C_c, dt_c, la_c))
    y = jnp.moveaxis(y_c, 0, 1).reshape(B, S, H, P)
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * xc32
    y = y.reshape(B, S, d_in).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    return (jnp.einsum("bse,ed->bsd", y, p["w_out"]),
            conv_out, state_out.astype(state_in.dtype))


def _step_in(p: dict, x: jax.Array, cfg: ArchConfig, conv_in: jax.Array):
    """A decode step up to the SSM: the in-projection and the conv over
    (conv_in ++ this token).  Returns (z, x [B,H,P], B, C, dt after
    softplus, A, conv_out)."""
    B, d = x.shape
    d_in, H, P, N = mamba2_dims(cfg)
    proj = jnp.einsum("bd,de->be", x, p["w_in"])
    z, xbc, dt = _split_in(cfg, proj)
    full = jnp.concatenate([conv_in.astype(xbc.dtype), xbc[:, None, :]], axis=1)
    conv_val = jnp.einsum("bwc,wc->bc", full, p["conv_w"]) + p["conv_b"]
    xbc = jax.nn.silu(conv_val)
    xc = xbc[..., :d_in].reshape(B, H, P)
    Bm = xbc[..., d_in:d_in + N]
    Cm = xbc[..., d_in + N:]
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    a = -jnp.exp(p["a_log"].astype(jnp.float32))
    return z, xc, Bm, Cm, dt, a, full[:, 1:, :]


def _step_out(p: dict, y: jax.Array, z: jax.Array, x: jax.Array,
              cfg: ArchConfig) -> jax.Array:
    """A decode step after the SSM: gated RMSNorm and out-projection of
    y [B,H,P] f32."""
    y = y.reshape(x.shape[0], -1).astype(x.dtype)
    y = rms_norm(y * jax.nn.silu(z), p["out_norm"], cfg.norm_eps)
    return jnp.einsum("be,ed->bd", y, p["w_out"])


def mamba2_step(p: dict, x: jax.Array, cfg: ArchConfig, *,
                conv_in: jax.Array, state_in: jax.Array,
                ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Exact O(1) decode step. x: [B,d]."""
    z, xc, Bm, Cm, dt, a, conv_out = _step_in(p, x, cfg, conv_in)
    dec = jnp.exp(dt * a[None, :])                                # [B,H]
    S = state_in.astype(jnp.float32)
    S = S * dec[..., None, None] + jnp.einsum(
        "bh,bhp,bn->bhpn", dt, xc.astype(jnp.float32), Bm.astype(jnp.float32))
    y = jnp.einsum("bhpn,bn->bhp", S, Cm.astype(jnp.float32))
    y = y + p["d_skip"].astype(jnp.float32)[None, :, None] * xc.astype(jnp.float32)
    return _step_out(p, y, z, x, cfg), conv_out, S.astype(state_in.dtype)


def mamba2_step_paged(p: dict, x: jax.Array, cfg: ArchConfig, *,
                      conv_rows: jax.Array, ssm_slab: jax.Array,
                      layer: jax.Array, slots: jax.Array,
                      kernel_mode: Optional[str] = None,
                      ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``mamba2_step`` against state slabs shared by every layer and
    sequence: row b's state is slot ``slots[b]`` of mamba layer
    ``layer``, in ssm_slab [Lm, S, H, P, N] f32 and in conv_rows
    [Lm*S, (cw-1)*C] (row ``layer * S + slot``).  The conv rows are
    gathered and scattered back; the SSM update is
    ``kernels.ops.ssm_decode_update``, which reads and writes the slab
    in place.  Returns (y [B,d], conv_rows, ssm_slab)."""
    from repro.kernels import ops as kernel_ops

    r = layer * ssm_slab.shape[1] + slots
    cw = cfg.ssm.conv_width
    z, xc, Bm, Cm, dt, a, conv_out = _step_in(
        p, x, cfg, conv_rows[r].reshape(len(slots), cw - 1, -1))
    conv_rows = conv_rows.at[r].set(
        conv_out.reshape(len(slots), -1).astype(conv_rows.dtype))
    y, ssm_slab = kernel_ops.ssm_decode_update(
        ssm_slab, layer, slots, xc, dt, Bm, Cm, a,
        p["d_skip"].astype(jnp.float32), mode=kernel_mode)
    return _step_out(p, y, z, x, cfg), conv_rows, ssm_slab

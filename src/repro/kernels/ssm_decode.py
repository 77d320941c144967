"""Pallas TPU kernel: one Mamba-2 decode step of the SSM state, in place.

Serving hot path of a hybrid model: every decode step reads and writes
each row's recurrent state once per Mamba-2 layer.  ``ssm_decode_update``
updates the row's slot of the **whole** state slab
``[layers, slots, H, P, N]`` in place: the layer index and the
row-to-slot map ride the scalar-prefetch channel (SMEM) into the state's
BlockSpec index map, and the slab is aliased input to output, so no
per-layer slice of it is copied in or out of the caller's layer scan.

Per row b and head h (f32 throughout; ``x``, ``dt``, ``B``, ``C`` are
the step's conv outputs and softplus'd step sizes, ``A < 0``):

    S <- exp(dt * A) * S + dt * (x outer B)          S: [P, N]
    y  = S C + D x                                   y: [P]

TPU layout: a head's state [P, N] is one (P, N) tile of lanes N, read as
a block of ``head_block`` heads.  ``x`` arrives lane-major ([1, P] per
head) and is turned into a column by a diagonal select-and-sum (an
exact f32 transpose on the VPU); ``y`` goes back to a row the same way.
The per-head scalars ``dt``, ``A``, ``D`` arrive as [1, head_block]
rows and are sliced per head.

Grid = (B, H / head_block): one state block is fetched, updated and
written back per grid step.  Rows that share a slot (a wave's padding
rows all write the scratch slot) leave it holding one of their results;
no live row shares a slot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_BYTES = 1 << 20          # a state block's bytes, at most


def head_block(H: int, P: int, N: int) -> int:
    """Heads per grid step: the largest divisor of ``H`` that is a
    multiple of 8 (or ``H`` itself) whose state fits ``BLOCK_BYTES``."""
    fits = [h for h in range(1, H + 1)
            if H % h == 0 and (h % 8 == 0 or h == H)
            and h * P * N * 4 <= BLOCK_BYTES]
    return max(fits) if fits else min(h for h in range(1, H + 1)
                                      if H % h == 0 and (h % 8 == 0 or h == H))


def _ssm_kernel(layer_ref, slot_ref, dt_ref, a_ref, d_ref, x_ref, b_ref,
                c_ref, s_ref, y_ref, so_ref, *, hb: int, P: int):
    del layer_ref, slot_ref            # used by the index maps only
    b_row = b_ref[0]                                   # [1, N]
    c_row = c_ref[0]
    dt = dt_ref[0, 0]                                  # [1, hb]
    dA = jnp.exp(dt * a_ref[0])                        # [1, hb]
    d = d_ref[0]
    xs = x_ref[0]                                      # [hb, P]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1))
    for h in range(hb):
        x_row = xs[h:h + 1, :]                         # [1, P]
        x_col = jnp.sum(jnp.where(eye, x_row, 0.0), axis=1,
                        keepdims=True)                 # [P, 1]
        s = (s_ref[0, 0, h] * dA[:, h:h + 1]
             + (dt[:, h:h + 1] * x_col) * b_row)       # [P, N]
        so_ref[0, 0, h] = s
        y_col = (jnp.sum(s * c_row, axis=1, keepdims=True)
                 + d[:, h:h + 1] * x_col)              # [P, 1]
        y_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, y_col, 0.0), axis=0,
                                       keepdims=True)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssm_decode_update(state: jax.Array, layer: jax.Array, slots: jax.Array,
                      x: jax.Array, dt: jax.Array, B: jax.Array,
                      C: jax.Array, A: jax.Array, D: jax.Array, *,
                      interpret: bool = False):
    """One SSM decode step over the slab, in place.

    state [L, S, H, P, N] f32 (the result aliases it: a caller that
    donates the slab has it updated in place); layer
    int32 scalar; slots [B] int32 state slot of each row; x [B, H, P];
    dt [B, H] (after softplus); B, C [B, N]; A, D [H] (A < 0).  Returns
    (y [B, H, P] f32, state)."""
    L, S, H, P, N = state.shape
    Bn = x.shape[0]
    hb = head_block(H, P, N)
    nb = H // hb
    f32 = jnp.float32
    dt4 = dt.astype(f32).reshape(Bn, nb, 1, hb)
    a3 = A.astype(f32).reshape(nb, 1, hb)
    d3 = D.astype(f32).reshape(nb, 1, hb)
    b3 = B.astype(f32).reshape(Bn, 1, N)
    c3 = C.astype(f32).reshape(Bn, 1, N)
    kern = functools.partial(_ssm_kernel, hb=hb, P=P)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bn, nb),
        in_specs=[
            pl.BlockSpec((1, 1, 1, hb), lambda b, j, li, sl: (b, j, 0, 0)),
            pl.BlockSpec((1, 1, hb), lambda b, j, li, sl: (j, 0, 0)),
            pl.BlockSpec((1, 1, hb), lambda b, j, li, sl: (j, 0, 0)),
            pl.BlockSpec((1, hb, P), lambda b, j, li, sl: (b, j, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j, li, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, N), lambda b, j, li, sl: (b, 0, 0)),
            pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, j, li, sl: (li[0], sl[b], j, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, hb, P), lambda b, j, li, sl: (b, j, 0)),
            pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, j, li, sl: (li[0], sl[b], j, 0, 0)),
        ],
    )
    y, state = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bn, H, P), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 counts the two scalar-prefetch operands
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssm_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      dt4, a3, d3, x.astype(f32), b3, c3, state)
    return y, state

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

TPU layout: a block of ``head_block`` heads' state [hb, P, N] is read
as one [hb * P, N] tile of lanes N (a reshape of leading dims, ``P`` a
multiple of 8).  ``x``, ``dt``, ``A`` and ``D`` arrive lane-major, one
value per (head, channel), as [1, hb * P] rows (the wrapper repeats the
per-head ``dt``, ``A``, ``D`` over ``P``).  The decay ``exp(dt A)`` and
``dt x`` are stacked into one [8, hb * P] tile whose single transpose
makes them the state tile's columns, so the update is one broadcast
multiply-add over the whole tile.  ``y`` is one MXU product of ``C``
(broadcast to 8 rows) with the tile, contracting ``N`` at ``HIGHEST``
precision, which leaves it lane-major beside ``D x``.

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
    N = b_ref.shape[-1]
    dt = dt_ref[0, 0]                                  # [1, hb*P]
    x = x_ref[0, 0]
    # row 0 the decay, the other rows dt x; one transpose makes both
    # columns of the state tile
    rows = jnp.where(
        jax.lax.broadcasted_iota(jnp.int32, (8, hb * P), 0) == 0,
        jnp.exp(dt * a_ref[0]), dt * x)                # [8, hb*P]
    cols = rows.T                                      # [hb*P, 8]
    s = (cols[:, 0:1] * s_ref[0, 0].reshape(hb * P, N)
         + cols[:, 1:2] * b_ref[0])                    # [hb*P, N]
    so_ref[0, 0] = s.reshape(hb, P, N)
    c8 = jnp.broadcast_to(c_ref[0], (8, N))
    y = jax.lax.dot_general(c8, s, (((1,), (1,)), ((), ())),
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=jnp.float32)
    y_ref[0, 0] = y[0:1] + d_ref[0] * x


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
    lanes = lambda t: t.astype(f32).reshape(Bn, nb, 1, hb * P)
    per_head = lambda t: jnp.repeat(t.astype(f32), P).reshape(nb, 1, hb * P)
    b3 = B.astype(f32).reshape(Bn, 1, N)
    c3 = C.astype(f32).reshape(Bn, 1, N)
    kern = functools.partial(_ssm_kernel, hb=hb, P=P)
    row = pl.BlockSpec((1, 1, 1, hb * P), lambda b, j, li, sl: (b, j, 0, 0))
    head = pl.BlockSpec((1, 1, hb * P), lambda b, j, li, sl: (j, 0, 0))
    vec = pl.BlockSpec((1, 1, N), lambda b, j, li, sl: (b, 0, 0))
    block = pl.BlockSpec((1, 1, hb, P, N),
                         lambda b, j, li, sl: (li[0], sl[b], j, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(Bn, nb),
        in_specs=[row, head, head, row, vec, vec, block],
        out_specs=[row, block],
    )
    y, state = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((Bn, nb, 1, hb * P), f32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 8 counts the two scalar-prefetch operands
        input_output_aliases={8: 1},
        interpret=interpret,
        name="ssm_decode_update",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots.astype(jnp.int32),
      lanes(jnp.repeat(dt, P, axis=1)), per_head(A), per_head(D), lanes(x),
      b3, c3, state)
    return y.reshape(Bn, H, P), state

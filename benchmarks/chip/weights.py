"""The benchmark's weights, drawn from the seed leaf by leaf.

Every leaf is drawn from (seed, leaf name, layer), so a layout
(``layouts/``) that lays the leaves out as the served model's parameter
tree and a reference (``references/``) that redraws them one layer at a
time read the very same values without either taking anything from the
other or from the program.
"""

from __future__ import annotations

import math
import zlib
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

NORM_STD = 0.1          # RMSNorm weights are 1 + NORM_STD * N(0, 1)


def seed_key(seed: int) -> jax.Array:
    """A PRNG key for any whole seed up to 64 bits."""
    k = jax.random.fold_in(jax.random.PRNGKey(0), seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def leaf_key(key: jax.Array, name: str, layer: int = 0) -> jax.Array:
    k = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    return jax.random.fold_in(k, layer)


def leaf_values(key: jax.Array, shape: Tuple[int, ...],
                fan_in: Optional[int]) -> jax.Array:
    """One layer's leaf as served (bf16).  For an RMSNorm weight this is
    the served model's stored offset: the weight is 1 + this."""
    std = NORM_STD if fan_in is None else 1.0 / math.sqrt(fan_in)
    return (jax.random.normal(key, shape, jnp.float32) * std
            ).astype(jnp.bfloat16)

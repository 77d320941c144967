"""Training step factories: SPMD (pjit-implicit collectives) and
explicit-collective DP (shard_map) with optional int8 gradient compression.

The SPMD path is what the dry-run lowers (GSPMD inserts the grad
all-reduces from the shardings). The manual path exists because gradient
compression must own its psum to actually shrink wire bytes.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import ArchConfig
from repro.distributed import compression as comp
from repro.models import transformer as tf
from repro.training.optimizer import OptConfig, adamw_update, init_opt_state


def make_loss_fn(cfg: ArchConfig, *, attn_chunk: int = 1024,
                 remat: bool = True, remat_group: int = 4, act_spec=None,
                 loss_chunk: int = 512) -> Callable:
    def loss_fn(params, batch):
        return tf.loss_fn(params, batch, cfg, attn_chunk=attn_chunk,
                          remat=remat, remat_group=remat_group,
                          act_spec=act_spec, loss_chunk=loss_chunk)
    return loss_fn


def make_train_step(cfg: ArchConfig, opt_cfg: OptConfig, *,
                    attn_chunk: int = 1024, remat: bool = True,
                    remat_group: int = 4, act_spec=None,
                    loss_chunk: int = 512, accum_steps: int = 1) -> Callable:
    """SPMD train step: (params, opt_state, batch) -> (params, opt_state,
    metrics). Shard via pjit in/out shardings; collectives are implicit.

    accum_steps > 1 splits the global batch into microbatches scanned with
    gradient accumulation: transient activation memory scales 1/accum at
    the cost of re-gathering FSDP weights per microbatch.
    """
    loss_fn = make_loss_fn(cfg, attn_chunk=attn_chunk, remat=remat,
                           remat_group=remat_group, act_spec=act_spec,
                           loss_chunk=loss_chunk)

    def train_step(params, opt_state, batch):
        if accum_steps <= 1:
            (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
                params, batch)
        else:
            micro = jax.tree.map(
                lambda a: a.reshape((accum_steps, a.shape[0] // accum_steps)
                                    + a.shape[1:]), batch)

            def mb(carry, mbatch):
                gacc, lsum, auxsum = carry
                (l, a), g = jax.value_and_grad(loss_fn, has_aux=True)(
                    params, mbatch)
                gacc = jax.tree.map(jnp.add, gacc, g)
                auxsum = jax.tree.map(jnp.add, auxsum, a)
                return (gacc, lsum + l, auxsum), None

            g0 = jax.tree.map(jnp.zeros_like, params)
            aux0 = {"ce": jnp.zeros(()), "aux": jnp.zeros(()),
                    "tokens": jnp.zeros(())}
            (grads, loss, aux), _ = jax.lax.scan(
                mb, (g0, jnp.zeros(()), aux0), micro)
            inv = 1.0 / accum_steps
            grads = jax.tree.map(lambda g: g * inv, grads)
            loss = loss * inv
            aux = {"ce": aux["ce"] * inv, "aux": aux["aux"] * inv,
                   "tokens": aux["tokens"]}
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        metrics = {"loss": loss, **aux, **om}
        return params, opt_state, metrics

    return train_step


def make_manual_dp_train_step(cfg: ArchConfig, opt_cfg: OptConfig,
                              mesh: Mesh, *, compress: bool = False,
                              axis: str = "data", attn_chunk: int = 1024,
                              remat: bool = True) -> Callable:
    """Pure-DP train step with explicit psum (compressible).

    Params replicated; batch sharded over ``axis``. State carries the
    error-feedback tree when compression is on.
    """
    loss_fn = make_loss_fn(cfg, attn_chunk=attn_chunk, remat=remat)

    def step(params, opt_state, err, batch):
        (loss, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        if compress:
            grads, err = comp.compressed_psum(grads, err, axis)
        else:
            grads = jax.lax.pmean(grads, axis)
        loss = jax.lax.pmean(loss, axis)
        params, opt_state, om = adamw_update(params, grads, opt_state, opt_cfg)
        return params, opt_state, err, {"loss": loss, **om}

    shard_step = jax.shard_map(
        step, mesh=mesh,
        in_specs=(P(), P(), P(), P(axis)),
        out_specs=(P(), P(), P(), P()),
        check_vma=False)
    return jax.jit(shard_step)


def init_training(cfg: ArchConfig, opt_cfg: OptConfig, key: jax.Array,
                  ) -> Tuple[Any, Any]:
    params = tf.init_params(cfg, key)
    return params, init_opt_state(params, opt_cfg)

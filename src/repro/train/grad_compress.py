"""Explicit data-parallel training with int8 gradient compression and
error feedback (shard_map variant).

The pjit path lets XLA insert gradient all-reduces; this variant makes the
sync explicit so it can be compressed — the distributed-optimization trick
for collective-bound training steps:

  1. local fp32 grads + error-feedback buffer,
  2. per-leaf int8 quantisation (scale = pmax |g| / 127),
  3. all_to_all(int8) → local reduction → all_gather(int8)
     (a quantised reduce-scatter + all-gather ring: collective bytes drop
     ~4× vs fp32 all-reduce — visible in the HLO roofline term),
  4. residual (g - dequantised(Q(g))) carried to the next step.

The second-stage quantisation (of the reduced sum) is not error-fed; its
error is bounded by 1/127 of the max summed gradient (documented).
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ..configs.base import ArchConfig
from ..optim import adamw
from . import train_loop


def _quantize(g: jax.Array, axis: str) -> tuple[jax.Array, jax.Array]:
    scale = jax.lax.pmax(jnp.max(jnp.abs(g)), axis) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(g / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _int8_allreduce_mean(q: jax.Array, scale: jax.Array, axis: str,
                         n_dev: int) -> jax.Array:
    """Quantised ring all-reduce of a flat int8 vector; returns fp32 mean."""
    n = q.shape[0]
    pad = (-n) % n_dev
    if pad:
        q = jnp.pad(q, (0, pad))
    qs = q.reshape(n_dev, -1)
    # reduce-scatter stage: everyone sends shard i to device i (int8 wire)
    shards = jax.lax.all_to_all(qs, axis, split_axis=0, concat_axis=0,
                                tiled=False)
    local_sum = jnp.sum(shards.astype(jnp.int32), axis=0)       # (m,)
    # requantise the reduced shard for the int8 gather stage
    s2 = jax.lax.pmax(jnp.max(jnp.abs(local_sum)), axis).astype(jnp.float32)
    s2 = jnp.maximum(s2 / 127.0, 1e-12)
    q2 = jnp.clip(jnp.round(local_sum.astype(jnp.float32) / s2),
                  -127, 127).astype(jnp.int8)
    gathered = jax.lax.all_gather(q2, axis)                      # (n_dev, m)
    out = gathered.reshape(-1).astype(jnp.float32) * s2 * scale / n_dev
    return out[:n]


def make_compressed_dp_train_step(cfg: ArchConfig,
                                  opt_cfg: adamw.AdamWConfig, mesh, *,
                                  axis: str = "data",
                                  attn_impl: str = "chunked",
                                  remat: bool = True) -> Callable:
    """(params, opt_state, ef, batch) → (params, opt_state, ef, metrics).

    params/opt_state replicated; ``ef`` leaves carry a leading device dim
    (the per-device residual); batch sharded over ``axis``."""
    loss_fn = train_loop.make_loss_fn(cfg, attn_impl=attn_impl, remat=remat)
    n_dev = mesh.shape[axis]

    def shard_fn(params, opt_state, ef, batch):
        ef = jax.tree.map(lambda e: e[0], ef)  # strip sharded leading dim
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        grads = jax.tree.map(
            lambda g, e: g.astype(jnp.float32) + e, grads, ef)

        def sync_leaf(g):
            q, scale = _quantize(g.reshape(-1), axis)
            deq_local = q.astype(jnp.float32) * scale
            ef_new = (g.reshape(-1) - deq_local).reshape(g.shape)
            mean = _int8_allreduce_mean(q, scale, axis, n_dev)
            return mean.reshape(g.shape), ef_new

        flat, tdef = jax.tree.flatten(grads)
        synced, ef_new = zip(*(sync_leaf(g) for g in flat), strict=True)
        g_sync = jax.tree.unflatten(tdef, list(synced))
        ef_new = jax.tree.unflatten(tdef, list(ef_new))
        new_params, new_state, metrics = adamw.update(
            g_sync, opt_state, params, opt_cfg)
        loss = jax.lax.pmean(loss, axis)
        metrics = dict(metrics, loss=loss)
        ef_new = jax.tree.map(lambda e: e[None], ef_new)  # re-add dev dim
        return new_params, new_state, ef_new, metrics

    return jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(P(), P(), P(axis), P(axis)),
        out_specs=(P(), P(), P(axis), P()),
        check_vma=False))


def init_error_feedback(params, n_dev: int):
    """Per-device residual buffers, leading dim = device axis extent."""
    return jax.tree.map(
        lambda p: jnp.zeros((n_dev,) + p.shape, jnp.float32), params)

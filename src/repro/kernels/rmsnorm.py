"""Fused RMSNorm Pallas kernel: one HBM read, one write per row block
(the unfused jnp version reads x twice — mean, then normalise)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import resolve_interpret


def _kernel(x_ref, g_ref, o_ref, *, eps: float):
    xf = x_ref[...].astype(jnp.float32)
    rms = jnp.sqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    o_ref[...] = ((xf / rms) * g_ref[...].astype(jnp.float32)).astype(
        o_ref.dtype)


def rmsnorm_pallas(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-6,
                   block_rows: int = 128, plan=None,
                   interpret: bool | None = None) -> jax.Array:
    """x: (rows, d); gamma: (d,).  rows must divide by block_rows
    (ops.py pads).  An externally-chosen ``plan`` (a ``tuning.BlockPlan``,
    e.g. a measured winner from ``autotune.KernelTuner``) overrides
    ``block_rows``."""
    if plan is not None:
        block_rows = plan.block
    rows, d = x.shape
    assert rows % block_rows == 0, (rows, block_rows)
    return pl.pallas_call(
        functools.partial(_kernel, eps=eps),
        grid=(rows // block_rows,),
        in_specs=[pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
                  pl.BlockSpec((d,), lambda i: (0,))],
        out_specs=pl.BlockSpec((block_rows, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, gamma)

"""Pallas TPU kernel for adjacent_difference (the paper's memory-bound
benchmark), with neighbour-block halo.

Each grid step i owns elements [i*B, (i+1)*B).  The first element of the
block needs x[i*B - 1]; rather than shifting the whole array in HBM, the
kernel receives the *previous block* as a second input (index_map i-1,
clamped at 0) — the TPU-idiomatic halo read.  Block size comes from the
adaptive plan (tuning.plan_1d), i.e. the paper's Eq. 10 on the VMEM/
pipeline level.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import resolve_interpret


def _kernel(x_ref, prev_ref, o_ref):
    i = pl.program_id(0)
    x = x_ref[...]
    prev_last = prev_ref[x.shape[0] - 1]
    # Shift x right by one within the block; position 0 gets the halo.
    shifted = jnp.concatenate([prev_last[None], x[:-1]])
    out = x - shifted
    # Block 0, element 0: out[0] = x[0] (definition) — prev block is a
    # clamped self-read there, so fix it up.
    first = jnp.where(i == 0, x[0], out[0])
    o_ref[...] = jnp.concatenate([first[None], out[1:]])


def adjacent_difference_pallas(x: jax.Array, *, block: int,
                               interpret: bool | None = None) -> jax.Array:
    """1-d adjacent difference.  ``x`` length must be a multiple of
    ``block`` (ops.py handles padding)."""
    n = x.shape[0]
    assert n % block == 0, (n, block)
    grid = n // block
    return pl.pallas_call(
        _kernel,
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((block,), lambda i: (i,)),
            pl.BlockSpec((block,), lambda i: (jnp.maximum(i - 1, 0),)),
        ],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x, x)

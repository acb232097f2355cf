"""Pallas TPU kernel for the paper's compute-bound "artificial work" body:
``iters`` dependent FMAs per element.  Pure map — no halo; the block size
(adaptive, tuning.plan_1d) controls the VMEM working set and pipeline
depth exactly as the paper's chunk size controls task granularity."""
from __future__ import annotations

import functools

import jax
from jax.experimental import pallas as pl

from .tuning import resolve_interpret


def _kernel(x_ref, o_ref, *, iters: int):
    x = x_ref[...]

    def body(_, c):
        return c * 1.000000119 + 0.1

    o_ref[...] = jax.lax.fori_loop(0, iters, body, x)


def artificial_work_pallas(x: jax.Array, *, iters: int, block: int,
                           interpret: bool | None = None) -> jax.Array:
    n = x.shape[0]
    assert n % block == 0, (n, block)
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=pl.BlockSpec((block,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((n,), x.dtype),
        interpret=resolve_interpret(interpret),
    )(x)

"""Pallas TPU kernels for chunked reduction and prefix sum.

Reduction: each grid step writes its block's partial into row ``i`` of a
``(grid, 1, 128)`` partials array (one lane-wide row per block: a
``(1,)`` block over a ``(grid,)`` vector is not a legal TPU tile); the
partials are combined outside (two-phase, like the algorithm layer and
the paper's chunked map-reduce).

Scan: three-phase chunk-parallel prefix sum —
  (1) kernel pass computes per-block inclusive scans (a log-step scan
      over the block's (rows, 128) tiles) and block totals,
  (2) an exclusive scan over the (grid,) totals (negligible, jnp),
  (3) kernel pass adds each block's offset.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .tuning import LANE, resolve_interpret


def _row_spec():
    """One lane-wide row per grid step of a ``(grid, 1, LANE)`` array —
    the block's last two dims equal the array's, so it always tiles."""
    return pl.BlockSpec((1, 1, LANE), lambda i: (i, 0, 0))


def _reduce_kernel(x_ref, o_ref):
    total = jnp.sum(x_ref[...].astype(jnp.float32))
    o_ref[...] = jnp.broadcast_to(total, o_ref.shape)


def reduce_sum_pallas(x: jax.Array, *, block: int | None = None, plan=None,
                      interpret: bool | None = None) -> jax.Array:
    """``block`` or an externally-chosen ``plan`` (``tuning.BlockPlan``,
    e.g. an ``autotune.KernelTuner`` winner) sets the grid step."""
    if plan is not None:
        block = plan.block
    assert block is not None, "need block= or plan="
    n = x.shape[0]
    assert n % block == 0, (n, block)
    grid = n // block
    partials = pl.pallas_call(
        _reduce_kernel,
        grid=(grid,),
        in_specs=[pl.BlockSpec((block,), lambda i: (i,))],
        out_specs=_row_spec(),
        out_shape=jax.ShapeDtypeStruct((grid, 1, LANE), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(x)
    return jnp.sum(partials[:, 0, 0], dtype=jnp.float32).astype(x.dtype)


def _prefix_sum_2d(x: jax.Array) -> jax.Array:
    """Inclusive prefix sum of a ``(rows, LANE)`` f32 block in row-major
    order.  Mosaic has no cumsum, so this is a Hillis-Steele scan: log2
    steps of lane rotations within each row, then log2 steps of sublane
    rotations carrying the row totals down."""
    from jax.experimental.pallas import tpu as pltpu

    rows, lanes = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
    s = 1
    while s < lanes:
        x = x + jnp.where(lane >= s, pltpu.roll(x, s, 1), 0.0)
        s *= 2
    # Exclusive prefix of the row totals, broadcast along each row.
    tot = jnp.broadcast_to(x[:, lanes - 1:], x.shape)
    carry = jnp.where(row >= 1, pltpu.roll(tot, 1, 0), 0.0)
    s = 1
    while s < rows:
        carry = carry + jnp.where(row >= s, pltpu.roll(carry, s, 0), 0.0)
        s *= 2
    return x + carry


def _scan_local_kernel(x_ref, scan_ref, total_ref):
    xf = x_ref[...].astype(jnp.float32)
    scan_ref[...] = _prefix_sum_2d(xf).astype(scan_ref.dtype)
    total_ref[...] = jnp.broadcast_to(jnp.sum(xf), total_ref.shape)


def _scan_offset_kernel(scan_ref, off_ref, o_ref):
    # Every lane of the offset row holds the same value.
    off = jnp.max(off_ref[...])
    o_ref[...] = (scan_ref[...].astype(jnp.float32) + off).astype(o_ref.dtype)


def inclusive_scan_pallas(x: jax.Array, *, block: int | None = None,
                          plan=None,
                          interpret: bool | None = None) -> jax.Array:
    """``block`` or an externally-chosen ``plan`` sets the grid step (see
    ``reduce_sum_pallas``); it must be a multiple of ``LANE``: the kernel
    scans ``(block // LANE, LANE)`` tiles of the row-major array."""
    if plan is not None:
        block = plan.block
    assert block is not None, "need block= or plan="
    n = x.shape[0]
    assert n % block == 0 and block % LANE == 0, (n, block)
    grid = n // block
    rows = block // LANE
    tile = pl.BlockSpec((rows, LANE), lambda i: (i, 0))
    interpret = resolve_interpret(interpret)
    local, totals = pl.pallas_call(
        _scan_local_kernel,
        grid=(grid,),
        in_specs=[tile],
        out_specs=[tile, _row_spec()],
        out_shape=[jax.ShapeDtypeStruct((n // LANE, LANE), x.dtype),
                   jax.ShapeDtypeStruct((grid, 1, LANE), jnp.float32)],
        interpret=interpret,
    )(x.reshape(n // LANE, LANE))
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.float32), jnp.cumsum(totals[:, 0, 0])[:-1]])
    out = pl.pallas_call(
        _scan_offset_kernel,
        grid=(grid,),
        in_specs=[tile, _row_spec()],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n // LANE, LANE), x.dtype),
        interpret=interpret,
    )(local, jnp.broadcast_to(offsets[:, None, None], (grid, 1, LANE)))
    return out.reshape(n)

"""jit'd public wrappers around the Pallas kernels.

Each wrapper: picks a block plan (static: tuning.py — the acc chunk
model; measured: an ``autotune.KernelTuner`` passed as ``tuner=``), pads
to the plan, dispatches the kernel, unpads.  ``interpret=None`` passes
through to the kernel, which resolves it (``tuning.resolve_interpret``:
the interpreter off-TPU, Mosaic-compiled on TPU), so the same call sites
validate on CPU.

The ``tuner=`` path is the paper's feedback loop at the kernel grid:
the tuner wall-clocks candidate blocks seeded from the analytic prior on
synthetic data of the same padded shape (its harness forces eager
evaluation, so the probes really execute even when a consumer resolves
plans while tracing inside an outer jit) and persists the winner, so
only the first process on a given (kernel, shape-bucket, dtype,
hardware) ever pays the search.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import tuning
from .adjacent_difference import adjacent_difference_pallas
from .artificial_work import artificial_work_pallas
from .flash_attention import flash_attention_pallas
from .reduce_scan import inclusive_scan_pallas, reduce_sum_pallas
from .rmsnorm import rmsnorm_pallas


def _pad_1d(x: jax.Array, padded: int, fill=0.0):
    n = x.shape[0]
    if padded == n:
        return x
    return jnp.pad(x, (0, padded - n), constant_values=fill)


def _tuned_block_1d(tuner, kernel: str, n: int, dtype, *,
                    arrays_in_vmem: int, call) -> int:
    """Measured block for a 1-d kernel: ``call(x, block)`` is the jit'd
    kernel invocation; the tuner times it on synthetic zeros at each
    candidate (its harness keeps the probes eager and concrete even
    mid-trace of an outer jit)."""

    def run(block: int) -> None:
        padded = ((n + block - 1) // block) * block
        jax.block_until_ready(call(jnp.zeros((padded,), dtype), block))

    return tuner.plan_1d(kernel, n, run, dtype=str(dtype),
                         bytes_per_elem=dtype.itemsize,
                         arrays_in_vmem=arrays_in_vmem).block


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _adjdiff_call(x, block, interpret):
    return adjacent_difference_pallas(x, block=block, interpret=interpret)


def adjacent_difference(x: jax.Array, *, block: int | None = None,
                        interpret: bool | None = None,
                        tuner=None) -> jax.Array:
    n = x.shape[0]
    if block is None and tuner is not None:
        block = _tuned_block_1d(
            tuner, "adjacent_difference", n, x.dtype, arrays_in_vmem=3,
            call=lambda xz, b: _adjdiff_call(xz, b, interpret))
    if block is None:
        block = tuning.plan_1d(n, bytes_per_elem=x.dtype.itemsize,
                               arrays_in_vmem=3).block
    padded = ((n + block - 1) // block) * block
    out = _adjdiff_call(_pad_1d(x, padded), block, interpret)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("iters", "block", "interpret"))
def _awork_call(x, iters, block, interpret):
    return artificial_work_pallas(x, iters=iters, block=block,
                                  interpret=interpret)


def artificial_work(x: jax.Array, *, iters: int = 256,
                    block: int | None = None,
                    interpret: bool | None = None,
                    tuner=None) -> jax.Array:
    n = x.shape[0]
    if block is None and tuner is not None:
        block = _tuned_block_1d(
            tuner, f"artificial_work_{iters}", n, x.dtype, arrays_in_vmem=2,
            call=lambda xz, b: _awork_call(xz, iters, b, interpret))
    if block is None:
        block = tuning.plan_1d(n, bytes_per_elem=x.dtype.itemsize,
                               arrays_in_vmem=2).block
    padded = ((n + block - 1) // block) * block
    return _awork_call(_pad_1d(x, padded), iters, block, interpret)[:n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _rsum_call(x, block, interpret):
    return reduce_sum_pallas(x, block=block, interpret=interpret)


def reduce_sum(x: jax.Array, *, block: int | None = None,
               interpret: bool | None = None, tuner=None) -> jax.Array:
    n = x.shape[0]
    if block is None and tuner is not None:
        block = _tuned_block_1d(
            tuner, "reduce_sum", n, x.dtype, arrays_in_vmem=1,
            call=lambda xz, b: _rsum_call(xz, b, interpret))
    if block is None:
        block = tuning.plan_1d(n, bytes_per_elem=x.dtype.itemsize,
                               arrays_in_vmem=1).block
    padded = ((n + block - 1) // block) * block
    return _rsum_call(_pad_1d(x, padded), block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _iscan_call(x, block, interpret):
    return inclusive_scan_pallas(x, block=block, interpret=interpret)


def inclusive_scan(x: jax.Array, *, block: int | None = None,
                   interpret: bool | None = None, tuner=None) -> jax.Array:
    n = x.shape[0]
    if block is None and tuner is not None:
        block = _tuned_block_1d(
            tuner, "inclusive_scan", n, x.dtype, arrays_in_vmem=2,
            call=lambda xz, b: _iscan_call(xz, b, interpret))
    if block is None:
        block = tuning.plan_1d(n, bytes_per_elem=x.dtype.itemsize,
                               arrays_in_vmem=2).block
    padded = ((n + block - 1) // block) * block
    out = _iscan_call(_pad_1d(x, padded), block, interpret)
    return out[:n]


# pallas_call has no autodiff rule, but the training step differentiates
# through model-layer norms when --kernel-autotune reroutes them here: the
# forward stays the fused kernel, the backward is the closed-form RMSNorm
# VJP in plain jnp (f32, matching the kernel's compute dtype).
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2))
def _rmsnorm_diffable(eps, block_rows, interpret, x, gamma):
    return rmsnorm_pallas(x, gamma, eps=eps, block_rows=block_rows,
                          interpret=interpret)


def _rmsnorm_diffable_fwd(eps, block_rows, interpret, x, gamma):
    out = rmsnorm_pallas(x, gamma, eps=eps, block_rows=block_rows,
                         interpret=interpret)
    return out, (x, gamma)


def _rmsnorm_diffable_bwd(eps, block_rows, interpret, res, dy):
    x, gamma = res
    xf = x.astype(jnp.float32)
    gf = gamma.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    xhat = xf * r
    dg = jnp.sum(dyf * xhat, axis=0).astype(gamma.dtype)
    gdy = dyf * gf
    dx = (gdy - xhat * jnp.mean(gdy * xhat, axis=-1, keepdims=True)) * r
    return dx.astype(x.dtype), dg


_rmsnorm_diffable.defvjp(_rmsnorm_diffable_fwd, _rmsnorm_diffable_bwd)


@functools.partial(jax.jit, static_argnames=("eps", "block_rows", "interpret"))
def _rmsnorm_call(x, gamma, eps, block_rows, interpret):
    return _rmsnorm_diffable(eps, block_rows, interpret, x, gamma)


def rmsnorm(x: jax.Array, gamma: jax.Array, *, eps: float = 1e-6,
            block_rows: int | None = None,
            interpret: bool | None = None, tuner=None) -> jax.Array:
    """x: (..., d) — leading dims flattened to rows."""
    shape = x.shape
    d = shape[-1]
    rows = 1
    for s in shape[:-1]:
        rows *= s
    x2 = x.reshape(rows, d)
    if block_rows is None and tuner is not None:
        # Row blocks: an element is one d-wide row, tiles are sublanes.
        def run(br: int) -> None:
            rp = ((rows + br - 1) // br) * br
            jax.block_until_ready(_rmsnorm_call(
                jnp.zeros((rp, d), x.dtype), jnp.zeros((d,), gamma.dtype),
                eps, br, interpret))

        block_rows = tuner.plan_1d(
            f"rmsnorm_d{d}", rows, run, dtype=str(x.dtype),
            bytes_per_elem=d * x.dtype.itemsize, arrays_in_vmem=2,
            align=tuning.SUBLANE,
            prior=min(128, max(8, rows))).block
    block_rows = block_rows or min(128, max(8, rows))
    padded = ((rows + block_rows - 1) // block_rows) * block_rows
    if padded != rows:
        x2 = jnp.pad(x2, ((0, padded - rows), (0, 0)))
    out = _rmsnorm_call(x2, gamma, eps, block_rows, interpret)
    return out[:rows].reshape(shape)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int | None = None,
                    scale: float | None = None,
                    block_q: int | None = None, block_kv: int | None = None,
                    interpret: bool | None = None,
                    tuner=None) -> jax.Array:
    """Padded + adaptively-tiled flash attention.  Shapes as in
    flash_attention_pallas; arbitrary Sq/Skv (padding handled here)."""
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    skv = k.shape[2]
    # The tuner searches (block_q, block_kv) *pairs*; with one block
    # pinned by the caller the winner's other half would come from a
    # pairing that was never measured, so the search only runs when both
    # are free (a pinned block falls through to the analytic plan).
    if block_q is None and block_kv is None and tuner is not None:
        def run(bq: int, bk: int) -> None:
            sq_p = ((sq + bq - 1) // bq) * bq
            skv_p = ((skv + bk - 1) // bk) * bk
            jax.block_until_ready(_flash_call(
                jnp.zeros((b, hq, sq_p, d), q.dtype),
                jnp.zeros((b, hkv, skv_p, d), k.dtype),
                jnp.zeros((b, hkv, skv_p, d), v.dtype),
                causal, window, scale, skv, bq, bk, sq, interpret))

        block_q, block_kv = tuner.plan_attention(
            "flash_attention", sq, skv, d, run, dtype=str(q.dtype),
            bytes_per_elem=q.dtype.itemsize,
            variant=(causal, window))
    if block_q is None or block_kv is None:
        bq, bk = tuning.plan_attention(sq, skv, d,
                                       bytes_per_elem=q.dtype.itemsize)
        block_q = block_q or bq
        block_kv = block_kv or bk
    block_q = min(block_q, max(8, sq))
    block_kv = min(block_kv, max(128, skv))
    sq_p = ((sq + block_q - 1) // block_q) * block_q
    skv_p = ((skv + block_kv - 1) // block_kv) * block_kv
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, skv_p - skv), (0, 0)))
    out = _flash_call(qp, kp, vp, causal, window, scale, skv,
                      block_q, block_kv, sq, interpret)
    return out[:, :, :sq]


@functools.partial(jax.jit, static_argnames=(
    "causal", "window", "scale", "kv_len", "block_q", "block_kv", "sq_true",
    "interpret"))
def _flash_call(q, k, v, causal, window, scale, kv_len, block_q, block_kv,
                sq_true, interpret):
    return flash_attention_pallas(
        q, k, v, causal=causal, window=window, scale=scale, kv_len=kv_len,
        sq_true=sq_true, block_q=block_q, block_kv=block_kv,
        interpret=interpret)

"""Map-type algorithms: transform, for_each, copy, fill, generate.

These are the algorithms the paper classifies as "map-type" (Section 1).
Each takes an execution policy first, mirroring the C++ API:

    transform(par.on(adaptive(HostParallelExecutor())), x, fn)
    transform(par.on(HostParallelExecutor()).with_(acc), x, fn)   # equivalent
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp

from . import detail


def _chunk_key(fn: Callable, x: jax.Array, tag: str):
    return (tag, id(fn), str(x.dtype))


def transform(policy, x: jax.Array, fn: Callable,
              y: jax.Array | None = None) -> jax.Array:
    """out[i] = fn(x[i])  (or fn(x[i], y[i]) for the binary overload)."""
    arrays = (x,) if y is None else (x, y)
    jf = jax.jit(jnp.vectorize(fn) if _is_scalar_fn(fn) else fn)
    count = x.shape[0]
    body = detail.measured_body(jf, *arrays)
    p = detail.plan(policy, count, body, key=_chunk_key(fn, x, "transform"))
    mexec = detail.mesh_executor_of(p.executor)
    if mexec is not None and p.parallel:
        if y is None:
            return detail.mesh_map(mexec, p.cores, jf, x)
        # binary: zip shards by stacking then splitting inside the shard
        mesh = detail.submesh_1d(mexec, p.cores)
        from jax.sharding import PartitionSpec as P

        xp, n = detail.pad_to(x, p.cores)
        yp, _ = detail.pad_to(y, p.cores)
        f = jax.jit(jax.shard_map(
            jf, mesh=mesh, in_specs=(P("data"), P("data")),
            out_specs=P("data")))
        return f(xp, yp)[:n]
    return detail.run_map_chunks(p, jf, *arrays)


def _is_scalar_fn(fn: Callable) -> bool:
    """Heuristic: treat fns as array-level (preferred).  Users pass
    jnp-vectorised bodies; scalar bodies can be wrapped with jnp.vectorize
    by the caller.  Kept for API parity."""
    return False


def for_each(policy, x: jax.Array, fn: Callable) -> jax.Array:
    """Apply fn to every element (returns the mapped array — JAX arrays are
    immutable, so for_each is transform with the result returned)."""
    return transform(policy, x, fn)


def copy(policy, x: jax.Array) -> jax.Array:
    return transform(policy, x, lambda a: a + 0)


def fill(policy, x: jax.Array, value) -> jax.Array:
    return transform(policy, x, lambda a: jnp.full_like(a, value))


def generate(policy, count: int, fn: Callable, dtype=jnp.float32) -> jax.Array:
    """out[i] = fn(i) — fn must be jnp-vectorised over an index array."""
    idx = jnp.arange(count, dtype=jnp.int32)
    out = transform(policy, idx, fn)
    return out.astype(dtype) if out.dtype != dtype else out

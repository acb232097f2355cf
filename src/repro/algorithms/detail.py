"""Shared machinery for the parallel-algorithm suite.

Every algorithm follows the paper's call sequence (Listing 1.1):

    t_iter = measure_iteration(params, exec, body, count)
    cores  = processing_units_count(params, exec, t_iter, count)
    chunk  = get_chunk_size(params, exec, t_iter, cores, count)

then executes its chunks on the policy's executor.  Two execution paths:

* host path — chunk thunks dispatched with ``bulk_async_execute`` and
  joined with ``when_all`` (each thunk is a jit-compiled slice
  computation; XLA releases the GIL);
* mesh path — shard_map over an acc-sized sub-mesh (taken when the bound
  executor is — or wraps — a ``MeshExecutor``; see ``mesh_executor_of``).

Execution parameters resolve from the policy first, then from the
executor's ``params`` annotation — that second step is what makes
``par.on(adaptive(ex))`` equivalent to ``par.on(ex).with_(acc)``.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from ..core import customization as cp
from ..core.executor import (Chunk, MeshExecutor, SequentialExecutor,
                             make_chunks, mesh_executor_of)
from ..core.future import when_all
from ..core.policy import ExecutionPolicy

__all__ = ["Plan", "plan", "measured_body", "run_map_chunks",
           "run_reduce_chunks", "mesh_executor_of", "submesh_1d",
           "pad_to", "mesh_map", "mesh_map_with_left_halo", "mesh_scan",
           "mesh_reduce"]


@dataclasses.dataclass
class Plan:
    executor: Any
    params: Any
    t_iter: float
    cores: int
    chunk_elems: int
    chunks: list[Chunk]

    @property
    def parallel(self) -> bool:
        return self.cores > 1 and len(self.chunks) > 1


def plan(policy: ExecutionPolicy, count: int,
         body: Callable[[int, int], Any] | Any = None,
         key: Any = None) -> Plan:
    """Run the three customization points and build the chunk list.

    The key (explicit, or derived from an analytic profile's name)
    labels the decision in the ExecutionModel trace and is where online
    feedback for this workload accumulates."""
    executor = policy.resolve_executor()
    params = policy.resolve_params(executor)
    if not policy.allows_parallel or count <= 1:
        return Plan(SequentialExecutor(), params, 0.0, 1, max(count, 1),
                    make_chunks(count, max(count, 1)))
    if key is None and getattr(body, "name", None) is not None \
            and not callable(body):
        key = ("algorithm", body.name)   # WorkloadProfile-style bodies
    kw = {"key": key} if (key is not None and params is not None
                          and hasattr(params, "measure_iteration")) else {}
    t_iter = cp.measure_iteration(params, executor, body, count, **kw)
    cores = cp.processing_units_count(params, executor, t_iter, count)
    chunk = cp.get_chunk_size(params, executor, t_iter, cores, count)
    return Plan(executor, params, t_iter, cores, chunk,
                make_chunks(count, chunk))


# ---------------------------------------------------------------------------
# Host path helpers
# ---------------------------------------------------------------------------

def measured_body(jitted_chunk_fn: Callable, *arrays: jax.Array):
    """Wrap a jitted chunk function into the body(start, size) thunk that
    ``measure_iteration`` times.  Synchronises before returning."""

    def body(start: int, size: int):
        out = jitted_chunk_fn(*(a[start:start + size] for a in arrays))
        jax.block_until_ready(out)
        return out

    return body


def run_map_chunks(plan_: Plan, jitted_chunk_fn: Callable,
                   *arrays: jax.Array) -> jax.Array:
    """Elementwise chunked execution + concatenation."""
    if not plan_.parallel:
        return jitted_chunk_fn(*arrays)

    def thunk(c: Chunk):
        out = jitted_chunk_fn(*(a[c.start:c.start + c.size] for a in arrays))
        jax.block_until_ready(out)
        return out

    futs = plan_.executor.bulk_async_execute(thunk, plan_.chunks)
    return jnp.concatenate(when_all(futs).result(), axis=0)


def run_reduce_chunks(plan_: Plan, jitted_partial_fn: Callable,
                      combine: Callable[[Any, Any], Any],
                      *arrays: jax.Array) -> Any:
    """Two-phase reduction: parallel chunk partials, serial combine."""
    if not plan_.parallel:
        return jitted_partial_fn(*arrays)

    def thunk(c: Chunk):
        out = jitted_partial_fn(*(a[c.start:c.start + c.size] for a in arrays))
        jax.block_until_ready(out)
        return out

    partials = when_all(
        plan_.executor.bulk_async_execute(thunk, plan_.chunks)).result()
    acc = partials[0]
    for p in partials[1:]:
        acc = combine(acc, p)
    return acc


# ---------------------------------------------------------------------------
# Mesh path helpers
# ---------------------------------------------------------------------------

def submesh_1d(mexec: MeshExecutor, cores: int) -> jax.sharding.Mesh:
    """A 1-d 'data' mesh over the first ``cores`` devices of the executor's
    mesh (cores already snapped to a divisor by MeshExecutor.submesh_size)."""
    devs = np.asarray(mexec.mesh.devices).reshape(-1)[:cores]
    return jax.sharding.Mesh(devs, ("data",))


def pad_to(x: jax.Array, multiple: int, fill=0):
    n = x.shape[0]
    rem = (-n) % multiple
    if rem == 0:
        return x, n
    pad_width = [(0, rem)] + [(0, 0)] * (x.ndim - 1)
    return jnp.pad(x, pad_width, constant_values=fill), n


def mesh_map(mexec: MeshExecutor, cores: int, shard_fn: Callable,
             x: jax.Array, fill=0) -> jax.Array:
    """Elementwise map via shard_map over an acc-chosen sub-mesh."""
    mesh = submesh_1d(mexec, cores)
    xp, n = pad_to(x, cores, fill)
    f = jax.jit(jax.shard_map(shard_fn, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data")))
    return f(xp)[:n]


def mesh_map_with_left_halo(mexec: MeshExecutor, cores: int,
                            shard_fn: Callable, x: jax.Array,
                            fill=0) -> jax.Array:
    """Map where each shard also needs its left neighbour's last element
    (adjacent_difference).  Halo moves by ppermute; shard_fn receives
    (local_block, left_halo_scalar_block) and the global shard index."""
    mesh = submesh_1d(mexec, cores)
    xp, n = pad_to(x, cores, fill)

    def wrapper(xl):
        idx = jax.lax.axis_index("data")
        last = xl[-1:]
        left = jax.lax.ppermute(
            last, "data", [(i, (i + 1) % cores) for i in range(cores)])
        return shard_fn(xl, left, idx)

    f = jax.jit(jax.shard_map(wrapper, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data")))
    return f(xp)[:n]


def mesh_scan(mexec: MeshExecutor, cores: int, x: jax.Array,
              local_scan: Callable, local_total: Callable,
              apply_offset: Callable, identity) -> jax.Array:
    """Distributed prefix sum: shard-local scan, all-gather of shard totals,
    local offset from an exclusive scan of the totals."""
    mesh = submesh_1d(mexec, cores)
    xp, n = pad_to(x, cores, identity)

    def wrapper(xl):
        idx = jax.lax.axis_index("data")
        scanned = local_scan(xl)
        total = local_total(xl)
        totals = jax.lax.all_gather(total, "data")        # (cores,)
        mask = jnp.arange(cores) < idx                     # exclusive
        offset = local_total(jnp.where(mask, totals, identity))
        return apply_offset(scanned, offset)

    f = jax.jit(jax.shard_map(wrapper, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data")))
    return f(xp)[:n]


def mesh_reduce(mexec: MeshExecutor, cores: int, x: jax.Array,
                local_partial: Callable, identity) -> jax.Array:
    """Shard-local partials, returned as a (cores,)-shaped array for the
    caller to combine (reduce-scatter shape; the final combine over
    ``cores`` elements is negligible)."""
    mesh = submesh_1d(mexec, cores)
    xp, _ = pad_to(x, cores, identity)

    def wrapper(xl):
        p = local_partial(xl)
        return jnp.reshape(p, (1,) + p.shape)

    f = jax.jit(jax.shard_map(wrapper, mesh=mesh,
                              in_specs=P("data"), out_specs=P("data")))
    return f(xp)

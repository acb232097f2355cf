"""HLO reshard/copy auditor for the fused decode loop.

PR 7 pinned the mesh-sharded pool's placement with a sharding
constraint plus explicit ``out_shardings`` precisely because GSPMD is
free to elect a different layout for a ``while`` carry — and a reshard
*inside* the decode loop body re-pays cache-pool-sized collectives
every iteration, silently turning a memory-bound step into a
link-bound one.  The lint pack (``repro.analysis.lint``) cannot see
that hazard: it lives in the partitioner, not in Python source.  This
module closes the gap by auditing the *compiled* artifact: lower the
live fused step, find every ``while`` loop body in the
post-partitioning HLO text, and fail if the body contains collective
traffic the sharding plan does not predict.

What the plan predicts for the decode body (measured on the 4x2
host-emulated serving mesh — see tests/test_analysis.py):

* ``all-reduce`` — tensor-parallel matmul partial sums over 'model';
  legitimate whenever ``model_parallel > 1`` (13 of them for the
  reduced qwen3 config: one per projection/MLP reduction).
* tiny ``all-gather`` — the greedy argmax runs over the vocab-sharded
  logits, so each lane gathers a per-shard (max, argmax) pair across
  'model': result bytes are per-lane scalars (8 B observed).  Anything
  over ``small_gather_max`` is a resharded buffer, not an argmax lane:
  the deliberate replicate-the-pool injection gathers the full
  per-device cache row (16 KiB on the same config) — three orders of
  magnitude over the threshold.
* nothing else.  ``reduce-scatter`` / ``all-to-all`` /
  ``collective-permute`` in the body always mean the partitioner moved
  the carry; on a single device (no mesh) *any* collective is a bug.

Plain ``copy`` ops inside the body are counted and reported, but are
not a failure by themselves — copy-based resharding on one device
cannot be told apart from layout copies by text alone, which is exactly
why the strict-mode runtime guards exist.

The decode step writes one K/V token per active lane into the carried
pool in place, so nothing in the body needs a buffer the size of a pool
leaf.  ``pool_selects`` and ``pool_copies`` count the body's ``select``
and ``copy`` ops whose result has a pool leaf's shape: 0 when the
in-place write engaged, ``2 x n_attn_layers`` of either when a step
rewrites the whole pool through a ``where`` or copies its carry.  A
full-row merge (speculative verify rows, recurrent states) selects by
design and is counted too.

CLI::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
      PYTHONPATH=src python -m repro.analysis.hlo_audit \\
        --arch qwen3-0.6b --reduced --mesh 4,2

exits non-zero on violations (the CI ``hlo-audit`` gate), and
``--inject-reshard`` flips the deliberate mid-loop reshard on to prove
the gate can fail.
"""
from __future__ import annotations

import dataclasses
import json
import re

from .roofline import _COLL_RE, _SHAPE_RE, _shape_bytes

# Computation definitions start at column 0: ``%name (params) -> ty {``
# (the entry computation carries an ``ENTRY`` prefix) and end at the
# first closing brace back at column 0.
_COMP_RE = re.compile(r"^(?:ENTRY )?(%?[\w.\-]+) \(", re.M)
_BODY_RE = re.compile(r"body=%?([\w.\-]+)")
_REF_RE = re.compile(r"(?:calls|to_apply|body|condition)=%?([\w.\-]+)")
_WHILE_RE = re.compile(r"\bwhile\(")
_COPY_RE = re.compile(r"\bcopy\(")
# An op's result shape and name: ``= f32[4,2,256,16]{3,2,1,0} select(``
# (a TPU layout adds tiling in the braces).
_RESULT_RE = re.compile(r"=\s*\w+\[([\d,]*)\](?:\{[^}]*\})?\s+(select|copy)\(")

# The argmax lane gathers per-lane (max, argmax) scalars across the
# vocab shards — bytes, not buffers.  A gathered cache row is KiB+.
SMALL_GATHER_MAX = 1024


def computations(hlo_text: str) -> dict[str, str]:
    """Split dumped HLO module text into named computation bodies."""
    out: dict[str, str] = {}
    for m in _COMP_RE.finditer(hlo_text):
        name = m.group(1).lstrip("%")
        end = hlo_text.find("\n}", m.start())
        out[name] = hlo_text[m.start():end + 2 if end >= 0 else None]
    return out


def loop_body_texts(hlo_text: str) -> dict[str, str]:
    """``{body_name: text}`` for every ``while`` loop body, including
    computations the body references (``calls=``/``to_apply=`` fusions,
    nested loops) — a collective hidden in a called computation still
    runs every iteration."""
    comps = computations(hlo_text)
    out: dict[str, str] = {}
    for text in comps.values():
        for line in text.splitlines():
            if not _WHILE_RE.search(line):
                continue
            b = _BODY_RE.search(line)
            if b is None:
                continue
            root = b.group(1)
            seen: set[str] = set()
            stack = [root]
            while stack:
                name = stack.pop()
                if name in seen or name not in comps:
                    continue
                seen.add(name)
                stack.extend(r.group(1)
                             for r in _REF_RE.finditer(comps[name]))
            out[root] = "\n".join(comps[n] for n in sorted(seen))
    return out


@dataclasses.dataclass(frozen=True)
class LoopOp:
    """One collective (or copy) op found inside a loop body."""

    body: str
    kind: str
    result_bytes: int
    text: str


def _scan_ops(body_name: str, body_text: str,
              op_re: re.Pattern, kind: str | None = None) -> list[LoopOp]:
    ops = []
    for line in body_text.splitlines():
        m = op_re.search(line)
        if m is None:
            continue
        eq = line.rfind("=", 0, m.start())
        if eq < 0:
            continue            # operand reference, not a definition
        size = sum(_shape_bytes(d, s)
                   for d, s in _SHAPE_RE.findall(line[eq:m.start()]))
        ops.append(LoopOp(body_name, kind or m.group(1), int(size),
                          line.strip()))
    return ops


@dataclasses.dataclass
class AuditPolicy:
    """What the sharding plan predicts inside the decode loop body."""

    model_parallel: int = 1
    small_gather_max: int = SMALL_GATHER_MAX

    def violation(self, op: LoopOp) -> str | None:
        """None when the plan predicts ``op``; else the reason it fails."""
        if self.model_parallel > 1:
            if op.kind == "all-reduce":
                return None     # TP partial-sum reductions
            if op.kind == "all-gather" \
                    and op.result_bytes <= self.small_gather_max:
                return None     # vocab-sharded argmax lanes
            if op.kind == "all-gather":
                return (f"all-gather of {op.result_bytes} B in the loop "
                        f"body (> {self.small_gather_max} B): a resharded "
                        f"buffer, not an argmax lane")
            return (f"{op.kind} in the loop body: never part of the "
                    f"decode sharding plan")
        return (f"{op.kind} in the loop body of an unsharded step: no "
                f"collective is predicted without a mesh")


def pool_shaped_ops(bodies: dict[str, str],
                    pool_shapes: set[tuple[int, ...]]) -> dict[str, int]:
    """``{"select": n, "copy": n}``: the loop bodies' ops of those kinds
    whose result has one of ``pool_shapes``.  A computation a nested
    loop shares with its parent's body is counted once."""
    seen: set[str] = set()
    out = {"select": 0, "copy": 0}
    for text in bodies.values():
        for line in text.splitlines():
            m = _RESULT_RE.search(line)
            if m is None or line in seen:
                continue
            seen.add(line)
            shape = tuple(int(x) for x in m.group(1).split(",") if x)
            if shape in pool_shapes:
                out[m.group(2)] += 1
    return out


@dataclasses.dataclass
class AuditReport:
    n_bodies: int
    collectives: list[LoopOp]
    violations: list[tuple[LoopOp, str]]
    copy_count: int
    copy_bytes: int
    pool_selects: int = 0
    pool_copies: int = 0

    @property
    def ok(self) -> bool:
        return not self.violations

    def counts(self) -> dict[str, int]:
        out: dict[str, int] = {}
        for op in self.collectives:
            out[op.kind] = out.get(op.kind, 0) + 1
        return out

    def to_dict(self) -> dict:
        return {
            "ok": self.ok,
            "n_loop_bodies": self.n_bodies,
            "collective_counts": self.counts(),
            "copy_count": self.copy_count,
            "copy_bytes": self.copy_bytes,
            "pool_selects": self.pool_selects,
            "pool_copies": self.pool_copies,
            "violations": [
                {"body": op.body, "kind": op.kind,
                 "result_bytes": op.result_bytes,
                 "reason": reason, "hlo": op.text}
                for op, reason in self.violations],
        }


def audit_hlo(hlo_text: str, policy: AuditPolicy,
              pool_shapes: set[tuple[int, ...]] = frozenset()
              ) -> AuditReport:
    """Audit every ``while`` body in ``hlo_text`` against ``policy``;
    ``pool_shapes`` (the per-device shapes of the pool's leaves) feeds
    the pool-shaped ``select``/``copy`` counts."""
    collectives: list[LoopOp] = []
    violations: list[tuple[LoopOp, str]] = []
    copy_count = copy_bytes = 0
    bodies = loop_body_texts(hlo_text)
    for name, text in bodies.items():
        for op in _scan_ops(name, text, _COLL_RE):
            collectives.append(op)
            reason = policy.violation(op)
            if reason is not None:
                violations.append((op, reason))
        for op in _scan_ops(name, text, _COPY_RE, kind="copy"):
            copy_count += 1
            copy_bytes += op.result_bytes
    pooled = pool_shaped_ops(bodies, set(pool_shapes))
    return AuditReport(n_bodies=len(bodies), collectives=collectives,
                       violations=violations, copy_count=copy_count,
                       copy_bytes=copy_bytes,
                       pool_selects=pooled["select"],
                       pool_copies=pooled["copy"])


def audit_scheduler(sched, *, inject_reshard: bool = False,
                    small_gather_max: int = SMALL_GATHER_MAX
                    ) -> AuditReport:
    """Lower the scheduler's *live* fused decode step and audit it.

    ``inject_reshard=True`` rebuilds the step with the deliberate
    mid-loop reshard (``decode_loop._inject_reshard``) — the failure
    demonstration; the audited step is a separate jit, the scheduler's
    own dispatch path is untouched.
    """
    import jax.numpy as jnp

    from ..serve.decode_loop import (make_fused_decode_step,
                                     make_paged_decode_step)

    if not sched._fused:
        raise ValueError("hlo-audit needs the fused decode path "
                         "(dispatch_depth != None)")
    paged = bool(getattr(sched, "paged", False))
    if inject_reshard:
        if paged:
            step = make_paged_decode_step(
                sched.cfg, page_size=sched.pool.page_size,
                max_len=sched.max_len, kernel_tuner=sched.kernel_tuner,
                max_depth=sched.max_dispatch_depth,
                cache_shardings=sched.pool.shardings,
                _inject_reshard=True)
        else:
            step = make_fused_decode_step(
                sched.cfg, window=sched.window,
                kernel_tuner=sched.kernel_tuner,
                max_depth=sched.max_dispatch_depth,
                cache_shardings=sched.pool.shardings,
                _inject_reshard=True)
    else:
        step = sched._fused_step()
    n = sched.pool.n_slots
    pt = (sched.pool.page_table_array(),) if paged else ()
    spec_d = sched._spec_depth if getattr(sched, "_spec", False) else 1
    if spec_d >= 2 and not inject_reshard:
        # Speculative draft/verify step: same donation and loop-body
        # discipline as the plain fused step, plus the history ring —
        # audited with the live depth's compiled executable.
        lowered = sched._spec_step(spec_d).lower(
            sched.params, sched.pool.caches, *pt, sched._decode_hist(),
            jnp.zeros(n, jnp.int32), sched.pool.positions_array(),
            jnp.zeros(n, jnp.int32))
    else:
        lowered = step.lower(
            sched.params, sched.pool.caches, *pt, jnp.zeros(n, jnp.int32),
            sched.pool.positions_array(), jnp.zeros(n, jnp.int32))
    model_parallel = 1
    if sched.mesh is not None:
        model_parallel = int(dict(sched.mesh.shape).get("model", 1))
    return audit_hlo(lowered.compile().as_text(),
                     AuditPolicy(model_parallel=model_parallel,
                                 small_gather_max=small_gather_max),
                     pool_shapes=pool_leaf_shapes(sched.pool))


def pool_leaf_shapes(pool) -> set[tuple[int, ...]]:
    """Per-device shapes of the pool's leaves (a sharded leaf's shard
    shape: the post-partitioning HLO holds shards)."""
    import jax

    leaves = jax.tree.leaves(pool.caches)
    shardings = jax.tree.leaves(pool.shardings) \
        if pool.shardings is not None else [None] * len(leaves)
    return {tuple(x.shape) if sh is None else tuple(sh.shard_shape(x.shape))
            for x, sh in zip(leaves, shardings, strict=True)}


def format_report(report: AuditReport) -> str:
    lines = [f"hlo-audit: {report.n_bodies} loop body(ies), "
             f"collectives={report.counts() or '{}'}, "
             f"copies={report.copy_count} "
             f"({report.copy_bytes} B result), "
             f"pool-shaped selects={report.pool_selects} "
             f"copies={report.pool_copies}"]
    for op, reason in report.violations:
        lines.append(f"  VIOLATION [{op.body}] {reason}")
        lines.append(f"    {op.text[:140]}")
    lines.append("hlo-audit: " + ("clean" if report.ok else
                                  f"{len(report.violations)} violation(s)"))
    return "\n".join(lines)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.hlo_audit",
        description="audit the fused decode loop's compiled HLO for "
                    "unpredicted reshard traffic")
    ap.add_argument("--arch", default="qwen3-0.6b")
    ap.add_argument("--reduced", action="store_true", default=True,
                    help="reduced config (default: on — the audit is "
                         "structural, not a throughput run)")
    ap.add_argument("--mesh", default="off",
                    help="'DATA,MODEL' device counts, or 'off'")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=32)
    ap.add_argument("--depth", type=int, default=4)
    ap.add_argument("--paged", action="store_true",
                    help="audit the paged fused step (page-table "
                         "gathers + flat-store scatters in the body)")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--speculate", type=int, default=0,
                    help="audit the speculative draft/verify step at "
                         "this width instead of the plain fused step "
                         "(0 = off; incompatible with "
                         "--inject-reshard)")
    ap.add_argument("--inject-reshard", action="store_true",
                    help="deliberately reshard the pool inside the loop "
                         "body (the audit must then FAIL — gate "
                         "self-test)")
    ap.add_argument("--out", default=None,
                    help="write the report as JSON to this path")
    args = ap.parse_args(argv)

    import jax

    from ..configs import get_config
    from ..core import SequentialExecutor, adaptive
    from ..core.acc import AdaptiveCoreChunk
    from ..models import init_params
    from ..serve import ServeScheduler

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = init_params(jax.random.PRNGKey(0), cfg)
    mesh = None
    if args.mesh != "off":
        from ..launch.mesh import make_serve_mesh

        data, model = (int(x) for x in args.mesh.split(","))
        mesh = make_serve_mesh(data, model)
    sched = ServeScheduler(
        cfg, params, n_slots=args.slots, max_len=args.max_len,
        executor=adaptive(SequentialExecutor(), AdaptiveCoreChunk()),
        dispatch_depth=args.depth, mesh=mesh,
        paged=args.paged, page_size=args.page_size,
        speculate=args.speculate if args.speculate >= 2 else None)
    # The paged store is replicated over 'data' (prefix sharing — see
    # launch/sharding.paged_cache_specs), so the plan predicts one
    # all-gather of the per-step lane updates: (slots, Hkv_shard, D)
    # rows per attn layer, not scalars.  Raise the small-gather budget
    # to one lane-update row set; a gathered *pool* is still MiB+.
    gmax = SMALL_GATHER_MAX
    if args.paged and mesh is not None:
        model_par = int(dict(mesh.shape).get("model", 1))
        gmax = max(gmax, 4 * args.slots * cfg.head_dim_ *
                   -(-cfg.n_kv_heads // model_par))
    report = audit_scheduler(sched, inject_reshard=args.inject_reshard,
                             small_gather_max=gmax)
    print(format_report(report))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(report.to_dict(), f, indent=2)
    if report.n_bodies == 0:
        print("hlo-audit: no while loop found in the fused step "
              "(trip count folded?) — refusing to pass an empty audit")
        return 1
    return 0 if report.ok else 1


if __name__ == "__main__":
    import sys

    sys.exit(main())

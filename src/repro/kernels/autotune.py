"""Measured Pallas block autotuner — the paper's feedback loop reaching
the kernel grid.

``tuning.py`` maps the paper's quantities onto VMEM tiling analytically
(chunk → one grid step, T_m floor → minimum block).  That is the static
Overhead-Law *prior*; the paper's actual claim — and HPX Smart Executors'
result — is that **measured** per-workload overheads beat any static
formula.  This module closes that gap for the kernels themselves:

* **candidate generation** — a small neighbourhood around the analytic
  prior (halved/doubled blocks), every candidate tile-aligned and inside
  the VMEM double-buffering budget, so the search space is the set of
  plans the static model would already consider legal;
* **measurement harness** — each candidate is wall-clocked through the
  ``ExecutionModel`` engine's measured-search policy (core/model.py)
  with the same cold-call discipline as ``core/feedback.py``: one
  untimed call pays XLA compilation, then best-of-``repeats`` timed
  calls strip scheduler noise (compile seconds must never be recorded
  as a winner's cost);
* **persistence** — the winner is stored through ``CalibrationCache``'s
  versioned JSON store under a ``(kernel, shape-bucket, dtype, hardware)``
  ``DecisionKey``, so a later process (serving or training — they share
  the store) skips the search, while a *different* accelerator keys
  separately: winners tuned on another machine are never inherited, and
  machines sharing one store coexist instead of overwriting each other.

Shapes are bucketed to powers of two: nearby problem sizes share one
winner, keeping the store and the search effort bounded under a serving
load where every request length differs.

Since the ExecutionModel unification, ``KernelTuner`` is a thin
kernel-facing front-end: candidate generation and ``BlockPlan``
packaging live here; the search loop, the store round-trip and the
decision trace live on the engine (one trace for kernel, algorithm,
serve and train decisions).
"""
from __future__ import annotations

import collections
import dataclasses
import math
from typing import Callable, Hashable, Sequence

from ..core.calibration import CalibrationCache
from ..core.hardware import TPU_V5E, HardwareSpec
from ..core.model import DecisionKey, ExecutionModel, hardware_key
from . import tuning
from .tuning import (LANE, SUBLANE, TILE_1D, BlockPlan,
                     attention_live_bytes, max_block_1d)

KEY_NAMESPACE = "pallas_block"

__all__ = ["KernelTuner", "TuneReport", "KEY_NAMESPACE", "hardware_key",
           "shape_bucket", "candidates_1d", "candidates_attention",
           "attention_live_bytes"]


def shape_bucket(n: int) -> int:
    """Smallest power of two >= n (>= 1) — the shape-key granularity."""
    return 1 << max(int(n) - 1, 0).bit_length()


def candidates_1d(n: int, *, bytes_per_elem: int = 4,
                  arrays_in_vmem: int = 2, hw: HardwareSpec = TPU_V5E,
                  align: int = TILE_1D, prior: int | None = None,
                  vmem_fraction: float = 0.25) -> list[int]:
    """Candidate block sizes for a 1-d kernel, analytic prior first.

    The prior (``tuning.plan_1d`` unless given) is bracketed by /4, /2,
    x2, x4 neighbours plus the budget extremes; everything is
    ``align``-aligned, within [align, max_block], and no wider than the
    padded problem — properties the tests sweep.
    """
    n = max(int(n), 1)
    cap = max_block_1d(bytes_per_elem=bytes_per_elem,
                       arrays_in_vmem=arrays_in_vmem, hw=hw, align=align,
                       vmem_fraction=vmem_fraction)
    cap = min(cap, ((n + align - 1) // align) * align)
    if prior is None:
        prior = tuning.plan_1d(n, bytes_per_elem=bytes_per_elem,
                               arrays_in_vmem=arrays_in_vmem, hw=hw,
                               vmem_fraction=vmem_fraction,
                               align=align).block

    def snap(b: int) -> int:
        return min(max((int(b) // align) * align, align), cap)

    prior = snap(prior)
    out = [prior]
    for b in (prior // 4, prior // 2, prior * 2, prior * 4, align, cap):
        b = snap(b)
        if b not in out:
            out.append(b)
    return out


def candidates_attention(sq: int, skv: int, d: int, *,
                         bytes_per_elem: int = 2,
                         hw: HardwareSpec = TPU_V5E,
                         vmem_fraction: float = 0.5
                         ) -> list[tuple[int, int]]:
    """Candidate (block_q, block_kv) pairs, analytic prior first.

    Each axis of the prior is varied by x1/2, x1, x2; pairs must stay
    tile-aligned (SUBLANE for q, LANE for kv), inside the VMEM budget,
    and no larger than the padded sequence lengths.
    """
    budget = hw.vmem_bytes * vmem_fraction / 2.0
    pbq, pbk = tuning.plan_attention(sq, skv, d,
                                     bytes_per_elem=bytes_per_elem, hw=hw,
                                     vmem_fraction=vmem_fraction)
    cap_q = ((max(sq, 1) + SUBLANE - 1) // SUBLANE) * SUBLANE
    cap_k = ((max(skv, 1) + LANE - 1) // LANE) * LANE
    out: list[tuple[int, int]] = []
    for fq in (1.0, 0.5, 2.0):
        for fk in (1.0, 0.5, 2.0):
            bq = min(max((int(pbq * fq) // SUBLANE) * SUBLANE, SUBLANE),
                     cap_q)
            bk = min(max((int(pbk * fk) // LANE) * LANE, LANE), cap_k)
            if attention_live_bytes(bq, bk, d, bytes_per_elem) > budget:
                continue
            if (bq, bk) not in out:
                out.append((bq, bk))
    if not out:  # prior itself may exceed a tiny budget: smallest tile
        out = [(SUBLANE, LANE)]
    return out


@dataclasses.dataclass(frozen=True)
class TuneReport:
    """One resolved lookup: where the blocks came from and what each
    candidate cost (empty timings when the store already had a winner)."""

    key: tuple
    winner: tuple
    prior: tuple
    measured: bool
    timings: tuple[tuple[tuple, float], ...] = ()

    @property
    def prior_seconds(self) -> float | None:
        for cand, sec in self.timings:
            if cand == self.prior:
                return sec
        return None

    @property
    def winner_seconds(self) -> float | None:
        for cand, sec in self.timings:
            if cand == self.winner:
                return sec
        return None


class KernelTuner:
    """Per-(kernel, shape-bucket, dtype, hardware) measured block store.

    ``run`` callables passed to the ``plan_*`` methods execute the real
    kernel once for a candidate on synthetic data of the right shape and
    must synchronise internally (``jax.block_until_ready``) — the same
    contract the executor feedback layer imposes on timed thunks.  The
    engine's search policy wraps every probe in an eager escape hatch,
    so the synthetic arrays stay concrete and the kernel really executes
    even when the consumer is mid-trace inside an outer ``jax.jit``
    (without it the probes would be staged and the clock would time
    tracing).
    """

    def __init__(self, cache: CalibrationCache | None = None, *,
                 hw: HardwareSpec = TPU_V5E, repeats: int = 3,
                 hardware: str | None = None):
        self.cache = cache if cache is not None else CalibrationCache()
        self.model = ExecutionModel.of(self.cache)
        self.hw = hw
        self.repeats = max(int(repeats), 1)
        self.hardware = hardware if hardware is not None else hardware_key()
        self.searches = 0      # measured searches (cache misses)
        self.cache_hits = 0    # lookups answered from the store
        # Recent lookups for benchmarks/tests; bounded — a serving loop
        # resolves a plan per compiled shape forever.
        self.reports: collections.deque[TuneReport] = \
            collections.deque(maxlen=256)

    @classmethod
    def persistent(cls, cache_dir: str | None = None, **kw) -> "KernelTuner":
        """A tuner over the same persistent store the acc calibrations
        use — training and serving processes share winners through it."""
        return cls(CalibrationCache.persistent(cache_dir), **kw)

    def _resolve(self, key: DecisionKey, candidates: Sequence[tuple],
                 run: Callable[..., None], fields: tuple[str, ...]) -> tuple:
        """Winner for ``key`` (which includes the hardware id): resolved
        by the ExecutionModel — from the store when present, else the
        measured-search policy sweeps ``candidates`` and persists."""
        decision = self.model.tuned_blocks(key, candidates, run, fields,
                                           repeats=self.repeats)
        measured = bool(decision.input("measured"))
        if measured:
            self.searches += 1
        else:
            self.cache_hits += 1
        self.reports.append(TuneReport(
            key=key.cache_key(), winner=decision.block_plan,
            prior=tuple(candidates[0]), measured=measured,
            timings=tuple(decision.input("timings", ()))))
        return decision.block_plan

    # -- public planning entry points ----------------------------------------
    def plan_1d(self, kernel: str, n: int,
                run: Callable[[int], None], *, dtype="float32",
                bytes_per_elem: int = 4, arrays_in_vmem: int = 2,
                align: int = TILE_1D, prior: int | None = None,
                vmem_fraction: float = 0.25) -> BlockPlan:
        """Measured ``BlockPlan`` for a 1-d kernel.

        ``run(block)`` must execute the kernel with that block size on a
        representative (padded) input and block until ready.
        """
        n = max(int(n), 1)
        cands = candidates_1d(n, bytes_per_elem=bytes_per_elem,
                              arrays_in_vmem=arrays_in_vmem, hw=self.hw,
                              align=align, prior=prior,
                              vmem_fraction=vmem_fraction)
        key = DecisionKey(kind=KEY_NAMESPACE,
                          shape=(kernel, shape_bucket(n)),
                          dtype=str(dtype), hardware=self.hardware)
        (block,) = self._resolve(key, [(c,) for c in cands],
                                 lambda b: run(int(b)), ("block",))
        block = min(block, ((n + align - 1) // align) * align)
        grid = math.ceil(n / block)
        return BlockPlan(block=block, grid=grid, padded=block * grid)

    def plan_attention(self, kernel: str, sq: int, skv: int, d: int,
                       run: Callable[[int, int], None], *, dtype="bfloat16",
                       bytes_per_elem: int = 2, variant: Hashable = (),
                       vmem_fraction: float = 0.5) -> tuple[int, int]:
        """Measured (block_q, block_kv) for a flash-attention-shaped
        kernel; ``run(bq, bk)`` executes it with those tiles.

        ``variant`` is any extra configuration that changes the work per
        tile — causal flag, sliding window — and therefore must key
        separately: the measurement runs under the caller's config, so a
        winner measured with one masking setup says nothing about
        another (a causal grid skips ~half its tiles).
        """
        cands = candidates_attention(sq, skv, d,
                                     bytes_per_elem=bytes_per_elem,
                                     hw=self.hw,
                                     vmem_fraction=vmem_fraction)
        # raw= pins the exact pre-unification (schema v2) tuple order —
        # dtype before variant — so winners persisted by older processes
        # keep resolving; the typed fields label the trace only.
        key = DecisionKey(kind=KEY_NAMESPACE,
                          shape=(kernel, shape_bucket(sq),
                                 shape_bucket(skv), int(d), repr(variant)),
                          dtype=str(dtype), hardware=self.hardware,
                          raw=(KEY_NAMESPACE, kernel, shape_bucket(sq),
                               shape_bucket(skv), int(d), str(dtype),
                               repr(variant), self.hardware))
        bq, bk = self._resolve(key, cands,
                               lambda q, k: run(int(q), int(k)),
                               ("block_q", "block_kv"))
        # A cached bucket-mate's winner may exceed this call's (smaller)
        # padded sequence; cap like plan_1d caps its winner to n.
        bq = min(bq, ((max(sq, 1) + SUBLANE - 1) // SUBLANE) * SUBLANE)
        bk = min(bk, ((max(skv, 1) + LANE - 1) // LANE) * LANE)
        return bq, bk

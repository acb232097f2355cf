"""Adaptive BlockSpec selection — the paper's chunking model applied to
VMEM tiling.

On a TPU chip a Pallas grid runs on one TensorCore with the grid steps
software-pipelined (HBM→VMEM DMA of step i+1 overlaps compute of step i).
The paper's quantities map as:

* "core"            → the TensorCore (1 per chip for this purpose);
* "chunk"           → one grid step's block;
* C = 8 chunks/core → minimum pipeline depth: at least 8 grid steps so the
  DMA/compute pipeline is busy and a straggling step costs ≤ 1/8 of the
  work (same load-balance argument as the paper's work stealing);
* T_m floor         → block must be big enough that per-step launch
  overhead is amortised (and MXU/VPU lanes are full): blocks are rounded
  to the 128-lane × 8-sublane tile and bounded by the VMEM budget.
"""
from __future__ import annotations

import dataclasses
import math

from ..core.hardware import TPU_V5E, HardwareSpec
from ..core.overhead_law import DEFAULT_CHUNKS_PER_CORE

LANE = 128          # TPU lane width (last dim tile)
SUBLANE = 8         # float32 sublane tile (second-to-last dim)
# A 1-d f32 array is laid out in whole (SUBLANE, LANE) tiles, and Mosaic
# refuses a 1-d block that does not divide that tiling ("has tiling
# (1024), but its block shape ... is not divisible"): 1-d blocks are
# multiples of this.
TILE_1D = LANE * SUBLANE


def round_up(x: int, m: int) -> int:
    return ((x + m - 1) // m) * m


def resolve_interpret(interpret: bool | None) -> bool:
    """``interpret=None`` means: compile with Mosaic on a TPU backend,
    run the Pallas interpreter anywhere else.  Every kernel entry point
    resolves its argument here, so a caller on the chip never lands in
    the interpreter by default."""
    if interpret is None:
        import jax

        return jax.default_backend() != "tpu"
    return bool(interpret)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    block: int          # elements per grid step (1-d kernels)
    grid: int           # number of grid steps
    padded: int         # padded array length (block * grid)


def max_block_1d(*, bytes_per_elem: int, arrays_in_vmem: int,
                 hw: HardwareSpec = TPU_V5E, align: int = TILE_1D,
                 vmem_fraction: float = 0.25) -> int:
    """Largest legal 1-d block under the double-buffered VMEM budget,
    floored at one tile.  The single budget model shared by the analytic
    planner below and the measured autotuner's candidate filter
    (autotune.py): budget = vmem * fraction / (2 * live arrays)."""
    budget = hw.vmem_bytes * vmem_fraction / (2.0 * arrays_in_vmem)
    return max((int(budget // bytes_per_elem) // align) * align, align)


def attention_live_bytes(bq: int, bk: int, d: int,
                         bytes_per_elem: int) -> int:
    """VMEM live set of one flash-attention grid step: q, k, v and the
    score tile in the kernel dtype plus the f32 accumulator.  Shared by
    ``plan_attention`` and the autotuner's candidate filter."""
    return (2 * bq * d + 2 * bk * d + bq * bk) * bytes_per_elem \
        + bq * d * 4


def plan_1d(n: int, *, bytes_per_elem: int = 4,
            arrays_in_vmem: int = 2,
            hw: HardwareSpec = TPU_V5E,
            chunks_per_core: int = DEFAULT_CHUNKS_PER_CORE,
            vmem_fraction: float = 0.25,
            align: int = TILE_1D) -> BlockPlan:
    """Choose a 1-d block size for an elementwise/stencil kernel.

    Eq. 10 with N_C = 1 TensorCore: block = N / C, then clamped to
    [align, vmem_budget] and rounded to the hardware tile (``align``).
    ``arrays_in_vmem`` counts live blocks (in + out + halo...) so double
    buffering fits: budget = vmem * fraction / (2 * arrays).  The VMEM
    budget is the hard constraint: ``max_block_1d`` floors it at one
    tile, never above.
    """
    n = max(int(n), 1)
    max_block = max_block_1d(bytes_per_elem=bytes_per_elem,
                             arrays_in_vmem=arrays_in_vmem, hw=hw,
                             vmem_fraction=vmem_fraction, align=align)
    target = round_up(math.ceil(n / chunks_per_core), align)
    block = min(max(target, align), max_block, round_up(n, align))
    grid = math.ceil(n / block)
    return BlockPlan(block=block, grid=grid, padded=block * grid)


def plan_attention(sq: int, skv: int, d: int, *,
                   bytes_per_elem: int = 2,
                   hw: HardwareSpec = TPU_V5E,
                   vmem_fraction: float = 0.5) -> tuple[int, int]:
    """(block_q, block_kv) for flash attention.

    VMEM live set per step ≈ (Bq*D + 2*Bk*D + Bq*Bk + Bq*D acc) * bytes.
    Blocks are multiples of the tile; prefer square-ish blocks (maximises
    arithmetic intensity Bq*Bk / (Bq + Bk)).
    """
    budget = hw.vmem_bytes * vmem_fraction / 2.0  # double buffering
    bq = min(512, round_up(min(sq, 512), SUBLANE))
    while bq > SUBLANE:
        bk = min(1024, round_up(min(skv, 1024), LANE))
        while bk >= LANE:
            if attention_live_bytes(bq, bk, d, bytes_per_elem) <= budget:
                return min(bq, round_up(sq, SUBLANE)), min(bk, round_up(skv, LANE))
            bk //= 2
        bq //= 2
    return SUBLANE, LANE

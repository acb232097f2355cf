"""Pallas TPU kernels for the perf-critical compute hot spots, with
adaptive (acc-model) block tiling — static analytic plans (tuning.py)
or measured, persisted winners (autotune.py).  Validated in interpret
mode on CPU against the pure-jnp oracles in ref.py, and compiled for a
described TPU v5e by tests/test_chip_compile.py."""
from . import autotune, ops, ref, tuning
from .autotune import KernelTuner
from .ops import (adjacent_difference, artificial_work, flash_attention,
                  inclusive_scan, reduce_sum, rmsnorm)

__all__ = [
    "autotune", "ops", "ref", "tuning", "KernelTuner",
    "adjacent_difference", "artificial_work", "flash_attention",
    "inclusive_scan", "reduce_sum", "rmsnorm",
]

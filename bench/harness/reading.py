"""What a finished run holds, and the arithmetic its metric files share.

A metric file (``bench/metrics/<name>.py``) defines ``read(run)`` and
returns a number, or None where the run holds nothing to read (the
harness then leaves the metric out of the result line).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass
class Run:
    cell: object                  # spec.Cell
    dims: dict                    # cell.family.dims of the configuration
    dtype: str                    # stated compute dtype
    n_slots: int
    setup_s: float
    window: object                # serving.Window
    trace: object | None          # xtrace.Summary of a traced run
    peaks: dict                   # bench/peaks.json entry of this device


def percentile(xs, p: float) -> float | None:
    return float(np.percentile(np.asarray(xs, float), p)) if len(xs) \
        else None


def due_in_window(run: Run) -> list:
    w = run.window
    return [c for c in w.clients
            if c.due is not None and c.due < w.t0 + w.seconds]


def ttfts_ms(run: Run) -> list[float]:
    """Per request due in the window: first token received less due
    time.  Still waiting at the close: its wait so far; refused or
    failed: the window's length."""
    w = run.window
    t_end = w.t0 + w.seconds
    out = []
    for c in due_in_window(run):
        if c.status == "failed":
            out.append(w.seconds * 1e3)
        elif c.times and c.times[0] < t_end:
            out.append((c.times[0] - c.due) * 1e3)
        else:
            out.append((t_end - c.due) * 1e3)
    return out


def ttft_halves_ms(run: Run) -> tuple:
    """Median time to first token of the requests due in the window's
    first half, and in its second: a queue that grows through the window
    shows as a second half well above the first."""
    w = run.window
    mid = w.t0 + w.seconds / 2
    due = due_in_window(run)
    ttft = ttfts_ms(run)
    return tuple(percentile([t for c, t in zip(due, ttft, strict=True)
                             if (c.due < mid) == first], 50)
                 for first in (True, False))


def tpots_ms(run: Run) -> list[float]:
    """Per request with two tokens or more received in the window: the
    span from its first to its last token there, per later token."""
    t_end = run.window.t0 + run.window.seconds
    out = []
    for c in run.window.clients:
        ts = [t for t in c.times if t < t_end]
        if len(ts) >= 2:
            out.append((ts[-1] - ts[0]) / (len(ts) - 1) * 1e3)
    return out


def tokens_in_window(run: Run) -> int:
    t_end = run.window.t0 + run.window.seconds
    return sum(1 for c in run.window.clients for t in c.times if t < t_end)


def program_seconds(run: Run, name: str) -> float | None:
    """Device seconds of the programs named ``name`` in the trace."""
    if run.trace is None or name not in run.trace.programs:
        return None
    return run.trace.programs[name]


def decode_totals(run: Run) -> tuple[int, int]:
    """(lane-tokens the decode loop produced, sum of their kv_len) over
    the counted span: generated token ``j`` (0-based; token 0 comes from
    prefill) of a prompt of length L attends L + j positions."""
    tokens = kv = 0
    for r in run.window.requests:
        a, b = max(r["generated0"], 1), r["generated"]
        n = max(b - a, 0)
        tokens += n
        kv += n * r["prompt_len"] + (a + b - 1) * n // 2
    return tokens, kv


def model_flops(run: Run) -> int:
    """Operations the model needs for every prompt token prefilled and
    every token decoded in the counted span."""
    fam, total = run.cell.family, 0
    for r in run.window.requests:
        total += fam.prefill_flops(run.dims, r["prefilled"] - r["computed"],
                                   r["prefilled"])
    tokens, kv = decode_totals(run)
    return total + fam.decode_work(run.dims, run.dtype, 0, kv, tokens)[0]


def decode_iter_ms(run: Run) -> float | None:
    """Device time of the fused decode program (``fused``) per loop
    iteration (``decode_loop_iters``) over the span."""
    dev = program_seconds(run, "fused")
    iters = run.window.counters["decode_loop_iters"]
    if dev is None or not iters:
        return None
    return dev / iters * 1e3


def decode_roofline(run: Run) -> float | None:
    """The fused decode program's share of its roofline: the least time
    the chip needs for the work the iterations require (the family's
    ``decode_work``: weights once per iteration at the stated dtype, live
    state only, flops of live lanes), over the program's measured device
    time.  Decode at these batch sizes is bound by bytes in every
    iteration, so the bound of the sums is the sum of the bounds."""
    dev = program_seconds(run, "fused")
    iters = run.window.counters["decode_loop_iters"]
    if not dev or not iters:
        return None
    tokens, kv = decode_totals(run)
    flops, byts = run.cell.family.decode_work(run.dims, run.dtype, iters,
                                              kv, tokens)
    least = max(flops / run.peaks["bf16_flops_per_s"],
                byts / run.peaks["hbm_bytes_per_s"])
    return 100.0 * least / dev


def idle_share(run: Run) -> float | None:
    """Share of the traced span in which no operation ran on the device."""
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * (1.0 - run.trace.busy_s / run.trace.window_s)

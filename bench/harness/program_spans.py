"""The serving program's own spans (``repro.serve.spans``) inside a run's
window, for the metric files that read them.

The program keeps its spans in a bounded ring in this process; the
window is ``[run.window.t0, t0 + seconds]`` on the same clock
(``time.perf_counter``).  Every reader here returns None where the
program records no spans (a checkout without ``repro.serve.spans``), or
where the ring no longer holds the window's start: a sum over what is
left would be short.
"""
from __future__ import annotations

LANES = ("decode", "clamped", "prefill", "free_queued", "free_idle")


def in_window(run) -> list | None:
    """The spans that began and ended inside the run's window."""
    try:
        from repro.serve import spans
    except ImportError:
        return None
    t0 = run.window.t0
    return spans.RECORDER.between(t0, t0 + run.window.seconds)


def lane_shares(run) -> dict | None:
    """Percent of the decode loop's lane-iterations (every slot, every
    iteration) under each kind of ``LANES``, summed over the window's
    dispatches; None without a lane-iteration in it."""
    held = in_window(run)
    return None if held is None else shares(held)


def shares(held) -> dict | None:
    """``lane_shares`` of the spans ``held``."""
    totals = dict.fromkeys(LANES, 0)
    for s in held:
        if "lane_decode" in s.attrs:
            for k in LANES:
                totals[k] += s.attrs[f"lane_{k}"]
    whole = sum(totals.values())
    if not whole:
        return None
    return {k: 100.0 * v / whole for k, v in totals.items()}

"""Model flops of every prompt token prefilled and every token decoded
in the traced span, over the span's length times the bf16 peak (as
``mfu.batch``, in granite-batch)."""
from harness import reading


def read(run):
    if run.trace is None or not run.trace.window_s:
        return None
    return 100.0 * reading.model_flops(run) / (
        run.trace.window_s * run.peaks["bf16_flops_per_s"])

"""The whole step's share of the chip's peak: model flops of every
prompt token prefilled and every token decoded in the traced span, over
the device's busy time in it times the bf16 peak.  It reads the trace's
busy time and no program's name, so it stays read when a later change
renames or removes a kernel."""
from harness import reading


def read(run):
    if run.trace is None or not run.trace.busy_s:
        return None
    return 100.0 * reading.model_flops(run) / (
        run.trace.busy_s * run.peaks["bf16_flops_per_s"])

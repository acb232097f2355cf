"""The prefill program's share of its roofline: the family's
``prefill_flops`` of the prompt tokens computed in the traced span (the
Mamba layers' carried-state SSD among them), over the device time of the
``prefill_chunk`` programs times the bf16 peak.  Pad positions are work
the program did and the prompt did not need, so they lower the share."""
from harness import reading


def read(run):
    dev = reading.program_seconds(run, "prefill_chunk")
    if not dev:
        return None
    fam = run.cell.family
    flops = sum(fam.prefill_flops(run.dims, r["prefilled"] - r["computed"],
                                  r["prefilled"])
                for r in run.window.requests)
    if not flops:
        return None
    return 100.0 * flops / (dev * run.peaks["bf16_flops_per_s"])

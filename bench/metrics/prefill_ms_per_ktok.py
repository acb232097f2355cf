"""Device time of the prefill programs (``prefill_chunk``) per thousand
prompt tokens they computed in the span."""
from harness import reading


def read(run):
    dev = reading.program_seconds(run, "prefill_chunk")
    tokens = sum(r["computed"] for r in run.window.requests)
    if dev is None or not tokens:
        return None
    return dev * 1e3 / tokens * 1e3

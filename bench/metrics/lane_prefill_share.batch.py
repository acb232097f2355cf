"""Share of the decode loop's lane-iterations in which the slot held a
request still in prefill (``program_spans.lane_shares``)."""
from harness import program_spans


def read(run):
    shares = program_spans.lane_shares(run)
    return None if shares is None else shares["prefill"]

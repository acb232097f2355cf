"""Share of the decode loop's lane-iterations in which the slot was free
while requests waited after the tick's admission
(``program_spans.lane_shares``)."""
from harness import program_spans


def read(run):
    shares = program_spans.lane_shares(run)
    return None if shares is None else shares["free_queued"]

"""95th percentile, over requests with two tokens or more received in
the window, of each request's time per output token after its first.
Per-request spans, not single gaps: the fused loop delivers tokens in
bursts, and a percentile of gaps would fall as dispatches deepen."""
from harness import reading


def read(run):
    return reading.percentile(reading.tpots_ms(run), 95)

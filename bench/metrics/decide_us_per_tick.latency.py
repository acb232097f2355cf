"""Microseconds of ``serve.decide`` spans (every engine query the
scheduler makes: tick width and chunk, admission, dispatch depth, and
the rest) per ``serve.tick`` span in the window."""
from harness import program_spans


def read(run):
    held = program_spans.in_window(run)
    if held is None:
        return None
    ticks = sum(1 for s in held if s.name == "serve.tick")
    if not ticks:
        return None
    decide = sum(s.end - s.start for s in held if s.name == "serve.decide")
    return decide * 1e6 / ticks

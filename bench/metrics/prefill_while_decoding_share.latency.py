"""Seconds of the program's ``serve.prefill`` spans that began with at
least one lane decoding, over the window's seconds: the time prefill
chunks hold the host, and the device, while decode lanes wait."""
from harness import program_spans


def read(run):
    held = program_spans.in_window(run)
    if held is None:
        return None
    busy = sum(s.end - s.start for s in held
               if s.name == "serve.prefill" and s.attrs["decoding"] > 0)
    return 100.0 * busy / run.window.seconds

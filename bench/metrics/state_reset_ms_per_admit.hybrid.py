"""Host milliseconds of the ``serve.state_reset`` spans (zeroing an
admitted slot's recurrent state) per request admitted in the window (the
``admitted`` of its ``serve.admit`` spans).  None where the program
records no reset."""
from harness import program_spans


def read(run):
    held = program_spans.in_window(run)
    if held is None:
        return None
    resets = [s for s in held if s.name == "serve.state_reset"]
    admitted = sum(s.attrs.get("admitted", 0) for s in held
                   if s.name == "serve.admit")
    if not resets or not admitted:
        return None
    return sum(s.end - s.start for s in resets) * 1e3 / admitted

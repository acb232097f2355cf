"""Pad positions over all positions the prefill chunks computed in the
window: the ``pad`` attribute of each ``serve.prefill.chunk`` span over
its ``tokens`` (the bucket it ran at).  None where the program's chunks
carry no ``pad``."""
from harness import program_spans


def read(run):
    held = program_spans.in_window(run)
    if held is None:
        return None
    chunks = [s.attrs for s in held
              if s.name == "serve.prefill.chunk" and "pad" in s.attrs]
    computed = sum(a["tokens"] for a in chunks)
    if not computed:
        return None
    return 100.0 * sum(a["pad"] for a in chunks) / computed

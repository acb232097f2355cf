"""Host milliseconds per prefill chunk outside the chunk's own device
wait: each ``serve.prefill`` span less its ``serve.prefill.chunk``
children (slot reads, write-backs, the first-token fetch, the chunk's
token slice), over the chunks the spans ran."""
from harness import program_spans


def read(run):
    held = program_spans.in_window(run)
    if held is None:
        return None
    prefill = {s.id: s for s in held if s.name == "serve.prefill"}
    chunks = sum(s.attrs["chunks"] for s in prefill.values())
    if not chunks:
        return None
    waits = sum(s.end - s.start for s in held
                if s.name == "serve.prefill.chunk" and s.parent in prefill)
    host = sum(s.end - s.start for s in prefill.values()) - waits
    return host * 1e3 / chunks

"""A traced run counts only the window's last seconds, with the pipeline
flushed where the trace starts: the decode work the span's requests show
must equal what the scheduler's counters dispatched in it (CPU, tiny
size; the reduction of a TPU trace is tested on its own fixture)."""
import tempfile

from benchutil import tiny_cell

from harness import cli, reading, serving

SEED, SECONDS = 2**31 + 99, 5.0


def test_traced_span_counts_what_it_dispatched(monkeypatch):
    monkeypatch.setattr(serving, "TRACE_SECONDS", 2.0)
    cell = tiny_cell("qwen3-chat")
    sched = cli.scheduler(cell, SEED)
    sched.warmup()
    clients, warm = cli.clients_and_warm_prompts(cell, SEED, SECONDS)
    with tempfile.TemporaryDirectory() as trace_dir:
        win = serving.run_window(sched, cell.traffic, clients, warm, SECONDS,
                                 serving.CompileCounter(), trace_dir=trace_dir)
    run = reading.Run(cell=cell, dims=cell.family.dims(cell.model),
                      dtype="bfloat16", n_slots=4,
                      setup_s=0.0, window=win, trace=None, peaks={})
    tokens, _ = reading.decode_totals(run)
    assert 1.5 < win.span_s < 3.5
    assert tokens == win.counters["decode_tokens"] > 0
    assert sum(r["computed"] for r in win.requests) <= sum(
        r["prompt_len"] for r in win.requests)

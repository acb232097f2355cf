"""The per-layer metrics that read the program's own spans
(``harness/program_spans.py``), each read on a tiny CPU window of its
cell: a finite value from the whole window, lane shares that cover every
slot of every iteration and agree with ``batch_occupancy.batch``, and
nothing where the ring has lost the window's start."""
import math

import pytest
from benchutil import tiny_cell

from harness import cli, program_spans, reading, serving, spec

SEED, SECONDS = 2**31 + 77, 4.0


def window_run(name: str) -> reading.Run:
    cell = tiny_cell(name)
    sched = cli.scheduler(cell, SEED)
    sched.warmup()
    clients, warm = cli.clients_and_warm_prompts(cell, SEED, SECONDS)
    win = serving.run_window(sched, cell.traffic, clients, warm, SECONDS,
                             serving.CompileCounter())
    return reading.Run(cell=cell, dims=cell.family.dims(cell.model),
                       dtype="bfloat16", n_slots=sched.pool.n_slots,
                       setup_s=0.0, window=win, trace=None, peaks={})


@pytest.fixture(scope="module")
def chat():
    return window_run("qwen3-chat")


@pytest.fixture(scope="module")
def batch():
    return window_run("danube-batch")


@pytest.fixture(scope="module")
def batch_tail(batch):
    """Every span from the window's opening on: the span the bench's
    counters cover, to the front end's stop after the window closed."""
    from repro.serve import spans

    return spans.RECORDER.between(batch.window.t0, math.inf)


@pytest.mark.parametrize("metric", [
    "prefill_while_decoding_share.latency",
    "prefill_host_ms_per_chunk.latency",
    "decide_us_per_tick.latency"])
def test_chat_metrics_read_the_window(chat, metric):
    value = spec.metric_reader(metric)(chat)
    assert value is not None and math.isfinite(value) and value >= 0
    if metric.endswith("share.latency"):
        assert value <= 100


@pytest.mark.parametrize("metric", [
    "lane_prefill_share.batch", "lane_free_queued_share.batch"])
def test_batch_metrics_read_the_window(batch, metric):
    value = spec.metric_reader(metric)(batch)
    assert value is not None and 0 <= value <= 100


def test_lane_shares_cover_the_lanes_and_match_occupancy(batch, batch_tail):
    """The five shares sum to 100, and over the span the bench's counters
    cover the decoding share is ``batch_occupancy.batch``
    (decode_tokens over iterations x slots)."""
    shares = program_spans.lane_shares(batch)
    assert sum(shares.values()) == pytest.approx(100.0, abs=1e-9)
    occupancy = spec.metric_reader("batch_occupancy.batch")(batch)
    tail = program_spans.shares(batch_tail)
    assert tail["decode"] == pytest.approx(occupancy, rel=1e-12)
    assert shares["decode"] == pytest.approx(occupancy, abs=2.0)


def test_a_lost_window_start_reads_nothing(chat, monkeypatch):
    """A ring that is full and holds nothing from before the window's
    start has dropped part of the window: every metric reads None."""
    from repro.serve import spans

    small = spans.Recorder(size=2)
    for _ in range(2):
        with small.span("serve.tick"):
            pass
    monkeypatch.setattr(spans, "RECORDER", small)
    assert small.earliest_start() > chat.window.t0
    for metric in ("prefill_while_decoding_share.latency",
                   "prefill_host_ms_per_chunk.latency",
                   "decide_us_per_tick.latency",
                   "lane_prefill_share.batch",
                   "lane_free_queued_share.batch"):
        assert spec.metric_reader(metric)(chat) is None

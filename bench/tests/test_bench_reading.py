"""The metric arithmetic on a hand-made run: what counts, from when."""
import types

import pytest

from benchutil import BENCH

import families
from harness import reading, spec
from harness.xtrace import Summary

DENSE = families.named("dense")


def client(due, times=(), status="completed"):
    return types.SimpleNamespace(due=due, times=list(times), status=status)


def make_run(clients, requests=(), counters=None, trace=None):
    window = types.SimpleNamespace(t0=100.0, seconds=10.0, clients=clients,
                                   requests=list(requests),
                                   counters=counters or {})
    model = spec.load_json(BENCH / "configs" / "qwen3-0.6b.json")
    return reading.Run(cell=types.SimpleNamespace(family=DENSE),
                       dims=DENSE.dims(model), dtype="bfloat16",
                       n_slots=4, setup_s=1.0, window=window, trace=trace,
                       peaks={"bf16_flops_per_s": 197e12,
                              "hbm_bytes_per_s": 819e9})


def test_ttft_counts_waiting_and_refused_requests():
    run = make_run([
        client(100.0, [100.5, 101.0]),       # served: 500 ms
        client(105.0, [111.0]),              # first token after the close
        client(108.0, status="failed"),      # refused: the window's length
        client(109.0, status="unsent"),      # never sent: its wait so far
        client(112.0, [112.1]),              # due after the close: not counted
    ])
    assert sorted(reading.ttfts_ms(run)) == pytest.approx(
        [500.0, 1000.0, 5000.0, 10000.0])


def test_ttft_halves_split_by_due_time():
    run = make_run([client(100.0, [100.2]), client(101.0, [101.6]),
                    client(106.0, [109.0]), client(108.0, [108.5]),
                    client(109.0)])              # waiting at the close
    assert reading.ttft_halves_ms(run) == pytest.approx((400.0, 1000.0))


def test_tpot_and_tokens_use_only_the_window():
    run = make_run([client(100.0, [101.0, 101.2, 101.4, 112.0]),
                    client(101.0, [102.0])])
    assert reading.tpots_ms(run) == pytest.approx([200.0])
    assert reading.tokens_in_window(run) == 4


def test_decode_work_counts_live_context():
    reqs = [{"prompt_len": 10, "generated0": 0, "generated": 4,
             "computed": 10, "prefilled": 10},
            # its first 1,024 prompt tokens prefilled before the span
            {"prompt_len": 1100, "generated0": 0, "generated": 1,
             "computed": 76, "prefilled": 1100},
            # decoding before the span: only its tokens 3..5 count
            {"prompt_len": 20, "generated0": 3, "generated": 6,
             "computed": 0, "prefilled": 20}]
    run = make_run([], reqs, {"decode_loop_iters": 3, "decode_tokens": 6})
    # tokens 1..3 of the first request attend 11, 12, 13 positions, and
    # tokens 3..5 of the third 23, 24, 25
    assert reading.decode_totals(run) == (6, 36 + 72)
    flops = reading.model_flops(run)
    assert flops == DENSE.prefill_flops(run.dims, 0, 10) \
        + DENSE.prefill_flops(run.dims, 1024, 1100) \
        + 6 * 2 * DENSE.matmul_params(run.dims) \
        + DENSE.attn_flops(run.dims, 108)


def test_roofline_share_stays_under_the_peak():
    reqs = [{"prompt_len": 512, "generated0": 0, "generated": 65,
             "computed": 512, "prefilled": 512}]
    byts = DENSE.weight_bytes(make_run([]).dims, "bfloat16") * 64
    least = byts / 819e9
    trace = Summary(window_s=1.0, busy_s=0.5, n_devices=1,
                    programs={"fused": 2 * least}, program_runs={"fused": 1},
                    ops=[], idle_gaps=[])
    run = make_run([], reqs, {"decode_loop_iters": 64, "decode_tokens": 64},
                   trace)
    share = spec.metric_reader("decode_roofline.latency")(run)
    # half, plus the live KV: 64 tokens over 513..576 rows of 112 KiB
    assert 52.5 < share < 52.7
    assert spec.metric_reader("device_idle_share.latency")(run) == 50.0
    assert spec.metric_reader("decode_iter_ms.latency")(run) == \
        pytest.approx(2 * least / 64 * 1e3)
    untraced = make_run([], reqs, {"decode_loop_iters": 64})
    assert spec.metric_reader("decode_roofline.latency")(untraced) is None

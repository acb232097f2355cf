"""The trace reduction on a small trace recorded on a TPU v5e
(``fixtures/record_trace.py``): two runs of ``fused`` (a loop of eight
1024 x 1024 matmuls) and one of ``prefill_chunk``, a 50 ms host sleep
between them under the span ``host.sleep``, all inside ``bench.window``."""
from pathlib import Path

import pytest

import benchutil  # noqa: F401  (import paths)
from harness import xtrace

FIXTURE = str(Path(__file__).parent / "fixtures" / "small.xplane.pb")


@pytest.fixture(scope="module")
def whole():
    return xtrace.reduce(FIXTURE)


def test_programs_and_busy_time(whole):
    assert whole.n_devices == 1
    assert whole.program_runs == {"fused": 2, "prefill_chunk": 1}
    assert whole.programs["fused"] == pytest.approx(2 * 104.4e-6, rel=0.01)
    # busy is the union of the ops, within the programs' own time
    assert 0.9 * sum(whole.programs.values()) < whole.busy_s \
        <= sum(whole.programs.values()) * 1.0001
    assert whole.busy_s < whole.window_s


def test_ops_are_self_times_by_program_and_kind(whole):
    ops = dict(whole.ops)
    assert max(ops, key=ops.get) == "fused/convolution_tanh_fusion"
    assert ops["fused/while"] < 1e-6          # the loop's body is not its own
    assert sum(ops.values()) == pytest.approx(whole.busy_s, rel=0.02)
    assert xtrace.op_kind("%select_fusion.65 = (bf16[2]) fusion(x)") \
        == "select_fusion"
    assert xtrace.program_name("jit_prefill_chunk(330804)") == "prefill_chunk"


def test_idle_gap_put_down_to_the_host_span(whole):
    gaps = dict(whole.idle_gaps)
    assert max(gaps, key=gaps.get) == "host.sleep"
    assert gaps["host.sleep"] == pytest.approx(0.05, abs=0.005)
    assert sum(gaps.values()) == pytest.approx(
        whole.window_s - whole.busy_s, rel=1e-6)


def test_window_span_bounds_the_reduction(whole):
    win = xtrace.reduce(FIXTURE, "bench.window")
    assert win.window_s == pytest.approx(0.0535, abs=0.001)
    assert win.busy_s <= whole.busy_s
    assert sum(dict(win.idle_gaps).values()) == pytest.approx(
        win.window_s - win.busy_s, rel=1e-6)

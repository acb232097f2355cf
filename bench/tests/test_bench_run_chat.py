"""A whole run of the chat cell at a tiny size on the CPU: it reports
its end-to-end metrics and ``correct`` true; the fp8 control put in the
program's place, and each fault of the timed path the cell can have,
come out not correct."""
import benchrun


def test_chat_run_is_correct_and_control_fails():
    res = benchrun.run("qwen3-chat")
    limit = res["compared"]["logit_gap"]["limit"]
    assert res["correct"] and benchrun.gap(res) <= limit
    assert set(res["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert res["attempted"] > 10 and res["failed"] == 0
    assert list(res)[-1] == "compared"
    ctl = benchrun.run("qwen3-chat", control=True)
    assert not ctl["correct"]
    assert ctl["compared"]["control_logit_gap"]["value"] > limit
    assert ctl["program_logit_gap"] <= limit


def test_chat_kv_left_unchanged_is_not_correct(monkeypatch):
    benchrun.break_kv_merge(monkeypatch)
    assert not benchrun.run("qwen3-chat")["correct"]


def test_chat_altered_token_is_not_correct(monkeypatch):
    benchrun.alter_tokens(monkeypatch)
    assert not benchrun.run("qwen3-chat")["correct"]

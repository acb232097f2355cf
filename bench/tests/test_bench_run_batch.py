"""The closed-loop batch cell at a tiny size on the CPU."""
import benchrun


def test_batch_run_is_correct():
    res = benchrun.run("danube-batch")
    assert res["correct"]
    assert set(res["metrics"]) == {"output_tok_s", "setup_s"}
    assert res["metrics"]["output_tok_s"]["value"] > 0


def test_batch_control_is_not_correct():
    res = benchrun.run("danube-batch", control=True)
    assert not res["correct"]
    assert res["compared"]["control_logit_gap"]["value"] > \
        res["compared"]["control_logit_gap"]["limit"]


def test_batch_callers_draw_the_set_again_once_spent():
    """A set smaller than the window serves is drawn again from its start,
    so a fast program never leaves its callers idle."""
    res = benchrun.run("danube-batch", requests=8, clients=4)
    assert res["correct"] and res["attempted"] > 8


def test_batch_altered_token_is_not_correct(monkeypatch):
    benchrun.alter_tokens(monkeypatch)
    assert not benchrun.run("danube-batch")["correct"]

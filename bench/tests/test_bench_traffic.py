"""The traffic generator: one seed, one plan; every seed the same sizes
and gaps in another order; lengths inside the mix's clipped ranges."""
import collections

import numpy as np
import pytest

import benchutil  # noqa: F401  (import paths)
from harness import cli, spec, traffic

MIXES = ["chat", "batch"]
SEED = 2**31 + 977          # wider than 32 signed bits


def _mix(name):
    return spec.load_json(spec.BENCH / "traffic" / f"{name}.json")


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_plan_and_tokens(name):
    mix = _mix(name)
    a, b = traffic.plan(mix, SEED, 45), traffic.plan(mix, SEED, 45)
    assert a == b
    pa = traffic.prompts(a, 1000, SEED)
    pb = traffic.prompts(b, 1000, SEED)
    assert all(np.array_equal(x, y) for x, y in zip(pa, pb, strict=True))


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_sizes_in_another_order(name):
    mix = _mix(name)
    a, b = traffic.plan(mix, SEED, 45), traffic.plan(mix, 7, 45)

    def sizes(plan):
        return collections.Counter((r.prompt_len, r.new_tokens) for r in plan)

    assert [r.prompt_len for r in a] != [r.prompt_len for r in b]
    assert sorted(r.prompt_len for r in a) == sorted(r.prompt_len for r in b)
    assert sorted(r.new_tokens for r in a) == sorted(r.new_tokens for r in b)
    if mix["loop"] == "open":
        gaps = [np.diff([0.0] + [r.due_s for r in p]) for p in (a, b)]
        np.testing.assert_allclose(sorted(gaps[0]), sorted(gaps[1]))
    assert sum(sizes(a).values()) == sum(sizes(b).values())


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_clipped_ranges(name):
    mix = _mix(name)
    plan = traffic.plan(mix, SEED, 45)
    for key, attr in (("prompt", "prompt_len"), ("output", "new_tokens")):
        vals = [getattr(r, attr) for r in plan]
        lo, hi = mix[key]["min"], mix[key]["max"]
        assert lo <= min(vals) and max(vals) <= hi
        assert np.median(vals) == pytest.approx(mix[key]["median"], rel=0.1)
    # prompt plus output always fits the configurations' 2,048 slots
    assert max(r.prompt_len + r.new_tokens for r in plan) < 2048


def test_open_loop_rate_and_window():
    mix = _mix("chat")
    plan = traffic.plan(mix, SEED, 45)
    due = [r.due_s for r in plan]
    assert all(b > a for a, b in zip(due, due[1:]))
    in_window = sum(d < 45 for d in due)
    assert in_window == pytest.approx(mix["rate_rps"] * 45, rel=0.25)
    assert due[-1] > 45          # the plan outlasts the window


@pytest.mark.parametrize("name", MIXES)
def test_warm_set_is_every_prompt_length_alike_for_every_seed(name):
    """Warm-up covers every prompt length a run can send, and that set
    does not depend on the seed: each run after a checkout's first finds
    every program it warms in the compile cache."""
    mix = _mix(name)
    warm = {s: cli.warm_set(traffic.plan(mix, s, 51)) for s in (SEED, 7, 11)}
    lengths = {s: [r.prompt_len for r in w] for s, w in warm.items()}
    assert lengths[SEED] == lengths[7] == lengths[11]
    assert set(lengths[SEED]) == {r.prompt_len
                                  for r in traffic.plan(mix, SEED, 51)}
    assert len(set(lengths[SEED])) == len(lengths[SEED])


def test_warm_prompts_differ_from_the_window_prompts():
    mix = _mix("chat")
    plan = traffic.plan(mix, SEED, 51)
    warm = traffic.prompts(plan, 1000, SEED, warm=True)
    for p, w, r in zip(traffic.prompts(plan, 1000, SEED), warm, plan,
                       strict=True):
        assert len(p) == len(w) == r.prompt_len
    assert not np.array_equal(traffic.prompts(plan, 1000, SEED)[0], warm[0])

"""The generator: same seed, same schedule; another seed, the same work in
another order; the stagger turns one slot over every 32 steps; the open
loop offers its rate and keeps its due times."""

import json
import os

import numpy as np
import pytest

from perfbench import traffic

HERE = os.path.dirname(os.path.abspath(__file__))
MIXES = os.path.join(os.path.dirname(HERE), "traffic")


def mix(name):
    with open(os.path.join(MIXES, f"{name}.json")) as f:
        return json.load(f)


def sizes(plan):
    return [sorted(r[key] for c in plan["clients"] for r in c)
            for key in ("prompt_len", "new_tokens")]


def test_closed_same_seed_same_schedule_other_seed_other_order():
    m = mix("batch-decode")
    a = traffic.closed_schedule(m, 2**31 + 5)
    b = traffic.closed_schedule(m, 2**31 + 5)
    c = traffic.closed_schedule(m, 17)
    assert a == b
    assert a != c
    assert sizes(a) == sizes(c)  # the same work, whatever the seed


def test_closed_every_round_holds_the_same_lengths():
    m = mix("batch-decode")
    plan = traffic.closed_schedule(m, 3)["clients"]
    rounds = [sorted(c[r]["prompt_len"] for c in plan)
              for r in range(m["requests_per_client"])]
    assert all(r == rounds[0] for r in rounds)
    assert min(rounds[0]) >= 64 and max(rounds[0]) <= 512


@pytest.mark.parametrize("seed", [0, 1, 2**31 + 9])
def test_stagger_turns_one_slot_over_every_32_steps(seed):
    plan = traffic.closed_schedule(mix("batch-decode"), seed)
    steps = traffic.simulate_closed_turnovers(plan, 2000)
    assert len(steps) > 50
    assert set(np.diff(steps)) == {32}


def test_prompt_tokens_are_seeded_and_in_the_vocabulary():
    a = traffic.prompt_tokens(5, 3, 100, 50257)
    assert a == traffic.prompt_tokens(5, 3, 100, 50257)
    assert a != traffic.prompt_tokens(6, 3, 100, 50257)
    assert a != traffic.prompt_tokens(5, 3, 100, 50257, stream=8)
    assert len(a) == 100 and all(0 < t < 50257 for t in a)


OPEN = {"kind": "open", "rate_per_s": 3.2, "block": 32,
        "prompt_tokens": {"dist": "lognormal", "median": 96, "sigma": 0.7,
                          "lo": 16, "hi": 384},
        "new_tokens": {"dist": "lognormal", "median": 48, "sigma": 0.6,
                       "lo": 8, "hi": 128}}


def test_open_same_seed_same_schedule_same_work_for_every_seed():
    m = OPEN
    a = traffic.open_schedule(m, 11, 60.0)["arrivals"]
    assert a == traffic.open_schedule(m, 11, 60.0)["arrivals"]
    b = traffic.open_schedule(m, 12, 60.0)["arrivals"]
    assert [x["due_s"] for x in a] != [x["due_s"] for x in b]
    assert len(a) == len(b) and len(a) % m["block"] == 0
    assert len(a) >= m["rate_per_s"] * 60.0
    for key in ("prompt_len", "new_tokens"):
        assert sorted(x[key] for x in a) == sorted(x[key] for x in b)
    # Whole blocks offer exactly the mean rate: the last arrival of the
    # last whole block is due at blocks * block / rate.
    assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"], rel=1e-9)
    assert a[-1]["due_s"] == pytest.approx(len(a) / m["rate_per_s"],
                                           rel=0.03)
    prompts = [x["prompt_len"] for x in a]
    assert min(prompts) >= 16 and max(prompts) <= 384


def test_quantile_sizes_follow_their_law():
    q = traffic.quantile_sizes({"dist": "lognormal", "median": 96,
                                "sigma": 0.7, "lo": 16, "hi": 384}, 1001)
    assert q[500] == 96 and q.min() >= 16 and q.max() == 384
    u = traffic.quantile_sizes({"dist": "loguniform", "lo": 64, "hi": 512},
                               3)
    assert list(u) == [91, 181, 362]  # 64 * 8**(1/6, 3/6, 5/6)

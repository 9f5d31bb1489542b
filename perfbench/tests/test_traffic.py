"""The generator: same seed, same schedule; another seed, the same work in
another order, or with ``order_seed`` in the same; the stagger turns one
slot over every 32 steps; the open loop offers its rate and keeps its due
times."""

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
    m = mix("moe-batch-decode")  # ordered by the run's seed
    a = traffic.closed_schedule(m, 2**31 + 5)
    b = traffic.closed_schedule(m, 2**31 + 5)
    c = traffic.closed_schedule(m, 17)
    assert a == b
    assert a != c
    assert sizes(a) == sizes(c)  # the same work, whatever the seed


def table_widths(plan, steps, page_size):
    """The page-table width of each decode step of a closed schedule
    served as ``simulate_closed_turnovers`` models it: the next power of
    two over the pages of the batch's longest context."""
    queues = [list(c) for c in plan["clients"]]
    running = []
    for q in queues:
        r = q.pop(0)
        running.append([r["prompt_len"] + 1, r["new_tokens"] - 1])
    out = []
    for _ in range(steps):
        pages = max(-(-ctx // page_size) for ctx, left in running if left)
        out.append(1 << (pages - 1).bit_length())
        for c, (ctx, left) in enumerate(running):
            if left:
                running[c] = [ctx + 1, left - 1]
            else:  # admitted this step: prefill, first token
                r = queues[c].pop(0)
                running[c] = [r["prompt_len"] + 1, r["new_tokens"] - 1]
    return out


def test_a_fixed_order_gives_every_seed_the_same_steps():
    """Which requests share the batch decides how wide a page table each
    decode step reads: by the run's seed, 76-90 % of XL's steps are at
    width 64. A mix that sets ``order_seed`` has one schedule for every
    seed, so the same width at every step; the token ids still differ."""
    m = dict(mix("batch-decode"))
    page = m["engine_options"]["page_size"]
    fixed_order = m.pop("order_seed", None)
    by_seed = [table_widths(traffic.closed_schedule(m, seed), 2300, page)
               for seed in (2**31 + 11, 2**31 + 101)]
    assert by_seed[0] != by_seed[1]
    assert set(by_seed[0]) == {32, 64}
    m["order_seed"] = 5 if fixed_order is None else fixed_order
    plans = [traffic.closed_schedule(m, seed)
             for seed in (2**31 + 11, 2**31 + 101, 3)]
    assert plans[0] == plans[1] == plans[2]
    widths = table_widths(plans[0], 2300, page)
    assert set(widths) == {32, 64}
    req = plans[0]["clients"][3][2]
    assert traffic.prompt_tokens(2**31 + 11, req["index"], req["prompt_len"],
                                 50257) \
        != traffic.prompt_tokens(2**31 + 101, req["index"],
                                 req["prompt_len"], 50257)
    # The open loop's arrivals take the same field.
    o = dict(OPEN, order_seed=9)
    assert traffic.open_schedule(o, 1, 30.0) == traffic.open_schedule(
        o, 2, 30.0)


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

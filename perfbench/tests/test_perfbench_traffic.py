"""The traffic generator: the same work for every seed, in another
order."""
import json
import sys
from collections import Counter
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

from harness import traffic  # noqa: E402

CHAT = json.loads((BENCH / "traffic" / "chat.json").read_text())
BURST = json.loads((BENCH / "traffic" / "burst.json").read_text())
SAT = json.loads((BENCH / "traffic" / "saturate.json").read_text())


@pytest.mark.parametrize("seed", [0, 7, 2**31 + 12345, 2**40 + 3, -5])
def test_every_seed_gets_the_same_lengths(seed):
    a = traffic.plan(CHAT, 51.0, seed)
    b = traffic.plan(CHAT, 51.0, 1)
    assert Counter(p.prompt_len for p in a) == \
        Counter(p.prompt_len for p in b)
    assert Counter(p.max_tokens for p in a) == \
        Counter(p.max_tokens for p in b)
    assert len(a) == len(b)


def test_seeds_change_the_order_and_repeat_exactly():
    a = traffic.plan(CHAT, 51.0, 11)
    assert a == traffic.plan(CHAT, 51.0, 11)
    b = traffic.plan(CHAT, 51.0, 12)
    assert [p.prompt_len for p in a] != [p.prompt_len for p in b]
    assert [round(p.t, 6) for p in a] != [round(p.t, 6) for p in b]


def test_poisson_rate_span_and_ranges():
    p = traffic.plan(CHAT, 51.0, 3)
    rate = CHAT["streams"][0]["rate"]
    end = 51.0 + CHAT["tail_s"]
    segs = [(-CHAT["lead_s"], 0.0), (0.0, 51.0), (51.0, end)]
    for a, b in segs:
        assert len([x for x in p if a <= x.t < b]) == round(rate * (b - a))
    s = CHAT["streams"][0]
    assert all(s["prompt"]["lo"] <= x.prompt_len <= s["prompt"]["hi"]
               for x in p)
    assert all(s["output"]["lo"] <= x.max_tokens <= s["output"]["hi"]
               for x in p)


@pytest.mark.parametrize("seed", [1, 2**31 + 5, 99])
def test_the_window_holds_the_same_requests_for_every_seed(seed):
    def window(sd):
        return [x for x in traffic.plan(CHAT, 51.0, sd) if 0 <= x.t < 51]
    a, b = window(seed), window(7)
    assert Counter(x.prompt_len for x in a) == \
        Counter(x.prompt_len for x in b)
    assert Counter(x.max_tokens for x in a) == \
        Counter(x.max_tokens for x in b)


def test_lengths_follow_the_lognormal_median():
    v = traffic.lognormal_lengths(1001, 512, 0.8, 64, 3072)
    assert sorted(v)[500] == 512
    assert v.min() >= 64 and v.max() <= 3072


def test_bursts_land_where_the_file_says():
    p = [x for x in traffic.plan(BURST, 51.0, 5) if x.tier == "priority"]
    s = BURST["streams"][1]
    starts = [s["first_s"] + k * s["period_s"] for k in range(5)]
    assert len(p) == s["size"] * len(starts)
    for b in starts:
        inside = [x for x in p if b <= x.t < b + s["spread_s"]]
        assert len(inside) == s["size"]


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 3])
def test_every_block_holds_the_same_lengths(seed):
    """Above the knee the window serves the queue's head: with blocks,
    every run of 16 consecutive arrivals has the same lengths."""
    s = SAT["streams"][0]
    k = s["block"]
    base_p = Counter(traffic.lognormal_lengths(
        k, *(s["prompt"][x] for x in ("median", "sigma", "lo", "hi"))))
    base_o = Counter(traffic.lognormal_lengths(
        k, *(s["output"][x] for x in ("median", "sigma", "lo", "hi"))))
    p = traffic.plan(SAT, 51.0, seed)
    full = len(p) // k
    assert full >= 4
    for b in range(full):
        blk = p[b * k:(b + 1) * k]
        assert Counter(x.prompt_len for x in blk) == base_p
        assert Counter(x.max_tokens for x in blk) == base_o
    free = {x: v for x, v in SAT.items() if x != "schedule_seed"}
    assert [x.prompt_len for x in traffic.plan(free, 51.0, seed)[:k]] != \
        [x.prompt_len for x in traffic.plan(free, 51.0, seed + 1)[:k]]


@pytest.mark.parametrize("seed", [0, 2**31 + 12345, 2**40 + 3, -5])
def test_a_schedule_seed_gives_every_run_the_same_schedule(seed):
    """Above the knee the order of the queue's head decides a window's
    work, so the saturate file fixes its schedule: every run seed gets
    the schedule of ``schedule_seed``, the warm-up replay another."""
    assert "schedule_seed" in SAT
    a = traffic.plan(SAT, 51.0, seed)
    assert a == traffic.plan(SAT, 51.0, 1)
    assert a == traffic.plan(SAT, 51.0, SAT["schedule_seed"])
    assert a != traffic.plan(SAT, 51.0, seed, salt=1)
    assert traffic.plan(SAT, 51.0, seed, salt=1) == \
        traffic.plan(SAT, 51.0, 5, salt=1)


@pytest.mark.parametrize("traffic_file", ["chat", "saturate"])
def test_the_warm_up_replay_is_another_schedule_of_the_same_mix(
        traffic_file):
    tr = json.loads((BENCH / "traffic" / f"{traffic_file}.json").read_text())
    a = traffic.plan(tr, 51.0, 77)
    b = traffic.plan(tr, 51.0, 77, salt=1)
    assert a == traffic.plan(tr, 51.0, 77, salt=0)
    assert [x.t for x in a] != [x.t for x in b]
    k = tr["streams"][0].get("block", len(a))
    n = len(a) // k * k       # whole blocks (a last partial one differs)
    assert n and Counter(x.prompt_len for x in a[:n]) == \
        Counter(x.prompt_len for x in b[:n])

"""The traffic generator: the same schedule for one seed, another for
another, and the same amount of work for every seed."""
import collections
import json
import os

import pytest

from chipbench.harness.cli import BENCH_DIR
from chipbench.harness.traffic import make_schedule


@pytest.fixture(scope="module")
def chat():
    with open(os.path.join(BENCH_DIR, "traffic", "chat.json")) as f:
        return json.load(f)


def test_same_seed_same_schedule(chat):
    a = make_schedule(chat, 6.0, 11, 40.0, 50257)
    b = make_schedule(chat, 6.0, 11, 40.0, 50257)
    assert a == b


def test_other_seed_other_schedule(chat):
    a = make_schedule(chat, 6.0, 11, 40.0, 50257)
    b = make_schedule(chat, 6.0, 12, 40.0, 50257)
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]


def test_every_seed_offers_the_same_work(chat):
    """The cell's lead-in and its window are one stratum each: every
    seed offers the same count in both, placed as a Poisson process
    places them, and the same lengths in every block of requests."""
    width = chat["arrivals"]["stratum_s"]
    assert width == 30.0
    a = make_schedule(chat, 2.2, 1, 60.0, 50257)
    b = make_schedule(chat, 2.2, 2, 60.0, 50257)
    assert len(a) == len(b) == 132
    per = [collections.Counter(int(r["due_s"] // width) for r in s)
           for s in (a, b)]
    assert per[0] == per[1] == {0: 66, 1: 66}
    # inside a stratum the gaps are a Poisson process's: about a third
    # are shorter than 0.4 of the mean gap (1 - exp(-0.4) = 0.33; evenly
    # spaced arrivals would have none)
    gaps = [y["due_s"] - x["due_s"] for s in (a, b)
            for x, y in zip(s, s[1:])]
    short = sum(g < 0.4 / 2.2 for g in gaps) / len(gaps)
    assert 0.23 < short < 0.43
    # the same multiset of lengths in every block of requests
    block = chat["lengths_block"]
    for lo in range(0, 128, block):
        assert sorted(r["max_new_tokens"] for r in a[lo:lo + block]) == \
            sorted(r["max_new_tokens"] for r in b[lo:lo + block])
        assert sorted(len(r["prompt"]) for r in a[lo:lo + block]) == \
            sorted(len(r["prompt"]) for r in b[lo:lo + block])


def test_lengths_respect_the_mix(chat):
    s = make_schedule(chat, 8.0, 3, 60.0, 50257)
    plens = [len(r["prompt"]) for r in s]
    outs = [r["max_new_tokens"] for r in s]
    assert min(plens) >= 16 and max(plens) <= 768
    assert min(outs) >= 8 and max(outs) <= 256
    assert all(p + o <= 1024 for p, o in zip(plens, outs))
    assert all(0 <= t < 50257 for r in s for t in r["prompt"])
    assert [r["due_s"] for r in s] == sorted(r["due_s"] for r in s)
    # lognormal medians, to the width of a 32-quantile block
    med = sorted(plens)[len(plens) // 2]
    assert 170 <= med <= 215
    med = sorted(outs)[len(outs) // 2]
    assert 85 <= med <= 108


def test_other_processes_and_lengths(chat):
    """What the cells to come are made of (PERF.md, Open questions):
    bursts, uniform and fixed lengths; and ISSUE 24's plain Poisson with
    every length a draw of its own, which ``tools/window_spread.py``
    measures beside the cell's mix."""
    mix = {"arrivals": {"process": "burst", "period_s": 3.0,
                        "jitter_s": 0.05},
           "prompt_len": {"dist": "uniform", "min": 512, "max": 896},
           "max_new_tokens": {"dist": "fixed", "value": 16},
           "max_total": 1024}
    s = make_schedule(mix, 20.0 / 3.0, 5, 9.0, 1000)
    assert len(s) == 60
    assert all(r["due_s"] % 3.0 <= 0.05 for r in s)
    assert all(r["max_new_tokens"] == 16 for r in s)
    assert all(512 <= len(r["prompt"]) <= 896 for r in s)
    plain = dict(chat, arrivals={"process": "poisson"}, lengths_block=0)
    counts = [len(make_schedule(plain, 10.0, seed, 100.0, 100))
              for seed in range(6)]
    assert all(850 < n < 1150 for n in counts) and len(set(counts)) > 1
    one = make_schedule(plain, 10.0, 1, 100.0, 100)
    other = make_schedule(plain, 10.0, 2, 100.0, 100)
    assert sorted(r["max_new_tokens"] for r in one[:32]) != \
        sorted(r["max_new_tokens"] for r in other[:32])
    assert min(r["max_new_tokens"] for r in one) >= 8

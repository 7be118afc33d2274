"""The seeded traffic generators: a fixed schedule of sizes and arrivals,
token ids from the seed."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from benchmarks.chip import run as bench
from benchmarks.chip.traffic import open_loop

CHAT = json.loads((Path(bench.HERE) / "traffic" / "chat.json").read_text())
SEEDS = [0, 7, 2**31 + 5, 2**40 + 3]


def _sched(seed, seconds=51.0, traffic=CHAT):
    return open_loop.schedule(traffic, seed=seed, seconds=seconds, vocab=151936)


@pytest.mark.parametrize("seed", SEEDS)
def test_same_seed_same_schedule(seed):
    a, b = _sched(seed), _sched(seed)
    assert a == b and len(a) > 0


def _block(reqs, lo, hi):
    return [r for r in reqs if lo <= r.due_s < hi]


def _work(reqs):
    return sorted(len(r.prompt) for r in reqs), sorted(r.max_new_tokens for r in reqs)


def test_seeds_share_sizes_and_arrivals_not_tokens():
    a, b = _sched(SEEDS[0]), _sched(SEEDS[-1])
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]
    assert any(x.prompt != y.prompt for x, y in zip(a, b))
    window = _block(a, 0.0, 51.0)
    assert len(window) == round(CHAT["rate_per_s"] * 51.0)
    # no long prompt arrives with the longest output
    longest = max(window, key=lambda r: r.max_new_tokens)
    assert len(longest.prompt) < max(len(r.prompt) for r in window)


def test_the_lead_in_repeats_the_window_s_schedule():
    reqs = _sched(11)
    lead = CHAT["lead_in_s"]
    assert -lead <= reqs[0].due_s < 0
    window = _block(reqs, 0.0, 51.0)
    before = [(r.due_s + 51.0, len(r.prompt), r.max_new_tokens) for r in _block(reqs, -lead, 0.0)]
    assert before == [(pytest.approx(r.due_s), len(r.prompt), r.max_new_tokens)
                      for r in window if r.due_s >= 51.0 - lead]


def test_lengths_stay_inside_their_clips_and_follow_the_mix():
    reqs = _block(_sched(1, seconds=20000.0), 0.0, 20000.0)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new_tokens for r in reqs])
    pt, ot = CHAT["prompt_tokens"], CHAT["output_tokens"]
    assert p.min() >= pt["min"] and p.max() <= pt["max"]
    assert o.min() >= ot["min"] and o.max() <= ot["max"]
    assert p.min() == pt["min"] and p.max() == pt["max"]  # the clips are reached
    assert np.median(p) == pytest.approx(pt["median"], rel=0.05)
    assert np.median(o) == pytest.approx(ot["median"], rel=0.05)
    assert len(reqs) / 20000.0 == pytest.approx(CHAT["rate_per_s"], rel=0.01)
    gaps = np.diff([r.due_s for r in reqs])
    assert np.std(gaps) / np.mean(gaps) == pytest.approx(1.0, rel=0.05)  # exponential
    assert all(0 <= t < 151936 for r in reqs[:50] for t in r.prompt)


def test_due_times_rise_and_stay_in_the_schedule():
    reqs = _sched(3, seconds=51.0)
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues) and -CHAT["lead_in_s"] <= dues[0] and dues[-1] < 51.0
    assert min(d for d in dues if d >= 0) == 0.0  # each block opens with an arrival
    gaps = np.diff(dues)
    assert (gaps > 0).all() and math.isfinite(gaps.sum())


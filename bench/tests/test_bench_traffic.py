"""The traffic generator: deterministic under the seed, the same work for
every seed in another order, and the lengths and sharing its mix
declares."""
import json
from collections import Counter

import numpy as np
import pytest

from bench import spec, traffic

MIXES = {name: json.loads((spec.BENCH / "traffic" / f"{name}.json")
                          .read_text())
         for name in ("reasoning", "code-completion", "chat-shared")}
# an open loop's period is levels / rate = 6.4 s: the window is three
# periods, and lead-in, window and drain together six
LOAD = {"rate": 5.0, "clients": 16, "lead_in_s": 6.4, "drain_s": 12.8}
WINDOW = 19.2
BIG = 2**33 + 12345          # more than 32 signed bits hold


def build(name, seed, seconds=WINDOW):
    return traffic.build(MIXES[name], LOAD, 1000, seed, seconds)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_same_seed_same_requests(name):
    a, b = build(name, BIG), build(name, BIG)
    assert len(a.requests) == len(b.requests)
    for x, y in zip(a.requests, b.requests):
        assert np.array_equal(x.prompt, y.prompt)
        assert (x.max_new, x.temperature, x.sample_seed, x.due) == \
            (y.max_new, y.temperature, y.sample_seed, y.due)
    c = build(name, BIG + 1)
    assert any(not np.array_equal(x.prompt, y.prompt)
               for x, y in zip(a.requests, c.requests))


@pytest.mark.parametrize("name", sorted(MIXES))
def test_every_seed_gets_the_same_work(name):
    mix = MIXES[name]
    runs = [build(name, s) for s in (1, 2, BIG)]
    # a closed loop cuts the budgets of whichever requests come first
    cut = bool(mix.get("first_budget_fraction"))

    def work(t):
        return (sorted(len(r.prompt) for r in t.requests),
                [] if cut else sorted(r.max_new for r in t.requests),
                sum(r.greedy for r in t.requests))
    assert work(runs[0]) == work(runs[1]) == work(runs[2])
    if mix["loop"] == "open":
        def gaps(t):
            end = WINDOW + LOAD["drain_s"]
            return np.sort(np.diff([r.due for r in t.requests] + [end]))
        assert np.allclose(gaps(runs[0]), gaps(runs[1]))
        assert np.allclose(gaps(runs[0]), gaps(runs[2]))

        # and the window alone holds the same work for every seed
        def held(t, lo=0.0, hi=WINDOW):
            inside = [r for r in t.requests if lo <= r.due < hi]
            return (len(inside), sorted(len(r.prompt) for r in inside),
                    sorted(r.max_new for r in inside),
                    sum(r.greedy for r in inside))
        assert held(runs[0]) == held(runs[1]) == held(runs[2])
        assert held(runs[0])[0] == LOAD["rate"] * WINDOW

        # and so does each block of ``levels`` requests of the window, in
        # its own share of the window's seconds
        k = mix["levels"]
        n = int(LOAD["rate"] * WINDOW)
        for b0 in range(0, n, k):
            lo, hi = WINDOW * b0 / n, WINDOW * min(b0 + k, n) / n
            part = [held(t, lo, hi) for t in runs]
            assert part[0] == part[1] == part[2]
            assert part[0][0] == min(k, n - b0)
    else:
        # each block of as many requests as clients holds the same sizes
        def block(t, b):
            part = t.requests[b * 16:(b + 1) * 16]
            return sorted(len(r.prompt) for r in part)
        for b in range(len(runs[0].requests) // 16):
            assert block(runs[0], b) == block(runs[1], b) == \
                block(runs[2], b)


@pytest.mark.parametrize("name", sorted(MIXES))
def test_lengths_stay_in_the_declared_ranges(name):
    mix = MIXES[name]
    t = build(name, 7)
    pre = mix["shared_prefixes"]["length"] if mix.get("shared_prefixes") \
        else 0
    body = [len(r.prompt) - pre for r in t.requests]
    assert min(body) >= mix["prompt"]["min"]
    assert max(body) <= mix["prompt"]["max"]
    assert set(body) <= set(traffic.level_table(mix["prompt"],
                                                 mix["levels"]))
    assert set(body) == set(traffic.warm_lengths(t))
    outs = [r.max_new for r in t.requests]
    assert max(outs) <= mix["output"]["max"]
    share = 1 - sum(r.greedy for r in t.requests) / len(t.requests)
    assert share == pytest.approx(mix.get("sampled_share", 0.0), abs=0.02)


def test_lognormal_table_has_the_declared_median():
    mix = MIXES["code-completion"]
    table = traffic.level_table(mix["prompt"], 33)
    assert table[16] == mix["prompt"]["median"]
    assert table == sorted(table)


def test_shared_prefixes_follow_zipf():
    mix = MIXES["chat-shared"]
    t = build("chat-shared", 3, seconds=60.0)
    n = len(t.requests)
    counts = Counter(r.prefix for r in t.requests)
    sp = mix["shared_prefixes"]
    w = np.array([1 / (k + 1) ** sp["zipf_s"] for k in range(sp["count"])])
    for k in range(sp["count"]):
        assert abs(counts[k] - n * w[k] / w.sum()) <= 1
    for r in t.requests:
        assert np.array_equal(r.prompt[:sp["length"]], t.prefixes[r.prefix])


def test_open_loop_rate_and_lead_in():
    t = build("code-completion", 11)
    dues = [r.due for r in t.requests]
    assert dues == sorted(dues)
    assert dues[0] == -LOAD["lead_in_s"]
    assert 0.0 in dues                      # the window opens on an arrival
    in_window = sum(0 <= d < WINDOW for d in dues)
    assert in_window == LOAD["rate"] * WINDOW
    widest = max(np.diff(dues))
    assert WINDOW - widest <= max(d for d in dues if d < WINDOW) < WINDOW
    assert max(dues) < WINDOW + LOAD["drain_s"]


@pytest.mark.parametrize("name", ["code-completion", "chat-shared"])
def test_open_loop_seeds_rotate_one_period(name):
    """Every seed's arrivals are the one fixed period, opened on another
    entry: the window's sizes and gaps, read as a cycle, are the same."""
    k = MIXES[name]["levels"]

    def cycle(t):
        inside = [r for r in t.requests if 0 <= r.due < WINDOW]
        nxt = [r.due for r in inside[1:]] + [WINDOW]
        return ([(len(r.prompt), r.max_new, r.greedy) for r in inside],
                np.array([b - r.due for r, b in zip(inside, nxt)]))

    runs = [cycle(build(name, s)) for s in (1, 2, 3, BIG)]
    sizes, gaps = runs[0][0][:k], runs[0][1][:k]
    starts = set()
    for c_sizes, c_gaps in runs:
        assert c_sizes == c_sizes[:k] * 3
        assert np.allclose(c_gaps, np.tile(c_gaps[:k], 3))
        j = next(j for j in range(k) if c_sizes[:k] == sizes[j:] + sizes[:j]
                 and np.allclose(c_gaps[:k], np.roll(gaps, -j)))
        starts.add(j)
    assert len(starts) > 1          # the seed moves the entry it opens on


def test_closed_loop_first_budgets_are_staggered():
    mix = MIXES["reasoning"]
    t = build("reasoning", 5)
    first = t.requests[:t.clients]
    assert all(r.due is None for r in t.requests)
    lo, hi = mix["first_budget_fraction"]
    assert len({r.max_new for r in first}) > t.clients // 2
    assert min(r.max_new for r in first) < \
        hi * mix["output"]["max"] * 0.5
    # ranked by the fraction kept, greedy and sampled take turns,
    # the shortest greedy (the uncut budgets come before any cut)
    uncut = dict(mix, first_budget_fraction=None)
    for seed in (5, 6, 2**33 + 1):
        t = build("reasoning", seed)
        full = traffic.build(uncut, LOAD, 1000, seed, WINDOW).requests
        frac = {j: r.max_new / full[j].max_new
                for j, r in enumerate(t.requests[:t.clients])}
        ranked = sorted(frac, key=frac.get)
        kinds = [t.requests[j].greedy for j in ranked]
        assert kinds[0] and kinds[::2] == [True] * len(kinds[::2])
        assert not any(kinds[1::2])

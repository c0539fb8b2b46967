"""The chip benchmark's traffic generator: fresh windows never repeat, the
open-loop schedule, and what the seed decides."""

import collections
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness, traffic  # noqa: E402

DAY = 86400
CONFIG = {"horizon_days": 120}


def mix(name):
    return harness.load_json(os.path.join(ROOT, "chipbench", "traffic", name + ".json"))


def _window(item):
    return tuple(item.request["window"])


def test_fresh_windows_never_repeat_and_are_day_aligned():
    tr = traffic.Traffic(mix("fresh_windows"), CONFIG, "log", 120 * DAY - 5, seed=3)
    warm = tr.warmup()
    stream = tr.closed_stream()
    timed = [next(stream) for _ in range(2000)]
    wins = [_window(i) for i in warm + timed]
    assert len(set(wins)) == len(wins)
    for a, b in wins:
        assert a % DAY == 0 and b % DAY == 0 and 0 <= a < b <= 120 * DAY
    assert all(i.request["sink"] == "dfg" for i in timed)


def test_every_window_of_the_horizon_is_drawn_once_then_the_stream_ends():
    tr = traffic.Traffic(mix("fresh_windows"), CONFIG, "log", 120 * DAY - 5, seed=5)
    wins = [_window(i) for i in tr.closed_stream()]
    assert len(wins) == len(set(wins)) == 121 * 120 // 2


def test_lengths_cycle_so_every_seed_sends_the_same_lengths():
    def lengths(seed, n):
        tr = traffic.Traffic(mix("fresh_windows"), CONFIG, "log", 120 * DAY - 5, seed)
        s = tr.closed_stream()
        return [next(s).days for _ in range(n)]

    # whole cycles: every length with a start left, once (a length of n
    # days has 121 - n starts, so the 120-day window drops out first)
    n = 120 + 119 + 118
    one, two = lengths(11, n), lengths(12, n)
    assert sorted(one[:120]) == list(range(1, 121))
    assert sorted(one) == sorted(two)
    assert one != two


def test_open_schedule_due_times():
    m = dict(mix("dashboards"), rate_qps=100.0)
    tr = traffic.Traffic(m, CONFIG, "log", 100 * DAY + 3600, seed=7)
    tr.warmup()
    sched = list(tr.open_schedule(30.0))
    due = np.asarray([i.due for i in sched])
    assert due[0] == 0.0 and np.all(np.diff(due) > 0) and due[-1] < 30.0
    assert abs(len(sched) - 3000) <= 1
    gaps = np.diff(due)
    assert gaps.mean() == pytest.approx(0.01, rel=0.02)  # 100 per second
    # exponential gaps: about 63% are shorter than the mean
    assert np.mean(gaps < 0.01) == pytest.approx(1 - np.exp(-1), abs=0.02)


def test_same_seed_same_requests_other_seed_same_set_other_order():
    m = dict(mix("dashboards"), rate_qps=100.0)

    def draw(seed):
        tr = traffic.Traffic(m, CONFIG, "log", 100 * DAY + 3600, seed)
        tr.warmup()
        return list(tr.open_schedule(30.0))

    a, b, c = draw(2**31 + 11), draw(2**31 + 11), draw(2**32 + 5)
    key = lambda s: [(i.due, i.tenant, repr(i.request)) for i in s]  # noqa: E731
    assert key(a) == key(b)
    assert key(a) != key(c)
    # the gaps of both are the 3000 exponential quantiles of the rate, but
    # for the one that would fall after the last request
    q = (np.arange(3000) + 0.5) / 3000
    quantiles = np.sort(-np.log1p(-q) / 100.0)
    for s in (a, c):
        gaps = np.sort(np.diff([i.due for i in s]))
        assert gaps.shape == (2999,)
        off = ~np.isclose(gaps, quantiles[:-1], rtol=0, atol=1e-9)
        k = int(np.argmax(off)) if off.any() else 2999
        assert np.allclose(gaps, np.delete(quantiles, k), rtol=0, atol=1e-9)
    tiles = lambda s: collections.Counter((i.component, i.tile) for i in s)  # noqa: E731
    assert tiles(a) == tiles(c)


def test_tiles_follow_zipf_and_last_days_end_at_the_next_midnight():
    m = dict(mix("dashboards"), rate_qps=200.0)
    tr = traffic.Traffic(m, CONFIG, "log", 100 * DAY + 3600, seed=1)
    sched = list(tr.open_schedule(30.0))
    tiles = collections.Counter(i.tile for i in sched if i.component == "tiles")
    counts = [tiles[k] for k in range(len(m["components"][0]["tiles"]))]
    assert counts == sorted(counts, reverse=True)
    assert sum(counts) == pytest.approx(0.98 * len(sched), abs=1)
    last = [i.request for i in sched if i.component == "tiles"
            and i.request.get("window") is not None]
    assert last and all(r["window"][1] == 101 * DAY for r in last)
    assert {r["window"][1] - r["window"][0] for r in last} == {DAY, 7 * DAY, 30 * DAY}


def test_a_prelude_stream_shares_no_window_with_the_timed_one():
    m = dict(mix("dashboards"), rate_qps=400.0)
    tr = traffic.Traffic(m, CONFIG, "log", 120 * DAY - 5, seed=9)
    timed = list(tr.open_schedule(30.0))
    prelude = list(tr.open_schedule(5.0, stream=1))
    w = lambda s: {_window(i) for i in s if i.component == "windows"}  # noqa: E731
    assert w(timed) and w(prelude) and not (w(timed) & w(prelude))

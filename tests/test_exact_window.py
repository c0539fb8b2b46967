"""The fused window test is exact for any float64 time.

The Pallas kernel (interpret mode on the CPU) compares int32 time ranks,
so every windowed DFG through ``QueryEngine`` must equal Algorithm 1
(``dfg_numpy``) under the float64 test ``t0 <= t < t1``, also where a
float32 test rounds events onto a window's edges: epoch seconds, whole
seconds past 2**24 s, millisecond fractions.  An append makes a new state
of the log, whose windows must see the appended events.
"""

import dataclasses
import sys
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core.dfg import dfg_numpy
from repro.core.repository import EventRepository, TimeRanks
from repro.core.streaming import MemmapLog
from repro.query import Q, QueryEngine
from repro.query.execute import repository_from_memmap
from repro.serve import QueryService

DAY = 86400.0
EPOCH = 1_450_000_000.0  # December 2015, in Unix seconds
PAST_2_24 = 194 * DAY  # the first midnight past 2**24 s (194.2 days)
A = 6


def _repo(times, rng) -> EventRepository:
    n = len(times)
    cases = rng.integers(0, max(n // 8, 1), size=n)
    acts = rng.integers(0, A, size=n)
    return EventRepository.from_event_table(
        [f"c{c}" for c in cases], [f"a{x}" for x in acts], times,
        activity_vocab=[f"a{i}" for i in range(A)],
    )


def _at_edges(edges, offsets):
    return (np.asarray(edges)[:, None] + np.asarray(offsets)[None, :]).ravel()


def _epoch_edges(rng):
    # events 1 s and 1 ms before a midnight, on it and after it
    edges = EPOCH + DAY * np.arange(5)
    times = np.concatenate([
        _at_edges(edges, [-1.0, -0.001, 0.0, 0.001, 1.0]),
        np.floor(rng.uniform(EPOCH, EPOCH + 4 * DAY, 300)),
    ])
    windows = [(edges[i], edges[j]) for i in range(5) for j in range(i + 1, 5)]
    return times, windows


def _past_2_24(rng):
    edges = PAST_2_24 + DAY * np.arange(6)
    times = np.concatenate([
        _at_edges(edges, [-2.0, -1.0, 0.0, 1.0]),
        np.floor(rng.uniform(PAST_2_24 - DAY, PAST_2_24 + 6 * DAY, 300)),
    ])
    windows = [(edges[i], edges[j]) for i in range(6) for j in range(i + 1, 6)]
    return times, windows


def _millis(rng):
    base = EPOCH + 0.25
    edges = base + np.asarray([0.0, 0.004, 0.5, 1.0, 60.001])
    times = np.concatenate([
        _at_edges(edges, [-0.001, 0.0, 0.001]),
        base + np.round(rng.uniform(-1.0, 61.0, 300), 3),
    ])
    windows = [(edges[i], edges[j]) for i in range(5) for j in range(i + 1, 5)]
    return times, windows


def _random_windows(rng):
    times = rng.uniform(0.0, 397 * DAY, 400)
    ts = np.sort(times)
    t = float(ts[17])
    windows = [
        (t, t),  # empty
        (float(ts[200]), float(ts[100])),  # reversed: empty
        (t, float(np.nextafter(t, np.inf))),  # one event, no pair
        (float(ts[0]), float(ts[-1]) + 1.0),  # the whole log
        (-np.inf, np.inf),
    ]
    windows += [tuple(sorted(rng.choice(ts, 2, replace=False).tolist()))
                for _ in range(8)]
    windows += [tuple(sorted(rng.uniform(-DAY, 398 * DAY, 2).tolist()))
                for _ in range(4)]
    return times, windows


CASES = {
    "epoch_seconds_at_edges": _epoch_edges,
    "whole_seconds_past_2_24": _past_2_24,
    "millisecond_fractions": _millis,
    "random_windows": _random_windows,
}


def _oracle(repo, t0, t1):
    src, dst, valid = repo.df_pairs()
    ts = repo.event_time
    inside = (ts >= t0) & (ts < t1)
    return dfg_numpy(src, dst, valid & inside[:-1] & inside[1:], A)


def _f32_miscounts(repo, t0, t1) -> int:
    """Pairs a float32 window test puts on the other side of an edge."""
    _, _, valid = repo.df_pairs()
    ts = repo.event_time
    t32, a, b = ts.astype(np.float32), np.float32(t0), np.float32(t1)
    in64 = (ts >= t0) & (ts < t1)
    in32 = (t32 >= a) & (t32 < b)
    return int((valid & ((in64[:-1] & in64[1:]) != (in32[:-1] & in32[1:]))).sum())


@pytest.mark.parametrize("case", list(CASES))
def test_fused_window_equals_algorithm1(case):
    rng = np.random.default_rng(sorted(CASES).index(case) + 41)
    times, windows = CASES[case](rng)
    repo = _repo(times, rng)
    engine = QueryEngine(tiny_pairs=0)
    f32_wrong = 0
    for t0, t1 in windows:
        res = Q.log(repo).using(engine).window(t0, t1).dfg(backend="pallas")
        # the optimizer drops an empty or an unbounded window
        bounded = np.isfinite(t0) or np.isfinite(t1)
        assert res.physical.fused_dicing == (t0 < t1 and bounded)
        assert res.names == repo.activity_names
        np.testing.assert_array_equal(
            np.asarray(res.value), _oracle(repo, t0, t1),
            err_msg=f"window [{t0!r}, {t1!r})",
        )
        f32_wrong += _f32_miscounts(repo, t0, t1)
    if case != "random_windows":
        # the data bites: a float32 window test would miscount here
        assert f32_wrong > 0
    # one rank column for the repository, however many windows
    assert engine.metrics_snapshot()["engine_time_rank_builds_total"] == 1


def test_time_ranks_follow_the_time_column():
    rng = np.random.default_rng(3)
    repo = _repo(EPOCH + np.floor(rng.uniform(0, 10 * DAY, 200)), rng)
    ranks = repo.time_ranks()
    assert repo.time_ranks() is ranks and repo.cached_time_ranks() is ranks
    assert ranks.rank.dtype == np.int32
    np.testing.assert_array_equal(ranks.distinct[ranks.rank], repo.event_time)
    lo, hi = ranks.bounds(EPOCH + DAY, EPOCH + 2 * DAY)
    ts = repo.event_time
    np.testing.assert_array_equal(
        (ranks.rank >= lo) & (ranks.rank < hi),
        (ts >= EPOCH + DAY) & (ts < EPOCH + 2 * DAY),
    )
    # another time column is another repository state: no stale ranks
    moved = dataclasses.replace(repo, event_time=repo.event_time + 0.5)
    assert moved.cached_time_ranks() is None
    assert moved.time_ranks() is not ranks
    # the same columns under another name keep them
    renamed = dataclasses.replace(repo, log_names=["other"])
    assert renamed.cached_time_ranks() is ranks
    assert TimeRanks.build(np.zeros(0)).bounds(0.0, 1.0).tolist() == [0, 0]


def test_concurrent_first_windows_build_the_ranks_once():
    rng = np.random.default_rng(9)
    times, windows = _past_2_24(rng)
    repo = _repo(times, rng)
    engine = QueryEngine(tiny_pairs=0)

    def query(window):
        return Q.log(repo).using(engine).window(*window).dfg(backend="pallas")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(16) as pool:
            futures = [pool.submit(query, w) for w in windows]
            results = [f.result(timeout=120) for f in futures]
    finally:
        sys.setswitchinterval(interval)
    for window, res in zip(windows, results):
        np.testing.assert_array_equal(np.asarray(res.value), _oracle(repo, *window))
    assert engine.metrics_snapshot()["engine_time_rank_builds_total"] == 1


def _stream(rng, n, t_lo, t_hi, case_lo, case_hi):
    t = np.sort(np.floor(rng.uniform(t_lo, t_hi, n)))
    return (rng.integers(0, A, n).astype(np.int32),
            rng.integers(case_lo, case_hi, n).astype(np.int32), t)


def test_a_window_after_an_append_sees_the_appended_events(tmp_path):
    rng = np.random.default_rng(7)
    act, case, t = _stream(rng, 4000, PAST_2_24, PAST_2_24 + 20 * DAY, 0, 400)
    w = MemmapLog.create(str(tmp_path / "loans"), act.shape[0], A, 400)
    w.append(act, case, t)
    log = w.close()
    svc = QueryService(QueryEngine(tiny_pairs=0))
    svc.register("loans", log)
    window = [PAST_2_24 + 10 * DAY, PAST_2_24 + 25 * DAY]
    req = {"log": "loans", "sink": "dfg", "window": window,
           "backend": "pallas"}

    def check():
        got = svc.query(req)
        repo = repository_from_memmap(MemmapLog.open(log.path))
        want = _oracle(repo, *window)
        np.testing.assert_array_equal(np.asarray(got["psi"]), want)
        return got

    check()
    # the appended events fall inside the window and continue open cases
    act2, case2, t2 = _stream(rng, 1500, PAST_2_24 + 20 * DAY,
                              PAST_2_24 + 25 * DAY - 1, 200, 600)
    svc.append({"log": "loans", "activity": act2.tolist(),
                "case": case2.tolist(), "time": t2.tolist()})
    got = check()
    assert got["backend"] == "pallas" and not got["from_cache"]
    snap = svc.engine.metrics_snapshot()
    assert snap["engine_time_rank_builds_total"] == 2
    assert snap["engine_time_rank_seconds"]["count"] == 2

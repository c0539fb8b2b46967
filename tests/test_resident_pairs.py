"""Device-resident pair columns.

A ``pallas`` count over a repository's own pairs reads them from the
device, where the first such count put them (``repro.query.resident``); a
query then ships only its window's rank bounds.  Every such count must
equal Algorithm 1 (``dfg_numpy``) exactly, every other count keeps its
per-query copy, and the set lives exactly as long as one state of a log.
"""

import gc
import sys
import weakref
from concurrent.futures import ThreadPoolExecutor

import numpy as np
import pytest

from repro.core import ActivityView
from repro.core.dfg import dfg_numpy
from repro.core.repository import EventRepository
from repro.core.streaming import MemmapLog
from repro.data import ProcessSpec, generate_memmap_log
from repro.kernels.dfg_count.ops import pick_blocks
from repro.query import Q, QueryEngine
from repro.query.execute import repository_from_memmap
from repro.serve import QueryService

DAY = 86400.0
EPOCH = 1_450_000_000.0  # December 2015, in Unix seconds
PAST_2_24 = 194 * DAY  # the first midnight past 2**24 s


def _repo(num_activities, start, seed, n=3000, days=30) -> EventRepository:
    rng = np.random.default_rng(seed)
    cases = rng.integers(0, n // 10, size=n)
    acts = rng.integers(0, num_activities, size=n)
    times = start + np.floor(rng.uniform(0, days * DAY, n))
    return EventRepository.from_event_table(
        [f"c{c}" for c in cases], [f"a{x}" for x in acts], times,
        activity_vocab=[f"a{i}" for i in range(num_activities)],
    )


REPOS = {
    "hundreds_of_activities_epoch_seconds": lambda: _repo(300, EPOCH, 1),
    "tens_of_activities_past_2_24": lambda: _repo(26, PAST_2_24, 2),
}


def _windows(repo):
    ts = np.unique(repo.event_time)
    start = float(np.floor(ts[0] / DAY) * DAY)
    return {
        "empty": (float(ts[40]), float(ts[40])),
        "one_day": (start + 3 * DAY, start + 4 * DAY),
        "whole_horizon": (float(ts[0]), float(ts[-1]) + 1.0),
        # both edges exactly on distinct event times
        "edges_on_times": (float(ts[len(ts) // 5]), float(ts[3 * len(ts) // 4])),
        "unwindowed": None,
    }


def _oracle(repo, window):
    src, dst, valid = repo.df_pairs()
    inside = np.ones(repo.num_events, bool)
    if window is not None:
        ts = repo.event_time
        inside = (ts >= window[0]) & (ts < window[1])
    psi = dfg_numpy(src, dst, valid & inside[:-1] & inside[1:], repo.num_activities)
    counts = np.bincount(repo.event_activity[inside], minlength=repo.num_activities)
    return psi, counts


@pytest.fixture(params=sorted(REPOS))
def repo(request):
    return REPOS[request.param]()


@pytest.mark.parametrize("sink", ["dfg", "process_map"])
@pytest.mark.parametrize(
    "window", ["empty", "one_day", "whole_horizon", "edges_on_times", "unwindowed"])
def test_resident_count_equals_algorithm1(repo, sink, window):
    engine = QueryEngine(tiny_pairs=0)
    w = _windows(repo)[window]
    q = Q.log(repo).using(engine)
    if w is not None:
        q = q.window(*w)
    psi, counts = _oracle(repo, w)
    if sink == "dfg":
        res = q.dfg(backend="pallas")
        np.testing.assert_array_equal(np.asarray(res.value), psi)
    else:
        res = q.process_map(top=1.0, edge_top=1.0, backend="pallas")
        pm = res.value
        active = np.nonzero(counts)[0]
        assert pm.activities == [repo.activity_names[i] for i in active]
        np.testing.assert_array_equal(pm.node_counts, counts[active])
        got = {(s, d): c for s, d, c in pm.edges}
        names = repo.activity_names
        want = {(names[s], names[d]): int(psi[s, d])
                for s, d in zip(*np.nonzero(psi))
                if counts[s] and counts[d]}
        assert got == want
    snap = engine.metrics_snapshot()
    if window == "empty":
        # an empty window short-circuits: no count, nothing resident
        assert snap["engine_scan_resident_total"] == 0
        return
    assert res.physical.backend == "pallas"
    assert snap["engine_scan_resident_total"] == 1
    assert snap["engine_resident_builds_total"] == 1
    assert snap["engine_h2d_bytes_total"] == (0 if w is None else 8)


def test_counts_that_do_not_read_the_own_pairs_keep_their_copy(repo):
    engine = QueryEngine(tiny_pairs=0)
    names = repo.activity_names
    keep = names[3:11]
    view = ActivityView({a: f"g{i % 3}" for i, a in enumerate(names[:-2])})
    t0, t1 = _windows(repo)["edges_on_times"]
    pairs = 17 * (repo.num_events - 1) + 8  # the copy of the windowed count
    # a view counted in group space, with and without a keep mask folded
    # into ``valid``
    for query in (
        lambda q: q.view(view),
        lambda q: q.activities(keep).view(view),
    ):
        before = engine.metrics_snapshot()["engine_h2d_bytes_total"]
        res = query(Q.log(repo).using(engine).window(t0, t1)).dfg(backend="pallas")
        numpy = query(Q.log(repo).using(QueryEngine()).window(t0, t1)).dfg(backend="numpy")
        np.testing.assert_array_equal(np.asarray(res.value), np.asarray(numpy.value))
        assert res.names == numpy.names
        snap = engine.metrics_snapshot()
        assert snap["engine_scan_resident_total"] == 0
        assert snap["engine_resident_builds_total"] == 0
        assert snap["engine_h2d_bytes_total"] - before > 8
    assert snap["engine_h2d_bytes_total"] >= 2 * pairs
    # a keep filter applied to the counts afterwards reads the own pairs
    res = Q.log(repo).using(engine).window(t0, t1).activities(keep).dfg(backend="pallas")
    assert res.physical.activities_as_output_mask
    psi, _ = _oracle(repo, (t0, t1))
    ids = [names.index(a) for a in keep]
    want = np.zeros_like(psi)
    want[np.ix_(ids, ids)] = psi[np.ix_(ids, ids)]
    np.testing.assert_array_equal(np.asarray(res.value), want)
    assert engine.metrics_snapshot()["engine_scan_resident_total"] == 1
    # a relinking filter derives a repository for the query, which counts
    # from a set of its own, freed with it (the window after the filter is
    # fused into the kernel)
    before = engine.metrics_snapshot()
    res = Q.log(repo).using(engine).activities(keep, relink=True).window(
        t0, t1).dfg(backend="pallas")
    numpy = Q.log(repo).using(QueryEngine()).activities(
        keep, relink=True).window(t0, t1).dfg(backend="numpy")
    np.testing.assert_array_equal(np.asarray(res.value), np.asarray(numpy.value))
    assert res.names == numpy.names
    snap = engine.metrics_snapshot()
    assert snap["engine_resident_builds_total"] - before["engine_resident_builds_total"] == 1
    assert snap["engine_scan_resident_total"] == 2
    assert snap["engine_h2d_bytes_total"] - before["engine_h2d_bytes_total"] == 8


def test_the_set_is_padded_to_the_kernel_event_block():
    repo = _repo(26, PAST_2_24, 3, n=5000)
    engine = QueryEngine(tiny_pairs=0)
    Q.log(repo).using(engine).dfg(backend="pallas")
    resident = repo.cached_device_pairs()
    block = pick_blocks(26)[0]
    n = repo.num_events - 1
    assert resident.padded % block == 0 and n <= resident.padded < n + block
    src, dst, valid, rank_src, rank_dst = (np.asarray(c) for c in resident.cols)
    assert [c.dtype for c in (src, dst, valid, rank_src, rank_dst)] == [
        np.int32, np.int32, np.bool_, np.int32, np.int32]
    want_src, want_dst, want_valid = repo.df_pairs()
    np.testing.assert_array_equal(src[:n], want_src)
    np.testing.assert_array_equal(dst[:n], want_dst)
    np.testing.assert_array_equal(valid[:n], want_valid)
    assert not valid[n:].any()
    rank = repo.time_ranks().rank
    np.testing.assert_array_equal(rank_src[:n], rank[:-1])
    np.testing.assert_array_equal(rank_dst[:n], rank[1:])
    assert engine.metrics_snapshot()["engine_resident_bytes_total"] == resident.nbytes


def test_an_append_builds_a_new_set_that_counts_the_appended_events(tmp_path):
    rng = np.random.default_rng(7)

    def stream(n, t_lo, t_hi, case_lo, case_hi):
        return (rng.integers(0, 26, n).astype(np.int32),
                rng.integers(case_lo, case_hi, n).astype(np.int32),
                np.sort(np.floor(rng.uniform(t_lo, t_hi, n))))

    act, case, t = stream(4000, PAST_2_24, PAST_2_24 + 20 * DAY, 0, 400)
    w = MemmapLog.create(str(tmp_path / "loans"), act.shape[0], 26, 400)
    w.append(act, case, t)
    log = w.close()
    svc = QueryService(QueryEngine(tiny_pairs=0))
    svc.register("loans", log)
    window = [PAST_2_24 + 10 * DAY, PAST_2_24 + 25 * DAY]
    req = {"log": "loans", "sink": "dfg", "window": window, "backend": "pallas"}

    def check():
        got = svc.query(req)
        now = repository_from_memmap(MemmapLog.open(log.path))
        want, _ = _oracle(now, window)
        np.testing.assert_array_equal(np.asarray(got["psi"]), want)
        return got

    check()
    act2, case2, t2 = stream(1500, PAST_2_24 + 20 * DAY, PAST_2_24 + 25 * DAY - 1, 200, 600)
    svc.append({"log": "loans", "activity": act2.tolist(),
                "case": case2.tolist(), "time": t2.tolist()})
    got = check()
    assert got["backend"] == "pallas" and not got["from_cache"]
    snap = svc.engine.metrics_snapshot()
    assert snap["engine_resident_builds_total"] == 2
    assert snap["engine_scan_resident_total"] == 2
    assert snap["engine_h2d_bytes_total"] == 16


def test_a_repository_evicted_from_the_memo_drops_its_device_columns(tmp_path):
    logs = [
        generate_memmap_log(
            str(tmp_path / f"l{i}"), 2_000,
            ProcessSpec(num_activities=7, seed=60 + i), seed=60 + i,
            batch_traces=100,
        )
        for i in range(2)
    ]
    engine = QueryEngine(tiny_pairs=0, repo_memo_size=1)
    Q.log(logs[0]).using(engine).window(0.0, 1e5).dfg(backend="pallas")
    (first,) = engine._repo_memo.values()
    held = weakref.ref(first.cached_device_pairs())
    Q.log(logs[1]).using(engine).window(0.0, 1e5).dfg(backend="pallas")
    assert all(r is not first for r in engine._repo_memo.values())
    assert first.cached_device_pairs() is None
    gc.collect()
    assert held() is None
    (second,) = engine._repo_memo.values()
    assert second.cached_device_pairs() is not None
    assert engine.metrics_snapshot()["engine_resident_builds_total"] == 2


def test_the_same_columns_keep_their_set_and_changed_columns_lose_it():
    import dataclasses

    repo = _repo(26, PAST_2_24, 4)
    engine = QueryEngine(tiny_pairs=0)
    Q.log(repo).using(engine).dfg(backend="pallas")
    resident = repo.cached_device_pairs()
    assert resident is not None
    renamed = dataclasses.replace(repo, log_names=["other"])
    assert renamed.cached_device_pairs() is resident
    moved = dataclasses.replace(repo, event_time=repo.event_time + 1.0)
    assert moved.cached_device_pairs() is None


def test_concurrent_first_windows_build_the_set_once():
    repo = _repo(26, PAST_2_24, 5)
    engine = QueryEngine(tiny_pairs=0)
    ts = np.unique(repo.event_time)
    windows = [(float(ts[10 * i]), float(ts[10 * i + 900])) for i in range(16)]

    def query(window):
        return Q.log(repo).using(engine).window(*window).dfg(backend="pallas")

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        with ThreadPoolExecutor(16) as pool:
            results = [f.result(timeout=120)
                       for f in [pool.submit(query, w) for w in windows]]
    finally:
        sys.setswitchinterval(interval)
    for window, res in zip(windows, results):
        np.testing.assert_array_equal(np.asarray(res.value), _oracle(repo, window)[0])
    snap = engine.metrics_snapshot()
    assert snap["engine_resident_builds_total"] == 1
    assert snap["engine_time_rank_builds_total"] == 1

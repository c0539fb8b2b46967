"""repro.query — plan equivalence vs the Algorithm 1 oracle, optimizer
rewrites, backend cost model, and the plan/result cache."""

import dataclasses
import json
import os
import shutil

import numpy as np
import pytest

from repro.core import (
    ActivityView,
    dfg_algorithm1,
    dfg_numpy,
    dice_repository,
    paper_example_repo,
    streaming_dfg,
)
from repro.core.dicing import pair_mask_for_window
from repro.core.streaming import MemmapLog
from repro.core.variants import trace_variants, variant_filtered_repository
from repro.data import ProcessSpec, generate_memmap_log, generate_repository
from repro.query import (
    Q,
    QueryCache,
    QueryEngine,
    QueryPlanError,
    canonicalize,
    fingerprint,
)
from repro.query.ast import DFGSink, Window
from repro.query.execute import repository_from_memmap


@pytest.fixture()
def engine():
    return QueryEngine()


@pytest.fixture(scope="module")
def repo():
    return generate_repository(400, ProcessSpec(num_activities=11, seed=9))


def _reference_dfg(repo, window=None, keep=None, view=None):
    """Naive single-backend evaluation: pair masks + oracle counting +
    post-hoc projection.  Every optimized plan must match this bit-exactly."""
    src, dst, valid = repo.df_pairs()
    if window is not None:
        valid = valid & pair_mask_for_window(repo, window)
    if keep is not None:
        ids = np.asarray([repo.activity_names.index(a) for a in keep])
        m = np.isin(repo.event_activity, ids)
        valid = valid & m[:-1] & m[1:]
    psi = dfg_numpy(src, dst, valid, repo.num_activities)
    if view is not None:
        psi = view.apply_to_dfg(psi, repo.activity_names)
    return psi


# ---------------------------------------------------------------------------
# plan equivalence — the acceptance criterion
# ---------------------------------------------------------------------------


def test_paper_example_matches_algorithm1(engine):
    repo = paper_example_repo()
    want, _ = dfg_algorithm1(repo.to_graph())
    for backend in ("auto", "numpy", "scatter", "onehot", "pallas"):
        res = Q.log(repo).using(engine).dfg(backend=backend)
        np.testing.assert_array_equal(res.value, want)
        assert res.names == repo.activity_names


@pytest.mark.parametrize("backend", ["numpy", "scatter", "onehot", "pallas"])
def test_windowed_query_equals_oracle(repo, engine, backend):
    t0 = float(np.quantile(repo.event_time, 0.3))
    t1 = float(np.quantile(repo.event_time, 0.8))
    res = Q.log(repo).using(engine).window(t0, t1).dfg(backend=backend)
    np.testing.assert_array_equal(
        res.value, _reference_dfg(repo, window=(t0, t1))
    )


def test_activity_filter_equals_pair_predicate(repo, engine):
    keep = repo.activity_names[2:7]
    res = Q.log(repo).using(engine).activities(keep).dfg()
    assert res.physical.activities_as_output_mask
    np.testing.assert_array_equal(res.value, _reference_dfg(repo, keep=keep))


def test_view_pushdown_equals_post_projection(repo, engine):
    names = repo.activity_names
    view = ActivityView(
        {a: f"g{i % 3}" for i, a in enumerate(names[:-2])}  # last 2 hidden
    )
    res = Q.log(repo).using(engine).view(view).dfg()
    assert res.physical.view_pushdown  # counted in G×G space
    np.testing.assert_array_equal(res.value, _reference_dfg(repo, view=view))
    assert res.names == view.visible_names(names)


def test_combined_window_filter_view(repo, engine):
    t0 = float(np.quantile(repo.event_time, 0.2))
    t1 = float(np.quantile(repo.event_time, 0.9))
    keep = repo.activity_names[1:8]
    view = ActivityView({a: a[-1] for a in repo.activity_names[:9]})
    res = (
        Q.log(repo).using(engine)
        .window(t0, t1).activities(keep).view(view).dfg()
    )
    np.testing.assert_array_equal(
        res.value, _reference_dfg(repo, window=(t0, t1), keep=keep, view=view)
    )


def test_fused_pallas_dicing_equals_oracle(engine):
    # whole-hour timestamps: many events share a time, so the window's
    # edges fall on runs of equal time ranks
    repo = generate_repository(300, ProcessSpec(num_activities=7, seed=2))
    repo = dataclasses.replace(
        repo, event_time=np.floor(repo.event_time / 3600.0)
    )
    window = (10.0, 500.0)
    res = Q.log(repo).using(engine).window(*window).dfg(backend="pallas")
    assert res.physical.fused_dicing
    np.testing.assert_array_equal(
        res.value, _reference_dfg(repo, window=window)
    )


def test_relink_activities_matches_dice_repository(repo, engine):
    keep = repo.activity_names[:6]
    res = Q.log(repo).using(engine).activities(keep, relink=True).dfg()
    want = _reference_dfg(dice_repository(repo, activities=keep))
    np.testing.assert_array_equal(res.value, want)


def test_top_variants_op(repo, engine):
    res = Q.log(repo).using(engine).top_variants(3).dfg()
    want = _reference_dfg(variant_filtered_repository(repo, 3))
    np.testing.assert_array_equal(res.value, want)


def test_variants_sink(repo, engine):
    res = Q.log(repo).using(engine).variants(5)
    tv = trace_variants(repo)
    np.testing.assert_array_equal(res.value.counts, tv.counts[:5])
    assert res.value.sequences == tv.sequences[:5]


def test_histogram_sink(repo, engine):
    res = Q.log(repo).using(engine).histogram()
    want = np.bincount(repo.event_activity, minlength=repo.num_activities)
    np.testing.assert_array_equal(res.value, want)


def test_distributed_backend_equals_oracle(repo):
    from repro.launch.mesh import make_test_mesh

    eng = QueryEngine(mesh=make_test_mesh((1,), ("data",)))
    res = Q.log(repo).using(eng).dfg()
    assert res.physical.backend == "distributed"
    np.testing.assert_array_equal(res.value, _reference_dfg(repo))


# ---------------------------------------------------------------------------
# memmap / streaming
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def mmlog(tmp_path_factory):
    path = tmp_path_factory.mktemp("qlog") / "mm"
    return generate_memmap_log(
        str(path), 25_000, ProcessSpec(num_activities=13, seed=31), seed=31,
        batch_traces=400,
    )


def test_streaming_plan_equals_direct_call(mmlog):
    eng = QueryEngine(memory_budget_events=100)  # force out-of-core
    res = Q.log(mmlog).using(eng).dfg()
    assert res.physical.backend == "streaming"
    np.testing.assert_array_equal(res.value, streaming_dfg(mmlog))


def test_streaming_window_row_range_pushdown(mmlog):
    eng = QueryEngine(memory_budget_events=100)
    t0 = float(np.quantile(np.asarray(mmlog.time), 0.25))
    t1 = float(np.quantile(np.asarray(mmlog.time), 0.75))
    res = Q.log(mmlog).using(eng).window(t0, t1).dfg()
    assert res.physical.row_range_window == (t0, t1)
    np.testing.assert_array_equal(
        res.value, streaming_dfg(mmlog, time_window=(t0, t1))
    )


def test_materialized_memmap_equals_streaming(mmlog):
    """Under the memory budget the cost model loads the log and uses a
    device backend — counts must be identical to the out-of-core scan."""
    res = Q.log(mmlog).using(QueryEngine()).dfg()
    assert res.physical.materialize and res.physical.backend != "streaming"
    np.testing.assert_array_equal(res.value, streaming_dfg(mmlog))


def test_memmap_window_matches_repository_semantics(mmlog):
    """Row-range dicing on the time-ordered stream == pair-endpoint masking
    on the materialized repository (paper semantics)."""
    t0 = float(np.quantile(np.asarray(mmlog.time), 0.4))
    t1 = float(np.quantile(np.asarray(mmlog.time), 0.9))
    stream = Q.log(mmlog).using(
        QueryEngine(memory_budget_events=100)
    ).window(t0, t1).dfg()
    repo = repository_from_memmap(mmlog)
    np.testing.assert_array_equal(
        stream.value, _reference_dfg(repo, window=(t0, t1))
    )


def test_streaming_histogram(mmlog):
    eng = QueryEngine(memory_budget_events=100)
    res = Q.log(mmlog).using(eng).histogram()
    want = np.zeros(mmlog.num_activities, np.int64)
    for a, _, _ in mmlog.iter_chunks():
        want += np.bincount(a, minlength=mmlog.num_activities)
    np.testing.assert_array_equal(res.value, want)


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------


def test_window_fusion_and_canonical_order(repo, engine):
    q1 = Q.log(repo).window(0.0, 1e9).window(5e5, 2e9).activities(
        repo.activity_names[:4]
    )
    q2 = Q.log(repo).activities(repo.activity_names[:4]).window(5e5, 1e9)
    p1, notes = canonicalize(q1.logical_plan(DFGSink()))
    p2, _ = canonicalize(q2.logical_plan(DFGSink()))
    assert "fuse_windows" in notes
    windows = [op for op in p1.ops if isinstance(op, Window)]
    assert windows == [Window(5e5, 1e9)]
    # differently chained but equivalent queries share one cache key
    assert p1.key() == p2.key()
    r1 = q1.using(engine).dfg()
    r2 = q2.using(engine).dfg()
    assert r2.from_cache
    np.testing.assert_array_equal(r1.value, r2.value)
    np.testing.assert_array_equal(
        r1.value, _reference_dfg(repo, window=(5e5, 1e9),
                                 keep=repo.activity_names[:4])
    )


def test_view_composition(repo, engine):
    v1 = ActivityView({a: f"g{i % 4}" for i, a in enumerate(repo.activity_names)})
    v2 = ActivityView({"g0": "x", "g1": "x"})  # g2, g3 fall to HIDDEN
    res = Q.log(repo).using(engine).view(v1).view(v2).dfg()
    psi1 = _reference_dfg(repo, view=v1)
    want = v2.apply_to_dfg(psi1, v1.visible_names(repo.activity_names))
    np.testing.assert_array_equal(res.value, want)


def test_drop_noop_rewrites(repo):
    q = Q.log(repo).window(-np.inf, np.inf).activities(repo.activity_names)
    plan, notes = canonicalize(
        q.logical_plan(DFGSink()), repo.activity_names
    )
    assert plan.ops == ()
    assert "drop_infinite_window" in notes
    assert "drop_keep_all_filter" in notes


def test_errors(repo, engine, mmlog):
    view = ActivityView({repo.activity_names[0]: "g"})
    with pytest.raises(QueryPlanError):
        Q.log(repo).using(engine).view(view).activities(["a"]).dfg()
    with pytest.raises(QueryPlanError):
        Q.log(repo).using(engine).activities(["not-an-activity"]).dfg()
    with pytest.raises(QueryPlanError):
        Q.log(repo).using(engine).dfg(backend="streaming")
    with pytest.raises(QueryPlanError):
        Q.log(repo).using(engine).view(view).variants()
    with pytest.raises(QueryPlanError):
        # materializing ops cannot run out-of-core
        Q.log(mmlog).using(
            QueryEngine(memory_budget_events=100)
        ).top_variants(2).dfg()
    with pytest.raises(QueryPlanError):
        # a view cannot be hoisted across a materialization barrier —
        # top_variants would rank raw variants, not projected ones
        Q.log(repo).using(engine).view(view).top_variants(1).dfg()
    with pytest.raises(QueryPlanError):
        Q.log(repo).using(engine).view(view).activities(
            [repo.activity_names[0]], relink=True
        ).dfg()
    with pytest.raises(QueryPlanError):
        # a pinned device backend must not slurp an out-of-core log
        Q.log(mmlog).using(
            QueryEngine(memory_budget_events=100)
        ).dfg(backend="scatter")


def test_explain_mentions_pushdown(mmlog):
    eng = QueryEngine(memory_budget_events=100)
    txt = Q.log(mmlog).using(eng).window(0.0, 1.0).explain()
    assert "row_range" in txt
    assert "streaming" in txt


# ---------------------------------------------------------------------------
# cache
# ---------------------------------------------------------------------------


def test_cache_hit_skips_execution(repo, engine, monkeypatch):
    t0 = float(np.quantile(repo.event_time, 0.1))
    q = Q.log(repo).using(engine).window(t0, t0 + 1e6)
    first = q.dfg()
    assert not first.from_cache and engine.stats.executions == 1

    def boom(*a, **k):  # any re-execution is a bug
        raise AssertionError("executor ran on a cached plan")

    monkeypatch.setattr(engine, "_execute", boom)
    second = q.dfg()
    assert second.from_cache
    assert engine.stats.cache_hits == 1 and engine.stats.executions == 1
    np.testing.assert_array_equal(first.value, second.value)


def test_cache_is_content_addressed(repo, engine):
    """An equal copy of the repository hits; appending one event misses."""
    clone = dataclasses.replace(
        repo,
        event_activity=repo.event_activity.copy(),
        event_time=repo.event_time.copy(),
    )
    Q.log(repo).using(engine).dfg()
    assert Q.log(clone).using(engine).dfg().from_cache

    grown = dataclasses.replace(
        repo,
        event_activity=np.append(repo.event_activity, 0).astype(np.int32),
        event_trace=np.append(
            repo.event_trace, repo.event_trace[-1]
        ).astype(np.int32),
        event_time=np.append(repo.event_time, repo.event_time[-1] + 1.0),
    )
    res = Q.log(grown).using(engine).dfg()
    assert not res.from_cache


def test_memmap_fingerprint_changes_after_append(mmlog, tmp_path):
    """Appending rows to the disk tier invalidates every cached result."""
    path = str(tmp_path / "copy")
    shutil.copytree(mmlog.path, path)
    log = MemmapLog.open(path)
    fp_before = fingerprint(log)

    eng = QueryEngine(memory_budget_events=100)
    assert not Q.log(log).using(eng).dfg().from_cache
    assert Q.log(log).using(eng).dfg().from_cache

    # append one event to each column + bump the row count
    with open(os.path.join(path, "activity.i32"), "ab") as f:
        f.write(np.asarray([1], np.int32).tobytes())
    with open(os.path.join(path, "case.i32"), "ab") as f:
        f.write(np.asarray([0], np.int32).tobytes())
    with open(os.path.join(path, "time.f64"), "ab") as f:
        f.write(np.asarray([float(log.time[-1]) + 1.0], np.float64).tobytes())
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    meta["num_events"] += 1
    with open(os.path.join(path, "meta.json"), "w") as f:
        json.dump(meta, f)

    appended = MemmapLog.open(path)
    assert fingerprint(appended) != fp_before
    res = Q.log(appended).using(eng).dfg()
    assert not res.from_cache  # recomputed on the appended log
    np.testing.assert_array_equal(res.value, streaming_dfg(appended))


def test_cached_results_are_isolated(repo, engine):
    first = Q.log(repo).using(engine).dfg()
    first.value[:] = -1  # caller scribbles on its copy
    second = Q.log(repo).using(engine).dfg()
    assert second.from_cache
    assert (second.value >= 0).all()
    np.testing.assert_array_equal(second.value, _reference_dfg(repo))


def test_cache_lru_eviction(repo):
    eng = QueryEngine(cache=QueryCache(max_entries=2))
    qs = [Q.log(repo).using(eng).window(0.0, float(t)) for t in (1e5, 2e5, 3e5)]
    for q in qs:
        q.dfg()
    assert len(eng.cache) == 2
    assert not qs[0].dfg().from_cache  # evicted
    assert eng.cache.stats.evictions >= 1


# ---------------------------------------------------------------------------
# serving integration
# ---------------------------------------------------------------------------


def test_query_service_end_to_end(repo, mmlog):
    from repro.core.views import AccessPolicy
    from repro.serve import QueryService

    svc = QueryService()
    svc.register("main", repo)
    svc.register("disk", mmlog)
    svc.register(
        "locked", repo,
        policy=AccessPolicy(time_windows_allowed=False),
    )
    out = svc.query({"log": "main", "sink": "dfg"})
    np.testing.assert_array_equal(np.asarray(out["psi"]), _reference_dfg(repo))
    assert not out["from_cache"]
    assert svc.query({"log": "main", "sink": "dfg"})["from_cache"]

    hist = svc.query({"log": "disk", "sink": "histogram"})
    assert sum(hist["counts"]) == mmlog.num_events

    var = svc.query({"log": "main", "sink": "variants", "k": 2})
    assert len(var["sequences"]) <= 2
    # wire-friendly: JSON/query-param values arrive as strings
    var_s = svc.query({"log": "main", "sink": "variants", "k": "2"})
    assert var_s["counts"] == var["counts"]

    from repro.core.views import AccessDenied

    with pytest.raises(AccessDenied):
        svc.query({"log": "locked", "sink": "dfg", "window": [0.0, 1.0]})


def test_query_service_view_policy_guards(repo):
    """A coarsening view must not be bypassable via raw-activity filters or
    raw variant sequences, and min_group_count suppresses all sinks."""
    from repro.core.views import AccessDenied, AccessPolicy
    from repro.serve import QueryService

    view = ActivityView({a: "g" for a in repo.activity_names[:4]})
    svc = QueryService()
    svc.register("v", repo, policy=AccessPolicy(view=view))
    svc.register("k", repo, policy=AccessPolicy(min_group_count=10**9))

    with pytest.raises(AccessDenied):
        svc.query({"log": "v", "sink": "dfg",
                   "activities": [repo.activity_names[0]]})
    with pytest.raises(AccessDenied):
        svc.query({"log": "v", "sink": "variants"})

    assert sum(svc.query({"log": "k", "sink": "histogram"})["counts"]) == 0
    assert not np.asarray(svc.query({"log": "k", "sink": "dfg"})["psi"]).any()
    assert svc.query({"log": "k", "sink": "variants"})["sequences"] == []


def test_analyst_session_through_engine(repo):
    from repro.core import AccessPolicy, AnalystSession

    view = ActivityView({a: a for a in repo.activity_names[:5]})
    ses = AnalystSession(repo, AccessPolicy(view=view))
    psi, names = ses.dfg()
    assert names == view.visible_names(repo.activity_names)
    np.testing.assert_array_equal(psi, _reference_dfg(repo, view=view))
    counts, names2 = ses.activity_histogram()
    assert names2 == names
    full = np.bincount(repo.event_activity, minlength=repo.num_activities)
    np.testing.assert_array_equal(counts, full[:5])

"""repro.obs — per-query execution traces, the lock-protected metrics
registry (counters / streaming histograms / exports), planner drift
detection, self-mining forensics, and the serving-layer introspection
sinks."""

import logging
import shutil
import threading

import numpy as np
import pytest

from repro.core import dfg_numpy
from repro.data import ProcessSpec, generate_memmap_log, generate_repository
from repro.obs import (
    MetricsRegistry,
    QueryTrace,
    kernel_registry,
    prometheus_text,
)
from repro.obs.metrics import BUCKET_BOUNDS
from repro.obs.trace import NullTrace
from repro.query import Q, QueryEngine
from repro.serve.query_service import QueryService


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------


@pytest.fixture()
def repo():
    return generate_repository(300, ProcessSpec(num_activities=7, seed=3),
                               seed=3)


@pytest.fixture()
def engine():
    return QueryEngine()


@pytest.fixture(scope="module")
def base_log(tmp_path_factory):
    path = tmp_path_factory.mktemp("obs") / "base"
    return generate_memmap_log(
        str(path), 20_000, ProcessSpec(num_activities=8, seed=11), seed=11,
        batch_traces=300,
    )


@pytest.fixture()
def log_copy(base_log, tmp_path):
    path = str(tmp_path / "log")
    shutil.copytree(base_log.path, path)
    from repro.core import MemmapLog

    return MemmapLog.open(path)


# ---------------------------------------------------------------------------
# metrics primitives
# ---------------------------------------------------------------------------


def test_histogram_percentiles_log_uniform():
    reg = MetricsRegistry()
    h = reg.histogram("lat")
    rng = np.random.default_rng(0)
    xs = 10.0 ** rng.uniform(-4, 0, 5000)  # 100 µs … 1 s, log-uniform
    for x in xs:
        h.observe(float(x))
    for q in (50.0, 95.0, 99.0):
        est = h.percentile(q)
        true = float(np.percentile(xs, q))
        # log-scale buckets: the estimate lands within one decade/4 step
        assert true / 2.5 <= est <= true * 2.5
    snap = h.snapshot()
    assert snap["count"] == 5000
    assert snap["min"] == pytest.approx(xs.min())
    assert snap["max"] == pytest.approx(xs.max())
    assert snap["sum"] == pytest.approx(xs.sum())
    assert snap["p50"] <= snap["p95"] <= snap["p99"] <= snap["max"]


def test_histogram_percentile_clamps_to_envelope():
    reg = MetricsRegistry()
    h = reg.histogram("x")
    h.observe(0.013)
    h.observe(0.013)
    # everything in one bucket: interpolation must not escape [min, max]
    assert h.percentile(50.0) == pytest.approx(0.013)
    assert h.percentile(99.0) == pytest.approx(0.013)
    assert reg.histogram("empty").percentile(95.0) == 0.0


def test_counter_and_histogram_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits")
    h = reg.histogram("lat")
    N, M = 8, 2000

    def work():
        for _ in range(M):
            c.inc()
            h.observe(1e-3)

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == N * M
    assert h.count == N * M
    assert h.sum == pytest.approx(N * M * 1e-3)


def test_counter_inc_returns_sequence():
    reg = MetricsRegistry()
    c = reg.counter("seq")
    assert [c.inc(), c.inc(), c.inc(5)] == [1, 2, 7]


def test_registry_get_or_create_and_labels():
    reg = MetricsRegistry()
    a = reg.counter("n", sink="dfg")
    b = reg.counter("n", sink="dfg")
    other = reg.counter("n", sink="histogram")
    assert a is b and a is not other
    a.inc(3)
    d = reg.to_dict()
    assert d["n{sink=dfg}"] == 3
    assert d["n{sink=histogram}"] == 0


def test_to_dict_floor_zeroes_small_counts():
    reg = MetricsRegistry()
    reg.counter("small").inc(2)
    reg.counter("big").inc(100)
    h = reg.histogram("few")
    h.observe(0.5)
    d = reg.to_dict(floor=5)
    assert d["small"] == 0 and d["big"] == 100
    assert d["few"]["count"] == 0 and d["few"]["sum"] == 0.0


def test_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("engine_queries_total").inc(4)
    h = reg.histogram("query_latency_seconds", sink="dfg")
    h.observe(0.002)
    h.observe(0.004)
    reg.gauge("cache_ratio", lambda: 0.5)
    text = reg.to_prometheus()
    assert "# TYPE engine_queries_total counter" in text
    assert "engine_queries_total 4" in text
    assert "# TYPE query_latency_seconds histogram" in text
    assert 'le="+Inf"} 2' in text
    assert 'query_latency_seconds_count{sink="dfg"} 2' in text
    assert 'query_latency_seconds_sum{sink="dfg"} 0.006' in text
    assert "# TYPE cache_ratio gauge" in text
    # cumulative bucket counts are monotone and end at the total
    cums = [int(line.rsplit(" ", 1)[1]) for line in text.splitlines()
            if line.startswith("query_latency_seconds_bucket")]
    assert cums == sorted(cums) and cums[-1] == 2
    assert prometheus_text(reg, MetricsRegistry()).startswith("# TYPE")


def test_json_lines_parse():
    import json

    reg = MetricsRegistry()
    reg.counter("a", x="1").inc()
    reg.histogram("b").observe(0.1)
    recs = [json.loads(l) for l in reg.to_json_lines().splitlines()]
    by_name = {r["name"]: r for r in recs}
    assert by_name["a"]["type"] == "counter" and by_name["a"]["value"] == 1
    assert by_name["b"]["type"] == "histogram" and by_name["b"]["count"] == 1
    assert by_name["a"]["labels"] == {"x": "1"}


def test_bucket_bounds_cover_engine_range():
    assert BUCKET_BOUNDS[0] == pytest.approx(1e-6)
    assert BUCKET_BOUNDS[-1] == pytest.approx(100.0)
    assert all(b < c for b, c in zip(BUCKET_BOUNDS, BUCKET_BOUNDS[1:]))


# ---------------------------------------------------------------------------
# traces
# ---------------------------------------------------------------------------


def test_trace_slab_growth_and_spans():
    tr = QueryTrace(1, "dfg", "repository")
    for i in range(40):  # forces several slab doublings
        s = tr.begin(f"s{i}")
        tr.end(s)
    tr.finish()
    assert [s.name for s in tr.spans] == [f"s{i}" for i in range(40)]
    assert all(s.duration_s >= 0.0 for s in tr.spans)
    assert 0.0 < tr.coverage() <= 1.0


def test_trace_finish_closes_orphaned_spans():
    tr = QueryTrace(1, "dfg", "repository")
    tr.begin("never_ended")
    tr.finish()
    assert tr.spans[0].duration_s >= 0.0
    assert tr.to_dict()["spans"][0]["name"] == "never_ended"


def test_null_trace_is_inert():
    tr = NullTrace(0, "dfg", "repository")
    assert tr.enabled is False
    assert tr.begin("x") == 0
    tr.end(0)
    tr.finish()
    assert tr.spans == []


def test_every_result_carries_a_trace(repo, engine):
    res = Q.log(repo).using(engine).dfg()
    tr = res.trace
    assert tr is not None and tr.enabled
    names = [s.name for s in tr.spans]
    # a count on the device nests its phases inside ``scan``
    nested = (
        [] if res.physical.backend == "numpy"
        else ["scan.prepare", "scan.h2d", "scan.device"]
    )
    assert names == ["parse", "cache_probe", "plan", "scan", *nested, "sink"]
    assert tr.executed_backend == tr.planned_backend
    assert tr.predicted_cost_s is not None and tr.actual_cost_s is not None
    assert tr.rows_scanned == repo.num_events
    assert tr.coverage() >= 0.90
    assert tr.total_s == pytest.approx(res.wall_s, abs=5e-3) or res.wall_s > 0


def test_cache_hit_gets_its_own_trace(repo, engine):
    first = Q.log(repo).using(engine).dfg()
    hit = Q.log(repo).using(engine).dfg()
    assert hit.from_cache
    assert hit.trace is not first.trace
    assert hit.trace.executed_backend == "cache"
    assert hit.trace.from_cache
    assert hit.trace.planned_backend == first.physical.backend
    # hit latency is the hit's own (probe) time, not the original scan
    assert hit.wall_s == pytest.approx(hit.trace.total_s)


def test_trace_disabled_engine(repo):
    engine = QueryEngine(trace=False)
    res = Q.log(repo).using(engine).dfg()
    assert res.trace is None
    assert len(engine.telemetry) == 0
    # counters still work without tracing
    assert engine.stats.queries == 1 and engine.stats.executions == 1


def test_delta_trace_and_metrics(log_copy):
    engine = QueryEngine(memory_budget_events=0)  # streaming-first
    Q.log(log_copy).using(engine).dfg()
    rng = np.random.default_rng(7)
    n = 200
    act = rng.integers(0, log_copy.num_activities, n).astype(np.int32)
    case = rng.integers(0, log_copy.num_traces, n).astype(np.int32)
    times = float(log_copy.time[-1]) + np.sort(rng.uniform(0.0, 50.0, n))
    grown = log_copy.append(act, case, times)
    res = Q.log(grown).using(engine).dfg()
    tr = res.trace
    assert tr.executed_backend == "delta"
    assert tr.planned_backend == "delta"
    assert tr.delta_rows is not None
    start, hi = tr.delta_rows
    assert hi - start == n
    assert tr.rows_scanned == n
    assert "delta" in [s.name for s in tr.spans]
    snap = engine.metrics_snapshot()
    assert snap["engine_delta_hits_total"] == 1
    frac = snap["delta_suffix_fraction"]
    assert frac["count"] == 1
    assert 0.0 < frac["max"] < 0.5


def test_union_trace_has_branches(repo, engine):
    other = generate_repository(200, ProcessSpec(num_activities=7, seed=4),
                                seed=4)
    res = Q.logs((repo, "a"), (other, "b")).using(engine).dfg()
    tr = res.trace
    assert tr is not None
    assert [n for n, _ in tr.branches] == ["a", "b"]
    for _, sub in tr.branches:
        assert sub.executed_backend is not None
    assert "merge" in [s.name for s in tr.spans]
    assert engine.stats.union_queries == 1


def test_engine_stats_is_a_consistent_snapshot(repo):
    engine = QueryEngine()
    N = 6

    def work():
        for _ in range(20):
            Q.log(repo).using(engine).dfg()

    threads = [threading.Thread(target=work) for _ in range(N)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    st = engine.stats
    assert st.queries == N * 20
    assert st.executions + st.cache_hits == st.queries
    assert st.executions >= 1


def test_explain_after_diffs_prediction(repo, engine):
    res = Q.log(repo).using(engine).dfg()
    txt = Q.log(repo).using(engine).explain(after=res)
    assert "-- after: recorded trace --" in txt
    assert "executed: " in txt and "matched prediction" in txt
    assert "coverage" in txt and "scanned" in txt
    off = QueryEngine(trace=False)
    res_off = Q.log(repo).using(off).dfg()
    no_trace = Q.log(repo).using(off).explain(after=res_off)
    assert "none recorded" in no_trace


def test_drift_detection_fires_counter_and_warning(repo, caplog):
    engine = QueryEngine()
    engine.drift_ratio = 1.0 + 1e-9   # any mismatch is drift
    engine.drift_min_s = 0.0
    with caplog.at_level(logging.WARNING, logger="repro.obs"):
        res = Q.log(repo).using(engine).dfg()
    assert res.trace.drift is not None
    snap = engine.metrics_snapshot()
    key = f"planner_drift_total{{backend={res.trace.executed_backend}}}"
    assert snap[key] == 1
    assert any("planner_cost_drift" in r.message for r in caplog.records)


def test_no_drift_at_default_tolerance(repo, engine):
    res = Q.log(repo).using(engine).dfg()
    # the 16x band with a 5ms floor must not flag a sub-ms toy query
    assert res.trace.drift is None


# ---------------------------------------------------------------------------
# self-mining forensics
# ---------------------------------------------------------------------------


def test_forensics_dfg_matches_algorithm1_oracle(repo, engine):
    Q.log(repo).using(engine).dfg()
    Q.log(repo).using(engine).dfg()          # cache hit: shorter chain
    Q.log(repo).using(engine).histogram()
    own = engine.own_telemetry()
    res = Q.log(own).using(engine).dfg()
    # oracle: numpy DFG over the same repository's consecutive pairs
    src, dst, valid = own.df_pairs()
    expect = dfg_numpy(src, dst, valid, own.num_activities)
    assert res.names == own.activity_names
    np.testing.assert_array_equal(np.asarray(res.value), expect)
    # the mined process contains the full-scan chain parse → cache_probe
    i = res.names.index("parse")
    j = res.names.index("cache_probe")
    assert np.asarray(res.value)[i, j] >= 1


def test_forensics_ring_buffer_bounds_memory(repo):
    engine = QueryEngine(telemetry_max_events=10)
    for _ in range(8):
        Q.log(repo).using(engine).dfg()
    assert len(engine.telemetry) == 10
    assert engine.telemetry.dropped > 0
    snap = engine.metrics_snapshot()
    assert snap["telemetry_events"] == 10
    assert snap["telemetry_dropped_events"] == engine.telemetry.dropped


# ---------------------------------------------------------------------------
# kernel timing hook
# ---------------------------------------------------------------------------


def test_kernel_timings_land_in_global_registry():
    from repro.kernels.dfg_count import dfg_count

    before = kernel_registry().histogram(
        "kernel_seconds", kernel="dfg_count"
    ).count
    out = dfg_count(
        np.array([0, 1, 2], np.int32), np.array([1, 2, 0], np.int32),
        np.array([True, True, True]), num_activities=3,
    )
    assert np.asarray(out).sum() == 3
    h = kernel_registry().histogram("kernel_seconds", kernel="dfg_count")
    assert h.count == before + 1
    assert "kernel_seconds{kernel=dfg_count}" in QueryEngine().metrics_snapshot()


# ---------------------------------------------------------------------------
# serving-layer introspection
# ---------------------------------------------------------------------------


def test_service_trace_option(repo):
    svc = QueryService()
    svc.register("main", repo)
    out = svc.query({"log": "main", "sink": "dfg", "trace": True})
    assert out["trace"]["executed_backend"] == out["backend"]
    assert [s["name"] for s in out["trace"]["spans"]][:2] == [
        "parse", "cache_probe",
    ]
    plain = svc.query({"log": "main", "sink": "histogram"})
    assert "trace" not in plain


def test_service_forensics_sink(repo):
    svc = QueryService()
    svc.register("main", repo)
    empty = QueryService().query({"sink": "forensics"})
    assert empty["events"] == 0 and empty["psi"] == []
    svc.query({"log": "main", "sink": "dfg"})
    out = svc.query({"sink": "forensics"})
    assert out["events"] >= 5
    assert "scan" in out["names"]
    psi = np.asarray(out["psi"])
    assert psi.sum() >= 1


def test_service_forensics_floor(repo):
    svc = QueryService(forensics_floor=1000)
    svc.register("main", repo)
    svc.query({"log": "main", "sink": "dfg"})
    out = svc.query({"sink": "forensics"})
    assert out["floor"] == 1000
    assert np.asarray(out["psi"]).sum() == 0  # toy volume is all sub-floor


def test_service_forensics_floor_joins_log_policy(repo):
    from repro.core.views import AccessPolicy

    svc = QueryService()
    svc.register("main", repo, policy=AccessPolicy(min_group_count=7))
    svc.query({"log": "main", "sink": "dfg"})
    out = svc.query({"log": "main", "sink": "forensics"})
    assert out["floor"] == 7


def test_service_metrics_sink(repo):
    svc = QueryService(forensics_floor=2)
    svc.register("main", repo)
    svc.query({"log": "main", "sink": "dfg"})
    out = svc.query({"sink": "metrics"})
    assert out["metrics"]["engine_queries_total"] == 0  # 1 query, floor 2
    prom = svc.query({"sink": "metrics", "format": "prometheus"})
    assert "engine_queries_total" in prom["prometheus"]
    assert "kernel_seconds" in prom["prometheus"]


# ---------------------------------------------------------------------------
# spans on the profiler's clock, scan phases, payload and collector pauses
# ---------------------------------------------------------------------------


def _host_event_names(log_dir) -> set:
    import glob

    import jax

    (path,) = glob.glob(str(log_dir / "**" / "*.xplane.pb"), recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    return {
        ev.name
        for plane in data.planes if plane.name.startswith("/host")
        for line in plane.lines for ev in line.events
    }


def test_spans_reach_the_profiler_host_plane(repo, tmp_path):
    import jax

    svc = QueryService(QueryEngine(tiny_pairs=0))  # a device count
    svc.register("main", repo)
    with jax.profiler.trace(str(tmp_path)):
        out = svc.query({"log": "main", "sink": "dfg"})
    assert out["backend"] not in ("numpy", "distributed")
    names = _host_event_names(tmp_path)
    assert {
        "repro.parse", "repro.scan", "repro.scan.prepare", "repro.scan.h2d",
        "repro.scan.device", "repro.serve.payload",
    } <= names
    # externally timed intervals stay stamp-only
    assert not any(n.startswith("repro.queue_wait") for n in names)


def test_no_trace_me_is_built_without_a_profiler_session(repo, monkeypatch,
                                                         tmp_path):
    import gc

    import jax

    import repro.obs.trace as obs_trace

    built = []

    class Counting(obs_trace._TraceMe):
        def __init__(self, name, **kw):
            built.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(obs_trace, "_TraceMe", Counting)
    svc = QueryService(QueryEngine(tiny_pairs=0))
    svc.register("main", repo)
    svc.query({"log": "main", "sink": "dfg"})
    svc.query({"log": "main", "sink": "histogram"})
    gc.collect()
    assert built == []
    # the same calls under a session do build them
    with jax.profiler.trace(str(tmp_path)):
        svc.query({"log": "main", "sink": "histogram"})
    assert "repro.serve.payload" in built


def test_scan_phases_and_h2d_bytes_of_one_query(repo, monkeypatch):
    import jax

    from repro.kernels.dfg_count.ops import pick_blocks

    moved = []
    put = jax.device_put

    def spy(x, *a, **kw):
        moved.append(sum(np.asarray(c).nbytes for c in jax.tree.leaves(x)))
        return put(x, *a, **kw)

    monkeypatch.setattr(jax, "device_put", spy)
    engine = QueryEngine(tiny_pairs=0)
    ts = repo.event_time
    t0, t1 = float(np.quantile(ts, 0.2)), float(np.quantile(ts, 0.7))
    res = Q.log(repo).using(engine).window(t0, t1).dfg(backend="pallas")
    assert res.physical.fused_dicing
    snap = engine.metrics_snapshot()
    # the first count puts the repository's pairs on the device once,
    # padded to the kernel's event block: two int32 ids, one bool and two
    # int32 time ranks a pair; the query itself ships the window's bounds
    block = pick_blocks(repo.num_activities)[0]
    resident = 17 * (-(-(repo.num_events - 1) // block) * block)
    assert moved == [resident, 8]
    assert snap["engine_resident_builds_total"] == 1
    assert snap["engine_resident_bytes_total"] == resident
    assert snap["engine_h2d_bytes_total"] == 8
    assert snap["engine_scan_resident_total"] == 1
    for phase in ("prepare", "h2d", "device"):
        h = snap[f"engine_scan_phase_seconds{{phase={phase}}}"]
        assert h["count"] == 1 and h["sum"] > 0.0
    spans = {s.name: s for s in res.trace.spans}
    scan = spans["scan"]
    for name in ("scan.prepare", "scan.h2d", "scan.device"):
        s = spans[name]
        assert scan.start_s <= s.start_s
        assert s.start_s + s.duration_s <= scan.start_s + scan.duration_s
    # the build is the query's ``resident`` span, inside ``scan.prepare``
    prep, built = spans["scan.prepare"], spans["resident"]
    assert prep.start_s <= built.start_s
    assert built.start_s + built.duration_s <= prep.start_s + prep.duration_s
    # the same bits as the numpy count
    ref = Q.log(repo).using(QueryEngine()).window(t0, t1).dfg(backend="numpy")
    np.testing.assert_array_equal(res.value, ref.value)
    # a second window ships its 8 bytes and builds nothing
    t2 = float(np.quantile(ts, 0.9))
    res = Q.log(repo).using(engine).window(t0, t2).dfg(backend="pallas")
    assert "resident" not in {s.name for s in res.trace.spans}
    snap = engine.metrics_snapshot()
    assert moved == [resident, 8, 8]
    assert snap["engine_h2d_bytes_total"] == 16
    assert snap["engine_resident_builds_total"] == 1
    assert snap["engine_scan_resident_total"] == 2
    ref = Q.log(repo).using(QueryEngine()).window(t0, t2).dfg(backend="numpy")
    np.testing.assert_array_equal(res.value, ref.value)
    # a count on the host opens no phase and moves nothing
    Q.log(repo).using(engine).dfg(backend="numpy")
    snap = engine.metrics_snapshot()
    assert snap["engine_scan_phase_seconds{phase=h2d}"]["count"] == 2
    assert snap["engine_h2d_bytes_total"] == 16


def test_coverage_counts_nested_spans_once():
    tr = QueryTrace(1, "dfg", "repository")
    t = 100.0
    tr.add_span("parse", t, 0.1)
    tr.add_span("scan", t + 0.1, 0.6)
    tr.add_span("scan.prepare", t + 0.1, 0.2)
    tr.add_span("scan.device", t + 0.3, 0.4)
    tr.add_span("sink", t + 0.8, 0.1)
    tr.total_s = 1.0
    # top-level spans: parse 0.1 + scan 0.6 + sink 0.1
    assert tr.coverage() == pytest.approx(0.8)


def test_forensics_with_nested_scan_spans_matches_oracle(repo):
    engine = QueryEngine(tiny_pairs=0)
    Q.log(repo).using(engine).dfg()
    Q.log(repo).using(engine).dfg()          # cache hit
    Q.log(repo).using(engine).histogram()
    own = engine.own_telemetry()
    res = Q.log(own).using(QueryEngine()).dfg()
    src, dst, valid = own.df_pairs()
    expect = dfg_numpy(src, dst, valid, own.num_activities)
    psi = np.asarray(res.value)
    np.testing.assert_array_equal(psi, expect)
    chain = ["scan", "scan.prepare", "scan.h2d", "scan.device", "sink"]
    for a, b in zip(chain, chain[1:]):
        assert psi[res.names.index(a), res.names.index(b)] >= 1


def test_payload_seconds_by_sink_and_exported(repo):
    svc = QueryService()
    svc.register("main", repo)
    svc.query({"log": "main", "sink": "dfg"})
    svc.query({"log": "main", "sink": "dfg"})    # a cache hit builds one too
    svc.query({"log": "main", "sink": "histogram"})
    snap = svc.engine.metrics_snapshot()
    assert snap["serve_payload_seconds{sink=dfg}"]["count"] == 2
    assert snap["serve_payload_seconds{sink=histogram}"]["count"] == 1
    assert "engine_cache_hit_ratio" not in snap
    prom = svc.query({"sink": "metrics", "format": "prometheus"})["prometheus"]
    assert 'serve_payload_seconds_count{sink="dfg"} 2' in prom
    assert "engine_scan_phase_seconds" in prom
    assert "engine_h2d_bytes_total" in prom
    assert 'process_gc_pause_seconds_count{generation="2"}' in prom


def test_gc_pauses_are_counted_once_per_process():
    import gc

    from repro.obs.process import _on_gc, install_gc_hook

    install_gc_hook()
    install_gc_hook()

    assert gc.callbacks.count(_on_gc) == 1
    h = kernel_registry().histogram("process_gc_pause_seconds", generation="2")
    n, total = h.count, h.sum
    gc.collect()
    assert h.count == n + 1
    assert h.sum > total

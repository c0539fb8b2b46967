"""repro.analysis — lint rules on fixture trees and the real tree, baseline
round-trip, the lockdep runtime sanitizer, the Pallas resource checker, and
regression tests for the violations the lint surfaced."""

import json
import shutil
import threading
from pathlib import Path

import pytest

from repro.analysis import lockdep
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.framework import (
    Finding,
    Project,
    load_baseline,
    run_rules,
    save_baseline,
    split_findings,
)
from repro.analysis.kernels_check import (
    KernelResourceError,
    build_report,
    validate_blocks,
)

REPO_ROOT = Path(__file__).resolve().parent.parent
FIXTURES = Path(__file__).resolve().parent / "analysis_fixtures"


def fixture_findings(tree, rules=None):
    return run_rules(Project(FIXTURES / tree), rules)


# ---------------------------------------------------------------------------
# Rule flag / pass cases on fixture trees
# ---------------------------------------------------------------------------


def test_unhandled_sink_is_flagged():
    found = fixture_findings("unhandled_sink", ["backend-coverage"])
    assert len(found) == 1
    f = found[0]
    assert "OrphanSink" in f.message
    assert f.path.endswith("query/planner.py")


def test_covered_sinks_pass_via_alias():
    # execute.py covers both sinks through the SINKS tuple alias
    found = fixture_findings("unhandled_sink", ["backend-coverage"])
    assert not any(f.path.endswith("execute.py") for f in found)


def test_unkeyed_plan_field_is_flagged():
    msgs = [f.message for f in fixture_findings(
        "unkeyed_field", ["cache-key-completeness"]
    )]
    assert any("unkeyed plan field: WindowSink.span" in m for m in msgs)
    assert any("MutableSink is not frozen=True" in m for m in msgs)
    assert any(
        "unkeyed plan field: ShardedDFGSink.num_shards" in m for m in msgs
    )
    assert any(
        "LogicalPlan.sink does not flow into the canonical payload" in m
        for m in msgs
    )


def test_unlocked_stats_mutation_is_flagged():
    msgs = [f.message for f in fixture_findings(
        "unlocked_stats", ["lock-discipline"]
    )]
    assert any(
        "StatsRegistry.reset: mutation of lock-protected attribute "
        "'counts'" in m
        for m in msgs
    )
    # annotated-only protection (no locked mutation site to infer from)
    assert any("AnnotatedRegistry.observe" in m and "'hists'" in m
               for m in msgs)
    # _locked-suffix helpers are exempt
    assert not any("_wipe_locked" in m for m in msgs)
    assert any("blocking call open()" in m for m in msgs)
    assert any("inconsistent lock order" in m for m in msgs)


def test_kernel_hygiene_is_flagged():
    msgs = [f.message for f in fixture_findings(
        "hygiene_bad", ["rng-time-hygiene"]
    )]
    assert any("time.time()" in m for m in msgs)
    assert any("np.random.uniform()" in m for m in msgs)
    assert any("time.perf_counter_ns()" in m for m in msgs)


def test_clean_tree_passes_every_rule():
    assert fixture_findings("clean_tree") == []


# ---------------------------------------------------------------------------
# Deliberate regressions against copies of the *real* engine files
# ---------------------------------------------------------------------------


def _copy_query_tree(tmp_path):
    qdir = tmp_path / "query"
    qdir.mkdir()
    for name in ("ast.py", "planner.py", "execute.py"):
        shutil.copy(REPO_ROOT / "src" / "repro" / "query" / name, qdir / name)
    return tmp_path


def test_new_sink_in_real_tree_is_caught(tmp_path):
    root = _copy_query_tree(tmp_path)
    with open(root / "query" / "ast.py", "a") as fh:
        fh.write(
            "\n\n@dataclasses.dataclass(frozen=True)\n"
            "class ShinyNewSink:\n    backend: str = 'auto'\n"
        )
    found = run_rules(Project(root), ["backend-coverage"])
    assert {f.path for f in found} == {"query/planner.py", "query/execute.py"}
    assert all("ShinyNewSink" in f.message for f in found)


def test_unkeyed_field_in_real_tree_is_caught(tmp_path):
    root = _copy_query_tree(tmp_path)
    with open(root / "query" / "ast.py", "a") as fh:
        fh.write(
            "\n\n@dataclasses.dataclass(frozen=True)\n"
            "class SneakySink:\n"
            "    backend: str = 'auto'\n"
            "    def __post_init__(self):\n"
            "        object.__setattr__(self, 'mode', 'fast')\n"
        )
    found = run_rules(Project(root), ["cache-key-completeness"])
    assert any("unkeyed plan field: SneakySink.mode" in f.message
               for f in found)


def test_new_sharded_sink_in_real_tree_is_caught(tmp_path):
    # the sharded-graph dispatch tables (planner _DFG_BACKENDS + executor
    # _execute_sharded) must not satisfy coverage for a sink they never saw
    root = _copy_query_tree(tmp_path)
    with open(root / "query" / "ast.py", "a") as fh:
        fh.write(
            "\n\n@dataclasses.dataclass(frozen=True)\n"
            "class ShardMergeSink:\n    backend: str = 'sharded-graph'\n"
        )
    found = run_rules(Project(root), ["backend-coverage"])
    assert {f.path for f in found} == {"query/planner.py", "query/execute.py"}
    assert all("ShardMergeSink" in f.message for f in found)


def test_unpatched_real_tree_is_clean(tmp_path):
    root = _copy_query_tree(tmp_path)
    assert run_rules(
        Project(root), ["backend-coverage", "cache-key-completeness"]
    ) == []


# ---------------------------------------------------------------------------
# The real tree + committed baseline (the CI gate, in-process)
# ---------------------------------------------------------------------------


def test_real_tree_has_no_new_findings():
    findings = run_rules(Project(REPO_ROOT))
    baseline = load_baseline(REPO_ROOT / "analysis_baseline.json")
    new, _known, stale = split_findings(findings, baseline)
    assert new == [], [f.format() for f in new]
    assert stale == [], f"stale baseline entries: {stale}"


def test_cli_fail_on_new_is_clean_on_real_repo(capsys):
    # the exact CI gate, end to end: the sharded tier's plan dataclasses
    # (HistogramSink.backend, the sharded dispatch tables, shard/store
    # locks) must not introduce findings over the committed baseline
    rc = analysis_main(
        ["--root", str(REPO_ROOT),
         "--baseline", str(REPO_ROOT / "analysis_baseline.json"),
         "--fail-on-new"]
    )
    capsys.readouterr()
    assert rc == 0


# ---------------------------------------------------------------------------
# Baseline round-trip + CLI
# ---------------------------------------------------------------------------


def test_baseline_roundtrip(tmp_path):
    findings = fixture_findings("unlocked_stats")
    assert findings
    path = tmp_path / "baseline.json"
    save_baseline(path, findings, justification="fixture")
    baseline = load_baseline(path)
    new, known, stale = split_findings(findings, baseline)
    assert new == [] and stale == []
    assert len(known) == len(findings)
    # a fixed finding leaves a stale entry behind (baselines only shrink)
    new, _known, stale = split_findings(findings[1:], baseline)
    assert new == [] and stale == [findings[0].identity()]


def test_finding_identity_ignores_line_numbers():
    a = Finding("r", "p.py", 10, "msg")
    b = Finding("r", "p.py", 99, "msg")
    assert a.identity() == b.identity()
    assert a.identity() != Finding("r", "p.py", 10, "other").identity()


def test_cli_exits_nonzero_on_new_findings(tmp_path, capsys):
    rc = analysis_main(
        ["--root", str(FIXTURES / "unlocked_stats"),
         "--baseline", str(tmp_path / "none.json"), "--fail-on-new"]
    )
    assert rc == 1
    assert "lock-discipline" in capsys.readouterr().out


def test_cli_baseline_gates_to_zero(tmp_path, capsys):
    baseline = tmp_path / "baseline.json"
    rc = analysis_main(
        ["--root", str(FIXTURES / "unlocked_stats"),
         "--baseline", str(baseline), "--write-baseline"]
    )
    assert rc == 0
    capsys.readouterr()  # drain the --write-baseline chatter
    rc = analysis_main(
        ["--root", str(FIXTURES / "unlocked_stats"),
         "--baseline", str(baseline), "--fail-on-new", "--json"]
    )
    assert rc == 0
    out = json.loads(capsys.readouterr().out)
    assert out["new"] == []
    assert out["baselined"]


# ---------------------------------------------------------------------------
# lockdep runtime sanitizer
# ---------------------------------------------------------------------------


@pytest.fixture()
def lockdep_on(monkeypatch):
    monkeypatch.setenv("REPRO_LOCKDEP", "1")
    lockdep.reset()
    yield
    lockdep.reset()


def test_make_lock_is_plain_lock_when_disabled(monkeypatch):
    monkeypatch.delenv("REPRO_LOCKDEP", raising=False)
    lock = lockdep.make_lock("x")
    assert not isinstance(lock, lockdep.LockdepLock)
    with lock:
        pass


def test_lockdep_detects_inverted_order(lockdep_on):
    a = lockdep.make_lock("A")
    b = lockdep.make_lock("B")
    with a:
        with b:
            pass
    with pytest.raises(lockdep.LockOrderError, match="inversion"):
        with b:
            with a:
                pass


def test_lockdep_detects_transitive_cycle(lockdep_on):
    a, b, c = (lockdep.make_lock(n) for n in "ABC")
    with a:
        with b:
            pass
    with b:
        with c:
            pass
    with pytest.raises(lockdep.LockOrderError):
        with c:
            with a:
                pass


def test_lockdep_detects_recursive_acquisition(lockdep_on):
    a = lockdep.make_lock("A")
    with pytest.raises(lockdep.LockOrderError, match="recursive"):
        with a:
            with a:
                pass


def test_lockdep_allows_same_name_family(lockdep_on):
    # per-log append locks share a name; members are never ordered
    a1 = lockdep.make_lock("append")
    a2 = lockdep.make_lock("append")
    with a1:
        with a2:
            pass
    with a2:
        with a1:
            pass


def test_lockdep_consistent_order_is_quiet(lockdep_on):
    a = lockdep.make_lock("A")
    b = lockdep.make_lock("B")
    for _ in range(3):
        with a:
            with b:
                pass
    assert ("A", "B") in lockdep.order_edges()


def test_engine_under_lockdep_runs_clean(lockdep_on):
    # the engine's real lock nestings must not trip the sanitizer
    from repro.data import ProcessSpec, generate_repository
    from repro.query import Q, QueryEngine

    engine = QueryEngine()
    repo = generate_repository(200, ProcessSpec(num_activities=7, seed=3))
    for _ in range(2):
        Q.log(repo).using(engine).dfg()
        Q.log(repo).using(engine).histogram()
    assert engine.metrics_snapshot()["engine_queries_total"] >= 4


# ---------------------------------------------------------------------------
# Pallas kernel resource checker
# ---------------------------------------------------------------------------


def test_validate_blocks_passes_for_picked_blocks():
    from repro.kernels.align_dp.ops import pick_blocks as pick_align
    from repro.kernels.dfg_count.ops import pick_blocks as pick_dfg
    from repro.kernels.segment_count.ops import pick_blocks as pick_seg

    for a in (8, 64, 512, 4096):
        pick_dfg(a)  # validates internally
        pick_seg(a)
    for v, l, s in ((5, 4, 3), (1000, 600, 400)):
        lp = max(128, -(-l // 128) * 128)
        sp = max(128, -(-s // 128) * 128)
        validate_blocks("align_dp", block_v=pick_align(v), lp=lp, s=sp)


def test_validate_blocks_rejects_vmem_overrun():
    with pytest.raises(KernelResourceError, match="VMEM"):
        validate_blocks(
            "dfg_count", block_e=1 << 20, block_s=512, block_d=512
        )


def test_validate_blocks_rejects_misaligned_lane():
    with pytest.raises(KernelResourceError, match="multiple of 128"):
        validate_blocks(
            "dfg_count", block_e=1536, block_s=384, block_d=384 + 12
        )


def test_validate_blocks_requires_full_env():
    with pytest.raises(KernelResourceError, match="unresolved symbol"):
        validate_blocks("align_dp", block_v=64)


def test_kernel_report_covers_all_kernels_within_limit():
    report = build_report()
    assert set(report["kernels"]) == {
        "dfg_count", "segment_count", "align_dp"
    }
    for kernel in report["kernels"].values():
        for scenario in kernel["scenarios"]:
            assert scenario["max_vmem_bytes"] <= report["vmem_limit_bytes"]
            for call in scenario["calls"]:
                assert call["errors"] == []


def test_committed_kernel_report_is_current():
    committed = json.loads((REPO_ROOT / "BENCH_analysis.json").read_text())
    assert committed == build_report()


# ---------------------------------------------------------------------------
# Regression tests for the violations this lint surfaced
# ---------------------------------------------------------------------------


def test_latency_hist_memo_single_instance_under_threads():
    # _trace_finish used to insert into _lat_hists without the engine lock;
    # racing threads could each build a Histogram and leak divergent memos
    from repro.data import ProcessSpec, generate_repository
    from repro.query import Q, QueryEngine

    engine = QueryEngine()
    repo = generate_repository(150, ProcessSpec(num_activities=5, seed=1))
    barrier = threading.Barrier(8)
    errors = []

    def worker():
        try:
            barrier.wait()
            for _ in range(3):
                Q.log(repo).using(engine).dfg()
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=worker) for _ in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert errors == []
    # pre-fix, racing threads each built a Histogram and observed into their
    # own copy while only one won the memo slot — observations were lost.
    # The memo keys by (sink, backend), so sum across all of them.
    assert all(k[0] == "dfg" for k in engine._lat_hists)
    assert sum(h.count for h in engine._lat_hists.values()) == 24


def test_cache_eviction_drops_hints_for_dead_entries():
    # _drop_hints_for → _drop_hints_locked: the caller-holds-lock rename;
    # eviction must still clear the delta hints of the evicted entry
    from repro.query.cache import QueryCache

    cache = QueryCache(max_entries=2)

    class _R:
        value = 0
        names = None
        trace = None

    for i in range(3):
        cache.put((f"fp{i}", "plan"), _R(), source_hint=f"src{i}")
    assert cache.delta_candidate("src0", "plan") is None  # evicted
    assert cache.delta_candidate("src2", "plan") is not None

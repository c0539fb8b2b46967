"""Physical planning — pick the execution backend and the pushdowns.

The cost model is deliberately small (the paper's point is that the *store*
picks the strategy, not the analyst):

========================  =====================================================
situation                 physical operator
========================  =====================================================
memmap log > budget       ``streaming`` scan (O(A²+chunk) memory), window
                          pushed to a **row range** via the chunk time index
memmap log ≤ budget       materialize once, then the device path below
tiny input (≤ tiny_pairs) ``numpy`` scatter-add — beats device dispatch
                          overhead by orders of magnitude at this size
mesh available            ``distributed`` shard_map + psum over every axis
CPU default backend       ``scatter`` (jnp .at[].add)
TPU/GPU                   ``pallas`` MXU kernel; a time window fuses into the
                          kernel's WHERE clause (``dfg_count_diced``)
graph built / amortized   ``graph`` — un-windowed topology sinks (DFG,
                          process map, neighborhood) become CSR lookups in
                          the event-knowledge graph store (repro.graph)
========================  =====================================================

Pushdown decisions recorded on the :class:`PhysicalPlan`:

* ``row_range`` — the memmap chunk-time-index dice (paper Experiment 2);
* ``fused_dicing`` — window evaluated inside the Pallas kernel, on the
  repository's int32 time ranks (exact for any float64 time);
* ``view_pushdown`` — when the projection shrinks the activity set, relabel
  pair columns to group ids *before* counting so the matmul/count runs at
  G×G instead of A×A;
* ``activities_as_output_mask`` — a paper-semantics activity filter commutes
  past counting: Ψ restricted to keep×keep equals counting masked pairs, so
  the filter becomes a free O(A²) mask on the result instead of an O(E)
  pair predicate.

One physical operator is chosen by the *engine*, not here: ``delta``.  When
a memmap source is proven to be an append-only extension of a cached scan
(prefix-preserving fingerprint), the engine resumes the cached streaming
state over just the appended suffix — ``delta_rows`` records the suffix row
range it scanned.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import os
from typing import Dict, Optional, Tuple

import jax
import numpy as np

from repro.core.repository import EventRepository
from repro.core.streaming import MemmapLog
from repro.graph.shard import ShardedLog

from .ast import (
    CONFORMANCE_SINKS,
    TOPOLOGY_SINKS,
    Activities,
    AlignmentsSink,
    ApplyView,
    CompareSink,
    DFGSink,
    FitnessSink,
    HistogramSink,
    LogicalPlan,
    NeighborhoodSink,
    ProcessMapSink,
    QueryPlanError,
    UnionSource,
    VariantsSink,
    Window,
    is_barrier,
    source_kind,
    union_activity_names,
)

__all__ = [
    "SourceInfo",
    "PhysicalPlan",
    "CrossoverCurve",
    "source_info",
    "plan_physical",
    "load_calibration",
    "resolve_threshold",
    "estimate_cost_s",
    "SLO_HOT_CUTOFF_S",
]

_LOG = logging.getLogger(__name__)

#: below this many pairs, numpy beats any device dispatch
TINY_PAIRS = 2048
#: above this many events a memmap log is mined out-of-core
MEMORY_BUDGET_EVENTS = 1 << 22
#: repeated topology queries on one source after which building the
#: event-knowledge graph (repro.graph) amortizes — measured crossover
#: comes from BENCH_graph.json when available
GRAPH_REPEAT_CROSSOVER = 3
#: memmap events above which the one-pass streaming replayer beats
#: materialize-then-replay for conformance sinks; the static default ties
#: it to the memory budget (identical behavior to the budget gate), the
#: measured value comes from BENCH_conformance.json
REPLAY_STREAMING_CROSSOVER = MEMORY_BUDGET_EVENTS
#: sharded-log events above which the sharded-graph backend (per-shard CSR
#: snapshots + aligned psum merge) beats concatenate-and-materialize on a
#: single host; the measured value comes from BENCH_shard.json
SHARDED_SINGLE_CROSSOVER = 1 << 18
#: predicted execution cost (seconds) below which the serving tier
#: (repro.transport) classifies a request *hot* — it rides the warm lane
#: with cache/delta/graph serves instead of queueing behind cold scans.
#: The static default sits between a cache hit (~100µs) and a cold
#: streaming scan (~300ms); the measured boundary comes from
#: BENCH_serve.json (geometric mean of the measured warm-lane p99 and
#: cold-lane median service times)
SLO_HOT_CUTOFF_S = 0.05

# Order-of-magnitude cost priors for the observability drift check: fixed
# per-backend dispatch overhead plus an events-per-second throughput.
# These exist so a recorded trace (repro.obs.QueryTrace) can be contrasted
# with the planner's choice — they never influence planning itself, which
# uses the measured calibration crossovers above.
_COST_DISPATCH_S = {
    "numpy": 5e-5,
    "scatter": 3e-4,      # jit-cache lookup
    "onehot": 3e-4,
    "pallas": 3e-4,       # jit-cache lookup + host↔device transfers
    "distributed": 1e-3,  # mesh collective setup
    "graph": 5e-5,        # CSR lookup / densify
    "sharded-graph": 5e-4,  # K store lookups + K·A² aligned merge
}
# Conservative CPU-measured throughputs (events/s), cold-path inclusive:
# a cold scan on a memmap source pays materialization + masking on top of
# the kernel itself, and the drift band (±16x by default) absorbs warm-path
# speedups and accelerator headroom.  These priors never influence planning
# — they only give traces a prediction to contrast with the measured span.
_COST_RATE_EVENTS_S = {
    "numpy": 2e7,
    "scatter": 2e6,
    "onehot": 1e6,
    "pallas": 5e6,
    "distributed": 1e7,
    "streaming": 5e6,
    "delta": 5e6,
    "graph": 4e8,
    "concat": 5e6,
    "sharded-graph": 2e8,  # per-shard CSR serves, minus the merge constant
}


def estimate_cost_s(backend: str, num_events: int) -> float:
    """Prior execution cost (seconds) of ``backend`` over ``num_events``
    events — the prediction a trace records so ``explain(after=...)`` and
    the ``planner_drift_total`` counter can contrast it with the measured
    span."""
    rate = _COST_RATE_EVENTS_S.get(backend, 5e6)
    return _COST_DISPATCH_S.get(backend, 1e-4) + num_events / rate


# ---------------------------------------------------------------------------
# Measured calibration (ROADMAP "smarter cost model")
# ---------------------------------------------------------------------------

#: sanity rails: a stray or corrupt bench record must not be able to flip
#: plans far outside the regime the bench actually measured
_CALIBRATION_CLAMPS = {
    "tiny_pairs": (256, 4096),
    "memory_budget_events": (1 << 20, 1 << 26),
}
_GRAPH_CLAMPS = {
    "graph_repeat_crossover": (1, 64),
}
_CONFORMANCE_CLAMPS = {
    "replay_streaming_crossover": (1 << 18, 1 << 26),
}
_SHARD_CLAMPS = {
    "sharded_single_crossover": (1 << 14, 1 << 24),
}
#: float clamp bounds mark float-valued calibration keys (the serve
#: boundary is seconds, not a count)
_SERVE_CLAMPS = {
    "slo_hot_cutoff_s": (1e-4, 2.0),
}
_REPO_ROOT = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..")
)

#: calibration basenames already warned about this process — a planner that
#: runs on static fallbacks should say so exactly once, not on every query
_warned_missing: set = set()


@dataclasses.dataclass(frozen=True)
class CrossoverCurve:
    """A crossover threshold as a *function of problem size* instead of one
    scalar.  Benches emit ``calibration.curves.<key>`` as measured
    ``[work, threshold]`` points where ``work = events × activities``; the
    curve interpolates them piecewise-linearly (clamped to the endpoint
    thresholds outside the measured range), so the same mechanism serves
    every backend crossover — tiny_pairs, replay, graph repeats, and the
    sharded-vs-single-host decision."""

    key: str
    xs: Tuple[float, ...]  # sorted work coordinates (events × activities)
    ys: Tuple[float, ...]  # measured thresholds at those sizes

    def value_at(self, work: float) -> int:
        return int(round(float(np.interp(float(work), self.xs, self.ys))))


def _parse_curves(
    cal: dict, clamps: Dict[str, Tuple[int, int]], out: Dict
) -> None:
    """Fit clamp-railed :class:`CrossoverCurve` objects from a record's
    ``curves`` section.  Only keys this record is allowed to calibrate (its
    scalar clamp keys) are accepted, and every threshold passes the same
    sanity rails as the scalar — one corrupt record still cannot flip plans
    outside the measured regime."""
    curves = cal.get("curves")
    if not isinstance(curves, dict):
        return
    for key, (lo, hi) in clamps.items():
        pts = curves.get(key)
        if not isinstance(pts, list) or not pts:
            continue
        try:
            parsed = sorted(
                (float(x), float(min(max(float(y), lo), hi)))
                for x, y in pts
                if float(x) >= 0 and float(y) > 0
            )
        except (TypeError, ValueError):
            continue
        if parsed:
            out.setdefault("curves", {})[key] = CrossoverCurve(
                key=key,
                xs=tuple(x for x, _ in parsed),
                ys=tuple(y for _, y in parsed),
            )


def _read_calibration(
    explicit: Optional[str],
    basename: str,
    clamps: Dict[str, Tuple[int, int]],
    out: Dict,
) -> None:
    """Merge one bench record's ``calibration`` section into ``out``,
    clamped.  An explicitly named record is authoritative: if it is missing
    or corrupt we fall back to the *static constants*, never to whatever
    record happens to sit in the cwd / repo root."""
    candidates = [explicit] if explicit else [
        basename,
        os.path.join(_REPO_ROOT, basename),
    ]
    for cand in candidates:
        if not cand or not os.path.isfile(cand):
            continue
        try:
            with open(cand) as f:
                data = json.load(f)
        except (OSError, ValueError):
            continue  # unreadable / corrupt: static fallback
        cal = data.get("calibration")
        if not isinstance(cal, dict):
            continue
        for key, (lo, hi) in clamps.items():
            v = cal.get(key)
            if isinstance(v, (int, float)) and not isinstance(v, bool) and v > 0:
                # float clamp bounds mark float-valued keys (e.g. the
                # serve-tier SLO boundary in seconds); everything else
                # stays an integer threshold
                if isinstance(lo, float) or isinstance(hi, float):
                    out[key] = float(min(max(float(v), lo), hi))
                else:
                    out[key] = int(min(max(int(v), lo), hi))
        _parse_curves(cal, clamps, out)
        return
    if basename not in _warned_missing:
        _warned_missing.add(basename)
        _LOG.warning(
            "calibration record %s not found (searched explicit path, cwd, "
            "repo root); the planner falls back to static thresholds for %s",
            basename, sorted(clamps),
        )


def load_calibration(
    path: Optional[str] = None,
    graph_path: Optional[str] = None,
    conformance_path: Optional[str] = None,
    shard_path: Optional[str] = None,
    serve_path: Optional[str] = None,
) -> Dict:
    """Cost-model thresholds, measured when available.

    ``benchmarks/bench_query_engine.py`` writes a ``calibration`` section
    (backend-crossover ``tiny_pairs``, machine-sized
    ``memory_budget_events``) into ``BENCH_query.json``, and
    ``benchmarks/bench_graph.py`` writes the columnar↔graph crossover
    (``graph_repeat_crossover`` — the repeat-query count above which
    building the event-knowledge graph amortizes) into
    ``BENCH_graph.json``, and ``benchmarks/bench_conformance.py`` the
    streaming↔materialize replay crossover
    (``replay_streaming_crossover`` events) into
    ``BENCH_conformance.json``, and ``benchmarks/bench_shard.py`` the
    sharded-vs-single-host crossover (``sharded_single_crossover`` events)
    into ``BENCH_shard.json``, and ``benchmarks/bench_serve.py`` the
    serving tier's measured hot/cold lane boundary (``slo_hot_cutoff_s``
    seconds — the crossover between warm-lane serves and cold scans that
    the transport SLO classifier splits traffic on) into
    ``BENCH_serve.json``.  When such records exist — searched as:
    explicit path argument, ``$GRAPHPM_BENCH_QUERY`` /
    ``$GRAPHPM_BENCH_GRAPH`` / ``$GRAPHPM_BENCH_CONFORMANCE`` /
    ``$GRAPHPM_BENCH_SHARD`` / ``$GRAPHPM_BENCH_SERVE``, ``./BENCH_*.json``, ``<repo
    root>/BENCH_*.json`` — their values replace the static constants,
    clamped to sanity rails, and any ``curves`` section becomes a
    :class:`CrossoverCurve` under ``out["curves"]`` (threshold as a function
    of events × activities — used in preference to the scalar when
    present).  The constants are always the fallback, so a machine that
    never benchmarked plans exactly as before; a missing record logs a
    one-time warning so silent static-fallback runs are visible.
    """
    out: Dict = {
        "tiny_pairs": TINY_PAIRS,
        "memory_budget_events": MEMORY_BUDGET_EVENTS,
        "graph_repeat_crossover": GRAPH_REPEAT_CROSSOVER,
        "replay_streaming_crossover": REPLAY_STREAMING_CROSSOVER,
        "sharded_single_crossover": SHARDED_SINGLE_CROSSOVER,
        "slo_hot_cutoff_s": SLO_HOT_CUTOFF_S,
        "curves": {},
    }
    _read_calibration(
        path or os.environ.get("GRAPHPM_BENCH_QUERY"),
        "BENCH_query.json", _CALIBRATION_CLAMPS, out,
    )
    _read_calibration(
        graph_path or os.environ.get("GRAPHPM_BENCH_GRAPH"),
        "BENCH_graph.json", _GRAPH_CLAMPS, out,
    )
    _read_calibration(
        conformance_path or os.environ.get("GRAPHPM_BENCH_CONFORMANCE"),
        "BENCH_conformance.json", _CONFORMANCE_CLAMPS, out,
    )
    _read_calibration(
        shard_path or os.environ.get("GRAPHPM_BENCH_SHARD"),
        "BENCH_shard.json", _SHARD_CLAMPS, out,
    )
    _read_calibration(
        serve_path or os.environ.get("GRAPHPM_BENCH_SERVE"),
        "BENCH_serve.json", _SERVE_CLAMPS, out,
    )
    return out


def resolve_threshold(calibration: Dict, key: str, work: float) -> int:
    """The effective crossover for ``key`` at problem size ``work``
    (events × activities): the fitted curve when the calibration carries
    one, else the (possibly bench-measured) scalar."""
    curve = calibration.get("curves", {}).get(key)
    if curve is not None:
        return curve.value_at(work)
    return int(calibration[key])

_DFG_BACKENDS = {
    "auto", "numpy", "scatter", "onehot", "pallas", "streaming", "distributed",
    "graph", "sharded-graph",
}
#: conformance sinks replay/align sequences — device counting backends do
#: not apply; "numpy" is the columnar replay, "streaming" the one-pass
#: replayer, "graph" the stored-event-table walk
_CONFORMANCE_BACKENDS = {"auto", "numpy", "streaming", "graph"}


@dataclasses.dataclass(frozen=True)
class SourceInfo:
    kind: str  # "repository" | "memmap" | "sharded" | "union(...)"
    num_events: int
    num_pairs: int
    num_activities: int
    activity_names: Optional[Tuple[str, ...]]
    # union sources only: per-branch shapes (costed individually — one
    # union may mix an out-of-core memmap branch with in-memory ones)
    branches: Optional[Tuple["SourceInfo", ...]] = None
    branch_names: Optional[Tuple[str, ...]] = None
    # sharded sources only: per-shard shapes (each shard is costed as an
    # independent memmap — windowed serves need every shard in budget)
    shards: Optional[Tuple["SourceInfo", ...]] = None
    shard_names: Optional[Tuple[str, ...]] = None


def source_info(source) -> SourceInfo:
    if isinstance(source, EventRepository):
        return SourceInfo(
            kind="repository",
            num_events=source.num_events,
            num_pairs=max(source.num_events - 1, 0),
            num_activities=source.num_activities,
            activity_names=tuple(source.activity_names),
        )
    if isinstance(source, MemmapLog):
        return SourceInfo(
            kind="memmap",
            num_events=source.num_events,
            num_pairs=max(source.num_events - 1, 0),
            num_activities=source.num_activities,
            activity_names=None,
        )
    if isinstance(source, ShardedLog):
        present = source.present_shards()
        infos = tuple(source_info(s) for _, s in present)
        return SourceInfo(
            kind="sharded",
            num_events=sum(i.num_events for i in infos),
            num_pairs=sum(i.num_pairs for i in infos),
            num_activities=source.num_activities,
            activity_names=tuple(source.activity_labels()),
            shards=infos,
            shard_names=tuple(f"shard{k}" for k, _ in present),
        )
    if isinstance(source, UnionSource):
        infos = tuple(source_info(b.resolve()) for b in source.branches)
        names = tuple(union_activity_names(source))
        return SourceInfo(
            kind=source_kind(source),
            num_events=sum(i.num_events for i in infos),
            num_pairs=sum(i.num_pairs for i in infos),
            num_activities=len(names),
            activity_names=names,
            branches=infos,
            branch_names=source.branch_names,
        )
    raise QueryPlanError(f"unsupported source {type(source).__name__}")


@dataclasses.dataclass(frozen=True)
class PhysicalPlan:
    # numpy | scatter | onehot | pallas | streaming | distributed | delta
    #   | union | compare | concat | graph
    # ("delta" is engine-chosen only: it resumes cached streaming state over
    # a proven append-only suffix and is never requestable by the analyst;
    # "union"/"compare" merge per-branch sub-plans — the notes record each
    # branch's own backend — "concat" materializes the concatenated
    # repository for ops that do not distribute, and "graph" answers
    # topology sinks from the CSR event-knowledge graph store)
    backend: str
    materialize: bool = False  # memmap source loaded into memory first
    row_range_window: Optional[Tuple[float, float]] = None
    fused_dicing: bool = False
    view_pushdown: bool = False
    activities_as_output_mask: bool = False
    delta_rows: Optional[Tuple[int, int]] = None  # suffix row range scanned
    notes: Tuple[str, ...] = ()

    def describe(self) -> str:
        parts = [f"backend={self.backend}"]
        if self.materialize:
            parts.append("materialize=memmap→memory")
        if self.row_range_window is not None:
            parts.append("pushdown=row_range(chunk time index)")
        if self.fused_dicing:
            parts.append("pushdown=fused_pallas_dicing")
        if self.view_pushdown:
            parts.append("pushdown=view_below_count")
        if self.activities_as_output_mask:
            parts.append("rewrite=activity_filter→output_mask")
        if self.delta_rows is not None:
            parts.append(f"delta=scan_rows[{self.delta_rows[0]}:{self.delta_rows[1]})")
        parts.extend(self.notes)
        return ", ".join(parts)


def _segment_features(plan: LogicalPlan):
    """Ops of the final (post-barrier) segment + whether barriers exist."""
    has_barrier = any(is_barrier(op) for op in plan.ops)
    tail = []
    for op in plan.ops:
        if is_barrier(op):
            tail = []
        else:
            tail.append(op)
    window = next((o for o in tail if isinstance(o, Window)), None)
    acts = next(
        (o for o in tail if isinstance(o, Activities) and not o.relink), None
    )
    view = next((o for o in tail if isinstance(o, ApplyView)), None)
    return has_barrier, window, acts, view


def _device_backend(
    num_pairs: int, *, mesh, tiny_pairs: int, requested: str
) -> str:
    if requested != "auto":
        return requested
    if mesh is not None and num_pairs > tiny_pairs:
        return "distributed"
    if num_pairs <= tiny_pairs:
        return "numpy"
    if jax.default_backend() == "cpu":
        return "scatter"
    return "pallas"


def _plan_conformance(
    plan: LogicalPlan,
    info: SourceInfo,
    *,
    memory_budget_events: int,
    replay_crossover: int,
    graph_available: bool,
) -> PhysicalPlan:
    """Physical plan for fitness/alignments on a single source.

    Replay (fitness) has all three evaluation paths; alignments need the
    variant table, so out-of-core sources are budget-gated like
    :class:`VariantsSink`.  The streaming↔materialize crossover for replay
    is measured (``replay_streaming_crossover``), the budget is the hard
    rail.
    """
    requested = plan.sink.backend
    if requested not in _CONFORMANCE_BACKENDS:
        raise QueryPlanError(
            f"backend {requested!r} is not a conformance backend; pick one "
            f"of {sorted(_CONFORMANCE_BACKENDS)}"
        )
    has_barrier, window, _acts, _view = _segment_features(plan)
    notes = []
    if window is not None and window.empty:
        notes.append("empty_window=zeros")
    is_align = isinstance(plan.sink, AlignmentsSink)

    if requested == "graph" or (
        requested == "auto" and graph_available and not has_barrier
    ):
        if has_barrier:
            raise QueryPlanError(
                "graph backend cannot evaluate materializing ops "
                "(top_variants / relink); drop them or use another backend"
            )
        if info.kind == "memmap" and info.num_events > memory_budget_events:
            raise QueryPlanError(
                "graph conformance replays the stored event tables; this "
                "out-of-core log builds a topology-only graph — use "
                "streaming/auto"
            )
        return PhysicalPlan(
            backend="graph",
            notes=("graph=event_table_replay",) + tuple(notes),
        )

    if info.kind == "memmap":
        if requested == "streaming" and (has_barrier or is_align):
            raise QueryPlanError(
                "streaming replay cannot evaluate "
                + ("materializing ops" if has_barrier else "alignments")
                + "; they need a materialized repository"
            )
        if has_barrier or is_align:
            if info.num_events > memory_budget_events:
                raise QueryPlanError(
                    "alignments / materializing ops on an out-of-core log "
                    "exceed the memory budget; raise memory_budget_events "
                    "or pre-dice the log"
                )
            return PhysicalPlan(
                backend="numpy", materialize=True, notes=tuple(notes)
            )
        out_of_core = info.num_events > memory_budget_events
        if out_of_core and requested == "numpy":
            raise QueryPlanError(
                "backend 'numpy' would materialize an out-of-core log into "
                "memory; use streaming/auto or raise memory_budget_events"
            )
        if requested == "streaming" or (
            requested == "auto"
            and info.num_events > min(memory_budget_events, replay_crossover)
        ):
            return PhysicalPlan(
                backend="streaming",
                row_range_window=(
                    (window.t0, window.t1)
                    if window is not None and not window.empty
                    else None
                ),
                notes=("replay=O(A²+chunk) scan",) + tuple(notes),
            )
        return PhysicalPlan(
            backend="numpy", materialize=True, notes=tuple(notes)
        )
    if requested == "streaming":
        raise QueryPlanError("streaming backend requires a MemmapLog source")
    return PhysicalPlan(backend="numpy", notes=tuple(notes))


def _plan_union(
    plan: LogicalPlan,
    info: SourceInfo,
    *,
    mesh,
    tiny_pairs: int,
    memory_budget_events: int,
    replay_crossover: int = REPLAY_STREAMING_CROSSOVER,
) -> PhysicalPlan:
    """Union costing: every branch is costed on its own shape (one union may
    mix an out-of-core memmap with tiny in-memory repositories), and the
    chosen per-branch backends are recorded in the notes."""
    has_barrier, window, acts, _view = _segment_features(plan)
    notes = []
    if window is not None and window.empty:
        notes.append("empty_window=zeros")

    if isinstance(plan.sink, CompareSink):
        if len(info.branches) < 2:
            raise QueryPlanError(
                "compare() needs at least two logs; got "
                f"{len(info.branches)}"
            )
        if has_barrier:
            raise QueryPlanError(
                "materializing ops (top_variants / relink) are not "
                "supported under compare(): they do not distribute over "
                "the union"
            )
        backend = "compare"
    elif has_barrier or isinstance(plan.sink, VariantsSink):
        # non-distributive: materialize the canonical concatenation
        if info.num_events > memory_budget_events:
            raise QueryPlanError(
                "variants / materializing ops on a union concatenate the "
                "branches in memory; the union exceeds the memory budget"
            )
        return PhysicalPlan(
            backend="concat",
            materialize=True,
            notes=("union=materialize_concatenation",) + tuple(notes),
        )
    else:
        backend = "union"

    # per-branch sub-plans: the window distributes into each branch, the
    # rest (activity mask / view) runs once at the merge.  Conformance
    # sinks distribute *every* op (sequence predicates transform each
    # branch's traces — traces never span branches) and keep their own
    # sink so each branch is costed as a replay, not a count.
    if isinstance(plan.sink, CONFORMANCE_SINKS):
        branch_ops = plan.ops
        branch_sink = plan.sink
    else:
        branch_ops = (window,) if window is not None else ()
        branch_sink = (
            HistogramSink()
            if isinstance(plan.sink, HistogramSink)
            else DFGSink(backend=plan.sink.backend)
        )
    for name, binfo in zip(info.branch_names, info.branches):
        bplan = LogicalPlan(binfo.kind, branch_ops, branch_sink)
        bphys = plan_physical(
            bplan, binfo,
            mesh=mesh, tiny_pairs=tiny_pairs,
            memory_budget_events=memory_budget_events,
            replay_crossover=replay_crossover,
        )
        notes.append(f"branch[{name}]={bphys.backend}")
    return PhysicalPlan(
        backend=backend,
        row_range_window=(
            (window.t0, window.t1)
            if window is not None and not window.empty
            else None
        ),
        activities_as_output_mask=acts is not None,
        notes=tuple(notes),
    )


def _plan_sharded(
    plan: LogicalPlan,
    info: SourceInfo,
    *,
    mesh,
    tiny_pairs: int,
    memory_budget_events: int,
    graph_available: bool,
    sharded_crossover: int,
) -> PhysicalPlan:
    """Physical plan for a case-partitioned :class:`ShardedLog`.

    The ``sharded-graph`` backend serves topology/histogram sinks from K
    per-shard CSR snapshots merged by an aligned pure sum (cases never span
    shards).  Below the measured sharded-vs-single-host crossover — and when
    the shard graphs are not already warm — a one-host
    concatenate-and-materialize count wins (the K-way merge constant
    dominates tiny logs), so ``auto`` falls back to it; the crossover joins
    the same calibration-curve mechanism as every other threshold.
    """
    has_barrier, window, acts, view = _segment_features(plan)
    windowed = window is not None and not window.empty
    notes = []
    if window is not None and window.empty:
        notes.append("empty_window=zeros")

    if isinstance(plan.sink, CompareSink):
        raise QueryPlanError(
            "compare() requires a multi-log source — build one with "
            "Q.logs(a, b, ...)"
        )
    if isinstance(plan.sink, CONFORMANCE_SINKS):
        raise QueryPlanError(
            "conformance sinks are not implemented for sharded logs; "
            "query one shard directly or materialize a dicing first"
        )
    requested = getattr(plan.sink, "backend", "auto")
    if requested not in ("auto", "sharded-graph"):
        raise QueryPlanError(
            f"backend {requested!r} is not available on a sharded log; "
            "use 'sharded-graph' or 'auto'"
        )

    if has_barrier or isinstance(plan.sink, VariantsSink):
        if requested == "sharded-graph":
            raise QueryPlanError(
                "sharded-graph cannot evaluate variants / materializing ops "
                "(top_variants / relink): they need the global trace table; "
                "drop them or use auto"
            )
        if info.num_events > memory_budget_events:
            raise QueryPlanError(
                "variants / materializing ops on a sharded log concatenate "
                "the shards in memory; the log exceeds the memory budget"
            )
        return PhysicalPlan(
            backend="numpy",
            materialize=True,
            notes=("sharded=materialize_concatenation",) + tuple(notes),
        )

    single_host = (
        requested == "auto"
        and not graph_available
        and info.num_events <= min(sharded_crossover, memory_budget_events)
    )
    if single_host:
        notes.append(
            f"sharded=single_host_below_crossover"
            f"({info.num_events}≤{sharded_crossover})"
        )
        if isinstance(plan.sink, HistogramSink):
            return PhysicalPlan(
                backend="numpy", materialize=True, notes=tuple(notes)
            )
        backend = _device_backend(
            info.num_pairs, mesh=mesh, tiny_pairs=tiny_pairs,
            requested="auto",
        )
        view_pushdown = False
        if view is not None and info.activity_names is not None:
            labels = view.to_view().visible_labels(info.activity_names)
            if len(labels) < info.num_activities:
                view_pushdown = True
                notes.append(
                    f"count_space=G×G ({len(labels)}<{info.num_activities})"
                )
        return PhysicalPlan(
            backend=backend,
            materialize=True,
            fused_dicing=backend == "pallas" and windowed,
            view_pushdown=view_pushdown,
            activities_as_output_mask=acts is not None and not view_pushdown,
            notes=tuple(notes),
        )

    if windowed:
        for name, sinfo in zip(info.shard_names, info.shards):
            if sinfo.num_events > memory_budget_events:
                raise QueryPlanError(
                    "windowed sharded-graph queries serve from per-shard "
                    f"event tables; {name} exceeds the memory budget — "
                    "repartition into more shards"
                )
    notes.append(
        "sharded=tables_window_merge" if windowed
        else "sharded=csr_psum_merge"
    )
    # per-shard cost estimates: each shard is an independent graph serve
    for name, sinfo in zip(info.shard_names, info.shards):
        notes.append(
            f"shard[{name}]=graph "
            f"cost≈{estimate_cost_s('graph', sinfo.num_events):.1e}s"
        )
    return PhysicalPlan(
        backend="sharded-graph",
        row_range_window=(window.t0, window.t1) if windowed else None,
        activities_as_output_mask=acts is not None,
        notes=tuple(notes),
    )


def plan_physical(
    plan: LogicalPlan,
    info: SourceInfo,
    *,
    mesh=None,
    tiny_pairs: int = TINY_PAIRS,
    memory_budget_events: int = MEMORY_BUDGET_EVENTS,
    graph_available: bool = False,
    replay_crossover: int = REPLAY_STREAMING_CROSSOVER,
    sharded_crossover: int = SHARDED_SINGLE_CROSSOVER,
    curves: Optional[Dict[str, CrossoverCurve]] = None,
) -> PhysicalPlan:
    """Map a canonical logical plan to a physical one.  ``plan`` must be the
    output of :func:`repro.query.optimize.canonicalize`.

    ``graph_available`` is the engine's amortization signal: True when the
    event-knowledge graph of this source is already built (or provably
    extendable / past the repeat-query crossover, so building it now pays).
    With it, un-windowed topology sinks route to the ``graph`` backend —
    CSR lookups instead of an O(E) recount — and conformance sinks replay
    the graph's stored event tables.

    ``curves`` (from ``load_calibration()["curves"]``) upgrades the scalar
    crossovers to fitted per-backend curves evaluated at this source's
    problem size (events × activities).
    """
    if curves:
        work = float(info.num_events) * float(max(info.num_activities, 1))
        for key, cur in (
            ("tiny_pairs", "tiny_pairs"),
            ("replay_streaming_crossover", "replay"),
            ("sharded_single_crossover", "sharded"),
        ):
            curve = curves.get(key)
            if curve is None:
                continue
            v = curve.value_at(work)
            if cur == "tiny_pairs":
                tiny_pairs = v
            elif cur == "replay":
                replay_crossover = v
            else:
                sharded_crossover = v
    if isinstance(plan.sink, (DFGSink, CompareSink, ProcessMapSink,
                              NeighborhoodSink, HistogramSink)):
        if plan.sink.backend not in _DFG_BACKENDS:
            raise QueryPlanError(f"unknown DFG backend {plan.sink.backend!r}")
        if (
            plan.sink.backend == "sharded-graph"
            and info.shards is None
        ):
            raise QueryPlanError(
                "backend 'sharded-graph' requires a ShardedLog source — "
                "partition one with repro.graph.partition_memmap_log"
            )
    if info.branches is not None:
        return _plan_union(
            plan, info,
            mesh=mesh, tiny_pairs=tiny_pairs,
            memory_budget_events=memory_budget_events,
            replay_crossover=replay_crossover,
        )
    if info.shards is not None:
        return _plan_sharded(
            plan, info,
            mesh=mesh, tiny_pairs=tiny_pairs,
            memory_budget_events=memory_budget_events,
            graph_available=graph_available,
            sharded_crossover=sharded_crossover,
        )
    if isinstance(plan.sink, CompareSink):
        raise QueryPlanError(
            "compare() requires a multi-log source — build one with "
            "Q.logs(a, b, ...)"
        )
    if isinstance(plan.sink, CONFORMANCE_SINKS):
        return _plan_conformance(
            plan, info,
            memory_budget_events=memory_budget_events,
            replay_crossover=replay_crossover,
            graph_available=graph_available,
        )
    has_barrier, window, acts, view = _segment_features(plan)
    notes = []
    if window is not None and window.empty:
        # the engine short-circuits to zeros before touching the backend
        notes.append("empty_window=zeros")

    if isinstance(plan.sink, (HistogramSink, VariantsSink)):
        needs_repo = isinstance(plan.sink, VariantsSink) or has_barrier
        requested = getattr(plan.sink, "backend", "auto")
        if info.kind == "memmap":
            # graph histograms: the stored :OF_TYPE in-degrees answer the
            # un-windowed counts as a lookup; a window reads the graph's
            # time index (event tables required, so out-of-core logs whose
            # graphs are topology-only can't serve windowed counts)
            windowed = window is not None and not window.empty
            graph_ok = not needs_repo and not (
                windowed and info.num_events > memory_budget_events
            )
            if requested == "graph":
                if not graph_ok:
                    raise QueryPlanError(
                        "graph histograms cannot evaluate materializing ops "
                        "or windows over out-of-core logs (topology-only "
                        "graph) — use streaming/auto"
                    )
                return PhysicalPlan(
                    backend="graph",
                    activities_as_output_mask=acts is not None,
                    notes=("graph=of_type_counts",) + tuple(notes),
                )
            if (
                requested == "auto" and graph_available and graph_ok
                and not windowed
            ):
                return PhysicalPlan(
                    backend="graph",
                    activities_as_output_mask=acts is not None,
                    notes=("graph=of_type_counts",) + tuple(notes),
                )
            if not needs_repo:  # chunked bincount, window → row range
                return PhysicalPlan(
                    backend="streaming",
                    row_range_window=(window.t0, window.t1) if window else None,
                )
            if info.num_events > memory_budget_events:
                raise QueryPlanError(
                    "variants / materializing ops on an out-of-core log "
                    "exceed the memory budget; raise memory_budget_events "
                    "or pre-dice the log"
                )
            return PhysicalPlan(backend="numpy", materialize=True)
        if requested == "graph" and not needs_repo:
            return PhysicalPlan(
                backend="graph",
                activities_as_output_mask=acts is not None,
                notes=("graph=of_type_counts",) + tuple(notes),
            )
        return PhysicalPlan(backend="numpy")

    # -- topology sinks (DFG / process map / neighborhood) -------------------
    requested = plan.sink.backend  # validated against _DFG_BACKENDS above

    # graph backend: the aggregated :DF CSR answers un-windowed topology
    # queries as lookups.  A window needs the event-level tables (out-of-core
    # graphs are topology-only), and barriers change the source itself.
    if requested == "graph" or (
        requested == "auto"
        and graph_available
        and not has_barrier
        and (window is None or window.empty)
    ):
        if has_barrier:
            raise QueryPlanError(
                "graph backend cannot evaluate materializing ops "
                "(top_variants / relink); drop them or use another backend"
            )
        windowed = window is not None and not window.empty
        if (
            windowed
            and info.kind == "memmap"
            and info.num_events > memory_budget_events
        ):
            raise QueryPlanError(
                "windowed graph queries need event tables; this out-of-core "
                "log builds a topology-only graph — use streaming/auto"
            )
        notes.append(
            "graph=event_tables_window" if windowed else "graph=csr_lookup"
        )
        return PhysicalPlan(
            backend="graph",
            activities_as_output_mask=acts is not None,
            notes=tuple(notes),
        )

    if info.kind == "memmap":
        if has_barrier:
            if requested == "streaming":
                raise QueryPlanError(
                    "streaming cannot evaluate materializing ops "
                    "(top_variants / relink)"
                )
            if info.num_events > memory_budget_events:
                raise QueryPlanError(
                    "materializing ops (top_variants / relink) on an "
                    "out-of-core log exceed the memory budget"
                )
        if (
            info.num_events > memory_budget_events
            and requested not in ("auto", "streaming")
        ):
            raise QueryPlanError(
                f"backend {requested!r} would materialize an out-of-core "
                "log into memory; use streaming/auto or raise "
                "memory_budget_events"
            )
        out_of_core = requested == "streaming" or (
            requested == "auto" and info.num_events > memory_budget_events
        )
        out_of_core = out_of_core and not has_barrier
        if out_of_core:
            return PhysicalPlan(
                backend="streaming",
                row_range_window=(window.t0, window.t1) if window else None,
                # streaming always post-masks the raw Ψ (before any view)
                activities_as_output_mask=acts is not None,
                notes=("streaming=O(A²+chunk) memory",),
            )
        backend = _device_backend(
            info.num_pairs, mesh=mesh, tiny_pairs=tiny_pairs,
            requested=requested,
        )
        materialize = True
    else:
        if requested == "streaming":
            raise QueryPlanError(
                "streaming backend requires a MemmapLog source"
            )
        backend = _device_backend(
            info.num_pairs, mesh=mesh, tiny_pairs=tiny_pairs,
            requested=requested,
        )
        materialize = False

    if backend == "distributed" and mesh is None:
        raise QueryPlanError("distributed backend requires a mesh")

    view_pushdown = False
    if view is not None and info.activity_names is not None:
        labels = view.to_view().visible_labels(info.activity_names)
        if len(labels) < info.num_activities:
            view_pushdown = True
            notes.append(f"count_space=G×G ({len(labels)}<{info.num_activities})")

    fuse = backend == "pallas" and window is not None and not window.empty
    return PhysicalPlan(
        backend=backend,
        materialize=materialize,
        fused_dicing=fuse,
        view_pushdown=view_pushdown,
        # with a view pushdown the filter must stay a pair predicate (the
        # result matrix is in group space, so raw-activity rows are gone);
        # without it the mask applies to the raw Ψ before any projection
        activities_as_output_mask=acts is not None and not view_pushdown,
        notes=tuple(notes),
    )

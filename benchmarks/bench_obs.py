"""Observability overhead benchmark: what does always-on tracing cost?

Wall-clock scan timings on a shared host are far noisier (±10% and more)
than the effect being measured (microseconds per query), so the headline
number is built from a noise-robust estimator:

* **per-query tax** — paired cache-hit loops, tracing on vs off, run in
  BOTH orders (on,off / off,on).  A hit is the cheapest query the engine
  serves (~0.3 ms of fingerprint + probe), so the constant per-query
  tracing cost (trace alloc, span stamps, histogram observe, forensics
  batch) is fully exposed.  Whichever side runs first in a pair is
  systematically slower (branch/cache state), so the tax is estimated as
  half the difference of the two orders' median deltas — the position
  bias cancels exactly.
* **workload** — one cold full scan + a fan of windowed misses + the
  same fan as cache hits, interleaved on/off repeats, best-of each; used
  as the denominator and recorded for context.

``trace_overhead`` = per-query tax × queries ÷ untraced workload wall —
the tracing tax a real scan-bearing dashboard workload actually pays —
and is asserted under the 3% budget.  The raw workload-vs-workload delta
is recorded too but not asserted: at bench scale the true signal
(~0.5 ms over hundreds of ms) sits far below host noise.

Also checks the acceptance criterion that a traced query's spans cover
≥95% of its wall time, and (direct invocation only) stamps the measured
``trace_overhead`` into every committed ``BENCH_*.json`` record so each
benchmark carries the observability tax it was measured under.

Emits CSV rows (and ``BENCH_obs.json``).
"""

from __future__ import annotations

import glob
import json
import math
import os
import statistics
import sys
import tempfile
import time

import numpy as np

# runnable directly (`python benchmarks/bench_obs.py`) without PYTHONPATH
_SRC = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

EVENTS = int(os.environ.get("BENCH_EVENTS", 2_000_000))
REPEATS = 4
WINDOWS = 16
HIT_PAIRS = 4_000
OVERHEAD_BUDGET = 0.03
#: head-sampling stride for the benchmark's trace store: the traced side
#: pays the real serving-tier cost (keep/drop decision every query, an
#: actual JSONL write every Nth)
STORE_SAMPLE_EVERY = 256


def _windows(log, k: int):
    ts = np.asarray(log.time)
    qs = np.linspace(0.05, 0.95, k + 1)
    edges = [float(np.quantile(ts, q)) for q in qs]
    return list(zip(edges[:-1], edges[1:]))


def _paired_delta_us(qa, qb, pairs: int) -> float:
    """Median of per-pair (a − b) call-time deltas, microseconds."""
    pc = time.perf_counter
    ds = []
    for _ in range(pairs):
        t0 = pc()
        qa.dfg()
        t1 = pc()
        qb.dfg()
        t2 = pc()
        ds.append(((t1 - t0) - (t2 - t1)) * 1e6)
    return statistics.median(ds)


def _traced_engine(store_dir: str):
    """The traced side runs the full distributed-observability stack: a
    trace store offered every finished root query (tail-sampling decision
    on the hot path, a JSONL write every ``STORE_SAMPLE_EVERY``-th) and
    latency-histogram observes carrying trace-id exemplars."""
    from repro.obs import TraceStore
    from repro.query import QueryEngine

    eng = QueryEngine()
    eng.trace_store = TraceStore(
        store_dir, sample_every=STORE_SAMPLE_EVERY, metrics=eng.metrics
    )
    return eng


def _per_query_tax_us(log, store_dir, pairs):
    """Bias-cancelled per-query tracing cost on the cache-hit hot path,
    with context propagation on: every traced query binds as a child of an
    ambient request context, exactly as under the transport tier."""
    from repro.obs.context import mint_context
    from repro.query import Q, QueryEngine

    eng_on = _traced_engine(store_dir)
    q_on = Q.log(log).using(eng_on)
    q_off = Q.log(log).using(QueryEngine(trace=False))
    q_on.dfg()  # populate both caches
    q_off.dfg()
    with eng_on.trace_scope(mint_context()):
        d_on_first = _paired_delta_us(q_on, q_off, pairs)
        d_off_first = _paired_delta_us(q_off, q_on, pairs)
    # d_on_first  = (c_on − c_off) + bias;  d_off_first = (c_off − c_on) + bias
    tax = (d_on_first - d_off_first) / 2.0
    # per-hit wall for context (median of the off side, second position)
    pc = time.perf_counter
    t0 = pc()
    for _ in range(1000):
        q_off.dfg()
    hit_us = (pc() - t0) / 1000 * 1e6
    return max(0.0, tax), hit_us


def _workload_s(trace: bool, log, windows, store_dir=None) -> float:
    import contextlib

    from repro.obs.context import mint_context
    from repro.query import Q, QueryEngine

    if trace:
        eng = _traced_engine(store_dir)  # fresh: cold plan/result cache
        scope = eng.trace_scope(mint_context())
    else:
        eng = QueryEngine(trace=False)
        scope = contextlib.nullcontext()
    t0 = time.perf_counter()
    with scope:
        Q.log(log).using(eng).dfg()  # cold full scan (cached after)
        for w0, w1 in windows:  # windowed fan: misses
            Q.log(log).using(eng).window(w0, w1).dfg()
        for w0, w1 in windows:  # same fan again: pure cache-hit hot path
            Q.log(log).using(eng).window(w0, w1).dfg()
    return time.perf_counter() - t0


def run(write_json: bool = False, fast: bool = False) -> list:
    """CSV rows; ``write_json=True`` (direct invocation only) also rewrites
    ``BENCH_obs.json`` and stamps ``trace_overhead`` into the other
    committed ``BENCH_*.json`` records — the aggregator's and CI's reduced
    ``--fast`` runs must not clobber them."""
    from repro.data import ProcessSpec, generate_memmap_log
    from repro.query import Q, QueryEngine

    events = min(EVENTS, 200_000) if fast else EVENTS
    hit_pairs = 400 if fast else HIT_PAIRS
    repeats = 2 if fast else REPEATS

    rows = []
    tmp = tempfile.mkdtemp(prefix="graphpm_bencho_")
    log = generate_memmap_log(
        os.path.join(tmp, "log"), events,
        ProcessSpec(num_activities=64, seed=31, horizon_days=120), seed=31,
    )
    windows = _windows(log, WINDOWS)

    # warm the jitted kernels so neither side pays compile time
    warm = QueryEngine()
    Q.log(log).using(warm).dfg()

    tax_us, hit_us = _per_query_tax_us(
        log, os.path.join(tmp, "traces_tax"), hit_pairs
    )
    rows.append((
        "obs_per_query_tax", tax_us,
        f"hit_us={hit_us:.1f};tax_of_hit={tax_us / hit_us * 100:.2f}%",
    ))

    # -- scan-bearing workload (denominator; noisy on shared hosts) ----------
    n_queries = 1 + 2 * WINDOWS
    on_s = off_s = math.inf
    for rep in range(repeats):
        order = (True, False) if rep % 2 else (False, True)
        for trace in order:
            dt = _workload_s(
                trace, log, windows,
                store_dir=os.path.join(tmp, f"traces_wl{rep}"),
            )
            if trace:
                on_s = min(on_s, dt)
            else:
                off_s = min(off_s, dt)
    overhead = (tax_us * 1e-6 * n_queries) / off_s
    rows.append((
        "obs_trace_overhead", on_s * 1e6,
        f"off_us={off_s * 1e6:.0f};overhead={overhead * 100:.3f}%;"
        f"queries={n_queries}",
    ))

    # acceptance: spans cover >=95% of a traced query's wall time
    eng = QueryEngine()
    res = Q.log(log).using(eng).dfg()
    coverage = res.trace.coverage()
    rows.append((
        "obs_trace_coverage", res.trace.total_s * 1e6,
        f"coverage={coverage * 100:.1f}%;spans={len(res.trace.spans)}",
    ))

    if not write_json:
        return rows

    assert overhead < OVERHEAD_BUDGET, (
        f"tracing overhead {overhead:.3%} exceeds {OVERHEAD_BUDGET:.0%} "
        f"budget (tax={tax_us:.1f}us/query × {n_queries} queries over "
        f"{off_s:.3f}s untraced workload)"
    )
    assert coverage >= 0.95, f"span coverage {coverage:.2%} below 95%"

    record = {
        "events": log.num_events,
        "queries": n_queries,
        "repeats": repeats,
        "propagation": True,   # traced side: ambient context + trace store
        "store_sample_every": STORE_SAMPLE_EVERY,
        "per_query_tax_us": tax_us,
        "hit_us": hit_us,
        "workload_traced_s": on_s,
        "workload_untraced_s": off_s,
        "trace_overhead": overhead,
        "trace_coverage": coverage,
    }
    with open("BENCH_obs.json", "w") as f:
        json.dump(record, f, indent=1)

    # stamp the measured tax into every other committed benchmark record;
    # BENCH_analysis.json is the kernel checker's report and must stay
    # exactly what build_report() regenerates
    for path in sorted(glob.glob("BENCH_*.json")):
        if os.path.basename(path) in ("BENCH_obs.json", "BENCH_analysis.json"):
            continue
        with open(path) as f:
            data = json.load(f)
        data["trace_overhead"] = overhead
        with open(path, "w") as f:
            json.dump(data, f, indent=1)
    return rows


if __name__ == "__main__":
    _fast = "--fast" in sys.argv[1:]
    for r in run(write_json=not _fast, fast=_fast):
        print(",".join(str(x) for x in r))

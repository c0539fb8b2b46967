"""``repro.obs`` — observability for the process-query engine.

Its pieces import nothing of the engine (stdlib, numpy, and JAX's
profiler for the bridge below), so every engine tier can import them
without cycles:

* :mod:`repro.obs.trace` — :class:`QueryTrace`, a per-query execution trace
  of timed spans (parse → cache-probe → plan → scan/resume → merge → sink)
  attached to every :class:`repro.query.QueryResult` as ``result.trace``.
  Always-on and near-zero overhead: preallocated span slabs, raw
  ``perf_counter`` reads, no string formatting on the hot path.  While a
  JAX profiler session is active each span is mirrored as a TraceMe named
  ``repro.<span>`` on the profiler's clock (``profile_begin``).
* :mod:`repro.obs.process` — one ``gc.callbacks`` hook per process:
  ``process_gc_pause_seconds{generation}`` and ``repro.gc.gen<k>`` spans.
* :mod:`repro.obs.metrics` — a lock-protected :class:`MetricsRegistry` of
  counters and streaming histograms (p50/p95/p99 from fixed log-scale
  buckets, no sample retention), exported as a dict, JSON lines, or
  Prometheus text.  A module-global :func:`kernel_registry` collects Pallas
  kernel wall-times via :mod:`repro.kernels.timing`.
* Self-mining forensics — the engine batches every finished trace into a
  :class:`repro.core.telemetry.EventCollector`, so
  ``Q.log(engine.own_telemetry())`` mines the engine's own process with the
  engine itself (the paper's Algorithm 1 over the engine's spans).
* :mod:`repro.obs.context` — W3C-traceparent-style :class:`TraceContext`
  propagated from the transport tier through coalescing, scheduler lanes,
  and into every engine (and per-shard) :class:`QueryTrace`, so one trace
  id stitches the full distributed request tree.
* :mod:`repro.obs.slo` — declarative :class:`Objective`s evaluated over
  live registries by :class:`SLOEngine`: verdicts, error budgets, and
  multi-window burn-rate alerts (``{"sink": "slo"}`` / ``GET /slo``).
* :mod:`repro.obs.store` — :class:`TraceStore`, a bounded on-disk JSONL
  ring of tail-sampled finished traces, readable back as an event log so
  cross-process traces mine bit-identically to Algorithm 1.
"""

from .context import TraceContext, mint_context, new_span_id, parse_traceparent
from .metrics import (
    Counter,
    Histogram,
    MetricsRegistry,
    kernel_registry,
    prometheus_text,
)
from .slo import Objective, SLOEngine, default_service_objectives
from .store import TraceStore
from .trace import Span, QueryTrace

__all__ = [
    "Counter",
    "Histogram",
    "MetricsRegistry",
    "Objective",
    "SLOEngine",
    "TraceContext",
    "TraceStore",
    "default_service_objectives",
    "kernel_registry",
    "mint_context",
    "new_span_id",
    "parse_traceparent",
    "prometheus_text",
    "Span",
    "QueryTrace",
]

"""The chip benchmark's harness: one run of one cell.

A run generates the cell's log from the seed, writes it through
``MemmapLog``, builds ``QueryService()`` and ``TransportApp(service)`` with
their defaults, warms up the shapes the cell's traffic will use, drives
``TransportApp.handle`` for the measured window on one asyncio loop, and
afterwards compares a seeded sample of the answers with the plain
reference (``reference.py``).  Everything that belongs to one
configuration, traffic mix or metric is found by name:

* ``configs/<config>.json``: the deployment's sizes;
* ``traffic/<traffic>.json``: the mix, read by ``traffic.py``;
* ``metrics/<metric>.py``: a reader ``read(run) -> float | None``.

Standard output ends with one JSON line, the result; earlier lines say
what the planner ran under, how the requests were served and how late the
load generator ran.  Standard error ends with each compared number beside
its limit.
"""

from __future__ import annotations

import asyncio
import collections
import dataclasses
import gc
import importlib.util
import json
import math
import os
import shutil
import sys
import threading
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import generator, reference, traffic as traffic_mod
from . import trace as trace_mod
from .roofline import peaks_for

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
DRAIN_S = 60.0  # how long answers are awaited after the window closes
RETRIES = 4  # a request refused with 429 is sent again after its Retry-After


# ---------------------------------------------------------------------------
# the cell and its files
# ---------------------------------------------------------------------------


def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    entry: Dict
    config: Dict
    mix: Dict
    metrics: List[Dict]  # end-to-end entries, then per-layer entries

    def metric_entries(self, traced: bool) -> List[Dict]:
        kind = "per_layer" if traced else "end_to_end"
        return [m for m in self.metrics if m["_kind"] == kind]


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    entry = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == entry["config"])
    config = load_json(os.path.join(root, cfg_entry["file"]))
    mix = load_json(os.path.join(HERE, "traffic", entry["traffic"] + ".json"))
    metrics = []
    for kind in ("end_to_end", "per_layer"):
        for m in bench[kind]:
            if "workloads" in m and workload not in m["workloads"]:
                continue
            metrics.append(dict(m, _kind=kind))
    return Cell(workload, entry, config, mix, metrics)


def load_reader(name: str) -> Callable:
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + name.replace(".", "_").replace("-", "_"), path
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# what a run records
# ---------------------------------------------------------------------------


@dataclasses.dataclass(slots=True)
class Req:
    """One timed request's record.  It keeps nothing of the request
    itself, so the records of a long window add little for Python's
    collector to walk."""

    due: float  # seconds after the window opened
    sent: float = math.nan  # first sent
    done: Optional[float] = None
    status: int = 0
    from_cache: Optional[bool] = None
    backend: Optional[str] = None
    wall_s: Optional[float] = None
    lane: Optional[str] = None
    retries: int = 0

    @property
    def ok(self) -> bool:
        return self.status == 200 and self.done is not None

    @property
    def latency_s(self) -> float:
        """Due time to answer; a failed or unanswered request is
        infinitely late."""
        return self.done - self.due if self.ok else math.inf


def latency_ms(reqs: List[Req], pct: float, seconds: float) -> Optional[float]:
    """Nearest-rank percentile of every timed request's latency, due time
    to answer.  A request that failed or was never answered is infinitely
    late; where the percentile falls on one, the value is the longest a
    request was waited for (window plus drain), which is later than any
    answered request."""
    lat = sorted(r.latency_s for r in reqs)
    if not lat:
        return None
    v = lat[max(0, math.ceil(pct / 100.0 * len(lat)) - 1)]
    if math.isinf(v):
        v = seconds + DRAIN_S
    return 1000.0 * v


class CompileMeter:
    """Backend compiles, counted from ``jax.monitoring`` events while
    ``active``.  One listener per process: JAX keeps listeners for good."""

    _instance: Optional["CompileMeter"] = None

    def __init__(self):
        import jax.monitoring

        self._lock = threading.Lock()
        self.active = False
        self.compiles = 0
        self.compile_s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    @classmethod
    def get(cls) -> "CompileMeter":
        if cls._instance is None:
            cls._instance = cls()
        return cls._instance

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if self.active and event.endswith("backend_compile_duration"):
            with self._lock:
                self.compiles += 1
                self.compile_s += duration

    def start(self) -> None:
        with self._lock:
            self.compiles, self.compile_s, self.active = 0, 0.0, True

    def stop(self) -> None:
        self.active = False


class GcPauses:
    """Collections of Python's cyclic garbage collector while started:
    generation, start (``perf_counter``) and length of each.  A collection
    holds the interpreter lock, so it stalls the event loop and every lane
    for its length."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t = 0.0

    def _on_gc(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"], self._t, time.perf_counter() - self._t))

    def start(self) -> None:
        self.pauses = []
        gc.callbacks.append(self._on_gc)

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)

    def summary(self, t_start: float) -> Dict:
        """Count and seconds per generation, and the window time and length
        of each collection of generation 2."""
        return {
            "count": [sum(1 for g, _, _ in self.pauses if g == k) for k in range(3)],
            "seconds": [sum(d for g, _, d in self.pauses if g == k) for k in range(3)],
            "gen2_at_s": [[t - t_start, d] for g, t, d in self.pauses if g == 2],
        }


def snapshot(*registries) -> Dict[str, float]:
    """Flat numbers of the program's metric registries: counters and
    gauges by key, histograms as ``<key>.count`` and ``<key>.sum``."""
    out: Dict[str, float] = {}
    for reg in registries:
        for k, v in reg.to_dict().items():
            if isinstance(v, dict):
                out[k + ".count"] = float(v["count"])
                out[k + ".sum"] = float(v["sum"])
            else:
                out[k] = float(v)
    return out


@dataclasses.dataclass
class Run:
    """What a metric reader is given."""

    cell: Cell
    seconds: float
    setup_s: float
    requests: List[Req]
    counters: Dict[str, float]  # deltas over the window and its drain
    compiles: int
    trace: Optional[trace_mod.Summary]
    device_kind: str
    log_events: int
    log_traces: int

    def delta(self, name: str, field: Optional[str] = None) -> float:
        """Sum of the deltas of every series of metric ``name``."""
        tot = 0.0
        for k, v in self.counters.items():
            base, _, rest = k.partition("{")
            if field is not None:
                if not k.endswith("." + field):
                    continue
                if not rest:
                    base = base[: -len(field) - 1]
            if base == name:
                tot += v
        return tot

    def peaks(self) -> Dict[str, float]:
        return peaks_for(self.device_kind)

    def latency_ms(self, pct: float) -> Optional[float]:
        return latency_ms(self.requests, pct, self.seconds)

    def mean_scan_ms(self) -> Optional[float]:
        """Mean engine ``wall_s`` of the answered requests that executed
        (not served from the result cache)."""
        walls = [r.wall_s for r in self.requests
                 if r.ok and r.from_cache is False and r.wall_s is not None]
        return 1000.0 * float(np.mean(walls)) if walls else None

    def idle_share_pct(self) -> Optional[float]:
        if self.trace is None or self.trace.span_s <= 0 or self.trace.devices == 0:
            return None
        return 100.0 * (1.0 - self.trace.busy_s / self.trace.span_s)


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def build_system(log_name: str, log):
    """``QueryService()`` and ``TransportApp(service)`` with their
    defaults; the log registered under the configuration's name."""
    from repro.serve import QueryService
    from repro.transport import TransportApp

    service = QueryService()
    service.register(log_name, log)
    return TransportApp(service)


def planner_inputs(app) -> Dict:
    from repro.query.planner import load_calibration

    cal = load_calibration()
    out = {k: v for k, v in cal.items() if k != "curves"}
    out["curves"] = sorted(cal.get("curves", {}))
    out["transport_hot_cutoff_s"] = getattr(app, "hot_cutoff_s", None)
    return out


async def send(app, item: traffic_mod.Item, req: Req, t_start: float,
               annotate: bool) -> Optional[Dict]:
    """One request through ``app.handle``; records status and provenance."""
    if math.isnan(req.sent):
        req.sent = time.perf_counter() - t_start
    try:
        if annotate:
            import jax

            with jax.profiler.TraceAnnotation(
                f"chipbench.{item.component}.{item.request.get('sink')}"
            ):
                resp = await app.handle(item.request, tenant=item.tenant)
        else:
            resp = await app.handle(item.request, tenant=item.tenant)
    except Exception as exc:  # noqa: BLE001 - a failed request, recorded
        req.status = 500
        req.done = time.perf_counter() - t_start
        print(f"chipbench: request failed: {type(exc).__name__}: {exc}",
              file=sys.stderr)
        return None
    req.done = time.perf_counter() - t_start
    req.status = int(resp.status)
    p = resp.payload
    req.from_cache = p.get("from_cache")
    req.backend = p.get("backend")
    req.wall_s = p.get("wall_s")
    req.lane = resp.headers.get("X-Lane")
    return p


class Sample:
    """Seeded reservoir of answers per group, plus the longest window."""

    def __init__(self, mix: Dict, seed: int):
        self.rng = np.random.default_rng([seed % (1 << 63), 9])
        self.size = {c["name"]: int(c.get("check", 8)) for c in mix["components"]}
        self.kept: Dict[str, List] = collections.defaultdict(list)
        self.seen: Dict[str, int] = collections.defaultdict(int)
        self.longest: Dict[str, tuple] = {}

    def offer(self, item: traffic_mod.Item, payload: Dict) -> None:
        group = item.component if item.tile is None else f"{item.component}#{item.tile}"
        k = self.size[item.component]
        self.seen[group] += 1
        n = self.seen[group]
        if len(self.kept[group]) < k:
            self.kept[group].append((item, payload))
        else:
            j = int(self.rng.integers(n))
            if j < k:
                self.kept[group][j] = (item, payload)
        if item.days is not None:
            best = self.longest.get(item.component)
            if best is None or item.days > best[0].days:
                self.longest[item.component] = (item, payload)

    def answers(self) -> List[tuple]:
        out = [a for group in self.kept.values() for a in group]
        ids = {id(i) for i, _ in out}
        out += [a for a in self.longest.values() if id(a[0]) not in ids]
        return out


async def run_window(app, cell: Cell, tr, schedule, seconds: float,
                     sample: Sample, annotate: bool) -> List[Req]:
    mix = cell.mix
    reqs: List[Req] = []
    # tasks in flight; a finished one is dropped at once, so a long window
    # keeps no record of its tasks for the collector to walk
    pending: set = set()
    errors: List[BaseException] = []
    t_start = time.perf_counter()

    async def one(item: traffic_mod.Item, req: Req):
        # as a client of the transport would: a refusal (429) is sent again
        # after its Retry-After, and the latency runs from the first due
        # time to the last answer; a request refused every time has failed
        payload = await send(app, item, req, t_start, annotate)
        while req.status == 429 and req.retries < RETRIES:
            req.retries += 1
            await asyncio.sleep(float(payload.get("retry_after_s", 0.0)))
            payload = await send(app, item, req, t_start, annotate)
        if payload is not None and req.status == 200:
            sample.offer(item, payload)

    def finished(task: asyncio.Task) -> None:
        pending.discard(task)
        if not task.cancelled() and task.exception() is not None:
            errors.append(task.exception())

    def launch(coro) -> None:
        task = asyncio.ensure_future(coro)
        pending.add(task)
        task.add_done_callback(finished)

    if mix["loop"] == "closed":
        stream = tr.closed_stream()

        async def worker():
            while time.perf_counter() - t_start < seconds:
                item = next(stream, None)
                if item is None:
                    return
                req = Req(due=time.perf_counter() - t_start)
                reqs.append(req)
                await one(item, req)

        for _ in range(int(mix["workers"])):
            launch(worker())
    else:
        for item in schedule:
            delay = t_start + item.due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            req = Req(due=item.due)
            reqs.append(req)
            launch(one(item, req))
    left = max(t_start + seconds - time.perf_counter(), 0.0) + DRAIN_S
    if pending:
        await asyncio.wait(set(pending), timeout=left)
    for t in list(pending):
        t.cancel()
    if errors:
        raise errors[0]
    return reqs


async def warm(app, items) -> List[Req]:
    out = []
    for item in items:
        req = Req(due=0.0)
        await send(app, item, req, time.perf_counter(), annotate=False)
        out.append(req)
    return out


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def _count_diff(got, want) -> int:
    if not isinstance(got, list):
        return max(len(want), 1)
    if len(got) != len(want):
        return max(len(got), len(want))
    return sum(1 for g, w in zip(got, want) if g != w)


def check_answers(answers: List[tuple], ref: reference.Log, err=None) -> Dict[str, int]:
    """``answers_wrong`` (cells, counts and fields that differ) and
    ``answers_checked``.  Each answer that differs is named on ``err``."""
    wrong = 0
    for item, p in answers:
        q = item.request
        sink, window = q.get("sink"), q.get("window")
        before = wrong
        if sink == "dfg":
            want = reference.dfg(ref, window)
            got = np.asarray(p["psi"], dtype=np.int64)
            wrong += int((got != want).sum()) if got.shape == want.shape else want.size
            wrong += int(p["names"] != ref.names)
        elif sink == "histogram":
            want = reference.histogram(ref, window)
            got = np.asarray(p["counts"], dtype=np.int64)
            wrong += int((got != want).sum()) if got.shape == want.shape else want.size
            wrong += int(p["names"] != ref.names)
        elif sink == "process_map":
            want = reference.process_map(ref, float(q.get("top", 0.2)), q.get("edge_top"), window)
            for key, w in want.items():
                g = p.get(key)
                wrong += _count_diff(g, w) if isinstance(w, list) else int(g != w)
        else:
            raise ValueError(f"no reference for sink {sink!r}")
        if err is not None and wrong != before:
            print(f"chipbench: answer differs from the reference: {json.dumps(q)} "
                  f"wrong {wrong - before}", file=err)
    return {"answers_wrong": wrong, "answers_checked": len(answers)}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def emit(obj: Dict, out) -> None:
    print(json.dumps(obj), file=out, flush=True)


def write_log(path: str, act, case, time_, num_traces: int, num_activities: int):
    from repro.core.streaming import MemmapLog

    if os.path.exists(path):
        shutil.rmtree(path)
    writer = MemmapLog.create(path, int(act.shape[0]), num_activities, num_traces)
    step = 1 << 20
    for s in range(0, act.shape[0], step):
        writer.append(act[s:s + step], case[s:s + step], time_[s:s + step])
    return writer.close()


def run_cell(
    workload: str,
    seed: int,
    seconds: float,
    traced: bool,
    *,
    t0: Optional[float] = None,
    root: str = ROOT,
    workdir: Optional[str] = None,
    config_overrides: Optional[Dict] = None,
    app_factory: Optional[Callable] = None,
    out=None,
    err=None,
) -> Optional[Dict]:
    """Run one cell and return its result line (also printed last on
    ``out``).  ``config_overrides`` and ``app_factory`` exist for the
    tests: a tiny log on the CPU, a broken system, the control."""
    import jax

    out = out or sys.stdout
    err = err or sys.stderr
    t0 = time.perf_counter() if t0 is None else t0
    cell = load_cell(workload, root)
    config = dict(cell.config, **(config_overrides or {}))
    name = cell.entry["config"]
    workdir = workdir or os.path.join(root, ".chipbench_work")
    os.makedirs(workdir, exist_ok=True)

    # -- data and system ------------------------------------------------------
    act, case, tcol, n_traces = generator.generate(config, seed)
    a = int(config["num_activities"])
    log = write_log(os.path.join(workdir, name), act, case, tcol, n_traces, a)
    ref: Optional[reference.Log] = None

    def reference_log() -> reference.Log:
        nonlocal ref
        if ref is None:
            ref = reference.Log(act, case, tcol, a)
        return ref

    if app_factory is None:
        app = build_system(name, log)
    else:
        app = app_factory(name, log, reference_log)
    planner = planner_inputs(app)
    emit({"planner": planner}, out)

    tr = traffic_mod.Traffic(cell.mix, config, name, float(tcol.max()), seed)
    warm_cfg = cell.mix.get("warmup", {})
    meter = CompileMeter.get()
    pauses = GcPauses()
    sample = Sample(cell.mix, seed)
    loop = asyncio.new_event_loop()
    try:
        warm_reqs = loop.run_until_complete(warm(app, tr.warmup()))
        bad = [r for r in warm_reqs if r.status != 200]
        if bad:
            print(f"chipbench: {len(bad)} warm-up requests failed", file=err)
            return None
        schedule = tr.open_schedule(seconds) if cell.mix["loop"] == "open" else None
        prelude_s = float(warm_cfg.get("prelude_s", 0.0))
        if prelude_s > 0:
            # the mix's own open-loop traffic, unmeasured, so the window
            # starts from the steady state (worker threads started, the
            # allocators and the result cache filled)
            loop.run_until_complete(run_window(
                app, cell, tr, tr.open_schedule(prelude_s, stream=1),
                prelude_s, Sample(cell.mix, seed), annotate=False,
            ))
        emit({"warmup": {
            "prelude_s": prelude_s,
            "requests": len(warm_reqs),
            "backends": dict(collections.Counter(
                "cache" if r.from_cache else str(r.backend) for r in warm_reqs
            )),
        }}, out)

        # -- the measured window --------------------------------------------
        from repro.obs import kernel_registry

        registries = [app.service.engine.metrics, kernel_registry()] \
            if hasattr(app, "service") else [kernel_registry()]
        trace_dir = os.path.join(workdir, "trace")
        if traced:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            t_trace = time.perf_counter()
        before = snapshot(*registries)
        meter.start()
        pauses.start()
        t_window = time.perf_counter()
        setup_s = t_window - t0
        reqs = loop.run_until_complete(
            run_window(app, cell, tr, schedule, seconds, sample, annotate=traced)
        )
        pauses.stop()
        meter.stop()
        after = snapshot(*registries)
        if traced:
            jax.profiler.stop_trace()
            traced_s = time.perf_counter() - t_trace
    finally:
        close = getattr(app, "close", None)
        if close is not None:
            close()
        loop.close()

    devices = jax.devices()
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    counters = {k: after.get(k, 0.0) - before.get(k, 0.0) for k in after}
    del app
    gc.collect()

    # -- how the window went --------------------------------------------------
    late = sorted(((r.sent - r.due, r.due) for r in reqs if not math.isnan(r.sent)),
                  reverse=True)
    slices = collections.defaultdict(list)
    for r in reqs:
        if r.ok:
            slices[int(r.due // 5.0)].append(r.latency_s)
    emit({"served": {
        "latency_ms": {f"p{p}": latency_ms(reqs, p, seconds) for p in (50, 95, 99)},
        # a backlog that grows through the window shows as later slices
        # slower than earlier ones (answered requests only)
        "p95_ms_by_5s": [
            round(1000.0 * float(np.percentile(slices[k], 95)), 3)
            for k in sorted(slices)
        ],
        "backends": dict(collections.Counter(
            "cache" if r.from_cache else str(r.backend) for r in reqs if r.ok
        )),
        "lanes": dict(collections.Counter(str(r.lane) for r in reqs if r.ok)),
        "statuses": dict(collections.Counter(str(r.status) for r in reqs)),
        "retried": dict(collections.Counter(str(r.retries) for r in reqs if r.retries)),
        "compiles_in_window": meter.compiles,
        "compile_s_in_window": meter.compile_s,
        # how late requests were sent against their due time: the event
        # loop stalled for that long
        "generator_late_s": {
            "mean": float(np.mean([x for x, _ in late])) if late else 0.0,
            "worst_at_s": [[due, x] for x, due in late[:3]],
        },
        "gc": pauses.summary(t_window),
    }}, out)

    # -- correctness, after the window and with the program's state freed ----
    t_ref = time.perf_counter()
    answers = sample.answers()
    got = check_answers(answers, reference_log(), err)
    values = {
        "answers_wrong": got["answers_wrong"],
        # refused (429), failed (500) or answered with any other error
        "answers_failed": sum(1 for r in reqs if r.done is not None and r.status != 200),
        "answers_missing": sum(1 for r in reqs if r.done is None),
    }
    limits = cell.mix["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in values.items()}
    correct = got["answers_checked"] > 0 and all(
        c["value"] <= c["limit"] for c in checks.values()
    )
    emit({"checked": {"answers": got["answers_checked"],
                      "reference_s": time.perf_counter() - t_ref}}, out)

    # -- metrics ----------------------------------------------------------------
    summary = None
    if traced:
        summary = trace_mod.summarize(trace_mod.load(trace_dir))
        # the traced window by the host clock, start_trace to stop_trace
        summary.span_s = traced_s
    run = Run(
        cell=cell, seconds=float(seconds), setup_s=setup_s, requests=reqs,
        counters=counters, compiles=meter.compiles, trace=summary,
        device_kind=devices[0].device_kind, log_events=int(act.shape[0]),
        log_traces=n_traces,
    )
    metrics = {}
    for m in cell.metric_entries(traced):
        value = load_reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }
    result = {
        "correct": bool(correct),
        "attempted": len(reqs),
        "failed": sum(1 for r in reqs if not r.ok),
        "metrics": metrics,
        "device": device,
    }
    if summary is not None:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.span_s
        result["breakdown"] = {
            "device_ops": [[n, s] for n, s in summary.top_ops(10)],
            "idle_gaps": [[n, s] for n, s in summary.gaps[:10]],
        }
    result["checks"] = checks
    for k, c in checks.items():
        print(f"check {k} = {c['value']!r} (limit {c['limit']!r})", file=err, flush=True)
    emit(result, out)
    return result

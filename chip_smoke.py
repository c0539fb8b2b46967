#!/usr/bin/env python3
"""Chip smoke test: the query engine's main path on a TPU, at the paper's
log size (``configs/graphpm.PAPER_EVAL``: 7.2M events, 600 activities,
mean trace length 12, 120 days), on data generated from ``--seed``.

    python chip_smoke.py                # one chip, phases a-f
    python chip_smoke.py --four-chips   # four chips: mesh paths only

One chip, every phase checked against a numpy oracle on the same data:

  a  full-log DFG through ``Q.log(log).dfg()`` (backend ``pallas``);
  b  three accumulating dices of Experiment 2 (days 1, 30, 120) through the
     fused ``dfg_count_diced`` kernel, which compares int32 time ranks:
     exact against the float64 window test;
  c  the graph tier: the graph build runs ``dfg_count`` and
     ``segment_count`` on the device, then ``dfg(backend="graph")`` and
     ``process_map()`` answer from it;
  d  alignments of one 1-day window through the ``align_dp`` kernel;
  e  the serving front: ``POST /query`` to an in-process
     ``TransportServer`` on 127.0.0.1 for ``dfg``, ``process_map`` and
     ``fitness``, each equal to the direct ``QueryService.query`` dict;
  f  the dices of b on a copy of the log whose times are epoch seconds
     (from 2016-01-01, about 1.45e9 s, far past 2**24 s): exact against the
     float64 window test where a float32 one would miscount, and the pairs
     it would miscount are printed.

Four chips: ``QueryEngine(mesh=...)`` (backend ``distributed``) and the
4-way ``partition_memmap_log`` merge on that mesh, each equal to the
one-chip Psi, with the sharded inputs on four distinct devices.

Each phase prints one JSON line: backend, wall seconds, the JAX compile
seconds inside it, kernel calls from ``repro.obs.kernel_registry()`` and
the oracle verdict.  The last line is ``{"ok": true, "device": {...}}``,
printed only when every phase passed.  The script exits non-zero, and
prints no result, on any failure and on a machine without a TPU.
"""

from __future__ import annotations

import argparse
import asyncio
import contextlib
import json
import os
import sys
import tempfile
import threading
import time
import traceback
import urllib.request

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

DAY = 86400.0
KERNELS = ("dfg_count", "dfg_count_diced", "segment_count", "align_dp")
DICE_DAYS = (1, 30, 120)
LOG_NAME = "paper"
#: 2016-01-01T00:00:00Z in Unix seconds, the first day of the BPI Challenge
#: 2017 log
EPOCH_S = 1_451_606_400.0


class PhaseFailed(AssertionError):
    pass


def check(cond, msg: str) -> None:
    if not cond:
        raise PhaseFailed(msg)


class Meter:
    """JAX compile seconds and kernel calls, read as deltas per phase."""

    def __init__(self):
        import jax.monitoring

        from repro.obs import kernel_registry

        self._lock = threading.Lock()
        self.compile_s = 0.0
        self.compiles = 0
        self._hists = {
            k: kernel_registry().histogram("kernel_seconds", kernel=k)
            for k in KERNELS
        }
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_kw) -> None:
        if event.startswith("/jax/core/compile/"):
            with self._lock:
                self.compile_s += duration
                if event.endswith("backend_compile_duration"):
                    self.compiles += 1

    def kernel_calls(self) -> dict:
        return {k: h.count for k, h in self._hists.items()}

    @contextlib.contextmanager
    def phase(self, name: str, results: list):
        rec = {"phase": name}
        calls0 = self.kernel_calls()
        c0, n0 = self.compile_s, self.compiles
        t0 = time.perf_counter()
        try:
            yield rec
            rec["ok"] = True
        except Exception as exc:  # noqa: BLE001 - reported, then exit 1
            rec["ok"] = False
            rec["error"] = f"{type(exc).__name__}: {exc}"
            traceback.print_exc(file=sys.stderr)
        rec["wall_s"] = time.perf_counter() - t0
        rec["compile_s"] = self.compile_s - c0
        rec["compiles"] = self.compiles - n0
        calls = self.kernel_calls()
        rec["kernel_calls"] = {
            k: calls[k] - calls0[k] for k in KERNELS if calls[k] > calls0[k]
        }
        print(json.dumps(rec), flush=True)
        results.append(rec)


# ---------------------------------------------------------------------------
# data and oracles
# ---------------------------------------------------------------------------


def epoch_copy(log, workdir: str):
    """The log with every time moved to epoch seconds (+ ``EPOCH_S``)."""
    from repro.core.streaming import MemmapLog

    w = MemmapLog.create(os.path.join(workdir, "epoch_log"), log.num_events,
                         log.num_activities, log.num_traces)
    for a, c, t in log.iter_chunks():
        w.append(a, c, t + EPOCH_S)
    return w.close()


def make_log(workdir: str, seed: int):
    from repro.configs.graphpm import PAPER_EVAL
    from repro.data import ProcessSpec, generate_memmap_log

    spec = ProcessSpec(
        num_activities=PAPER_EVAL.num_activities,
        mean_trace_len=PAPER_EVAL.mean_trace_len,
        horizon_days=PAPER_EVAL.horizon_days,
        seed=seed,
    )
    return generate_memmap_log(
        os.path.join(workdir, "log"), PAPER_EVAL.num_events, spec,
        seed=seed,
    )


class Oracle:
    """Algorithm 1 in numpy on the canonical columns the engine counts."""

    def __init__(self, log):
        import numpy as np

        from repro.core.dfg import dfg_numpy
        from repro.query.execute import repository_from_memmap

        self.repo = repository_from_memmap(log)
        self.names = list(self.repo.activity_names)
        self.a = len(self.names)
        self.src, self.dst, self.valid = self.repo.df_pairs()
        self.psi = dfg_numpy(self.src, self.dst, self.valid, self.a)
        self.t_min = float(self.repo.event_time.min())
        self.degrees = np.bincount(
            self.repo.event_activity, minlength=self.a
        ).astype(np.int64)

    def window_masks(self, t0: float, t1: float):
        """(f64 in-window mask, f32-cast in-window mask) per pair."""
        import numpy as np

        ts = self.repo.event_time
        f64 = (
            (ts[:-1] >= t0) & (ts[:-1] < t1) & (ts[1:] >= t0) & (ts[1:] < t1)
        )
        t32 = ts.astype(np.float32)
        a, b = np.float32(t0), np.float32(t1)
        f32 = (t32[:-1] >= a) & (t32[:-1] < b) & (t32[1:] >= a) & (t32[1:] < b)
        return f64, f32

    def model(self):
        import numpy as np

        from repro.core.discovery import discover_dependency_graph

        trace = self.repo.event_trace
        act = self.repo.event_activity
        first = np.ones(trace.shape[0], dtype=bool)
        first[1:] = trace[1:] != trace[:-1]
        last = np.ones(trace.shape[0], dtype=bool)
        last[:-1] = trace[:-1] != trace[1:]
        starts = np.bincount(act[first], minlength=self.a)
        ends = np.bincount(act[last], minlength=self.a)
        return discover_dependency_graph(self.psi, self.names, starts, ends)


def exact(psi, ref, what: str) -> str:
    import numpy as np

    psi = np.asarray(psi)
    check(psi.shape == ref.shape, f"{what}: shape {psi.shape} != {ref.shape}")
    diff = int((psi != ref).sum())
    check(diff == 0, f"{what}: {diff} cells differ from the oracle")
    return "exact"


# ---------------------------------------------------------------------------
# one chip: phases a-e
# ---------------------------------------------------------------------------


def phase_full_dfg(rec, log, engine, orc):
    from repro.query import Q

    res = Q.log(log).using(engine).dfg()
    rec["backend"] = res.physical.backend
    check(res.physical.backend == "pallas", f"backend {res.physical.backend}")
    check(res.names == orc.names, "activity axis differs")
    rec["pairs"] = int(orc.valid.sum())
    rec["oracle"] = exact(res.value, orc.psi, "full-log DFG")


def phase_windowed_dfg(rec, log, engine, orc, need_f32_misses=False):
    from repro.core.dfg import dfg_numpy
    from repro.query import Q

    rec["windows"] = []
    f32_misses = 0
    for days in DICE_DAYS:
        t0, t1 = orc.t_min, orc.t_min + days * DAY
        res = Q.log(log).using(engine).window(t0, t1).dfg()
        phys = res.physical
        check(phys.backend == "pallas" and phys.fused_dicing,
              f"day {days}: plan {phys.describe()}")
        in64, in32 = orc.window_masks(t0, t1)
        mismatches = int((orc.valid & (in64 != in32)).sum())
        f32_misses += mismatches
        ref = dfg_numpy(orc.src, orc.dst, orc.valid & in64, orc.a)
        rec["windows"].append({
            "days": days, "t0": t0, "backend": phys.backend,
            "fused_dicing": phys.fused_dicing,
            "f32_edge_mismatches": mismatches,
            "oracle": exact(res.value, ref, f"day {days}") + " (f64 oracle)",
        })
    if need_f32_misses:
        check(f32_misses > 0, "no pair a float32 window test would miscount")
    rec["backend"] = "pallas"


def phase_graph(rec, log, engine, orc):
    import numpy as np

    from repro.query import Q

    res = Q.log(log).using(engine).dfg(backend="graph")
    check(res.physical.backend == "graph", f"dfg backend {res.physical.backend}")
    rec["backend"] = "graph"
    rec["oracle_psi"] = exact(res.value, orc.psi, "graph DFG")
    res = Q.log(log).using(engine).process_map(top=1.0, edge_top=1.0)
    check(res.physical.backend == "graph",
          f"process_map backend {res.physical.backend}")
    pm = res.value
    ids = [orc.names.index(n) for n in pm.activities]
    check(np.array_equal(np.asarray(pm.node_counts), orc.degrees[ids]),
          "process-map node degrees differ from np.bincount")
    check(int((orc.degrees > 0).sum()) == len(ids), "observed nodes missing")
    rec["oracle_degrees"] = "exact (np.bincount)"


def phase_alignments(rec, log, engine, orc, model, meter):
    import numpy as np

    from repro.conformance.align import align_arrays
    from repro.query import Q

    t0, t1 = orc.t_min, orc.t_min + DAY
    before = meter.kernel_calls()["align_dp"]
    res = Q.log(log).using(engine).window(t0, t1).alignments(model)
    check(meter.kernel_calls()["align_dp"] > before,
          "alignments did not reach the align_dp kernel")
    rec["backend"] = res.physical.backend
    got = res.value
    rec["variants"] = int(got.variant_costs.shape[0])
    check(rec["variants"] >= 128,
          f"{rec['variants']} variants do not fill a 128-variant block")
    m = (orc.repo.event_time >= t0) & (orc.repo.event_time < t1)
    ref = align_arrays(
        orc.repo.event_activity[m], orc.repo.event_trace[m], orc.names,
        model, backend="numpy",
    )
    check(np.array_equal(got.variant_costs, ref.variant_costs),
          "variant costs differ from align_dp_numpy")
    check(np.array_equal(got.trace_cost, ref.trace_cost),
          "trace costs differ from align_dp_numpy")
    rec["mean_cost"] = float(got.trace_cost.mean())
    rec["oracle"] = "exact (align_dp_numpy)"


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(body).encode(), method="POST",
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=900) as f:
        return json.loads(f.read())


def phase_serving(rec, log, engine):
    from repro.serve import QueryService
    from repro.transport import TransportApp, TransportServer, canonical_payload

    svc = QueryService(engine)
    svc.register(LOG_NAME, log)
    srv = TransportServer(TransportApp(svc), host="127.0.0.1", port=0)
    loop = asyncio.new_event_loop()
    started = threading.Event()

    def serve():
        asyncio.set_event_loop(loop)
        loop.run_until_complete(srv.start())
        started.set()
        loop.run_forever()

    thread = threading.Thread(target=serve, name="transport", daemon=True)
    thread.start()
    try:
        check(started.wait(60), "transport server did not start")
        rec["requests"] = []
        for sink in ("dfg", "process_map", "fitness"):
            req = {"log": LOG_NAME, "sink": sink}
            over_http = _post(srv.address + "/query", req)
            direct = svc.query(req)
            same = canonical_payload(over_http) == canonical_payload(direct)
            rec["requests"].append({
                "sink": sink, "backend": over_http.get("backend"),
                "identical": same,
            })
            check(same, f"{sink}: transport payload differs from the direct dict")
        rec["oracle"] = "transport == direct QueryService.query"
    finally:
        if started.is_set():
            asyncio.run_coroutine_threadsafe(srv.stop(), loop).result(120)
        loop.call_soon_threadsafe(loop.stop)
        thread.join(120)
        loop.close()


def run_one_chip(args, meter, workdir, results) -> None:
    from repro.query import QueryEngine

    with meter.phase("setup", results) as rec:
        log = make_log(workdir, args.seed)
        orc = Oracle(log)
        model = orc.model()
        rec.update(events=log.num_events, traces=log.num_traces,
                   activities=log.num_activities)
    if not results[-1]["ok"]:
        return
    # the budget keeps the paper log in memory: the device path, not the
    # host streaming scan
    engine = QueryEngine(memory_budget_events=max(log.num_events, 1 << 22))
    with meter.phase("a_full_dfg", results) as rec:
        phase_full_dfg(rec, log, engine, orc)
    with meter.phase("b_windowed_dfg", results) as rec:
        phase_windowed_dfg(rec, log, engine, orc)
    with meter.phase("c_graph", results) as rec:
        phase_graph(rec, log, engine, orc)
    with meter.phase("d_alignments", results) as rec:
        phase_alignments(rec, log, engine, orc, model, meter)
    with meter.phase("e_serving", results) as rec:
        phase_serving(rec, log, engine)
    with meter.phase("f_epoch_windows", results) as rec:
        epoch_log = epoch_copy(log, workdir)
        epoch_orc = Oracle(epoch_log)
        check(epoch_orc.t_min > 2 ** 24, f"times start at {epoch_orc.t_min}")
        phase_windowed_dfg(rec, epoch_log, engine, epoch_orc,
                           need_f32_misses=True)


# ---------------------------------------------------------------------------
# four chips: the distributed backend and the sharded merge
# ---------------------------------------------------------------------------


@contextlib.contextmanager
def placements():
    """Record every array the engine lays out over a mesh (through
    ``core.distributed.place_sharded``, the one placement both mesh paths
    use): its shape and the distinct devices its shards sit on."""
    from repro.core import distributed

    seen = []
    place = distributed.place_sharded

    def recording(mesh, *arrays):
        placed = place(mesh, *arrays)
        seen.extend(
            {"shape": list(a.shape),
             "devices": len({s.device for s in a.addressable_shards})}
            for a in placed
        )
        return placed

    distributed.place_sharded = recording
    try:
        yield seen
    finally:
        distributed.place_sharded = place


def check_on_four(seen, what: str) -> None:
    check(seen, f"{what}: nothing was placed on the mesh")
    check(all(p["devices"] == 4 for p in seen),
          f"{what}: inputs not on 4 devices: {seen}")


def run_four_chips(args, meter, workdir, results) -> None:
    import numpy as np

    from repro.graph import partition_memmap_log
    from repro.launch.mesh import make_test_mesh
    from repro.query import Q, QueryEngine

    with meter.phase("setup", results) as rec:
        mesh = make_test_mesh((4,), ("data",))
        log = make_log(workdir, args.seed)
        orc = Oracle(log)
        rec.update(events=log.num_events, mesh=dict(mesh.shape))
    if not results[-1]["ok"]:
        return
    budget = max(log.num_events, 1 << 22)
    one_chip = {}
    with meter.phase("one_chip_dfg", results) as rec:
        res = Q.log(log).using(QueryEngine(memory_budget_events=budget)).dfg()
        rec["backend"] = res.physical.backend
        check(res.physical.backend == "pallas", f"backend {res.physical.backend}")
        rec["oracle"] = exact(res.value, orc.psi, "one-chip DFG")
        one_chip["psi"] = np.asarray(res.value)
    if "psi" not in one_chip:
        return
    engine = QueryEngine(mesh=mesh, memory_budget_events=budget)
    with meter.phase("distributed_dfg", results) as rec:
        with placements() as seen:
            res = Q.log(log).using(engine).dfg()
        rec["backend"] = res.physical.backend
        check(res.physical.backend == "distributed",
              f"backend {res.physical.backend}")
        rec["placed"] = seen
        check_on_four(seen, "distributed DFG")
        rec["oracle"] = exact(res.value, one_chip["psi"], "distributed DFG")
    with meter.phase("sharded_merge", results) as rec:
        sharded = partition_memmap_log(log, 4, os.path.join(workdir, "shards"))
        with placements() as seen:
            res = Q.log(sharded).using(engine).dfg(backend="sharded-graph")
        rec["backend"] = res.physical.backend
        check(res.physical.backend == "sharded-graph",
              f"backend {res.physical.backend}")
        rec["placed"] = seen
        check(any(len(p["shape"]) == 3 for p in seen),
              f"the shard stack was not placed on the mesh: {seen}")
        check_on_four(seen, "sharded merge")
        order = [res.names.index(n) for n in orc.names]
        psi = np.asarray(res.value)[np.ix_(order, order)]
        rec["oracle"] = exact(psi, one_chip["psi"], "sharded merge")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__, formatter_class=argparse.RawTextHelpFormatter
    )
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the four-chip mesh phases")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found platform {platform!r}",
              file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, found {len(devices)}",
              file=sys.stderr)
        return 2

    try:
        from repro.core.compile_cache import enable_compile_cache
    except ImportError as exc:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({exc})", file=sys.stderr)
        return 2
    cache_dir = enable_compile_cache()

    meter = Meter()
    results: list = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.four_chips:
            run_four_chips(args, meter, workdir, results)
        else:
            run_one_chip(args, meter, workdir, results)

    calls = meter.kernel_calls()
    summary = {
        "summary": True,
        "compile_s": meter.compile_s,
        "compiles": meter.compiles,
        "compile_cache": cache_dir,
        "kernel_calls": calls,
        "phases_failed": [r["phase"] for r in results if not r["ok"]],
    }
    print(json.dumps(summary), flush=True)
    ok = bool(results) and all(r["ok"] for r in results)
    if not args.four_chips:
        missing = [k for k in KERNELS if calls[k] < 1]
        if missing:
            print(f"chip_smoke: kernels never called: {missing}",
                  file=sys.stderr)
            ok = False
    if not ok:
        return 1
    dev = devices[0]
    print(json.dumps({
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

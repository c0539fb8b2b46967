"""Serving-tier benchmark — admission, coalescing, and SLO isolation under
concurrent load.

Drives :class:`repro.transport.TransportApp` directly (the HTTP framing
above it is protocol plumbing measured in the transport tests; the
contended paths — probe, admission, coalescing, the two lanes, the engine
— are all exercised here) with a mixed-traffic load test over an
out-of-core memmap log whose cold scans are genuinely expensive.

Measurements (CSV rows; ``BENCH_serve.json`` on direct invocation):

* **coalesce** — N identical concurrent cold requests execute the engine
  exactly once (asserted through ``EngineStats``: one execution, one full
  scan) and every fanned-out response is bit-identical to the leader's.
* **mixed_load** — ≥8 concurrent clients, ≥20% cold traffic (fresh
  windows, real streaming scans) against warm cached dashboards.  The
  contract: warm-lane p99 stays under 25 ms *while the cold lane is
  saturated* — cold scans never head-of-line-block warm traffic.
* **shed** — a starved tenant's over-quota requests get 429 + Retry-After
  instead of queueing.
* **identity** — transport responses equal the direct
  ``QueryService.query`` dict path (modulo execution provenance).
* **calibration** — the measured hot/cold boundary
  (``slo_hot_cutoff_s``: the geometric mean of warm-lane p99 and
  cold-lane median) consumed by ``planner.load_calibration``.
"""

from __future__ import annotations

import asyncio
import json
import os
import sys
import tempfile
import time

import numpy as np

# runnable directly (`python benchmarks/bench_serve.py`) without PYTHONPATH
_SRC = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"
)
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

# below ~400k events a fresh-window scan costs less than the hot cutoff,
# every request takes the hot lane and there is no cold lane to measure
EVENTS = max(int(os.environ.get("BENCH_EVENTS", 1_000_000)), 400_000)
CLIENTS = int(os.environ.get("BENCH_CLIENTS", 12))
REQUESTS_PER_CLIENT = int(os.environ.get("BENCH_REQUESTS", 24))
COALESCE_N = 16
COLD_EVERY = 4  # every 4th request is a fresh cold window: 25% cold


def _pct(samples, q):
    return float(np.percentile(np.asarray(samples, dtype=np.float64), q))


def run(write_json: bool = False) -> list:
    """CSV rows; ``write_json=True`` (direct invocation only) also rewrites
    the committed ``BENCH_serve.json`` record — the aggregator's reduced
    ``--fast`` runs must not clobber it (same guard as bench_shard)."""
    from repro.data import ProcessSpec, generate_memmap_log
    from repro.query import QueryEngine
    from repro.query.planner import SLO_HOT_CUTOFF_S
    from repro.serve import QueryService
    from repro.transport import TransportApp, TransportConfig, canonical_payload

    rows = []
    results = {}
    tmp = tempfile.mkdtemp(prefix="graphpm_benchserve_")
    log = generate_memmap_log(
        os.path.join(tmp, "log"), EVENTS,
        ProcessSpec(num_activities=24, seed=17, horizon_days=120), seed=17,
    )
    t_all = np.concatenate([t for _, _, t in log.iter_chunks()])
    t_min, t_max = float(t_all[0]), float(t_all[-1])
    span = t_max - t_min
    del t_all

    # the log is out of the materialization budget: every fresh window is a
    # genuine streaming scan, which is exactly what the cold lane is for
    engine = QueryEngine(memory_budget_events=max(log.num_events // 4, 1))
    svc = QueryService(engine)
    svc.register("bpi", log)
    # pin the static boundary: this run *measures* the calibrated value and
    # must not read a previous run's BENCH_serve.json back as its input
    app = TransportApp(svc, TransportConfig(
        rate=100_000.0, burst=100_000.0, hot_cutoff_s=SLO_HOT_CUTOFF_S,
    ))
    results["events"] = log.num_events
    results["clients"] = CLIENTS
    results["requests_per_client"] = REQUESTS_PER_CLIENT

    rng = np.random.default_rng(3)

    def fresh_window():
        a = float(rng.uniform(0.0, 0.55))
        w = float(rng.uniform(0.25, 0.4))
        return (t_min + a * span, t_min + (a + w) * span)

    # -- 1. coalescing: N identical concurrent requests, one execution -------
    coalesce_req = {"log": "bpi", "sink": "dfg", "window": list(fresh_window())}
    before = engine.stats

    async def coalesce_phase():
        t0 = time.perf_counter()
        resps = await asyncio.gather(*[
            app.handle(coalesce_req) for _ in range(COALESCE_N)
        ])
        return resps, time.perf_counter() - t0

    resps, coalesce_wall = asyncio.run(coalesce_phase())
    after = engine.stats
    executions = after.executions - before.executions
    fanned = sum(1 for r in resps if r.headers["X-Coalesced"] == "1")
    payloads = [canonical_payload(r.payload) for r in resps]
    identical = all(r.status == 200 for r in resps) and all(
        p == payloads[0] for p in payloads
    )
    rows.append((
        "serve_coalesce", coalesce_wall * 1e6,
        f"n={COALESCE_N};executions={executions};fanout={fanned};"
        f"identical={identical}",
    ))
    results["coalesce"] = {
        "n": COALESCE_N,
        "executions": int(executions),
        "fanout": int(fanned),
        "wall_us": coalesce_wall * 1e6,
    }
    if executions != 1 or fanned != COALESCE_N - 1 or not identical:
        raise AssertionError(
            "coalescing contract violated: "
            f"executions={executions} fanout={fanned} identical={identical}"
        )

    # -- 2. mixed-traffic load test ------------------------------------------
    warm_reqs = [
        {"log": "bpi", "sink": "dfg"},
        {"log": "bpi", "sink": "histogram"},
        {"log": "bpi", "sink": "process_map", "top": 1.0},
    ]
    cold_windows = [
        fresh_window()
        for _ in range(CLIENTS * REQUESTS_PER_CLIENT // COLD_EVERY + CLIENTS)
    ]

    async def load_phase():
        for r in warm_reqs:  # pre-warm: the steady-state dashboard set
            assert (await app.handle(r)).status == 200
        lat = {"hot": [], "cold": []}
        overlapped = [0]

        async def client(ci):
            for j in range(REQUESTS_PER_CLIENT):
                seq = ci * REQUESTS_PER_CLIENT + j
                if seq % COLD_EVERY == 0:
                    req = {
                        "log": "bpi", "sink": "dfg",
                        "window": list(cold_windows[seq // COLD_EVERY]),
                    }
                else:
                    req = warm_reqs[seq % len(warm_reqs)]
                if app.scheduler.depth("cold") > 0:
                    overlapped[0] += 1
                t0 = time.perf_counter()
                resp = await app.handle(req, tenant=f"client{ci}")
                dt = time.perf_counter() - t0
                assert resp.status == 200, resp.payload
                lat[resp.headers["X-Lane"]].append(dt)
                await asyncio.sleep(0)  # yield: clients interleave

        t0 = time.perf_counter()
        await asyncio.gather(*[client(i) for i in range(CLIENTS)])
        return lat, time.perf_counter() - t0, overlapped[0]

    lat, load_wall, overlapped = asyncio.run(load_phase())
    warm_p50 = _pct(lat["hot"], 50)
    warm_p99 = _pct(lat["hot"], 99)
    cold_p50 = _pct(lat["cold"], 50)
    cold_p99 = _pct(lat["cold"], 99)
    cold_share = len(lat["cold"]) / max(len(lat["hot"]) + len(lat["cold"]), 1)
    total = len(lat["hot"]) + len(lat["cold"])
    rows.append((
        "serve_warm_lane", warm_p99 * 1e6,
        f"p50_us={warm_p50 * 1e6:.0f};p99_ms={warm_p99 * 1e3:.2f};"
        f"budget_ms=25",
    ))
    rows.append((
        "serve_cold_lane", cold_p50 * 1e6,
        f"p99_ms={cold_p99 * 1e3:.1f};share={cold_share:.2f}",
    ))
    rows.append((
        "serve_mixed_load", load_wall * 1e6,
        f"clients={CLIENTS};requests={total};"
        f"rps={total / max(load_wall, 1e-9):.0f};"
        f"overlapped={overlapped}",
    ))
    results["mixed_load"] = {
        "requests": total,
        "cold_share": cold_share,
        "warm_p50_us": warm_p50 * 1e6,
        "warm_p99_ms": warm_p99 * 1e3,
        "cold_p50_ms": cold_p50 * 1e3,
        "cold_p99_ms": cold_p99 * 1e3,
        "wall_s": load_wall,
        "rps": total / max(load_wall, 1e-9),
        "cold_overlapped_requests": overlapped,
    }
    if cold_share < 0.20:
        raise AssertionError(f"cold share {cold_share:.2f} below the 20% floor")
    if warm_p99 >= 0.025:
        raise AssertionError(
            f"warm-lane p99 {warm_p99 * 1e3:.2f} ms blew the 25 ms SLO "
            "while the cold lane was loaded"
        )

    # the SLO engine must reproduce the warm-lane verdict just asserted
    # from the live request_latency_seconds{lane=hot} series alone
    slo = svc.slo.evaluate()
    warm_obj = next(
        o for o in slo["objectives"] if o["name"] == "warm_latency"
    )
    rows.append((
        "serve_slo_verdict", (warm_obj["measured"] or 0.0) * 1e6,
        f"ok={warm_obj['ok']};budget_left="
        f"{warm_obj['error_budget_remaining']:.3f}",
    ))
    results["slo"] = {
        "warm_latency_ok": warm_obj["ok"],
        "warm_latency_measured_p99_ms": (warm_obj["measured"] or 0.0) * 1e3,
        "error_budget_remaining": warm_obj["error_budget_remaining"],
    }
    if warm_obj["ok"] is not True:
        raise AssertionError(
            "SLO engine disagrees with the measured warm-lane verdict: "
            f"{warm_obj}"
        )

    # -- 3. admission: a starved tenant sheds, never queues ------------------
    app.admission.set_quota("starved", rate=0.5, burst=4.0)

    async def shed_phase():
        out = []
        for _ in range(12):
            out.append(await app.handle(warm_reqs[0], tenant="starved"))
        return out

    shed_resps = asyncio.run(shed_phase())
    shed = [r for r in shed_resps if r.status == 429]
    retry_ok = all(float(r.headers["Retry-After"]) > 0 for r in shed)
    rows.append((
        "serve_shed", float(len(shed)),
        f"sent=12;shed={len(shed)};retry_after_ok={retry_ok}",
    ))
    results["shed"] = {"sent": 12, "shed": len(shed), "retry_after_ok": retry_ok}
    if len(shed) != 8 or not retry_ok:
        raise AssertionError(
            f"admission contract violated: shed={len(shed)} retry={retry_ok}"
        )

    # -- 4. bit-identity with the direct dict path ---------------------------
    probe_reqs = warm_reqs + [
        {"log": "bpi", "sink": "dfg", "window": list(cold_windows[0])},
        {"log": "bpi", "sink": "histogram", "window": list(cold_windows[1])},
    ]

    async def identity_phase():
        return [await app.handle(r) for r in probe_reqs]

    ident = all(
        canonical_payload(resp.payload) == canonical_payload(svc.query(req))
        for req, resp in zip(probe_reqs, asyncio.run(identity_phase()))
    )
    rows.append(("serve_identity", float(ident), f"requests={len(probe_reqs)}"))
    results["identity"] = {"requests": len(probe_reqs), "identical": ident}
    if not ident:
        raise AssertionError("transport response diverged from direct path")

    # -- 5. calibration: the measured hot/cold boundary ----------------------
    # The boundary should sit between what the hot lane actually delivers
    # and what a real cold scan costs: the geometric mean of warm-lane p99
    # and cold-lane median, clamped to the planner's rails.
    cutoff = float(np.sqrt(max(warm_p99, 1e-6) * max(cold_p50, 1e-6)))
    cutoff = min(max(cutoff, 1e-4), 2.0)
    results["calibration"] = {"slo_hot_cutoff_s": cutoff}
    rows.append((
        "serve_calibration", cutoff * 1e6,
        f"slo_hot_cutoff_s={cutoff:.6f};warm_p99_s={warm_p99:.6f};"
        f"cold_p50_s={cold_p50:.6f}",
    ))

    app.close()
    if not write_json:
        return rows
    with open("BENCH_serve.json", "w") as f:
        json.dump(results, f, indent=1)
    return rows


if __name__ == "__main__":
    if "--fast" in sys.argv:
        EVENTS = max(int(os.environ.get("BENCH_EVENTS", 400_000)), 400_000)
    for r in run(write_json=True):
        print(",".join(str(x) for x in r))

"""Process-mining CLI — the paper's pipeline end to end, through the
declarative query engine (``repro.query``): the CLI states *what* to mine
(log, dice, sink) and the engine's cost model picks the physical path
(streaming scan, device kernel, or mesh-distributed psum).

    # generate a synthetic BPI-like log and mine it
    PYTHONPATH=src python -m repro.launch.mine --events 500000 --dice-days 30

    # distributed DFG over a mesh of every device present
    PYTHONPATH=src python -m repro.launch.mine --events 200000 --distributed
"""

from __future__ import annotations

import argparse
import json
import os
import tempfile
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--events", type=int, default=200_000)
    ap.add_argument("--activities", type=int, default=32)
    ap.add_argument("--dice-days", type=float, default=None)
    ap.add_argument("--backend", default="auto",
                    choices=["auto", "scatter", "onehot", "pallas",
                             "streaming"])
    ap.add_argument("--distributed", action="store_true",
                    help="shard_map DFG over a 1-D mesh of every device "
                         "present")
    ap.add_argument("--min-count", type=int, default=10)
    ap.add_argument("--dot-out", default=None)
    args = ap.parse_args()

    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    from repro.core import discover_dependency_graph, to_dot
    from repro.data import ProcessSpec, generate_memmap_log
    from repro.query import Q, QueryEngine

    tmp = tempfile.mkdtemp(prefix="graphpm_mine_")
    spec = ProcessSpec(num_activities=args.activities, seed=7)
    t0 = time.perf_counter()
    log = generate_memmap_log(os.path.join(tmp, "log"), args.events, spec, seed=7)
    gen_s = time.perf_counter() - t0

    window = None
    if args.dice_days is not None:
        t_min = float(log.time[0])
        window = (t_min, t_min + args.dice_days * 86400.0)

    mesh = None
    if args.distributed:
        import jax

        from repro.launch.mesh import make_test_mesh

        mesh = make_test_mesh((len(jax.devices()),), ("data",))
    engine = QueryEngine(
        mesh=mesh,
        # --distributed pins the device path: lift the out-of-core budget so
        # the pairs materialize onto the mesh instead of streaming host-side
        memory_budget_events=(
            max(args.events + 1, 1 << 22) if mesh is not None else 1 << 22
        ),
    )
    q = Q.log(log).using(engine)
    if window is not None:
        q = q.window(*window)

    t0 = time.perf_counter()
    res = q.dfg(backend=args.backend)
    psi = res.value
    mode = res.physical.backend
    if mode == "distributed":
        mode += f"({'x'.join(str(s) for s in mesh.devices.shape)})"
    dfg_s = time.perf_counter() - t0

    from repro.core.discovery import filter_dfg

    t0 = time.perf_counter()
    filtered = filter_dfg(psi, min_count=args.min_count)
    import numpy as np

    starts = np.zeros(log.num_activities, np.int64)
    ends = np.zeros(log.num_activities, np.int64)
    model = discover_dependency_graph(
        filtered, [f"act_{i:03d}" for i in range(log.num_activities)],
        starts, ends, min_count=args.min_count, min_dependency=0.3,
    )
    disc_s = time.perf_counter() - t0

    print(json.dumps({
        "events": log.num_events,
        "mode": mode,
        "plan": res.physical.describe(),
        "diced": window is not None,
        "gen_s": round(gen_s, 2),
        "dfg_s": round(dfg_s, 3),
        "discover_s": round(disc_s, 3),
        "total_pairs": int(psi.sum()),
        "edges_discovered": len(model.edges),
    }, indent=1))
    if args.dot_out:
        with open(args.dot_out, "w") as f:
            f.write(to_dot(model))
        print(f"wrote {args.dot_out}")


if __name__ == "__main__":
    main()

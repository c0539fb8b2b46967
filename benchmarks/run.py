"""Benchmark aggregator — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  Set ``BENCH_EVENTS`` to scale
the Fig.4/Fig.5 logs (default 2M events ≈ the paper's dicing range start).
Use ``--fast`` for a reduced smoke pass (CI).
"""

from __future__ import annotations

import os
import sys
import time

# `python benchmarks/run.py` (direct path) puts benchmarks/ itself on
# sys.path instead of the repo root that `python -m benchmarks.run` gets
# from the cwd; add the root (and src/, so PYTHONPATH=src is optional)
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (_ROOT, os.path.join(_ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)


def main() -> int:
    if "--fast" in sys.argv:
        os.environ.setdefault("BENCH_EVENTS", "200000")
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()
    print("name,us_per_call,derived")
    t0 = time.time()
    from benchmarks import (
        bench_conformance,
        bench_delta,
        bench_dfg_example,
        bench_dicing,
        bench_graph,
        bench_kernels,
        bench_memory_scaling,
        bench_multilog,
        bench_obs,
        bench_query_engine,
        bench_serve,
        bench_shard,
        roofline_table,
    )

    failed = []
    for mod, label in (
        (bench_dfg_example, "table1"),
        (bench_memory_scaling, "fig4"),
        (bench_dicing, "fig5"),
        (bench_kernels, "kernels"),
        (bench_query_engine, "query"),
        (bench_delta, "delta"),
        (bench_multilog, "multilog"),
        (bench_graph, "graph"),
        (bench_conformance, "conformance"),
        (bench_obs, "obs"),
        (bench_shard, "shard"),
        (bench_serve, "serve"),
        (roofline_table, "roofline"),
    ):
        try:
            for row in mod.run():
                print(",".join(str(x) for x in row), flush=True)
        except Exception as e:  # noqa: BLE001
            print(f"{label}_ERROR,0,{e!r}", flush=True)
            failed.append(label)
    print(f"total_wall,{(time.time() - t0) * 1e6:.0f},seconds="
          f"{time.time() - t0:.1f}")
    if failed:
        print(f"failed modules: {', '.join(failed)}", file=sys.stderr)
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell of the chip benchmark.

    python3 chipbench/run.py --workload bpi16_clicks.fresh_windows \
        --seed 12345 --seconds 30 --trace 0

Runs from the root of a checkout of this repository, on the machine that
holds the chips the cell asks for.  It exits non-zero and prints no result
where JAX finds no TPU, or fewer chips than the cell asks for, or where the
program (``src/repro``) is not beside the benchmark.  The last line of
standard output is the result (see ``harness.py``).
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail(f"the program is not beside the benchmark ({ROOT}/src/repro)")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        cells = {w["name"]: w for w in json.load(f)["workloads"]}
    if args.workload not in cells:
        return fail(f"no workload {args.workload!r}")
    sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]
    # one fixed cache directory inside the checkout: the path is part of
    # the cache key, so only the first run of a cell here compiles
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR

    import jax

    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"needs a TPU, JAX found platform {devices[0].platform!r}")
    chips = int(cells[args.workload]["chips"])
    if len(devices) < chips:
        return fail(f"the cell needs {chips} chips, JAX found {len(devices)}")

    from chipbench.harness import run_cell

    result = run_cell(args.workload, args.seed, args.seconds, bool(args.trace), t0=T0)
    return 0 if result is not None else 1


if __name__ == "__main__":
    sys.exit(main())

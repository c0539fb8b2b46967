"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

MUST set the placeholder-device flag before ANY jax import — jax locks the
device count at first init.  Only this entry point does so; tests and
benches see the single real CPU device.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
)

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import (  # noqa: E402
    LONG_CONTEXT_SKIPS,
    SHAPES,
    TrainHParams,
    cells,
    get_config,
    get_shape,
)
from repro.launch.mesh import make_production_mesh, mesh_devices  # noqa: E402
from repro.launch.steps import (  # noqa: E402
    abstract_decode_args,
    abstract_prefill_args,
    abstract_train_args,
    make_decode_step,
    make_prefill_step,
    make_train_step,
)
from repro.roofline.analyze import analyze_compiled  # noqa: E402

ARTIFACT_DIR = os.path.join(
    os.path.dirname(__file__), "..", "..", "..", "benchmarks", "artifacts", "dryrun"
)


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool, opts=None):
    """Lower one cell; returns (lowered, meta)."""
    cfg = get_config(arch)
    if opts:
        import dataclasses

        cfg = dataclasses.replace(cfg, **opts)
    shape = get_shape(shape_name)
    mesh = make_production_mesh(multi_pod=multi_pod)

    if shape.kind == "train":
        fn, in_sh, out_sh, args, donate = make_train_step(cfg, shape, mesh)
    elif shape.kind == "prefill":
        fn, in_sh, out_sh, args, donate = make_prefill_step(cfg, shape, mesh)
    else:
        fn, in_sh, out_sh, args, donate = make_decode_step(cfg, shape, mesh)

    jitted = jax.jit(
        fn, in_shardings=in_sh, out_shardings=out_sh,
        donate_argnums=donate,
    )
    t0 = time.time()
    lowered = jitted.lower(*args)
    meta = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": mesh_devices(mesh),
        "kind": shape.kind,
        "lower_s": round(time.time() - t0, 2),
    }
    return lowered, meta, cfg, shape, mesh


def run_cell(arch: str, shape_name: str, *, multi_pod: bool, opts=None,
             tag: str = "baseline") -> dict:
    lowered, meta, cfg, shape, mesh = lower_cell(
        arch, shape_name, multi_pod=multi_pod, opts=opts
    )
    t0 = time.time()
    compiled = lowered.compile()
    meta["compile_s"] = round(time.time() - t0, 2)
    meta["tag"] = tag
    meta.update(analyze_compiled(compiled, cfg, shape, mesh))
    return meta


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true", help="run every assigned cell")
    ap.add_argument("--tag", default="baseline")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    ap.add_argument("--opts", default=None,
                    help="JSON dict of ModelConfig overrides (perf iteration)")
    args = ap.parse_args()
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    opts = json.loads(args.opts) if args.opts else None

    if args.all:
        todo = cells()
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        todo = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = 0
    for arch, shape_name in todo:
        if shape_name == "long_500k" and arch in LONG_CONTEXT_SKIPS:
            print(f"SKIP {arch} × long_500k (pure full attention, DESIGN §4)")
            continue
        for mp in meshes:
            mesh_tag = "2x16x16" if mp else "16x16"
            out_path = os.path.join(
                args.out, f"{arch}__{shape_name}__{mesh_tag}__{args.tag}.json"
            )
            try:
                result = run_cell(
                    arch, shape_name, multi_pod=mp, opts=opts, tag=args.tag
                )
                with open(out_path, "w") as f:
                    json.dump(result, f, indent=1)
                print(
                    f"OK   {arch} × {shape_name} × {mesh_tag}: "
                    f"compile {result['compile_s']}s, "
                    f"{result['per_device_bytes'] / 2**30:.2f} GiB/chip, "
                    f"dominant={result['dominant_term']}"
                )
            except Exception as e:  # noqa: BLE001 — report, keep sweeping
                failures += 1
                print(f"FAIL {arch} × {shape_name} × {mesh_tag}: {e!r}")
                traceback.print_exc()
    if failures:
        raise SystemExit(f"{failures} cell(s) failed")


if __name__ == "__main__":
    main()

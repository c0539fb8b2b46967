"""Serving CLI: batched generation with the wave engine (reduced configs on
CPU; the decode step is the one the dry-run compiled for the pod).

    PYTHONPATH=src python -m repro.launch.serve --arch gemma2-9b --requests 6
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import time


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    import numpy as np

    from repro.configs import get_config
    from repro.models import init_params
    from repro.serve import ServeEngine

    cfg = get_config(args.arch).reduced()
    cfg = dataclasses.replace(cfg, vocab_size=512, loss_chunk=32)
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(
        cfg, params, max_batch=args.max_batch, max_cache=256,
        temperature=args.temperature,
    )
    rng = np.random.default_rng(1)
    prompts = [
        rng.integers(1, cfg.vocab_size, size=rng.integers(2, 24)).tolist()
        for _ in range(args.requests)
    ]
    t0 = time.perf_counter()
    results = engine.generate(prompts, max_new_tokens=args.max_new)
    dt = time.perf_counter() - t0
    toks = sum(len(r.tokens) for r in results)
    print(json.dumps({
        "arch": cfg.name,
        "requests": len(results),
        "generated_tokens": toks,
        "wall_s": round(dt, 3),
        "tok_per_s": round(toks / dt, 1),
        "sample": results[0].tokens[:8],
    }, indent=1))


if __name__ == "__main__":
    main()

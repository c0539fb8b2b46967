"""Training CLI.

On this CPU container it drives reduced configs end-to-end (the example
path); on a pod the same entry point runs the full configs — the step
function/shardings are exactly the ones the dry-run compiled.

    PYTHONPATH=src python -m repro.launch.train --arch starcoder2-3b \
        --preset tiny --steps 100
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True)
    ap.add_argument("--preset", default="tiny", choices=["tiny", "small", "full"])
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/graphpm_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mine", action="store_true",
                    help="process-mine the run's telemetry at the end")
    args = ap.parse_args()
    from repro.core.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.configs import get_config
    from repro.configs.base import TrainHParams
    from repro.data.lm_data import TokenPipeline
    from repro.train import Trainer

    cfg = get_config(args.arch)
    if args.preset == "tiny":
        cfg = cfg.reduced()
        cfg = dataclasses.replace(cfg, vocab_size=256, loss_chunk=32)
    elif args.preset == "small":
        # ~100M-class model of the same family
        cfg = dataclasses.replace(
            cfg.reduced(), d_model=512, n_heads=8, head_dim=64, d_ff=2048,
            n_layers=len(cfg.layer_pattern) * 4, vocab_size=8192,
            loss_chunk=128,
        )
    hp = TrainHParams(
        learning_rate=args.lr, warmup_steps=max(10, args.steps // 20),
        total_steps=args.steps,
    )
    data = TokenPipeline(
        vocab_size=cfg.vocab_size, batch=args.batch, seq_len=args.seq, seed=17
    )
    trainer = Trainer(
        cfg, hp, data, args.ckpt_dir, ckpt_every=args.ckpt_every,
        q_chunk=min(1024, args.seq),
    )
    out = trainer.run(args.steps)
    print(json.dumps({
        "arch": cfg.name,
        "steps": out["final_step"],
        "first_loss": out["history"][0],
        "last_loss": out["history"][-1],
        "bigram_entropy_floor": data.bigram_entropy(),
        "stragglers": out["stragglers"],
    }, indent=1))

    if args.mine:
        from repro.core import dfg_from_repository, discover_dependency_graph, to_dot

        repo = trainer.collector.to_repository()
        psi = dfg_from_repository(repo)
        starts, ends = repo.trace_boundaries()
        model = discover_dependency_graph(
            psi, repo.activity_names, starts, ends, min_dependency=0.0
        )
        print("\n== mined training process (GraphPM on the trainer's own log) ==")
        print(to_dot(model))


if __name__ == "__main__":
    main()

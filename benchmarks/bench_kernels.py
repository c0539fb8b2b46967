"""dfg_count kernel benchmark: interpret-mode validation + analytic v5e
roofline (no TPU in this container — the kernel's TPU cost is derived from
its block schedule, and the jnp backends give measured CPU baselines)."""

from __future__ import annotations

import time

import jax.numpy as jnp
import numpy as np

from repro.core.dfg import dfg_onehot, dfg_scatter
from repro.kernels.dfg_count import dfg_count, dfg_count_ref, pick_blocks
from repro.roofline import hw


def _time(fn, reps=3):
    fn()
    t0 = time.perf_counter()
    for _ in range(reps):
        fn()
    return (time.perf_counter() - t0) / reps * 1e6


def kernel_analytic_v5e(n_pairs: int, num_acts: int) -> dict:
    """Roofline terms of the kernel's block schedule on one v5e core."""
    be, bs, bd = pick_blocks(num_acts)
    tiles = -(-num_acts // bs) * -(-num_acts // bd)
    e_pad = max(be, -(-n_pairs // be) * be)
    # per grid step: build 2 int8 one-hots + matmul 2·BE·BS·BD int8 ops
    flops = tiles * (e_pad // be) * 2 * be * bs * bd
    # HBM traffic: the int32 id rows re-read per output tile + output once
    bytes_hbm = tiles * e_pad * (4 + 4) + tiles * bs * bd * 4
    return {
        "block_e": be, "block_s": bs, "block_d": bd,
        "grid": tiles * (e_pad // be),
        "compute_s": flops / hw.PEAK_OPS_INT8,
        "memory_s": bytes_hbm / hw.HBM_BW,
        "flops": flops,
    }


def run() -> list:
    rows = []
    rng = np.random.default_rng(0)
    for n_pairs, acts in [(100_000, 64), (1_000_000, 64), (1_000_000, 512)]:
        src = jnp.asarray(rng.integers(0, acts, n_pairs), jnp.int32)
        dst = jnp.asarray(rng.integers(0, acts, n_pairs), jnp.int32)
        valid = jnp.asarray(rng.random(n_pairs) < 0.9)

        t_scatter = _time(
            lambda: dfg_scatter(src, dst, valid, num_activities=acts).block_until_ready()
        )
        t_onehot = _time(
            lambda: dfg_onehot(src, dst, valid, num_activities=acts).block_until_ready()
        )
        rows.append((f"dfg_scatter_cpu_{n_pairs}x{acts}", t_scatter, "measured"))
        rows.append((f"dfg_onehot_cpu_{n_pairs}x{acts}", t_onehot, "measured"))

        # interpret-mode correctness on a subsample (full E is slow in python)
        sub = 20_000
        got = dfg_count(src[:sub], dst[:sub], valid[:sub],
                        num_activities=acts, interpret=True)
        want = dfg_count_ref(src[:sub], dst[:sub], valid[:sub],
                             num_activities=acts)
        ok = bool((np.asarray(got) == np.asarray(want)).all())

        a = kernel_analytic_v5e(n_pairs, acts)
        dom = "compute" if a["compute_s"] > a["memory_s"] else "memory"
        rows.append((
            f"dfg_pallas_v5e_{n_pairs}x{acts}",
            max(a["compute_s"], a["memory_s"]) * 1e6,
            f"analytic;blocks=({a['block_e']},{a['block_s']},{a['block_d']});"
            f"dominant={dom};interpret_match={ok}",
        ))
    return rows


if __name__ == "__main__":
    for r in run():
        print(",".join(str(x) for x in r))

"""Jitted public wrappers around the dfg_count Pallas kernel.

Handles padding (events to BE; the vocabulary to whole output tiles),
backend selection (interpret mode on CPU — kernel body runs in Python for
validation; compiled Mosaic on TPU), and the tiling from a VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.kernels_check import (
    LANE,
    VMEM_BUDGET_BYTES,
    validate_blocks,
)

from .kernel import SUBLANE, dfg_count_pallas

__all__ = ["dfg_count", "dfg_count_diced", "pick_blocks", "working_set"]

#: event-block bounds: the shortest block a tile must fit beside, and the
#: longest taken
BLOCK_E_MIN, BLOCK_E_MAX = 512, 4096
#: cap on the two one-hots of one step.  Mosaic unrolls their build over
#: the block, so compile time grows with them: on one v5e at A = 600, 2048
#: events a block compile in 1.39 s against 1.72 s for 4096, for a call
#: 1.8% longer (16.36 against 16.08 ms at 7.2M pairs); A = 26 keeps 4096
ONE_HOTS_MAX_BYTES = 5 << 19


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def working_set(block_e: int, block_s: int, block_d: int) -> int:
    """VMEM bytes of one grid step: the resident int32 output tile plus the
    two int8 one-hots."""
    return 4 * block_s * block_d + block_e * (block_s + block_d)


def pick_blocks(
    num_activities: int, vmem_budget_bytes: int = VMEM_BUDGET_BYTES
) -> tuple[int, int, int]:
    """Choose (block_e, block_s, block_d).

    The output tile ``(block_s, block_d)`` covers the vocabulary in the
    fewest tiles whose working set at ``BLOCK_E_MIN`` events fits the
    budget — one tile for every vocabulary up to 1280 — each padded only to
    the int8 sublane tile (``src`` rows, ``SUBLANE``) and to 128 lanes
    (``dst`` columns); among equal counts, the least padded area, then the
    fewest one-hot rows.
    ``block_e`` is then as large as the budget and ``ONE_HOTS_MAX_BYTES``
    allow: a longer event block amortizes the read-add-write of the
    resident tile and the cost of a grid step.
    """
    a = max(1, int(num_activities))
    best = None
    n = 0
    while best is None:
        n += 1
        for n_s in (d for d in range(1, n + 1) if n % d == 0):
            bs = _round_up(-(-a // n_s), SUBLANE)
            bd = _round_up(-(-a // (n // n_s)), LANE)
            if working_set(BLOCK_E_MIN, bs, bd) > vmem_budget_bytes:
                continue
            cost = (n * bs * bd, n * (bs + bd))  # MXU, then VPU work
            if best is None or cost < best[0]:
                best = (cost, bs, bd)
    _, block_s, block_d = best
    one_hots = min(
        vmem_budget_bytes - 4 * block_s * block_d, ONE_HOTS_MAX_BYTES
    )
    be = one_hots // (block_s + block_d)
    block_e = max(BLOCK_E_MIN, min(BLOCK_E_MAX, be // 512 * 512))
    # static resource check: BlockSpec VMEM bound + MXU/VPU tile alignment
    validate_blocks(
        "dfg_count", block_e=block_e, block_s=block_s, block_d=block_d
    )
    return block_e, block_s, block_d


def _event_rows(block_e, src, valid, *cols):
    """Fold ``valid`` into ``src`` (a pair that does not count carries id
    -1, which matches no one-hot row), pad every column to a multiple of
    ``block_e`` and lay each out as one lane-major ``(1, E)`` row."""
    src = jnp.where(valid.astype(jnp.bool_), src.astype(jnp.int32), -1)
    n = src.shape[0]
    pad = (-n) % block_e or (block_e if n == 0 else 0)
    out = [jnp.pad(src, (0, pad), constant_values=-1)]
    out += [jnp.pad(c, (0, pad)) for c in cols]
    return [c.reshape(1, -1) for c in out]


def _blocks(num_activities, block_e, block_s, block_d):
    auto = pick_blocks(num_activities)
    return block_e or auto[0], block_s or auto[1], block_d or auto[2]


_STATIC = ("num_activities", "block_e", "block_s", "block_d", "interpret")


@functools.partial(jax.jit, static_argnames=_STATIC)
def dfg_count(
    src: jax.Array,
    dst: jax.Array,
    valid: jax.Array,
    *,
    num_activities: int,
    block_e: int | None = None,
    block_s: int | None = None,
    block_d: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """DFG count matrix (num_activities², int32) from pair columns."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_e, block_s, block_d = _blocks(
        num_activities, block_e, block_s, block_d
    )
    src, dst = _event_rows(block_e, src, valid, dst.astype(jnp.int32))
    out = dfg_count_pallas(
        src, dst,
        num_activities=num_activities,
        block_e=block_e,
        block_s=block_s,
        block_d=block_d,
        interpret=interpret,
    )
    return out[:num_activities, :num_activities]


@functools.partial(jax.jit, static_argnames=_STATIC)
def dfg_count_diced(
    src: jax.Array,
    dst: jax.Array,
    valid: jax.Array,
    ts_src: jax.Array,
    ts_dst: jax.Array,
    window: jax.Array,  # shape (2,): [t0, t1)
    *,
    num_activities: int,
    block_e: int | None = None,
    block_s: int | None = None,
    block_d: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused WHERE-clause dicing + counting (paper §4, Experiment 2)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    block_e, block_s, block_d = _blocks(
        num_activities, block_e, block_s, block_d
    )
    src, dst, ts_src, ts_dst = _event_rows(
        block_e, src, valid, dst.astype(jnp.int32),
        ts_src.astype(jnp.float32), ts_dst.astype(jnp.float32),
    )
    out = dfg_count_pallas(
        src, dst,
        num_activities=num_activities,
        block_e=block_e,
        block_s=block_s,
        block_d=block_d,
        interpret=interpret,
        ts_src=ts_src,
        ts_dst=ts_dst,
        window=window.astype(jnp.float32).reshape(2),
    )
    return out[:num_activities, :num_activities]

# Timing hook: every call lands in the process-global kernel registry as
# kernel_seconds{kernel=...} (see repro.kernels.timing).
from ..timing import timed_kernel

dfg_count = timed_kernel("dfg_count", dfg_count)
dfg_count_diced = timed_kernel("dfg_count_diced", dfg_count_diced)

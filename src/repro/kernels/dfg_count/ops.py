"""Jitted public wrappers around the dfg_count Pallas kernel.

Handles padding (events to BE, activities to BA), backend selection
(interpret mode on CPU — kernel body runs in Python for validation; compiled
Mosaic on TPU), and block-size auto-tuning from a VMEM budget.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.kernels_check import validate_blocks

from .kernel import dfg_count_pallas

__all__ = ["dfg_count", "dfg_count_diced", "pick_blocks"]


def pick_blocks(
    num_activities: int, vmem_budget_bytes: int = 8 << 20
) -> tuple[int, int]:
    """Choose (block_e, block_a).

    block_a: lane-aligned tile of the activity axis (≤512 keeps the output
    tile small); block_e: as large as the VMEM budget allows for the two
    one-hot tiles (f32) — bigger BE amortizes the output-tile revisits.
    """
    block_a = 128
    while block_a < 512 and block_a < num_activities:
        block_a *= 2
    block_a = min(block_a, 512)
    # 2 one-hot tiles of (BE, BA) f32 + out (BA, BA) f32 within budget
    be = (vmem_budget_bytes - 4 * block_a * block_a) // (2 * 4 * block_a)
    block_e = max(512, min(4096, int(be) // 512 * 512))
    # static resource check: BlockSpec VMEM bound + MXU/VPU tile alignment
    validate_blocks("dfg_count", block_e=block_e, block_a=block_a)
    return block_e, block_a


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


@functools.partial(
    jax.jit,
    static_argnames=("num_activities", "block_e", "block_a", "interpret"),
)
def dfg_count(
    src: jax.Array,
    dst: jax.Array,
    valid: jax.Array,
    *,
    num_activities: int,
    block_e: int | None = None,
    block_a: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """DFG count matrix (num_activities², int32) from pair columns."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    auto_e, auto_a = pick_blocks(num_activities)
    block_e = block_e or auto_e
    block_a = block_a or auto_a
    a_pad = max(block_a, -(-num_activities // block_a) * block_a)

    src, dst = _event_rows(block_e, src, valid, dst.astype(jnp.int32))
    out = dfg_count_pallas(
        src, dst,
        num_activities_padded=a_pad,
        block_e=block_e,
        block_a=block_a,
        interpret=interpret,
    )
    return out[:num_activities, :num_activities].astype(jnp.int32)


@functools.partial(
    jax.jit,
    static_argnames=("num_activities", "block_e", "block_a", "interpret"),
)
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
    block_a: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused WHERE-clause dicing + counting (paper §4, Experiment 2)."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    auto_e, auto_a = pick_blocks(num_activities)
    block_e = block_e or auto_e
    block_a = block_a or auto_a
    a_pad = max(block_a, -(-num_activities // block_a) * block_a)

    src, dst, ts_src, ts_dst = _event_rows(
        block_e, src, valid, dst.astype(jnp.int32),
        ts_src.astype(jnp.float32), ts_dst.astype(jnp.float32),
    )
    out = dfg_count_pallas(
        src, dst,
        num_activities_padded=a_pad,
        block_e=block_e,
        block_a=block_a,
        interpret=interpret,
        ts_src=ts_src,
        ts_dst=ts_dst,
        window=window.astype(jnp.float32).reshape(2),
    )
    return out[:num_activities, :num_activities].astype(jnp.int32)

# Timing hook: every call lands in the process-global kernel registry as
# kernel_seconds{kernel=...} (see repro.kernels.timing).
from ..timing import timed_kernel

dfg_count = timed_kernel("dfg_count", dfg_count)
dfg_count_diced = timed_kernel("dfg_count_diced", dfg_count_diced)

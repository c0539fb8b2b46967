"""Jitted public wrapper around the segment_count Pallas kernel.

Handles padding (ids to BN, segments to BS) and backend selection
(interpret mode on CPU — kernel body runs in Python for validation;
compiled Mosaic on TPU), mirroring :mod:`repro.kernels.dfg_count.ops`.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.analysis.kernels_check import validate_blocks

from .kernel import segment_count_pallas

__all__ = ["segment_count", "pick_blocks"]


def pick_blocks(
    num_segments: int, vmem_budget_bytes: int = 8 << 20
) -> tuple[int, int]:
    """Choose (block_n, block_s): a lane-aligned segment tile (≤512) and the
    largest id block whose one-hot tile (f32) fits the VMEM budget."""
    block_s = 128
    while block_s < 512 and block_s < num_segments:
        block_s *= 2
    block_s = min(block_s, 512)
    bn = (vmem_budget_bytes - 4 * block_s) // (4 * block_s)
    block_n = max(512, min(4096, int(bn) // 512 * 512))
    # static resource check: BlockSpec VMEM bound + MXU/VPU tile alignment
    validate_blocks("segment_count", block_n=block_n, block_s=block_s)
    return block_n, block_s


@functools.partial(
    jax.jit,
    static_argnames=("num_segments", "block_n", "block_s", "interpret"),
)
def segment_count(
    ids: jax.Array,
    valid: jax.Array,
    *,
    num_segments: int,
    block_n: int | None = None,
    block_s: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Count occurrences of each segment id in ``[0, num_segments)``.

    Equivalent to ``jnp.bincount(ids[valid], length=num_segments)`` — the
    TPU-native histogram the graph builder uses for node degrees.
    """
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    auto_n, auto_s = pick_blocks(num_segments)
    block_n = block_n or auto_n
    block_s = block_s or auto_s
    s_pad = max(block_s, -(-num_segments // block_s) * block_s)

    ids = jnp.where(valid.astype(jnp.bool_), ids.astype(jnp.int32), -1)
    n = ids.shape[0]
    pad = (-n) % block_n or (block_n if n == 0 else 0)
    ids = jnp.pad(ids, (0, pad), constant_values=-1).reshape(1, -1)

    out = segment_count_pallas(
        ids,
        num_segments_padded=s_pad,
        block_n=block_n,
        block_s=block_s,
        interpret=interpret,
    )
    return out[:num_segments].astype(jnp.int32)

# Timing hook: every call lands in the process-global kernel registry as
# kernel_seconds{kernel=segment_count} (see repro.kernels.timing).
from ..timing import timed_kernel

segment_count = timed_kernel("segment_count", segment_count)

"""Pallas TPU kernel for DFG counting (Algorithm 1's hot loop).

GPU/graph-DB intuition would scatter-add each directly-follows pair into
``Ψ[src, dst]`` — scatters serialize on TPU.  The TPU-native formulation
builds one-hot tiles **in VMEM** from the integer id blocks and accumulates

    Ψ[tile] += OneHot_src(block) · OneHot_dst(block)ᵀ

on the MXU, contracting over the events of the block.

Grid ``(A_s/BS, A_d/BD, E/BE)`` with the event dimension innermost, so each
``(BS, BD)`` output tile stays resident in VMEM while the event stream
flows through; the tile is zeroed at the first event block (standard Pallas
accumulation pattern).  For the vocabularies a deployment has the whole
padded ``Ψ`` is one tile (grid ``(1, 1, E/BE)``): every event block is read
once, and its ``src`` and ``dst`` one-hots are each built once and
contracted once.  Only a vocabulary whose resident tile and one-hots would
overrun the VMEM budget is split, into the fewest tiles that fit
(``ops.pick_blocks``); each event block is then read once per tile.

Padding follows the hardware, not powers of two: the tile's lane axis
(``dst``) is a multiple of 128 and its sublane axis (``src``) a multiple of
the int8 sublane tile, 32 rows (``SUBLANE``).  For A = 600 that is one
608 × 640 tile, where a power-of-two tiling would contract 1024 × 1024.

Layout: each event block arrives as one lane-major ``(1, BE)`` row, and the
one-hots are built transposed, ``(BS, BE) = iota over sublanes == row``, so
no vector ever changes shape in the kernel (Mosaic cannot relayout a 1-D
block into a column).  The row is shifted by the tile's first id, so the
iota is the same for every tile.  Pairs that do not count carry the id
``-1``, which matches no one-hot row; the wrapper folds the valid mask into
the ids, so the kernel reads int32 ids only.

One-hots are int8 and the MXU accumulates their products in int32: exact
for every count below 2^31, at the v5e's int8 rate, twice its bf16 rate
(on one v5e at A = 600, 7.2M pairs and 2048 events a block: 16.4 ms a
call, against 30.0 ms with bf16 one-hots and 30.1 ms with f32).

VMEM working set per step: the resident int32 ``(BS, BD)`` tile plus the
two one-hots, ``BE · (BS + BD)`` bytes; A = 600 at BE = 2048 is
1.5 MiB + 2.4 MiB, under the 8 MiB budget ``ops.pick_blocks`` tunes to.

The fused **dicing** variant additionally streams the pairs' int32 time
ranks (``EventRepository.time_ranks``) and applies ``lo ≤ rank < hi``
in-register — the paper's WHERE clause ``t0 ≤ t < t1``, exact for any
float64 time, at zero extra HBM traffic (no filtered copy is ever
materialized).  The window sits in SMEM as the two int32 rank bounds.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "SUBLANE", "dfg_kernel", "dfg_dice_kernel", "dfg_count_pallas",
]

#: rows of one int8 (sublane) tile: the one-hots' row padding
SUBLANE = 32


def _one_hot(ids, n: int):
    """``(n, BE)`` one-hot of a ``(1, BE)`` id row: row ``r`` is ``ids == r``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, (n, ids.shape[1]), 0)
    return (rows == ids).astype(jnp.int8)


def _accumulate(src, dst, out_ref):
    """``out += OneHot(src) · OneHot(dst)ᵀ`` for one (1, BE) event row."""
    block_s, block_d = out_ref.shape
    oh_src = _one_hot(src - pl.program_id(0) * block_s, block_s)  # (BS, BE)
    oh_dst = _one_hot(dst - pl.program_id(1) * block_d, block_d)  # (BD, BE)
    out_ref[...] += jax.lax.dot_general(
        oh_src,
        oh_dst,
        dimension_numbers=(((1,), (1,)), ((), ())),  # contract over events
        preferred_element_type=jnp.int32,
    )


def dfg_kernel(src_ref, dst_ref, out_ref):
    """One grid step: accumulate the output tile over one event block."""

    @pl.when(pl.program_id(2) == 0)  # event block (innermost)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    _accumulate(src_ref[...], dst_ref[...], out_ref)


def dfg_dice_kernel(src_ref, dst_ref, rank_src_ref, rank_dst_ref, win_ref, out_ref):
    """Fused dicing: a pair counts iff ``lo <= rank < hi`` for both
    endpoints, the window ``[t0, t1)`` as time-rank bounds.

    Paper semantics — both endpoints of the pair must be inside the window."""

    @pl.when(pl.program_id(2) == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    lo = win_ref[0]
    hi = win_ref[1]
    r_s = rank_src_ref[...]
    r_d = rank_dst_ref[...]
    inside = (r_s >= lo) & (r_s < hi) & (r_d >= lo) & (r_d < hi)
    src = jnp.where(inside, src_ref[...], -1)
    _accumulate(src, dst_ref[...], out_ref)


def dfg_count_pallas(
    src: jax.Array,
    dst: jax.Array,
    *,
    num_activities: int,
    block_e: int,
    block_s: int,
    block_d: int,
    interpret: bool,
    rank_src: jax.Array | None = None,
    rank_dst: jax.Array | None = None,
    window: jax.Array | None = None,
) -> jax.Array:
    """Raw pallas_call wrapper.  Event columns are ``(1, E)`` rows with
    ``E % block_e == 0``; the output covers ``num_activities`` in whole
    ``(block_s, block_d)`` tiles and is returned padded."""
    a = max(num_activities, 1)  # an empty vocabulary still gets one tile
    grid = (pl.cdiv(a, block_s), pl.cdiv(a, block_d), src.shape[1] // block_e)

    ev_spec = pl.BlockSpec((1, block_e), lambda i, j, e: (0, e))
    out_spec = pl.BlockSpec((block_s, block_d), lambda i, j, e: (i, j))
    out_shape = jax.ShapeDtypeStruct(
        (grid[0] * block_s, grid[1] * block_d), jnp.int32
    )

    if window is None:
        return pl.pallas_call(
            dfg_kernel,
            grid=grid,
            in_specs=[ev_spec, ev_spec],
            out_specs=out_spec,
            out_shape=out_shape,
            interpret=interpret,
        )(src, dst)

    win_spec = pl.BlockSpec(
        (2,), lambda i, j, e: (0,), memory_space=pltpu.SMEM
    )
    return pl.pallas_call(
        dfg_dice_kernel,
        grid=grid,
        in_specs=[ev_spec, ev_spec, ev_spec, ev_spec, win_spec],
        out_specs=out_spec,
        out_shape=out_shape,
        interpret=interpret,
    )(src, dst, rank_src, rank_dst, window)

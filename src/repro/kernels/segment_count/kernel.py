"""Pallas TPU kernel for segment counting (node-degree histograms).

The graph builder needs per-segment cardinalities everywhere a CSR index is
assembled: events per Activity node (`:OF_TYPE` degree, the process-map node
significance), events per Case node (`:BELONGS_TO` degree), run lengths of
sorted edge keys.  On CPU that is ``np.bincount``; on TPU a scatter-add
serializes, so — exactly like :mod:`repro.kernels.dfg_count` — the kernel
reformulates the histogram as a dense one-hot contraction on the MXU:

    counts[j·BS:(j+1)·BS] += Σ_block OneHot(ids) · 1

Grid ``(S/BS, N/BN)`` with the id-block dimension innermost so each count
tile stays resident in VMEM while the id stream flows through; the tile is
zeroed at the first block (standard Pallas accumulation pattern).

Layout, as in :mod:`repro.kernels.dfg_count`: each id block is one
lane-major ``(1, BN)`` row, the one-hot is built transposed as
``(BS, BN) = iota over sublanes == row`` and reduced over lanes into a
``(BS, 1)`` count column.  Ids that do not count carry ``-1`` (the wrapper
folds the valid mask in), so the kernel reads int32 ids only.

VMEM working set per step (BN=4096, BS=512, f32):
  one-hot 512×4096×4 B = 8 MiB + out tile 2 KiB  < 16 MiB v5e VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["segment_count_kernel", "segment_count_pallas"]


def segment_count_kernel(ids_ref, out_ref, *, block_s: int):
    """One grid step: accumulate a (BS, 1) count tile over one id block."""
    j = pl.program_id(0)  # segment tile
    b = pl.program_id(1)  # id block (innermost)

    @pl.when(b == 0)
    def _zero():
        out_ref[...] = jnp.zeros_like(out_ref)

    ids = ids_ref[...]  # (1, BN) int32
    rows = jax.lax.broadcasted_iota(jnp.int32, (block_s, ids.shape[1]), 0)
    onehot = (rows + j * block_s == ids).astype(jnp.float32)  # (BS, BN)
    out_ref[...] += jnp.sum(onehot, axis=1, keepdims=True)


def segment_count_pallas(
    ids: jax.Array,
    *,
    num_segments_padded: int,
    block_n: int,
    block_s: int,
    interpret: bool,
) -> jax.Array:
    """Raw pallas_call wrapper.  ``ids`` is a ``(1, N)`` row with
    ``N % block_n == 0``; ``num_segments_padded % block_s == 0``."""
    n_total = ids.shape[1]
    grid = (num_segments_padded // block_s, n_total // block_n)

    id_spec = pl.BlockSpec((1, block_n), lambda j, b: (0, b))
    out_spec = pl.BlockSpec((block_s, 1), lambda j, b: (j, 0))

    kernel = functools.partial(segment_count_kernel, block_s=block_s)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[id_spec],
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(
            (num_segments_padded, 1), jnp.float32
        ),
        interpret=interpret,
    )(ids)
    return out[:, 0]

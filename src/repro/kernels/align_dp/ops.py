"""Jitted public wrapper + numpy reference for the align_dp Pallas kernel.

Handles padding (variants to BV, layers/states to lane multiples) and
backend selection (numpy fallback on CPU — the kernel body is additionally
interpret-validated against it in the tests; compiled Mosaic on TPU),
mirroring :mod:`repro.kernels.segment_count.ops`.

All costs are small integers carried in f32 (exact below 2²⁴), so the
pallas and numpy paths agree bit for bit.
"""

from __future__ import annotations

import numpy as np

from repro.analysis.kernels_check import validate_blocks

from .kernel import BIG_COST, align_dp_pallas

__all__ = ["align_dp", "align_dp_numpy", "pick_blocks", "BIG_COST"]


def pick_blocks(num_variants: int) -> int:
    """Variant block: one MXU-height tile; small inputs shrink to one
    sublane-aligned block instead of padding 128-wide."""
    bv = 8
    while bv < 128 and bv < num_variants:
        bv *= 2
    return bv


def _pad_lane(n: int, lane: int = 128) -> int:
    return max(lane, -(-n // lane) * lane)


def align_dp_numpy(
    seqs: np.ndarray,
    lens: np.ndarray,
    m: np.ndarray,
    d0: np.ndarray,
    endcost: np.ndarray,
) -> np.ndarray:
    """Reference layered DP, vectorized across variants (f32 throughout so
    it is the kernel's bit-exact oracle)."""
    v, lp = seqs.shape
    s = m.shape[0]
    d = np.broadcast_to(d0.astype(np.float32), (v, s)).copy()
    cols = np.arange(s, dtype=np.int64)[None, :]
    for i in range(lp):
        a = seqs[:, i].astype(np.int64)
        mcol = m.T[a].astype(np.float32)  # (V, S): M[s, a_v]
        sync = (d + mcol).min(axis=1)
        onehot = cols == a[:, None]
        nd = np.minimum(
            d + np.float32(1.0),
            np.where(onehot, sync[:, None], np.float32(BIG_COST)),
        )
        d = np.where((lens > i)[:, None], nd, d)
    return (d + endcost.astype(np.float32)[None, :]).min(axis=1)


def align_dp(
    seqs: np.ndarray,
    lens: np.ndarray,
    m: np.ndarray,
    d0: np.ndarray,
    endcost: np.ndarray,
    *,
    backend: str = "auto",
    block_v: int | None = None,
    interpret: bool | None = None,
) -> np.ndarray:
    """Per-variant alignment cost for the layered DFG-alignment DP.

    ``seqs`` (V, L) int32 activity ids (padding rows masked via ``lens``),
    ``m`` (S, A≤S) the model-move+sync cost closure, ``d0`` / ``endcost``
    (S,) the virtual-START/END folds.  ``backend``: ``auto`` (numpy on CPU,
    pallas on TPU) | ``numpy`` | ``pallas``.
    """
    seqs = np.ascontiguousarray(seqs, dtype=np.int32)
    lens = np.ascontiguousarray(lens, dtype=np.int32)
    v, l = seqs.shape
    s_real, a_real = m.shape

    if backend == "auto":
        import jax

        backend = "numpy" if jax.default_backend() == "cpu" else "pallas"

    sp = _pad_lane(max(s_real, a_real))
    mp = np.full((sp, sp), BIG_COST, dtype=np.float32)
    mp[:s_real, :a_real] = m
    d0p = np.full((sp,), BIG_COST, dtype=np.float32)
    d0p[:s_real] = d0
    endp = np.full((sp,), BIG_COST, dtype=np.float32)
    endp[:s_real] = endcost

    if backend == "numpy":
        if v == 0:
            return np.zeros((0,), dtype=np.float32)
        return align_dp_numpy(seqs, lens, mp, d0p, endp)
    if backend != "pallas":
        raise ValueError(f"unknown align_dp backend {backend!r}")

    import jax
    import jax.numpy as jnp

    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    bv = block_v or pick_blocks(v)
    vp = max(bv, -(-v // bv) * bv)
    lp = _pad_lane(l)
    # static resource check on the concrete block assignment (pick_blocks
    # alone cannot: the VMEM bound also depends on the padded L / S axes)
    validate_blocks("align_dp", block_v=bv, lp=lp, s=sp)
    # variants on lanes: layer i of the DP is row i of seqs_p
    seqs_p = np.zeros((lp, vp), dtype=np.int32)
    seqs_p[:l, :v] = seqs.T
    lens_p = np.zeros((1, vp), dtype=np.int32)
    lens_p[0, :v] = lens

    out = _align_dp_kernel(
        jnp.asarray(seqs_p), jnp.asarray(lens_p), jnp.asarray(mp),
        jnp.asarray(d0p[:, None]), jnp.asarray(endp[:, None]),
        block_v=bv, interpret=bool(interpret),
    )
    return np.asarray(out)[:v]

# Timing hook: every kernel call (not the numpy path) lands in the
# process-global kernel registry as kernel_seconds{kernel=align_dp} (see
# repro.kernels.timing).
from ..timing import timed_kernel

_align_dp_kernel = timed_kernel("align_dp", align_dp_pallas)

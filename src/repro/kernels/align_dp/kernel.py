"""Pallas TPU kernel for the DFG-alignment banded DP.

:mod:`repro.conformance.align` reduces a trace-to-model alignment to a
layered DP over the model's state space: for every consumed event the
(V, S) cost front takes one of two moves —

* **log move** (skip the event): ``d += 1`` elementwise;
* **model moves + sync**: land on the event's state at
  ``min_s d[s] + M[s, a]`` (``M`` pre-folds any number of model moves
  followed by one synchronous move via an APSP closure).

Each layer is a one-hot gather of ``M``'s column — an MXU contraction
(``M · OneHot(a)``) — followed by a sublane min-reduce: the same
"scatter → dense one-hot matmul" reformulation as the dfg_count and
segment_count kernels ("Pallas where it pays").  The kernel walks all L
layers for one variant block with the cost front resident in VMEM (the
band), so HBM traffic is the padded sequence block plus M once.

Layout: states on sublanes, variants on lanes.  ``seqs`` arrives as
``(L, BV)``, so layer ``i`` is one sublane row read from the ref with
``pl.ds`` (no dynamic slice of a value), and the cost front is an
``(S, BV)`` VMEM scratch.  The gather runs at ``Precision.HIGHEST``: the
costs and the :data:`BIG_COST` sentinel must pass the MXU unrounded for
the result to match the f32 numpy oracle bit for bit.

VMEM working set per step (BV=128, S≤640, L≤1024, f32/int32):
  seqs 1024×128×4 B = 512 KiB + M 640×640×4 B = 1.6 MiB
  + front, one-hot, gathered column 3×640×128×4 B = 960 KiB  « 16 MiB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["align_dp_kernel", "align_dp_pallas", "BIG_COST"]

#: "unreachable" sentinel — large enough that no real alignment cost ever
#: reaches it, small enough that f32 sums of a few of them stay finite
BIG_COST = 1e9


def align_dp_kernel(
    seqs_ref, lens_ref, m_ref, d0_ref, end_ref, out_ref, d_ref, *,
    num_layers: int,
):
    """One grid step: run the full layered DP for one variant block."""
    m = m_ref[...]  # (S, S) f32 — M, padded states carry BIG_COST
    lens = lens_ref[...]  # (1, BV) int32
    s, bv = d_ref.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (s, bv), 0)
    d_ref[...] = jnp.broadcast_to(d0_ref[...], (s, bv))  # d0 is (S, 1)

    def layer(i, carry):
        a = seqs_ref[pl.ds(i, 1), :]  # (1, BV) activity of layer i
        onehot = (rows == a).astype(jnp.float32)  # (S, BV)
        # Mcol[s, v] = M[s, a_v]  via  M · OneHot(a) on the MXU
        mcol = jnp.dot(
            m, onehot,
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
        d = d_ref[...]
        sync = jnp.min(d + mcol, axis=0, keepdims=True)  # (1, BV)
        nd = jnp.minimum(d + 1.0, jnp.where(onehot > 0, sync, BIG_COST))
        d_ref[...] = jnp.where(lens > i, nd, d)
        return carry

    jax.lax.fori_loop(0, num_layers, layer, 0)
    out_ref[...] = jnp.min(d_ref[...] + end_ref[...], axis=0, keepdims=True)


def align_dp_pallas(
    seqs: jax.Array,
    lens: jax.Array,
    m: jax.Array,
    d0: jax.Array,
    endcost: jax.Array,
    *,
    block_v: int,
    interpret: bool,
) -> jax.Array:
    """Raw pallas_call wrapper.  All shapes must be pre-padded: ``seqs``
    (Lp, Vp) and ``lens`` (1, Vp) with Vp % block_v == 0, ``m`` (S, S)
    and the ``d0`` / ``endcost`` columns (S, 1) with S lane-aligned."""
    lp, vp = seqs.shape
    s = m.shape[0]
    grid = (vp // block_v,)

    kernel = functools.partial(align_dp_kernel, num_layers=lp)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((lp, block_v), lambda v: (0, v)),
            pl.BlockSpec((1, block_v), lambda v: (0, v)),
            pl.BlockSpec((s, s), lambda v: (0, 0)),
            pl.BlockSpec((s, 1), lambda v: (0, 0)),
            pl.BlockSpec((s, 1), lambda v: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_v), lambda v: (0, v)),
        out_shape=jax.ShapeDtypeStruct((1, vp), jnp.float32),
        scratch_shapes=[pltpu.VMEM((s, block_v), jnp.float32)],
        interpret=interpret,
    )(seqs, lens, m, d0, endcost)
    return out[0]

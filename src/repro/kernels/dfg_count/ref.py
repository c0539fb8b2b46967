"""Pure-jnp oracle for the dfg_count Pallas kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["dfg_count_ref", "dfg_count_diced_ref"]


@functools.partial(jax.jit, static_argnames=("num_activities",))
def dfg_count_ref(
    src: jax.Array, dst: jax.Array, valid: jax.Array, *, num_activities: int
) -> jax.Array:
    psi = jnp.zeros((num_activities, num_activities), dtype=jnp.int32)
    v = valid.astype(jnp.int32)
    # clip ids so padded/garbage rows can't index OOB (they carry v == 0)
    s = jnp.clip(src, 0, num_activities - 1)
    d = jnp.clip(dst, 0, num_activities - 1)
    # rows with ids outside range contribute 0
    in_range = (src >= 0) & (src < num_activities) & (dst >= 0) & (dst < num_activities)
    return psi.at[s, d].add(v * in_range.astype(jnp.int32))


@functools.partial(jax.jit, static_argnames=("num_activities",))
def dfg_count_diced_ref(
    src: jax.Array,
    dst: jax.Array,
    valid: jax.Array,
    rank_src: jax.Array,
    rank_dst: jax.Array,
    window: jax.Array,
    *,
    num_activities: int,
) -> jax.Array:
    """The window ``[lo, hi)`` over int32 time ranks, as the kernel takes it."""
    lo, hi = window[0], window[1]
    v = (
        valid
        & (rank_src >= lo) & (rank_src < hi)
        & (rank_dst >= lo) & (rank_dst < hi)
    )
    return dfg_count_ref(src, dst, v, num_activities=num_activities)

"""Mesh and shard_map front-ends on the installed JAX (0.9).

Everything mesh- or shard_map-shaped in the codebase goes through these
helpers, so every mesh is built with all axes Auto and every shard_map
spells its manual axes the same way.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax

__all__ = ["make_mesh", "shard_map", "cost_analysis"]


def make_mesh(
    axis_shapes: Sequence[int],
    axis_names: Sequence[str],
    *,
    devices: Optional[Sequence] = None,
):
    """``jax.make_mesh`` with all axes Auto."""
    if devices is None:
        devices = jax.devices()[: math.prod(axis_shapes)]
    return jax.make_mesh(
        tuple(axis_shapes),
        tuple(axis_names),
        devices=devices,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
    )


def cost_analysis(compiled) -> dict:
    """``compiled.cost_analysis()``, empty when the backend reports none."""
    return compiled.cost_analysis() or {}


def shard_map(f, *, mesh, in_specs, out_specs, axis_names=None, check=False):
    """``jax.shard_map``: ``axis_names`` are the mesh axes the body is
    manual over (``None``: all of them; the rest stay auto), ``check`` maps
    to ``check_vma``."""
    kw = dict(
        mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=check
    )
    if axis_names is not None:
        kw["axis_names"] = set(axis_names)
    return jax.shard_map(f, **kw)

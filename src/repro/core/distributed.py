"""Distributed DFG — the paper's horizontal scaling, on a TPU mesh.

Neo4j scales DFG computation by adding database nodes; here the "database"
is the pod: event-pair columns live sharded across every device's HBM, each
device counts its resident shard (MXU one-hot matmul or the Pallas kernel),
and a single ``psum`` of the (A, A) matrix produces the global DFG.

Privacy property preserved *by construction*: the only cross-device /
device-to-host traffic is the aggregated count matrix — raw events never
move (the paper's "remove the requirement to move data into analysts'
computer").

Works on any mesh rank — ``("data",)``, ``("data", "model")``, or the
production ``("pod", "data", "model")`` — events are sharded over *all*
axes flattened, because DFG counting is embarrassingly data-parallel.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .compat import shard_map

__all__ = [
    "distributed_dfg",
    "place_sharded",
    "shard_pairs",
    "local_dfg_fn",
    "merge_shard_psis",
    "merge_shard_counts",
]


def _n_devices(mesh: Mesh) -> int:
    return int(np.prod(list(mesh.shape.values())))


def local_dfg_fn(num_activities: int, backend: str = "onehot", chunk: int = 4096):
    """Per-shard DFG counting function (runs inside shard_map)."""

    def fn(src, dst, valid):
        if backend == "pallas":
            from repro.kernels.dfg_count import ops as _ops

            return _ops.dfg_count(
                src, dst, valid, num_activities=num_activities
            ).astype(jnp.float32)
        # one-hot MXU formulation, chunked (see core.dfg.dfg_onehot)
        n = src.shape[0]
        c = min(chunk, n)
        pad = (-n) % c
        if pad:
            src = jnp.pad(src, (0, pad))
            dst = jnp.pad(dst, (0, pad))
            valid = jnp.pad(valid, (0, pad))
        k = (n + pad) // c
        srcs = src.reshape(k, c)
        dsts = dst.reshape(k, c)
        valids = valid.reshape(k, c)

        def body(acc, xs):
            s, d, v = xs
            oh_s = jax.nn.one_hot(s, num_activities, dtype=jnp.float32)
            oh_s = oh_s * v.astype(jnp.float32)[:, None]
            oh_d = jax.nn.one_hot(d, num_activities, dtype=jnp.float32)
            return acc + jnp.dot(oh_s.T, oh_d, preferred_element_type=jnp.float32), None

        init = jnp.zeros((num_activities, num_activities), jnp.float32)
        acc, _ = jax.lax.scan(body, init, (srcs, dsts, valids))
        return acc

    return fn


def shard_pairs(
    src: np.ndarray,
    dst: np.ndarray,
    valid: np.ndarray,
    n_shards: int,
) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Pad pair columns to a multiple of ``n_shards`` (padding marked
    invalid) so they shard evenly across devices."""
    n = src.shape[0]
    padded = max(n_shards, math.ceil(n / n_shards) * n_shards)
    pad = padded - n
    return (
        np.pad(src, (0, pad)).astype(np.int32),
        np.pad(dst, (0, pad)).astype(np.int32),
        np.pad(valid, (0, pad)).astype(bool),
    )


def place_sharded(mesh: Mesh, *arrays):
    """Lay each array's leading axis out over every device of ``mesh``
    (all axes flattened) — the placement both mesh paths use."""
    sharding = NamedSharding(mesh, P(tuple(mesh.axis_names)))
    return [jax.device_put(x, sharding) for x in arrays]


def distributed_dfg(
    mesh: Mesh,
    src: np.ndarray,
    dst: np.ndarray,
    valid: np.ndarray,
    num_activities: int,
    *,
    backend: str = "onehot",
    hierarchical: bool = True,
) -> np.ndarray:
    """Compute the global DFG with events sharded over every mesh axis.

    ``hierarchical=True`` reduces over the fastest (intra-pod) axes first and
    the ``pod`` axis last — on real hardware the last hop crosses DCN, so the
    matrix is reduced intra-pod before it ever touches the slow link (the
    multi-pod collective-schedule optimization).
    """
    axes = tuple(mesh.axis_names)
    all_axes_spec = P(axes)  # events sharded over the flattened device axis
    n_dev = _n_devices(mesh)
    src_s, dst_s, valid_s = shard_pairs(src, dst, valid, n_dev)

    local = local_dfg_fn(num_activities, backend=backend)

    def shard_fn(s, d, v):
        psi_local = local(s, d, v)
        if hierarchical:
            # intra-pod first (data, model, ...), cross-pod ("pod") last
            for ax in reversed(axes):
                psi_local = jax.lax.psum(psi_local, axis_name=ax)
        else:
            psi_local = jax.lax.psum(psi_local, axis_name=axes)
        return psi_local

    mapped = shard_map(
        shard_fn,
        mesh=mesh,
        in_specs=(all_axes_spec, all_axes_spec, all_axes_spec),
        out_specs=P(),  # fully replicated aggregate — the only thing leaving
    )
    psi = jax.jit(mapped)(*place_sharded(mesh, src_s, dst_s, valid_s))
    return np.asarray(psi, dtype=np.int64)


# ---------------------------------------------------------------------------
# Sharded-graph merge (case-partitioned shards → global sinks)
# ---------------------------------------------------------------------------


def _align_dense(mat: np.ndarray, ids, num_activities: int) -> np.ndarray:
    """Embed a shard-local (a, a) matrix into the (A, A) union frame.
    ``ids[i]`` is the union id of shard-local activity ``i`` (unique), so
    plain assignment places every cell — no accumulation inside one shard."""
    out = np.zeros((num_activities, num_activities), dtype=np.int64)
    idx = np.asarray(ids, dtype=np.int64)
    out[np.ix_(idx, idx)] = mat
    return out


def merge_shard_psis(
    psis,
    id_maps,
    num_activities: int,
    *,
    mesh: Optional[Mesh] = None,
) -> np.ndarray:
    """Merge per-shard (a_k, a_k) Ψ matrices into the global (A, A) Ψ.

    Cases never span shards under the ``case % K`` partition, so every
    directly-follows pair is counted by exactly one shard and the merge is a
    *pure sum* on the aligned union vocabulary — no reconciliation, the same
    psum contract as :func:`distributed_dfg`.  With a ``mesh`` the aligned
    stack is sharded over the flattened device axis and reduced with an
    on-device ``psum`` (int32 lanes — exact, unlike a float accumulate);
    host-side the sum is a K·A² numpy reduction.
    """
    aligned = [
        _align_dense(psi, ids, num_activities)
        for psi, ids in zip(psis, id_maps)
    ]
    if not aligned:
        return np.zeros((num_activities, num_activities), dtype=np.int64)
    if mesh is None or _n_devices(mesh) <= 1:
        return np.sum(aligned, axis=0, dtype=np.int64)

    axes = tuple(mesh.axis_names)
    n_dev = _n_devices(mesh)
    stack = np.stack(aligned).astype(np.int32)
    pad = (-stack.shape[0]) % n_dev
    if pad:
        stack = np.concatenate(
            [stack, np.zeros((pad, *stack.shape[1:]), dtype=np.int32)]
        )

    def shard_fn(x):
        acc = jnp.sum(x, axis=0)
        for ax in reversed(axes):
            acc = jax.lax.psum(acc, axis_name=ax)
        return acc

    mapped = shard_map(
        shard_fn, mesh=mesh, in_specs=(P(axes),), out_specs=P(),
    )
    (arg,) = place_sharded(mesh, stack)
    return np.asarray(jax.jit(mapped)(arg), dtype=np.int64)


def merge_shard_counts(counts, id_maps, num_activities: int) -> np.ndarray:
    """Merge per-shard activity-count vectors (histogram / process-map node
    weights) onto the union vocabulary.  Each event lives on exactly one
    shard, so this too is a pure aligned sum."""
    out = np.zeros(num_activities, dtype=np.int64)
    for vec, ids in zip(counts, id_maps):
        idx = np.asarray(ids, dtype=np.int64)
        out[idx] += np.asarray(vec, dtype=np.int64)
    return out


def lower_distributed_dfg(
    mesh: Mesh,
    num_pairs: int,
    num_activities: int,
    *,
    backend: str = "onehot",
):
    """Lower (no execution) the distributed DFG for dry-run/roofline use."""
    axes = tuple(mesh.axis_names)
    spec = P(axes)
    sharding = NamedSharding(mesh, spec)
    local = local_dfg_fn(num_activities, backend=backend)

    def shard_fn(s, d, v):
        psi_local = local(s, d, v)
        for ax in reversed(axes):
            psi_local = jax.lax.psum(psi_local, axis_name=ax)
        return psi_local

    mapped = shard_map(
        shard_fn, mesh=mesh, in_specs=(spec, spec, spec), out_specs=P(),
    )
    n_dev = _n_devices(mesh)
    padded = max(n_dev, math.ceil(num_pairs / n_dev) * n_dev)
    mk = lambda dt: jax.ShapeDtypeStruct((padded,), dt, sharding=sharding)
    return jax.jit(mapped).lower(
        mk(jnp.int32), mk(jnp.int32), mk(jnp.bool_)
    )

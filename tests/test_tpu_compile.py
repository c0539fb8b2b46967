"""The three Pallas kernels compile for a described TPU v5e.

Nothing runs: each kernel is lowered and compiled by the TPU compiler
against a ``v5e:2x2`` topology description, at the paper deployment's
widths (7.2M pairs, 600 activities) and at a small vocabulary (26); the DFG
kernels also at a vocabulary that splits their output into several tiles
(3000).  This catches what interpret mode cannot: layouts Mosaic refuses,
primitives it cannot lower, tiles that overrun VMEM.

The topology is described only inside the module-scoped fixture, never at
import time: only one process may load the TPU library at a time.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.graphpm import PAPER_EVAL
from repro.kernels.align_dp import kernel as align_kernel
from repro.kernels.align_dp.ops import _pad_lane
from repro.kernels.align_dp.ops import pick_blocks as align_blocks
from repro.kernels.dfg_count import ops as dfg_ops
from repro.kernels.segment_count import ops as seg_ops

PAIRS = PAPER_EVAL.num_events
VOCABULARIES = (26, PAPER_EVAL.num_activities)
#: the first vocabulary past one resident DFG tile is 1281
MULTI_TILE = 3000
#: the paper log's 1-day window: ~4.4k variants, traces up to 114 events
VARIANTS, TRACE_LEN = 4370, 114


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def topo(no_compile_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *shapes):
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the Mosaic kernel
    return compiled


def _kernel(name):
    """The jitted kernel entry point under its timing wrapper."""
    return getattr(dfg_ops if name.startswith("dfg") else seg_ops,
                   name).__wrapped_kernel__


def _compile_dfg(kernel, a, spec):
    fn = _kernel(kernel)
    ids = spec((PAIRS,), jnp.int32)
    mask = spec((PAIRS,), jnp.bool_)
    if kernel == "dfg_count":
        return _compile(
            lambda s, d, v: fn(s, d, v, num_activities=a, interpret=False),
            ids, ids, mask,
        )
    # the window test on int32 time ranks, the bounds in SMEM
    return _compile(
        lambda s, d, v, r0, r1, w: fn(
            s, d, v, r0, r1, w, num_activities=a, interpret=False
        ),
        ids, ids, mask, ids, ids, spec((2,), jnp.int32),
    )


@pytest.mark.parametrize("a", VOCABULARIES)
@pytest.mark.parametrize(
    "kernel", ["dfg_count", "dfg_count_diced", "segment_count", "align_dp"]
)
def test_kernel_compiles_for_v5e(kernel, a, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ids = spec((PAIRS,), jnp.int32)
    mask = spec((PAIRS,), jnp.bool_)
    if kernel.startswith("dfg"):
        _compile_dfg(kernel, a, spec)
    elif kernel == "segment_count":
        fn = _kernel(kernel)
        _compile(
            lambda s, v: fn(s, v, num_segments=a, interpret=False),
            ids, mask,
        )
    else:
        bv = align_blocks(VARIANTS)
        vp = -(-VARIANTS // bv) * bv
        lp, sp = _pad_lane(TRACE_LEN), _pad_lane(a + 1)  # + virtual START
        _compile(
            lambda q, n, m, d0, e: align_kernel.align_dp_pallas(
                q, n, m, d0, e, block_v=bv, interpret=False
            ),
            spec((lp, vp), jnp.int32), spec((1, vp), jnp.int32),
            spec((sp, sp), jnp.float32), spec((sp, 1), jnp.float32),
            spec((sp, 1), jnp.float32),
        )


@pytest.mark.parametrize("kernel", ["dfg_count", "dfg_count_diced"])
def test_dfg_multi_tile_compiles_for_v5e(kernel, one_chip):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    block_e, block_s, block_d = dfg_ops.pick_blocks(MULTI_TILE)
    assert -(-MULTI_TILE // block_s) * -(-MULTI_TILE // block_d) > 1
    _compile_dfg(kernel, MULTI_TILE, spec)

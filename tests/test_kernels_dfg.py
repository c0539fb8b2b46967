"""Pallas dfg_count kernel vs pure-jnp oracle: shape/dtype sweeps + property
tests, all in interpret mode on CPU (per the kernel-validation protocol)."""

import numpy as np
import jax.numpy as jnp
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels.dfg_count import (
    dfg_count,
    dfg_count_diced,
    dfg_count_diced_ref,
    dfg_count_ref,
    pick_blocks,
)
from repro.kernels.dfg_count.kernel import SUBLANE
from repro.kernels.dfg_count.ops import ONE_HOTS_MAX_BYTES, working_set


def _random_pairs(rng, n, a):
    src = rng.integers(0, a, size=n).astype(np.int32)
    dst = rng.integers(0, a, size=n).astype(np.int32)
    valid = rng.random(n) < 0.8
    return src, dst, valid


# -- shape sweep -------------------------------------------------------------
@pytest.mark.parametrize("n_pairs", [0, 1, 7, 128, 1000, 5000])
@pytest.mark.parametrize("num_acts", [1, 3, 26, 130, 257])
def test_kernel_matches_ref_shapes(n_pairs, num_acts):
    rng = np.random.default_rng(n_pairs * 1000 + num_acts)
    src, dst, valid = _random_pairs(rng, n_pairs, num_acts)
    got = dfg_count(src, dst, valid, num_activities=num_acts, interpret=True)
    want = dfg_count_ref(src, dst, valid, num_activities=num_acts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- vocabularies on the padding edges -----------------------------------------
@pytest.mark.parametrize("num_acts", [127, 128, 129, 600, 641, 3000])
def test_kernel_matches_ref_padding_edges(num_acts):
    # 3000 overruns one tile's VMEM budget: the output is split in tiles
    rng = np.random.default_rng(num_acts)
    src, dst, valid = _random_pairs(rng, 1500, num_acts)
    src[:2], dst[:2], valid[:2] = num_acts - 1, num_acts - 1, True  # last id
    got = dfg_count(src, dst, valid, num_activities=num_acts, interpret=True)
    want = dfg_count_ref(src, dst, valid, num_activities=num_acts)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- block-size sweep --------------------------------------------------------
@pytest.mark.parametrize("block_e", [512, 1024, 2048])
@pytest.mark.parametrize(
    "block_s, block_d",
    [(224, 256), (96, 128), (32, 256)],  # 1, 3 × 2 and 7 × 1 output tiles
)
def test_kernel_block_sizes(block_e, block_s, block_d):
    rng = np.random.default_rng(42)
    src, dst, valid = _random_pairs(rng, 3000, 200)
    got = dfg_count(
        src, dst, valid, num_activities=200, block_e=block_e,
        block_s=block_s, block_d=block_d, interpret=True,
    )
    want = dfg_count_ref(src, dst, valid, num_activities=200)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- input dtype tolerance -----------------------------------------------------
@pytest.mark.parametrize("id_dtype", [np.int32, np.int64, np.int16])
@pytest.mark.parametrize("valid_dtype", [bool, np.int32, np.float32])
def test_kernel_dtypes(id_dtype, valid_dtype):
    rng = np.random.default_rng(7)
    src = rng.integers(0, 50, size=900).astype(id_dtype)
    dst = rng.integers(0, 50, size=900).astype(id_dtype)
    valid = (rng.random(900) < 0.5).astype(valid_dtype)
    got = dfg_count(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
        num_activities=50, interpret=True,
    )
    want = dfg_count_ref(
        jnp.asarray(src).astype(jnp.int32),
        jnp.asarray(dst).astype(jnp.int32),
        jnp.asarray(valid).astype(jnp.bool_),
        num_activities=50,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- fused dicing vs oracle ----------------------------------------------------
# the kernel takes the pairs' int32 time ranks and the window as rank bounds
@pytest.mark.parametrize(
    "window", [(0, 1000), (200, 700), (900, 950), (2000, 3000)]
)
def test_diced_kernel_matches_ref(window):
    rng = np.random.default_rng(11)
    n, a = 2500, 40
    src, dst, valid = _random_pairs(rng, n, a)
    rank_src = rng.integers(0, 1000, size=n).astype(np.int32)
    rank_dst = rng.integers(0, 1000, size=n).astype(np.int32)
    win = np.asarray(window, dtype=np.int32)
    got = dfg_count_diced(
        src, dst, valid, rank_src, rank_dst, win,
        num_activities=a, interpret=True,
    )
    want = dfg_count_diced_ref(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
        jnp.asarray(rank_src), jnp.asarray(rank_dst), jnp.asarray(win),
        num_activities=a,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("num_acts, block_s, block_d", [
    (40, None, None),  # one tile
    (200, 96, 128),    # 3 × 2 tiles
])
def test_diced_window_edges_on_event_blocks(num_acts, block_s, block_d):
    # sorted ranks, window [rank[512], rank[1536]): both edges fall on the
    # first pair of an event block, so a block boundary off by one shows
    rng = np.random.default_rng(5)
    n, be = 2500, 512
    src, dst, valid = _random_pairs(rng, n, num_acts)
    rank_src = 2 * np.arange(n, dtype=np.int32)
    rank_dst = rank_src + 1
    win = np.asarray([rank_src[be], rank_src[3 * be]], dtype=np.int32)
    got = dfg_count_diced(
        src, dst, valid, rank_src, rank_dst, win, num_activities=num_acts,
        block_e=be, block_s=block_s, block_d=block_d, interpret=True,
    )
    want = dfg_count_diced_ref(
        jnp.asarray(src), jnp.asarray(dst), jnp.asarray(valid),
        jnp.asarray(rank_src), jnp.asarray(rank_dst), jnp.asarray(win),
        num_activities=num_acts,
    )
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    assert np.asarray(got).sum() == valid[be:3 * be].sum()


def test_diced_full_window_equals_undediced():
    rng = np.random.default_rng(3)
    n, a = 1500, 30
    src, dst, valid = _random_pairs(rng, n, a)
    rank = rng.integers(0, n, size=n).astype(np.int32)
    win = np.asarray([0, n], dtype=np.int32)
    a1 = dfg_count_diced(
        src, dst, valid, rank, rank, win, num_activities=a, interpret=True
    )
    a2 = dfg_count(src, dst, valid, num_activities=a, interpret=True)
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))


def test_diced_refuses_float_times():
    rng = np.random.default_rng(4)
    src, dst, valid = _random_pairs(rng, 64, 8)
    ts = rng.random(64).astype(np.float32)
    with pytest.raises(TypeError, match="integer time ranks"):
        dfg_count_diced(
            src, dst, valid, ts, ts, np.asarray([0.0, 1.0], np.float32),
            num_activities=8, interpret=True,
        )


# -- properties ---------------------------------------------------------------
@settings(max_examples=50, deadline=None)
@given(
    n=st.integers(min_value=0, max_value=600),
    a=st.integers(min_value=1, max_value=70),
    seed=st.integers(min_value=0, max_value=10**6),
)
def test_property_kernel_equals_ref(n, a, seed):
    rng = np.random.default_rng(seed)
    src, dst, valid = _random_pairs(rng, n, a)
    got = dfg_count(src, dst, valid, num_activities=a, interpret=True)
    want = dfg_count_ref(src, dst, valid, num_activities=a)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@settings(max_examples=30, deadline=None)
@given(
    n=st.integers(min_value=1, max_value=400),
    a=st.integers(min_value=1, max_value=40),
    seed=st.integers(min_value=0, max_value=10**6),
)
def test_property_total_equals_valid_count(n, a, seed):
    rng = np.random.default_rng(seed)
    src, dst, valid = _random_pairs(rng, n, a)
    got = np.asarray(dfg_count(src, dst, valid, num_activities=a, interpret=True))
    assert got.sum() == valid.sum()
    assert (got >= 0).all()


def test_pick_blocks_alignment():
    for a in [1, 26, 127, 128, 129, 500, 600, 641, 1280, 1281, 3000, 5000]:
        be, bs, bd = pick_blocks(a)
        assert bd % 128 == 0  # lanes: dst
        assert bs % SUBLANE == 0 and SUBLANE == 32  # int8 sublanes: src
        assert be % 512 == 0 and 512 <= be <= 4096
        # resident int32 tile + two int8 one-hots under the budget
        assert working_set(be, bs, bd) == 4 * bs * bd + be * (bs + bd)
        assert working_set(be, bs, bd) <= 8 << 20
        assert be * (bs + bd) <= ONE_HOTS_MAX_BYTES or be == 512
        n_s, n_d = -(-a // bs), -(-a // bd)
        # each tile padded only to the alignment, never to a power of two
        assert bs - 32 < -(-a // n_s) <= bs
        assert bd - 128 < -(-a // n_d) <= bd
        if a <= 1280:  # one resident tile: every event block read once
            assert (n_s, n_d) == (1, 1)
        else:
            assert n_s * n_d > 1
    assert pick_blocks(600) == (2048, 608, 640)

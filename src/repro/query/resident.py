"""A repository's directly-follows pairs, resident on the device.

Fresh windows over one state of a log differ only in their bounds, so the
columns a device count reads are put on the device once per repository:
``src`` / ``dst`` (int32 activity ids), ``valid`` (bool, both events in one
trace) and the two int32 time-rank slices, 17 bytes a pair.  They are
padded to the ``dfg_count`` kernel's event block, with ``valid`` false in
the padding, so the kernel pads nothing per call.  A query then ships only
its window's 8-byte ``[lo, hi]`` rank bounds.

The set is kept with the repository
(:meth:`~repro.core.repository.EventRepository.set_device_pairs`) and is
valid only while the activity, trace and time columns are the ones it was
built from; an append makes a new repository, and so a new set.
"""

from __future__ import annotations

from typing import Tuple

import jax
import numpy as np

from repro.core.repository import EventRepository, TimeRanks

__all__ = ["ResidentPairs", "build_resident_pairs"]

#: bytes a pair: two int32 ids, one bool, two int32 time ranks
PAIR_BYTES = 17


class ResidentPairs:
    """``cols`` = (src, dst, valid, rank_src, rank_dst) on the device, each
    of ``padded`` pairs; ``ranks`` turns a window into its rank bounds."""

    __slots__ = ("cols", "ranks", "padded", "__weakref__")

    def __init__(self, cols, ranks: TimeRanks, padded: int):
        self.cols: Tuple[jax.Array, ...] = cols
        self.ranks = ranks
        self.padded = padded

    @property
    def nbytes(self) -> int:
        return PAIR_BYTES * self.padded


def build_resident_pairs(
    repo: EventRepository, ranks: TimeRanks, block_e: int
) -> ResidentPairs:
    """Put ``repo``'s pairs and their time ranks on the device, padded to a
    whole number of ``block_e`` event blocks (at least one)."""
    n = max(repo.num_events - 1, 0)
    padded = max(block_e, -(-n // block_e) * block_e)
    src, dst, rank_src, rank_dst = (np.zeros(padded, np.int32) for _ in range(4))
    valid = np.zeros(padded, bool)
    if n:
        act, trace, rank = repo.event_activity, repo.event_trace, ranks.rank
        src[:n], dst[:n] = act[:-1], act[1:]
        np.equal(trace[:-1], trace[1:], out=valid[:n])
        rank_src[:n], rank_dst[:n] = rank[:-1], rank[1:]
    cols = jax.block_until_ready(
        jax.device_put((src, dst, valid, rank_src, rank_dst))
    )
    return ResidentPairs(tuple(cols), ranks, padded)

"""Peaks by device kind, and the least time a kernel's work can take.

The work counts depend only on what a query must read and write, never on
how a kernel computes it, so a later kernel that does the same query
another way is held to the same count.
"""

from __future__ import annotations

import json
import os
from typing import Dict

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")


class UnknownDevice(KeyError):
    pass


def peaks_for(device_kind: str, path: str = PEAKS_FILE) -> Dict[str, float]:
    """The row of ``peaks.json`` for ``device_kind``; a device that is not
    in the table is an error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise UnknownDevice(
            f"no peaks for device kind {device_kind!r}; known: {sorted(table)}"
        )
    return {k: float(v) for k, v in table[device_kind].items()}


def dfg_window_bytes(num_pairs: int, num_activities: int) -> int:
    """Bytes a windowed DFG query must move when it scans the whole log:
    every directly-follows pair at 4 bytes each for source id, target id
    and the two timestamps, plus the int32 ``A x A`` result.  No operation
    is counted.  This assumes a full scan per window query; an index that
    skips pairs outside the window changes the algorithm and this count."""
    return 16 * int(num_pairs) + 4 * int(num_activities) ** 2


def least_time_s(num_bytes: float, peaks: Dict[str, float]) -> float:
    return float(num_bytes) / peaks["hbm_bytes_per_s"]

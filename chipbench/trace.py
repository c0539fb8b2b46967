"""Reduction of a JAX profiler trace to the benchmark's device numbers.

The profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``.  A
device plane is a chip's own, ``/device:TPU:<n>``.  Its operations are the events of its ``XLA Ops`` line (the
``XLA Modules`` and ``Steps`` lines repeat the same time at coarser
grain and are not counted).  From those:

* busy time: the union of the operations' intervals, per device, then
  averaged over the devices;
* kernel time: the union of the intervals of the operations whose HLO
  text (``%name = type op(operands), ...``) matches a regular expression;
* idle gaps: the stretches of the traced span in which no operation ran,
  each labelled by the benchmark's request annotations (``chipbench.``
  host events) that overlap it.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SKIP_LINES = ("XLA Modules", "Steps", "XLA TraceMe", "Source code")
ANNOTATION_PREFIX = "chipbench."


@dataclasses.dataclass
class Event:
    name: str  # the HLO text of the operation
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Summary:
    busy_s: float  # union of device-op time, averaged over devices
    span_s: float  # first to last timestamp seen on any plane
    devices: int
    ops: List[List[Event]]  # device ops, per device plane
    gaps: List[Tuple[str, float]]  # (label, seconds), longest first

    def kernel_seconds(self, pattern: str) -> float:
        """Device time in which an operation matching ``pattern`` ran: per
        device the union of their intervals, so that calls which the trace
        shows overlapping (two host threads feeding one chip) count once;
        summed over the devices."""
        rx = re.compile(pattern)
        return sum(
            union_ns((e.start_ns, e.end_ns) for e in evs if rx.search(e.name))[0]
            for evs in self.ops
        ) / 1e9

    def top_ops(self, n: int = 10) -> List[Tuple[str, float]]:
        """Device seconds by operation name (the HLO name before ``=``,
        without its numeric suffix), most first."""
        tot: Dict[str, float] = {}
        for e in (e for evs in self.ops for e in evs):
            key = op_name(e.name)
            tot[key] = tot.get(key, 0.0) + e.dur_ns / 1e9
        return sorted(tot.items(), key=lambda kv: -kv[1])[:n]


def op_name(hlo: str) -> str:
    return re.sub(r"\.\d+$", "", hlo.split(" = ", 1)[0].strip())


def union_ns(intervals: Iterable[Tuple[float, float]]) -> Tuple[float, List[Tuple[float, float]]]:
    """Total covered length of ``(start, end)`` intervals, and the merged
    intervals in order."""
    merged: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1] = (merged[-1][0], e)
        else:
            merged.append((s, e))
    return sum(e - s for s, e in merged), merged


DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def _is_device(name: str) -> bool:
    """A chip's own plane, ``/device:TPU:0``; not the host, and not the
    ``/device:CUSTOM:...`` planes the profiler adds beside the chips."""
    return DEVICE_PLANE.match(name) is not None


def summarize(
    planes: List[Tuple[str, List[Tuple[str, List[Event]]]]],
    window: Optional[Tuple[float, float]] = None,
    max_gaps: int = 10,
) -> Summary:
    """``planes``: ``[(plane name, [(line name, events)])]``.  ``window``
    clips every device interval to ``(start_ns, end_ns)``; without it the
    span is from the first to the last event of any plane."""
    all_ev = [e for _, lines in planes for _, evs in lines for e in evs]
    if window is None:
        window = (
            min((e.start_ns for e in all_ev), default=0.0),
            max((e.end_ns for e in all_ev), default=0.0),
        )
    w0, w1 = window
    span = max(w1 - w0, 0.0)
    ops: List[List[Event]] = []
    busy_total = 0.0
    merged_all: List[List[Tuple[float, float]]] = []
    n_dev = 0
    for pname, lines in planes:
        if not _is_device(pname):
            continue
        named = {ln: evs for ln, evs in lines}
        if OPS_LINE in named:
            evs = named[OPS_LINE]
        else:
            evs = [e for ln, es in lines if ln not in SKIP_LINES for e in es]
        n_dev += 1
        ops.append(evs)
        busy, merged = union_ns(
            (max(e.start_ns, w0), min(e.end_ns, w1)) for e in evs
        )
        busy_total += busy
        merged_all.append(merged)
    busy_s = busy_total / n_dev / 1e9 if n_dev else 0.0

    # idle gaps of the first device, labelled by overlapping annotations
    gaps: List[Tuple[str, float]] = []
    if merged_all:
        notes = [
            e for pname, lines in planes if not _is_device(pname)
            for _, evs in lines for e in evs
            if e.name.startswith(ANNOTATION_PREFIX)
        ]
        edges = [w0] + [x for iv in merged_all[0] for x in iv] + [w1]
        holes = [
            (edges[i], edges[i + 1]) for i in range(0, len(edges) - 1, 2)
            if edges[i + 1] > edges[i]
        ]
        holes.sort(key=lambda h: -(h[1] - h[0]))
        for s, e in holes[:max_gaps]:
            over: Dict[str, float] = {}
            for n in notes:
                ov = min(e, n.end_ns) - max(s, n.start_ns)
                if ov > 0:
                    key = n.name[len(ANNOTATION_PREFIX):]
                    over[key] = over.get(key, 0.0) + ov
            label = (
                "in flight: " + ",".join(sorted(over, key=lambda k: -over[k])[:3])
                if over else "no request in flight"
            )
            gaps.append((label, (e - s) / 1e9))
    return Summary(busy_s=busy_s, span_s=span / 1e9, devices=n_dev, ops=ops, gaps=gaps)


def load(log_dir: str) -> List[Tuple[str, List[Tuple[str, List[Event]]]]]:
    """Planes of the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(
        glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    out = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            evs = [
                Event(ev.name, float(ev.start_ns), float(ev.duration_ns))
                for ev in line.events
            ]
            lines.append((line.name, evs))
        out.append((plane.name, lines))
    return out

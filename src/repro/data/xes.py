"""Minimal XES / CSV event-log import-export.

XES is the IEEE standard the paper's tooling (ProM, pm4py) consumes; the
subset handled here is the one the paper's data model needs:
``concept:name`` on traces (case id) and events (activity), and
``time:timestamp``: ISO-8601 dates as the standard and real exports (BPI
Challenge logs) write them, read as float64 epoch seconds, or the float
this writer emits.  CSV is the pragmatic interchange format
(case, activity, timestamp columns).
"""

from __future__ import annotations

import csv
import xml.etree.ElementTree as ET
from datetime import datetime, timezone
from typing import Iterable, Optional, TextIO, Tuple

from repro.core.repository import EventRepository

__all__ = ["write_csv", "read_csv", "write_xes", "read_xes"]


# ---------------------------------------------------------------------------
# CSV
# ---------------------------------------------------------------------------


def write_csv(repo: EventRepository, f: TextIO) -> None:
    w = csv.writer(f)
    w.writerow(["case", "activity", "timestamp"])
    for i in range(repo.num_events):
        w.writerow([
            repo.trace_names[int(repo.event_trace[i])],
            repo.activity_names[int(repo.event_activity[i])],
            repr(float(repo.event_time[i])),
        ])


def read_csv(f: TextIO) -> EventRepository:
    r = csv.reader(f)
    header = next(r)
    idx = {name: i for i, name in enumerate(header)}
    cases, acts, times = [], [], []
    for row in r:
        if not row:
            continue
        cases.append(row[idx["case"]])
        acts.append(row[idx["activity"]])
        times.append(float(row[idx["timestamp"]]))
    return EventRepository.from_event_table(cases, acts, times)


# ---------------------------------------------------------------------------
# XES
# ---------------------------------------------------------------------------


def write_xes(repo: EventRepository, f: TextIO) -> None:
    root = ET.Element("log", {"xes.version": "1.0"})
    for t in range(repo.num_traces):
        tr = ET.SubElement(root, "trace")
        ET.SubElement(
            tr, "string",
            {"key": "concept:name", "value": repo.trace_names[t]},
        )
        for i in range(repo.num_events):
            if int(repo.event_trace[i]) != t:
                continue
            ev = ET.SubElement(tr, "event")
            ET.SubElement(ev, "string", {
                "key": "concept:name",
                "value": repo.activity_names[int(repo.event_activity[i])],
            })
            ET.SubElement(ev, "float", {
                "key": "time:timestamp",
                "value": repr(float(repo.event_time[i])),
            })
    f.write(ET.tostring(root, encoding="unicode"))


def _timestamp(value: str) -> float:
    """A ``time:timestamp`` as float64 seconds: the float this module
    writes, or an ISO-8601 date (``2016-01-01T09:51:15.304+01:00``) as
    seconds since the Unix epoch, a date without an offset taken as UTC."""
    try:
        return float(value)
    except ValueError:
        pass
    dt = datetime.fromisoformat(value)
    if dt.tzinfo is None:
        dt = dt.replace(tzinfo=timezone.utc)
    return dt.timestamp()


def read_xes(f: TextIO) -> EventRepository:
    root = ET.parse(f).getroot()
    cases, acts, times = [], [], []
    seq = 0.0
    for tr in root.iter("trace"):
        case = "<unnamed>"
        for attr in tr:
            if attr.tag == "string" and attr.get("key") == "concept:name":
                case = attr.get("value")
        for ev in tr.iter("event"):
            act: Optional[str] = None
            ts: Optional[float] = None
            for attr in ev:
                if attr.get("key") == "concept:name":
                    act = attr.get("value")
                if attr.get("key") == "time:timestamp":
                    ts = _timestamp(attr.get("value"))
            if act is None:
                continue
            seq += 1.0
            cases.append(case)
            acts.append(act)
            times.append(ts if ts is not None else seq)
    return EventRepository.from_event_table(cases, acts, times)

"""The per-layer readers of the program's scan phases, payload and
collector pauses, on hand-made runs; each finds nothing in a run of a
program without those series."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

FW = "bpi16_clicks.fresh_windows"
DB = "bpi16_clicks.dashboards"
PHASE = "engine_scan_phase_seconds{phase=%s}"


def _run(workload, counters, seconds=50.0):
    return harness.Run(
        cell=harness.load_cell(workload), seconds=seconds, setup_s=20.0,
        requests=[], counters=counters, compiles=0, trace=None,
        device_kind="TPU v5 lite", log_events=7_200_018, log_traces=600_112,
    )


SCAN = {
    PHASE % "prepare" + ".count": 600.0, PHASE % "prepare" + ".sum": 12.0,
    PHASE % "h2d" + ".count": 600.0, PHASE % "h2d" + ".sum": 9.0,
    PHASE % "device" + ".count": 600.0, PHASE % "device" + ".sum": 90.0,
    "engine_h2d_bytes_total": 600.0 * (17 * 7_200_017 + 8),
    "serve_payload_seconds{sink=dfg}.count": 500.0,
    "serve_payload_seconds{sink=dfg}.sum": 1.5,
    "serve_payload_seconds{sink=histogram}.count": 100.0,
    "serve_payload_seconds{sink=histogram}.sum": 0.3,
    "process_gc_pause_seconds{generation=0}.count": 3000.0,
    "process_gc_pause_seconds{generation=0}.sum": 4.5,
    "process_gc_pause_seconds{generation=2}.count": 2.0,
    "process_gc_pause_seconds{generation=2}.sum": 0.5,
    # neighbours the readers must not pick up
    "kernel_seconds{kernel=dfg_count_diced}.sum": 48.0,
    "transport_queue_wait_seconds{lane=cold}.sum": 7.0,
}


@pytest.mark.parametrize("name, workload, want", [
    ("executor.prepare_ms.qps", FW, 20.0),
    ("executor.h2d_ms.qps", FW, 15.0),
    ("executor.h2d_mb.qps", FW, (17 * 7_200_017 + 8) / 1e6),
    ("executor.device_ms.qps", FW, 150.0),
    ("serve.payload_ms.qps", FW, 3.0),
    ("serve.payload_ms.lat", DB, 3.0),
    ("process.gc_ms_per_s.lat", DB, 100.0),
])
def test_reader_reads_its_series(name, workload, want):
    assert harness.load_reader(name)(_run(workload, dict(SCAN))) == pytest.approx(want)


@pytest.mark.parametrize("name, workload", [
    ("executor.prepare_ms.qps", FW), ("executor.h2d_ms.qps", FW),
    ("executor.h2d_mb.qps", FW), ("executor.device_ms.qps", FW),
    ("serve.payload_ms.qps", FW), ("serve.payload_ms.lat", DB),
    ("process.gc_ms_per_s.lat", DB),
])
def test_reader_finds_nothing_in_a_program_without_the_series(name, workload):
    # what a program that predates these series leaves in a run
    older = {k: v for k, v in SCAN.items()
             if k.startswith(("kernel_seconds", "transport_queue_wait"))}
    assert harness.load_reader(name)(_run(workload, older)) is None


def test_gc_share_reads_a_window_without_a_collection_as_zero():
    quiet = {"process_gc_pause_seconds{generation=0}.count": 0.0,
             "process_gc_pause_seconds{generation=0}.sum": 0.0}
    assert harness.load_reader("process.gc_ms_per_s.lat")(_run(DB, quiet)) == 0.0


def test_each_new_metric_is_listed_with_its_cell():
    names = {
        FW: {"executor.prepare_ms.qps", "executor.h2d_ms.qps", "executor.h2d_mb.qps",
             "executor.device_ms.qps", "serve.payload_ms.qps"},
        DB: {"serve.payload_ms.lat", "process.gc_ms_per_s.lat"},
    }
    for workload, want in names.items():
        listed = {m["name"] for m in harness.load_cell(workload).metric_entries(True)}
        assert want <= listed

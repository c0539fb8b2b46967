"""The reader of the share of device counts served from resident pair
columns, on hand-made runs: 100 where every count read them, nothing in a
run of a program without the counter."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

NAME = "executor.resident_share.qps"
DEVICE = "engine_scan_phase_seconds{phase=device}.count"
CELLS = ["bpi16_clicks.fresh_windows", "bpi17_loans.fresh_windows"]


def _run(workload, counters):
    return harness.Run(
        cell=harness.load_cell(workload), seconds=50.0, setup_s=20.0,
        requests=[], counters=counters, compiles=0, trace=None,
        device_kind="TPU v5 lite", log_events=1_202_267, log_traces=31_509,
    )


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("resident, want", [(2500.0, 100.0), (500.0, 20.0), (0.0, 0.0)])
def test_resident_share_reads_the_counter(workload, resident, want):
    run = _run(workload, {
        DEVICE: 2500.0, "engine_scan_phase_seconds{phase=device}.sum": 40.0,
        "engine_scan_resident_total": resident,
        "engine_h2d_bytes_total": 2500.0 * 8,
    })
    assert harness.load_reader(NAME)(run) == pytest.approx(want)


@pytest.mark.parametrize("counters", [
    # a program that predates the counter
    {DEVICE: 2500.0, "engine_h2d_bytes_total": 2500.0 * 20_439_000},
    # no device count in the window
    {DEVICE: 0.0, "engine_scan_resident_total": 0.0},
    {},
])
def test_resident_share_finds_nothing_without_the_counter(counters):
    assert harness.load_reader(NAME)(_run(CELLS[1], counters)) is None


def test_resident_share_is_listed_with_the_clicks_cell():
    entries = {m["name"]: m for m in harness.load_cell(CELLS[0]).metric_entries(True)}
    entry = entries[NAME]
    assert (entry["source"], entry["layer"], entry["moves"], entry["unit"]) == (
        "program_counter", "host→device", "completed_qps", "%")

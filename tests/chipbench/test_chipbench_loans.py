"""The loan-log cell: its configuration, its metrics and the reader of the
time-rank builds, on hand-made runs."""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

LOANS = "bpi17_loans.fresh_windows"
CLICKS = "bpi16_clicks.fresh_windows"
BUILDS = "executor.time_rank_builds.qps"


def _run(workload, counters):
    return harness.Run(
        cell=harness.load_cell(workload), seconds=50.0, setup_s=8.0,
        requests=[], counters=counters, compiles=0, trace=None,
        device_kind="TPU v5 lite", log_events=1_202_280, log_traces=31_642,
    )


def test_the_loan_cell_loads_with_its_size_and_its_metrics():
    cell = harness.load_cell(LOANS)
    assert cell.entry["chips"] == 1 and cell.mix["loop"] == "closed"
    cfg = cell.config
    assert (cfg["num_events"], cfg["num_activities"], cfg["horizon_days"]) == (1202267, 26, 397)
    # the horizon passes 2**24 s, where a float32 window test is not exact
    assert cfg["horizon_days"] * 86400 > 2 ** 24
    end_to_end = {m["name"] for m in cell.metric_entries(False)}
    per_layer = {m["name"] for m in cell.metric_entries(True)}
    assert end_to_end == {"completed_qps", "setup_s"}
    assert {BUILDS, "executor.scan_ms.qps", "executor.prepare_ms.qps",
            "executor.h2d_ms.qps", "executor.h2d_mb.qps", "executor.device_ms.qps",
            "serve.payload_ms.qps", "device.idle_share.qps",
            "dfg_count_diced_roofline"} == per_layer
    for name in per_layer | end_to_end:
        assert callable(harness.load_reader(name))


@pytest.mark.parametrize("workload", [LOANS, CLICKS])
def test_rank_builds_read_the_counter_delta(workload):
    read = harness.load_reader(BUILDS)
    assert read(_run(workload, {"engine_time_rank_builds_total": 0.0})) == 0.0
    assert read(_run(workload, {"engine_time_rank_builds_total": 2.0})) == 2.0
    # a program without the counter leaves nothing to read
    assert read(_run(workload, {"engine_h2d_bytes_total": 1e9})) is None

"""The chip benchmark's peaks table and roofline work count."""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from chipbench import harness  # noqa: E402
from chipbench.roofline import (  # noqa: E402
    UnknownDevice,
    dfg_window_bytes,
    least_time_s,
    peaks_for,
)
from chipbench.trace import Event, summarize  # noqa: E402


def test_bytes_are_what_the_window_query_reads_and_writes():
    # 16 bytes per pair (two int32 ids, two float32 times) + int32 A x A
    assert dfg_window_bytes(6_600_000, 600) == 16 * 6_600_000 + 4 * 600 * 600
    assert dfg_window_bytes(0, 26) == 4 * 26 * 26


def test_v5e_peaks_and_least_time():
    p = peaks_for("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    assert least_time_s(819e9, p) == pytest.approx(1.0)


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5e", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(UnknownDevice):
        peaks_for(kind)


def test_roofline_reader_divides_least_time_by_kernel_device_time():
    kernel = "%dfg_count_diced.3 = f32[1024,1024]{1,0} custom-call(s32[1,8]{1,0} %a)"
    ops = [Event(kernel, 0, 80e6), Event(kernel, 100e6, 80e6),
           Event("%pad.1 = f32[8] pad(%dfg_count_diced.3)", 180e6, 5e6)]
    summary = summarize([("/device:TPU:0", [("XLA Ops", ops)])])
    cell = types.SimpleNamespace(config={"num_activities": 600})
    run = harness.Run(
        cell=cell, seconds=1.0, setup_s=1.0, requests=[],
        counters={"kernel_seconds{kernel=dfg_count_diced}.count": 2.0},
        compiles=0, trace=summary, device_kind="TPU v5 lite",
        log_events=7_200_000, log_traces=600_000,
    )
    got = harness.load_reader("dfg_count_diced_roofline")(run)
    least = 2 * dfg_window_bytes(6_600_000, 600) / 819e9
    assert got == pytest.approx(100.0 * least / 0.160)
    assert 0 < got < 100


def test_roofline_reader_reads_nothing_without_a_trace_or_calls():
    cell = types.SimpleNamespace(config={"num_activities": 600})
    base = dict(cell=cell, seconds=1.0, setup_s=1.0, requests=[], compiles=0,
                device_kind="TPU v5 lite", log_events=10, log_traces=1)
    read = harness.load_reader("dfg_count_diced_roofline")
    assert read(harness.Run(counters={}, trace=None, **base)) is None
    empty = summarize([("/device:TPU:0", [("XLA Ops", [])])])
    assert read(harness.Run(counters={}, trace=empty, **base)) is None

"""The chip benchmark's reduction of a profiler trace, on a synthetic trace."""

import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from chipbench.trace import Event, op_name, summarize, union_ns  # noqa: E402

MS = 1_000_000  # ns


def test_union_merges_overlaps_and_drops_empty():
    total, merged = union_ns([(0, 10), (5, 15), (20, 30), (30, 31), (40, 40), (25, 27)])
    assert merged == [(0, 15), (20, 31)]
    assert total == 26


def _planes(devices=1):
    kernel = "%dfg_count_diced.1 = f32[1024,1024]{1,0} custom-call(s32[1,8]{1,0} %a), custom_call_target=\"tpu_custom_call\""
    pad = "%pad.5 = f32[8]{0} pad(f32[7]{0} %dfg_count_diced.1, f32[] %c), padding=0_1"
    ops = [
        Event(kernel, 10 * MS, 30 * MS),
        Event(pad, 35 * MS, 5 * MS),  # names the kernel's output, is not it
        Event(kernel, 60 * MS, 20 * MS),
        Event(pad, 70 * MS, 20 * MS),  # overlaps the second kernel call
    ]
    modules = [Event("jit_dfg_count_diced(1)", 10 * MS, 80 * MS)]
    planes = [
        (f"/device:TPU:{d}", [("XLA Modules", modules), ("XLA Ops", ops)])
        for d in range(devices)
    ]
    host = [
        Event("chipbench.windows.dfg", 0, 50 * MS),
        Event("chipbench.tiles.histogram", 95 * MS, 4 * MS),
        Event("unrelated host span", 40 * MS, 20 * MS),
    ]
    planes.append(("/host:CPU", [("python3", host)]))
    return planes


def test_busy_idle_and_kernel_time():
    s = summarize(_planes(), window=(0, 100 * MS))
    # ops cover [10,40) and [60,90): 60 ms; the module line is not counted
    assert s.busy_s == pytest.approx(0.060)
    assert s.span_s == pytest.approx(0.100)
    assert s.devices == 1
    assert s.kernel_seconds(r"^%dfg_count_diced(\.\d+)? = ") == pytest.approx(0.050)
    assert s.top_ops(2) == [("%dfg_count_diced", pytest.approx(0.050)), ("%pad", pytest.approx(0.025))]


def test_idle_gaps_are_labelled_by_requests_in_flight():
    s = summarize(_planes(), window=(0, 100 * MS))
    # holes: [0,10) 10 ms, [40,60) 20 ms, [90,100) 10 ms, longest first
    assert [round(g, 6) for _, g in s.gaps] == [0.02, 0.01, 0.01]
    labels = dict((round(g, 6), l) for l, g in s.gaps)
    assert labels[0.02] == "in flight: windows.dfg"
    assert sorted(l for l, g in s.gaps if round(g, 6) == 0.01) == [
        "in flight: tiles.histogram", "in flight: windows.dfg"]


def test_kernel_calls_that_overlap_in_the_trace_count_once():
    kernel = "%dfg_count_diced = f32[8]{0} custom-call(s32[8]{0} %a)"
    ops = [Event(kernel, 0, 80 * MS), Event(kernel, 40 * MS, 80 * MS)]
    s = summarize([("/device:TPU:0", [("XLA Ops", ops)])])
    assert s.kernel_seconds(r"^%dfg_count_diced") == pytest.approx(0.120)
    assert s.busy_s == pytest.approx(0.120)
    # the breakdown keeps each operation's own duration
    assert s.top_ops() == [("%dfg_count_diced", pytest.approx(0.160))]


def test_busy_time_is_averaged_over_devices():
    s = summarize(_planes(devices=2), window=(0, 100 * MS))
    assert s.devices == 2
    assert s.busy_s == pytest.approx(0.060)
    assert s.kernel_seconds(r"^%dfg_count_diced") == pytest.approx(0.100)


def test_span_defaults_to_first_and_last_event():
    s = summarize(_planes())
    # the last host annotation ends at 99 ms
    assert s.span_s == pytest.approx(0.099)


def test_only_the_chips_own_planes_are_devices():
    planes = _planes() + [("/device:CUSTOM:Megascale Trace", []),
                          ("/device:TPU:0 SparseCore", [("XLA Ops", [])])]
    s = summarize(planes, window=(0, 100 * MS))
    assert s.devices == 1
    assert s.busy_s == pytest.approx(0.060)


def test_a_trace_without_device_planes_reads_no_busy_time():
    s = summarize([("/host:CPU", [("python3", [Event("x", 0, MS)])])])
    assert s.devices == 0 and s.busy_s == 0.0 and s.gaps == []


def test_op_name_strips_the_hlo_text():
    assert op_name("%tpu_custom_call.12 = f32[1,8]{1,0} custom-call(x)") == "%tpu_custom_call"
    assert op_name("%copy-done = f32[] copy-done(y)") == "%copy-done"

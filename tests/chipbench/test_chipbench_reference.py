"""The chip benchmark's plain reference and its control, on a log counted
by hand.

Activities a, b, c are ids 0, 1, 2.  In recording order::

    row  activity  case  time
    0    a         0     0
    1    b         1     5
    2    b         0     10
    3    b         1     10
    4    c         0     20
    5    a         1     25
    6    c         2     30
    7    a         1     10      (ties row 3 in case 1; recorded after it)

Traces: case 0 = a b c, case 1 = b b a a, case 2 = c.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from chipbench import reference  # noqa: E402
from chipbench.control import ControlApp, bf16  # noqa: E402

A, B, C = 0, 1, 2
ACT = np.array([A, B, B, B, C, A, C, A], dtype=np.int32)
CASE = np.array([0, 1, 0, 1, 0, 1, 2, 1], dtype=np.int32)
TIME = np.array([0, 5, 10, 10, 20, 25, 30, 10], dtype=np.float64)
N = reference.activity_names(3)


@pytest.fixture()
def log():
    return reference.Log(ACT, CASE, TIME, 3)


def test_traces_follow_time_then_recording_order(log):
    assert log.act.tolist() == [A, B, C, B, B, A, A, C]
    assert log.num_traces == 3


def test_whole_log_dfg(log):
    want = np.zeros((3, 3), dtype=np.int64)
    want[A, B] = want[B, C] = want[B, B] = want[B, A] = want[A, A] = 1
    assert np.array_equal(reference.dfg(log), want)


def test_window_keeps_pairs_with_both_events_inside(log):
    # [10, 25): b@10 -> c@20 (case 0) and b@10 -> a@10 (case 1) count;
    # a@0 -> b@10 starts outside and a@10 -> a@25 ends on the open edge
    want = np.zeros((3, 3), dtype=np.int64)
    want[B, C] = want[B, A] = 1
    assert np.array_equal(reference.dfg(log, [10, 25]), want)
    assert reference.dfg(log, [10, 10]).sum() == 0


def test_histogram(log):
    assert reference.histogram(log).tolist() == [3, 3, 2]
    assert reference.histogram(log, [10, 25]).tolist() == [1, 2, 1]


def test_process_map(log):
    pm = reference.process_map(log, 0.5, None)
    assert pm == {
        "activities": [N[A], N[B]],
        "node_counts": [3, 3],
        "edges": [[N[A], N[A], 1], [N[A], N[B], 1]],
        "top": 0.5,
        "edge_top": 0.5,
        "dropped_activities": 1,
        "dropped_edges": 2,
    }


def test_variants(log):
    assert reference.variant_counts(log) == {(A, B, C): 1, (B, B, A, A): 1, (C,): 1}
    assert reference.variant_counts(log, [10, 25]) == {(B, C): 1, (B, A): 1}


def test_discovered_model(log):
    m = reference.discover(log)
    allowed = {(int(i), int(j)) for i, j in zip(*np.nonzero(m.allowed))}
    # a<->b cancel out (dependency 0); b->c has 1/2; loops of one have 1/2
    assert allowed == {(B, C), (B, B), (A, A)}
    assert m.starts.tolist() == [True, True, True]
    assert m.ends.tolist() == [True, False, True]


def test_alignment_costs_by_hand(log):
    m = reference.discover(log)
    hops = reference.hop_distances(m)
    costs, empty = reference.align_costs([(A, B, C), (B, B, A, A), (C,), (B, A)], m, hops)
    # a b c: skip a, then b c; b b a a: skip b b, then a a; b a: skip b
    assert costs.tolist() == [1, 2, 0, 1]
    assert empty == 1  # the model must execute one end activity


def test_alignment_through_model_moves():
    # a -> b -> c only: "a c" needs b on the model, "c" needs a and b
    m = reference.Model(
        allowed=np.array([[0, 1, 0], [0, 0, 1], [0, 0, 0]], dtype=bool),
        starts=np.array([True, False, False]),
        ends=np.array([False, False, True]),
    )
    costs, empty = reference.align_costs([(A, C), (C,), (A, B, C), ()], m,
                                         reference.hop_distances(m))
    assert costs.tolist() == [1, 2, 0, 3]
    assert empty == 3


def test_alignment_without_a_walk_to_the_end():
    m = reference.Model(
        allowed=np.zeros((3, 3), dtype=bool),
        starts=np.array([True, False, False]),
        ends=np.array([False, False, False]),
    )
    costs, empty = reference.align_costs([(A, B), (C,)], m, reference.hop_distances(m))
    assert empty == -1 and costs.tolist() == [2, 1]


def test_alignments_payload(log):
    m = reference.discover(log)
    got = reference.alignments(log, m, reference.hop_distances(m), None)
    assert got["total_traces"] == 3 and got["perfect_traces"] == 1
    assert got["empty_cost"] == 1
    assert got["mean_cost"] == pytest.approx(1.0)
    assert got["fitness"] == pytest.approx((0.75 + 0.6 + 1.0) / 3)
    assert got["deviations"] == [
        {"edge": [N[A], N[B]], "count": 1}, {"edge": [N[B], N[A]], "count": 1}]
    win = reference.alignments(log, m, reference.hop_distances(m), [10, 25])
    assert win["total_traces"] == 2
    assert win["fitness"] == pytest.approx((1.0 + 2.0 / 3.0) / 2)


def test_bf16_rounds_counts_above_256():
    assert bf16([0, 1, 255, 256, 257, 259, 1000, 70001]).tolist() == [
        0, 1, 255, 256, 256, 260, 1000, 70144]


def test_control_answers_differ_only_where_bf16_rounds(log):
    app = ControlApp(log)
    psi = np.asarray(app._answer({"sink": "dfg", "window": None})["psi"])
    assert np.array_equal(psi, reference.dfg(log))  # counts of 1 are exact

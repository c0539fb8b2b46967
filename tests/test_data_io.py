"""Synthetic log generator properties + CSV/XES IO + LM pipeline."""

import io

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core import check_columnar, dfg_from_repository
from repro.data import ProcessSpec, generate_memmap_log, generate_repository
from repro.data.lm_data import TokenPipeline
from repro.data.xes import read_csv, read_xes, write_csv, write_xes


def test_generator_deterministic():
    r1 = generate_repository(100, ProcessSpec(num_activities=10, seed=5), seed=5)
    r2 = generate_repository(100, ProcessSpec(num_activities=10, seed=5), seed=5)
    np.testing.assert_array_equal(r1.event_activity, r2.event_activity)
    np.testing.assert_array_equal(r1.event_time, r2.event_time)


def test_generator_sound_and_plausible():
    repo = generate_repository(500, ProcessSpec(num_activities=20, seed=2))
    assert check_columnar(repo).ok
    assert repo.num_traces == 500
    lens = np.bincount(repo.event_trace)
    assert lens.min() >= 1
    assert 5 < lens.mean() < 30  # geometric around mean_trace_len=12


def test_memmap_log_target_size(tmp_path):
    log = generate_memmap_log(str(tmp_path / "l"), 30_000,
                              ProcessSpec(num_activities=8, seed=1),
                              batch_traces=200)
    assert abs(log.num_events - 30_000) < 300  # lands near the target
    t = np.asarray(log.time)
    assert (np.diff(t) >= 0).all()  # globally time-ordered


def test_csv_roundtrip():
    repo = generate_repository(50, ProcessSpec(num_activities=6, seed=3))
    buf = io.StringIO()
    write_csv(repo, buf)
    buf.seek(0)
    back = read_csv(buf)
    np.testing.assert_array_equal(
        dfg_from_repository(repo), dfg_from_repository(back)
    )


def test_xes_roundtrip():
    repo = generate_repository(30, ProcessSpec(num_activities=5, seed=9))
    buf = io.StringIO()
    write_xes(repo, buf)
    buf.seek(0)
    back = read_xes(buf)
    assert back.num_events == repo.num_events
    np.testing.assert_array_equal(
        dfg_from_repository(repo), dfg_from_repository(back)
    )


#: a BPI Challenge 2017 style export: ISO-8601 dates with offsets
ISO_XES = """<log xes.version="1.0">
<trace><string key="concept:name" value="Application_1"/>
<event><string key="concept:name" value="A_Create Application"/>
<date key="time:timestamp" value="2016-01-01T09:51:15.304+01:00"/></event>
<event><string key="concept:name" value="A_Submitted"/>
<date key="time:timestamp" value="2016-01-01T09:51:15.352+01:00"/></event>
<event><string key="concept:name" value="W_Complete application"/>
<date key="time:timestamp" value="2017-02-01T08:00:00Z"/></event>
</trace>
<trace><string key="concept:name" value="Application_2"/>
<event><string key="concept:name" value="A_Create Application"/>
<date key="time:timestamp" value="2016-06-30T23:59:59.999-02:00"/></event>
<event><string key="concept:name" value="A_Submitted"/>
<date key="time:timestamp" value="2016-07-01T02:00:00"/></event>
</trace>
</log>"""


@pytest.mark.parametrize("case", ["iso_dates", "float_times"])
def test_xes_timestamps_roundtrip(case):
    if case == "iso_dates":
        text = ISO_XES
        want = [1451638275.304, 1451638275.352, 1485936000.0,
                1467338399.999, 1467338400.0]
    else:
        repo = generate_repository(20, ProcessSpec(num_activities=5, seed=4))
        # epoch times past 2**24 s with millisecond fractions
        repo.event_time = 1.45e9 + np.round(repo.event_time * 1e3) / 1e3
        buf = io.StringIO()
        write_xes(repo, buf)
        text = buf.getvalue()
        want = repo.event_time.tolist()
    first = read_xes(io.StringIO(text))
    assert first.event_time.tolist() == want
    # the float form this module writes reads back bit for bit
    buf = io.StringIO()
    write_xes(first, buf)
    back = read_xes(io.StringIO(buf.getvalue()))
    np.testing.assert_array_equal(back.event_time, first.event_time)
    np.testing.assert_array_equal(back.event_activity, first.event_activity)
    assert back.activity_names == first.activity_names


def _dfg_by_name(repo):
    psi = dfg_from_repository(repo)
    out = {}
    for i, a in enumerate(repo.activity_names):
        for j, b in enumerate(repo.activity_names):
            if psi[i, j]:
                out[(a, b)] = int(psi[i, j])
    return out


@settings(max_examples=20, deadline=None)
@given(n=st.integers(5, 80), seed=st.integers(0, 1000))
def test_csv_roundtrip_property(n, seed):
    """Name-keyed DFG equality: the roundtripped vocab only contains
    *observed* activities, so matrix indices may shift — counts must not."""
    repo = generate_repository(n, ProcessSpec(num_activities=7, seed=seed),
                               seed=seed)
    buf = io.StringIO()
    write_csv(repo, buf)
    buf.seek(0)
    back = read_csv(buf)
    assert _dfg_by_name(repo) == _dfg_by_name(back)


def test_token_pipeline_markov_learnable():
    p = TokenPipeline(vocab_size=32, batch=4, seq_len=64, seed=0, branching=4)
    ent = p.bigram_entropy()
    assert 0 < ent < np.log(32)  # strictly easier than uniform
    b = p(0)
    assert b["tokens"].shape == (4, 64)
    assert b["tokens"].max() < 32


def test_token_pipeline_uniform():
    p = TokenPipeline(vocab_size=16, batch=2, seq_len=8, mode="uniform")
    assert abs(p.bigram_entropy() - np.log(16)) < 1e-9

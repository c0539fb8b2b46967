"""The chip benchmark end to end on the CPU, at a tiny size.

``run_cell`` is called directly, which skips the command's look for a
chip: the log is cut to a few ten thousand events (``config_overrides``),
the window to a second or two.  Besides sound runs, the timed path is
broken underneath (an answer altered where it is produced) and the
control is put in the program's place: ``correct`` must come out false.
"""

import asyncio
import io
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402
from chipbench.control import control_factory  # noqa: E402

TINY = {"num_events": 40_000, "num_activities": 40, "horizon_days": 20}
FW = "bpi16_clicks.fresh_windows"
CELLS = [w["name"] for w in harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))["workloads"]]


def run(tmp_path, workload, seed=2**31 + 17, seconds=1.0, traced=False, **kw):
    out, err = io.StringIO(), io.StringIO()
    kw.setdefault("config_overrides", TINY)
    res = harness.run_cell(workload, seed, seconds, traced, workdir=str(tmp_path),
                           out=out, err=err, **kw)
    return res, out.getvalue().splitlines(), err.getvalue().splitlines()


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct_and_prints_the_result_last(tmp_path, workload):
    res, out, err = run(tmp_path, workload)
    assert res["correct"] is True
    assert json.loads(out[-1]) == res
    assert list(res)[-1] == "checks"
    assert set(res) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert res["attempted"] > 0 and res["failed"] == 0
    cell = harness.load_cell(workload)
    assert set(res["metrics"]) == {m["name"] for m in cell.metric_entries(False)}
    assert res["metrics"]["setup_s"]["unit"] == "s"
    assert set(res["device"]) >= {"platform", "kind", "count", "memory_peak_bytes"}
    # the compared numbers come last on standard error, each with its limit
    assert [line.split()[1] for line in err[-len(res["checks"]):]] == list(res["checks"])
    assert any("planner" in json.loads(line) for line in out[:-1])


@pytest.mark.parametrize("workload", CELLS)
def test_traced_run_reports_per_layer_metrics(tmp_path, workload):
    res, _, _ = run(tmp_path, workload, traced=True)
    assert res["correct"] is True
    entries = harness.load_cell(workload).metric_entries(True)
    # the CPU has no device plane, so only the trace readers find nothing
    assert set(res["metrics"]) == {
        m["name"] for m in entries if m["source"] != "device_trace"}
    assert all(m["unit"] == res["metrics"][m["name"]]["unit"]
               for m in entries if m["name"] in res["metrics"])
    assert "busy_s" in res["device"] and res["device"]["window_s"] > 0
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}


@pytest.fixture()
def altered_counts(monkeypatch):
    """One count of every DFG the executor produces is off by one."""
    from repro.query.execute import QueryEngine

    count = QueryEngine._count

    def wrong(self, *args, **kw):
        out = np.array(count(self, *args, **kw), copy=True)
        out[0, 0] += 1
        return out

    monkeypatch.setattr(QueryEngine, "_count", wrong)


@pytest.mark.parametrize("workload", CELLS)
def test_an_answer_altered_where_it_is_produced_is_not_correct(tmp_path, altered_counts, workload):
    res, _, err = run(tmp_path, workload)
    assert res["correct"] is False
    assert res["checks"]["answers_wrong"]["value"] > 0
    assert any(line.startswith("chipbench: answer differs") for line in err)


@pytest.fixture()
def window_refuses(monkeypatch, request):
    """Once the window opens, every request the scheduler takes is either
    refused (429, its queue full: every attempt, or only the first of each
    request) or fails in the engine (500)."""
    from repro.transport.scheduler import TwoLaneScheduler

    state = {"on": False}
    refused = set()
    submit = TwoLaneScheduler.try_submit

    def broken(self, lane, cost, fn, *args, **kw):
        if not state["on"]:
            return submit(self, lane, cost, fn, *args, **kw)
        if request.param == "shed":
            return None, 0.1
        if request.param == "shed_once":
            # the client's task and the request: its first attempt
            key = (asyncio.current_task(), repr(args[0]))
            if key not in refused:
                refused.add(key)
                return None, 0.3
            return submit(self, lane, cost, fn, *args, **kw)

        def fail(*a, **k):
            raise RuntimeError("engine failed")

        return submit(self, lane, cost, fail, *args, **kw)

    window = harness.run_window

    async def run_window(*args, **kw):
        state["on"] = True
        return await window(*args, **kw)

    monkeypatch.setattr(TwoLaneScheduler, "try_submit", broken)
    monkeypatch.setattr(harness, "run_window", run_window)
    return request.param


@pytest.mark.parametrize("window_refuses", ["shed", "error"], indirect=True)
@pytest.mark.parametrize("workload", CELLS)
def test_requests_refused_or_failed_in_the_window_are_not_correct(tmp_path, window_refuses,
                                                                   workload):
    res, out, _ = run(tmp_path, workload)
    assert res["correct"] is False
    assert res["checks"]["answers_failed"]["value"] == res["failed"] > 0
    served = next(json.loads(line)["served"] for line in out if "served" in line)
    assert set(served["statuses"]) == {"429" if window_refuses == "shed" else "500"}
    if window_refuses == "shed":
        assert served["retried"] == {str(harness.RETRIES): res["failed"]}


@pytest.mark.parametrize("window_refuses", ["shed_once"], indirect=True)
@pytest.mark.parametrize("workload", CELLS)
def test_a_request_refused_once_is_sent_again_and_waits_count(tmp_path, window_refuses, workload):
    res, out, _ = run(tmp_path, workload)
    assert res["correct"] is True and res["failed"] == 0
    served = next(json.loads(line)["served"] for line in out if "served" in line)
    assert set(served["statuses"]) == {"200"} and set(served["retried"]) == {"1"}
    # every request waited out its Retry-After of 0.3 s
    assert served["latency_ms"]["p50"] >= 300.0


@pytest.mark.parametrize("workload", CELLS)
def test_the_control_is_not_correct(tmp_path, workload):
    # counts above 256 round in bfloat16: a log large enough to have them
    big = dict(TINY, num_events=200_000)
    res, _, _ = run(tmp_path, workload, config_overrides=big, app_factory=control_factory)
    assert res["correct"] is False
    assert res["checks"]["answers_wrong"]["value"] > 0


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", FW, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300,
    )


def test_the_command_fails_without_a_tpu():
    proc = _command(ROOT)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "needs a TPU" in proc.stderr


def test_the_command_fails_beside_no_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in json.load(open(os.path.join(ROOT, "BENCHMARK.json")))["paths"]:
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert proc.stdout == ""

"""Event-log generator of the chip benchmark.

A copy of the trace sampler of ``repro.data.synthetic_log`` (the Markov
process over activities with geometric trace lengths, 10-minute mean gaps
and uniform case arrivals), kept here so that no change to the program can
move the benchmark's data.  It differs in two respects:

* times are whole seconds from the log's start (``floor`` of the sampled
  time), so a window test in float32 is exact on a log whose horizon stays
  below 2**24 s;
* a trace's length is 1 plus a geometric draw with mean
  ``mean_trace_len - 1``, so the mean length is ``mean_trace_len`` (before
  the cap) and every trace has at least one directly-follows pair; the
  original draws with mean ``mean_trace_len`` and adds 1.

``generate(config, seed)`` returns the event stream in time order as three
numpy columns ``(activity int32, case int32, time float64)``, batch by
batch exactly as ``generate_memmap_log`` lays it out: batch ``k`` owns the
time slab ``[k*slab, (k+1)*slab)``.
"""

from __future__ import annotations

from typing import Iterator, Tuple

import numpy as np

DAY = 86400.0
BATCH_TRACES = 50_000


def _rng(*key: int) -> np.random.Generator:
    return np.random.default_rng([k % (1 << 63) for k in key])


class ProcessModel:
    """The random-but-structured process: ``branching`` successors per
    activity with Dirichlet weights, five entry activities, trace lengths
    of mean ``mean_trace_len``."""

    def __init__(self, config: dict, seed: int):
        a = int(config["num_activities"])
        br = min(int(config["branching"]), a)
        rng = _rng(seed, 0)
        self.succ = np.zeros((a, br), dtype=np.int64)
        self.w = np.zeros((a, br))
        for i in range(a):
            self.succ[i] = rng.choice(a, size=br, replace=False)
            self.w[i] = rng.dirichlet(np.ones(br))
        self.entry_acts = rng.choice(a, size=min(5, a), replace=False)
        self.entry_w = rng.dirichlet(np.ones(self.entry_acts.shape[0]))
        self.p_stop = 1.0 / (float(config["mean_trace_len"]) - 1.0)
        self.max_len = int(config["max_trace_len"])
        self.mean_gap_s = float(config["mean_gap_s"])
        self.arrival_share = float(config["arrival_share"])

    def sample_lens(self, n: int, rng: np.random.Generator) -> np.ndarray:
        return np.minimum(rng.geometric(self.p_stop, size=n) + 1, self.max_len)

    def sample_traces(
        self, lens: np.ndarray, rng: np.random.Generator, horizon: float
    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """Flat ``(case, activity, time)`` for traces of the given lengths,
        sorted by time (stable), times as floats inside ``[0, horizon)``."""
        n_tr = lens.shape[0]
        total = int(lens.sum())
        case = np.repeat(np.arange(n_tr, dtype=np.int64), lens).astype(np.int32)
        arrivals = rng.uniform(0, horizon * self.arrival_share, size=n_tr)
        offsets = np.concatenate([[0], np.cumsum(lens)[:-1]])
        pos = np.arange(total, dtype=np.int64) - np.repeat(offsets, lens)
        cum = np.cumsum(rng.exponential(self.mean_gap_s, size=total))
        base = np.repeat(
            np.concatenate([[0.0], cum[np.cumsum(lens)[:-1] - 1]]), lens
        )
        time = np.repeat(arrivals, lens) + (cum - base) * (pos > 0)
        time = np.minimum(time, horizon - 1e-3)

        act = np.zeros(total, dtype=np.int32)
        act[offsets] = rng.choice(
            self.entry_acts, size=n_tr, p=self.entry_w
        ).astype(np.int32)
        for step in range(1, int(lens.max()) if total else 0):
            idx = offsets[lens > step] + step
            prev = act[idx - 1]
            u = rng.random(idx.shape[0])
            choice = (u[:, None] > np.cumsum(self.w[prev], axis=1)).sum(axis=1)
            act[idx] = self.succ[prev, np.minimum(choice, self.succ.shape[1] - 1)]
        order = np.argsort(time, kind="stable")
        return case[order], act[order], time[order]


def batch_lengths(config: dict, seed: int, model: ProcessModel) -> list:
    """Per-batch trace lengths until the event target is reached."""
    out, remaining, bi = [], int(config["num_events"]), 0
    while remaining > 0:
        lens = model.sample_lens(BATCH_TRACES, _rng(seed, 1, bi))
        csum = np.cumsum(lens)
        if csum[-1] > remaining:
            lens = lens[: int(np.searchsorted(csum, remaining)) + 1]
        out.append(lens)
        remaining -= int(lens.sum())
        bi += 1
    return out


def iter_batches(
    config: dict, seed: int
) -> Iterator[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Yield ``(activity, case, time)`` batches in time order."""
    model = ProcessModel(config, seed)
    lens_all = batch_lengths(config, seed, model)
    slab = float(config["horizon_days"]) * DAY / len(lens_all)
    case_base = 0
    for bi, lens in enumerate(lens_all):
        case, act, time = model.sample_traces(lens, _rng(seed, 2, bi), slab)
        # whole seconds from the log's start; floor keeps the order
        yield (
            act,
            (case + case_base).astype(np.int32),
            np.floor(time + bi * slab),
        )
        case_base += lens.shape[0]


def generate(config: dict, seed: int):
    """The whole stream as ``(activity, case, time)`` columns plus the
    number of cases."""
    parts = list(iter_batches(config, seed))
    act = np.concatenate([p[0] for p in parts])
    case = np.concatenate([p[1] for p in parts])
    time = np.concatenate([p[2] for p in parts])
    return act, case, time, int(case.max()) + 1 if case.size else 0

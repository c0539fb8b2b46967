"""Plain reference of the benchmark: Algorithm 1 and the answers built on it.

Straight numpy over the generated event columns, written from the
definitions and importing nothing of the program:

* a trace is the events of one case ordered by time, ties kept in the
  order they were recorded;
* Psi (the directly-follows graph) counts consecutive events of a trace;
  a window ``[t0, t1)`` keeps a pair when both its events lie inside;
* a histogram counts events per activity inside the window;
* a process map keeps the most frequent share of the observed activities
  and then the most frequent share of the flows among them;
* variants group traces by their activity sequence;
* an alignment of a trace against a directly-follows model is its cheapest
  explanation by synchronous moves (cost 0), moves on the log (cost 1) and
  moves on the model (cost 1) along a walk from the model's start to its
  end; conformance drops the events outside the window and re-links the
  rest.

The model of a log is its own dependency graph: an edge ``a -> b`` where
``|a>b| >= 1`` and ``(|a>b| - |b>a|) / (|a>b| + |b>a| + 1) >= 1/2``
(``|a>a| / (|a>a| + 1) >= 1/2`` for a loop), with the activities that start
and end some trace.  The test is done in integers.
"""

from __future__ import annotations

import math
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

INF = np.iinfo(np.int64).max // 4


def activity_names(num_activities: int) -> List[str]:
    return [f"act_{i:03d}" for i in range(num_activities)]


class Log:
    """The generated columns in trace order (case, then time, then the
    order of recording), with dense trace ids."""

    def __init__(self, activity, case, time, num_activities: int):
        n = activity.shape[0]
        order = np.lexsort((np.arange(n), time, case))
        self.act = np.asarray(activity)[order].astype(np.int64)
        self.time = np.asarray(time)[order].astype(np.float64)
        c = np.asarray(case)[order]
        new_trace = np.ones(n, dtype=bool)
        new_trace[1:] = c[1:] != c[:-1]
        self.trace = np.cumsum(new_trace) - 1
        self.num_traces = int(new_trace.sum())
        self.a = int(num_activities)
        self.names = activity_names(self.a)
        self.same = self.trace[1:] == self.trace[:-1]

    def in_window(self, window) -> np.ndarray:
        if window is None:
            return np.ones(self.act.shape[0], dtype=bool)
        t0, t1 = float(window[0]), float(window[1])
        return (self.time >= t0) & (self.time < t1)


def dfg(log: Log, window=None) -> np.ndarray:
    inw = log.in_window(window)
    keep = log.same & inw[:-1] & inw[1:]
    ids = log.act[:-1][keep] * log.a + log.act[1:][keep]
    return np.bincount(ids, minlength=log.a * log.a).reshape(log.a, log.a)


def histogram(log: Log, window=None) -> np.ndarray:
    return np.bincount(log.act[log.in_window(window)], minlength=log.a)


def _top(n: int, frac: float) -> int:
    return 0 if n == 0 else min(n, max(1, math.ceil(frac * n)))


def process_map(log: Log, top: float, edge_top: Optional[float], window=None):
    """The payload fields of a process map, as plain lists."""
    edge_top = top if edge_top is None else edge_top
    counts = histogram(log, window)
    psi = dfg(log, window)
    active = [i for i in range(log.a) if counts[i] > 0]
    ranked = sorted(active, key=lambda i: (-int(counts[i]), i))
    kept = sorted(ranked[: _top(len(ranked), top)])
    kept_set = set(kept)
    flows = [
        (int(psi[s, d]), s, d)
        for s in kept for d in kept if psi[s, d] > 0
    ]
    flows.sort(key=lambda f: (-f[0], f[1], f[2]))
    shown = flows[: _top(len(flows), edge_top)]
    assert all(s in kept_set and d in kept_set for _, s, d in shown)
    return {
        "activities": [log.names[i] for i in kept],
        "node_counts": [int(counts[i]) for i in kept],
        "edges": [[log.names[s], log.names[d], c] for c, s, d in shown],
        "top": float(top),
        "edge_top": float(edge_top),
        "dropped_activities": len(active) - len(kept),
        "dropped_edges": len(flows) - len(shown),
    }


def _sequences(log: Log, mask: np.ndarray) -> List[Tuple[int, ...]]:
    """Activity sequence of every trace with an event under ``mask``."""
    act, tr = log.act[mask], log.trace[mask]
    if act.shape[0] == 0:
        return []
    cuts = np.nonzero(tr[1:] != tr[:-1])[0] + 1
    return [tuple(s.tolist()) for s in np.split(act, cuts)]


def variant_counts(log: Log, window=None) -> Dict[Tuple[int, ...], int]:
    out: Dict[Tuple[int, ...], int] = {}
    for seq in _sequences(log, log.in_window(window)):
        out[seq] = out.get(seq, 0) + 1
    return out


# -- the model and alignments -------------------------------------------------


class Model:
    def __init__(self, allowed: np.ndarray, starts: np.ndarray, ends: np.ndarray):
        self.allowed = allowed  # (A, A) bool
        self.starts = starts  # (A,) bool
        self.ends = ends  # (A,) bool


def discover(log: Log) -> Model:
    psi = dfg(log)
    rev = psi.T
    allowed = (psi >= 1) & (2 * (psi - rev) >= psi + rev + 1)
    diag = np.diag(psi)
    allowed[np.arange(log.a), np.arange(log.a)] = diag >= 1
    first = np.ones(log.act.shape[0], dtype=bool)
    first[1:] = ~log.same
    last = np.ones(log.act.shape[0], dtype=bool)
    last[:-1] = ~log.same
    starts = np.bincount(log.act[first], minlength=log.a) > 0
    ends = np.bincount(log.act[last], minlength=log.a) > 0
    return Model(allowed, starts, ends)


def hop_distances(model: Model) -> Tuple[np.ndarray, np.ndarray]:
    """Breadth-first distances over the edge relation, with state ``A`` the
    virtual start: ``h[q, x]`` is the fewest activities the model executes
    to go from state ``q`` to having just executed ``x`` (``h[q, q] = 0``),
    and ``h1[q, x]`` the same with at least one activity executed (``x``
    itself last), which is what a synchronous move onto ``x`` needs."""
    a = model.allowed.shape[0]
    step = np.zeros((a + 1, a + 1), dtype=np.int64)
    step[:a, :a] = model.allowed
    step[a, :a] = model.starts

    def bfs(frontier: np.ndarray, k: int) -> np.ndarray:
        dist = np.full((a + 1, a + 1), INF, dtype=np.int64)
        reached = frontier.copy()
        while frontier.any():
            dist[frontier] = k
            k += 1
            frontier = (frontier.astype(np.int64) @ step > 0) & ~reached
            reached |= frontier
        return dist

    return bfs(np.eye(a + 1, dtype=bool), 0), bfs(step > 0, 1)


def align_costs(
    seqs: Sequence[Tuple[int, ...]], model: Model, hops: Tuple[np.ndarray, np.ndarray]
) -> Tuple[np.ndarray, int]:
    """Optimal cost of each sequence, and the cost of the empty trace
    (``-1`` when the model has no walk from start to end)."""
    h, h1 = hops
    a = model.allowed.shape[0]
    start = a
    ends = np.nonzero(model.ends)[0]
    endcost = h[:, ends].min(axis=1) if ends.shape[0] else np.full(a + 1, INF)
    empty = int(endcost[start])
    if empty >= INF:
        return np.asarray([len(s) for s in seqs], dtype=np.int64), -1
    if not seqs:
        return np.zeros(0, dtype=np.int64), empty
    # all sequences advance together, one event per step; d[v, q] is the
    # cheapest cost of sequence v's prefix ending in model state q
    lens = np.asarray([len(s) for s in seqs], dtype=np.int64)
    pad = np.zeros((len(seqs), int(lens.max())), dtype=np.int64)
    for v, seq in enumerate(seqs):
        pad[v, : len(seq)] = seq
    rows = np.arange(len(seqs))
    # sync onto x from q: the model walks q -> ... -> x, executing x last,
    # and every activity it executes before x is a move on the model
    sync_cost = np.where(h1 < INF, h1 - 1, INF)
    d = np.full((len(seqs), a + 1), INF, dtype=np.int64)
    d[:, start] = 0
    for i in range(pad.shape[1]):
        x = pad[:, i]
        sync = np.minimum(d + sync_cost[:, x].T, INF).min(axis=1)
        nd = np.minimum(d + 1, INF)  # a move on the log
        nd[rows, x] = np.minimum(nd[rows, x], sync)
        live = lens > i
        d[live] = nd[live]
    return np.minimum(d + endcost[None, :], INF).min(axis=1), empty


def alignments(log: Log, model: Model, hops, window) -> Dict:
    """The payload fields of an alignments answer for ``window``."""
    mask = log.in_window(window)
    seqs = _sequences(log, mask)
    uniq = sorted(set(seqs))
    index = {s: i for i, s in enumerate(uniq)}
    ucost, empty = align_costs(uniq, model, hops)
    cost = np.asarray([ucost[index[s]] for s in seqs], dtype=np.int64)
    lens = np.asarray([len(s) for s in seqs], dtype=np.int64)
    if empty < 0:
        fit = [0.0 if n else 1.0 for n in lens.tolist()]
    else:
        fit = [1.0 - c / max(n + empty, 1) for c, n in zip(cost.tolist(), lens.tolist())]
    act, tr = log.act[mask], log.trace[mask]
    bad = (tr[1:] == tr[:-1]) & ~model.allowed[act[:-1], act[1:]]
    census: Dict[Tuple[int, int], int] = {}
    for s, d in zip(act[:-1][bad].tolist(), act[1:][bad].tolist()):
        census[(s, d)] = census.get((s, d), 0) + 1
    deviations = sorted(
        ({"edge": [log.names[s], log.names[d]], "count": c}
         for (s, d), c in census.items()),
        key=lambda e: (-e["count"], e["edge"]),
    )
    n = len(seqs)
    return {
        "fitness": math.fsum(fit) / n if n else 1.0,
        "perfect_traces": int((cost == 0).sum()) if n else 0,
        "total_traces": n,
        "mean_cost": math.fsum(cost.tolist()) / n if n else 0.0,
        "empty_cost": empty,
        "deviations": deviations,
    }

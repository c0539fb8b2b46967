"""The one traffic generator: turns a mix file (``traffic/<name>.json``) and
a seed into warm-up requests and the timed stream.

A mix names a loop and its components:

* ``"loop": "closed"`` with ``"workers"``: each worker sends its next
  request when the previous one is answered, with no think time;
* ``"loop": "open"`` with ``"rate_qps"``: requests fall due on a Poisson
  schedule at that rate, whether or not earlier ones were answered.

Each component has a ``share`` of the requests, a ``kind`` and the
parameters of that kind:

* ``day_windows``: ``{"sink", "min_days", "max_days"}``: day-aligned
  windows ``[a*86400, b*86400)`` inside the log's horizon, never repeated
  within a run; lengths cycle through a seeded order of every allowed
  length, so every seed sends the same set of lengths;
* ``tiles``: ``{"tiles": [...], "zipf"}``: fixed dashboard requests with
  Zipf popularity in the listed order; a tile with ``last_days`` asks for
  the days that end at the midnight after the log's last event.

Every seed gets the same set of inter-arrival gaps (the exponential
quantiles of the rate), the same number of requests per component and per
tile, and its own order of them.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Dict, Iterator, List, Optional

import numpy as np

DAY = 86400


@dataclasses.dataclass
class Item:
    component: str
    tenant: str
    request: Dict
    days: Optional[int] = None  # window length in days, for day_windows
    tile: Optional[int] = None
    due: Optional[float] = None  # seconds after the window opens (open loop)


def _rng(seed: int, *key: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 63), 7, *key])


def _apportion(total: int, weights: List[float]) -> List[int]:
    """Largest-remainder split of ``total`` by ``weights``."""
    w = np.asarray(weights, dtype=np.float64)
    raw = total * w / w.sum()
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[: total - int(out.sum())]:
        out[i] += 1
    return out.tolist()


class DayWindows:
    """Fresh day-aligned windows, never repeated."""

    def __init__(self, comp: Dict, horizon_days: int, rng: np.random.Generator):
        lo = int(comp["min_days"])
        hi = min(int(comp.get("max_days") or horizon_days), horizon_days)
        self.rng = rng
        self.free = {n: list(range(horizon_days - n + 1)) for n in range(lo, hi + 1)}
        self.cycle: List[int] = []

    def next(self) -> Optional[tuple]:
        for _ in range(2):
            while self.cycle:
                n = self.cycle.pop()
                starts = self.free[n]
                if starts:
                    a = starts.pop(int(self.rng.integers(len(starts))))
                    return n, [a * DAY, (a + n) * DAY]
            live = [n for n, s in self.free.items() if s]
            if not live:
                return None
            self.cycle = [live[i] for i in self.rng.permutation(len(live))]
        return None


class Traffic:
    def __init__(self, mix: Dict, config: Dict, log_name: str,
                 last_event_s: float, seed: int):
        self.mix = mix
        self.log_name = log_name
        self.seed = seed
        self.horizon_days = int(math.ceil(float(config["horizon_days"])))
        self.end_s = (math.floor(last_event_s / DAY) + 1) * DAY
        self.tenants = int(mix.get("tenants", 1))
        self._tenant_rng = _rng(seed, 1)
        self.windows: Dict[str, DayWindows] = {}
        for i, comp in enumerate(mix["components"]):
            if comp["kind"] == "day_windows":
                self.windows[comp["name"]] = DayWindows(
                    comp, self.horizon_days, _rng(seed, 2, i)
                )
            elif comp["kind"] != "tiles":
                raise ValueError(f"unknown traffic kind {comp['kind']!r}")

    # -- single requests ---------------------------------------------------
    def _tenant(self) -> str:
        return f"tenant{int(self._tenant_rng.integers(self.tenants)):02d}"

    def tile_request(self, comp: Dict, k: int) -> Dict:
        tile = dict(comp["tiles"][k])
        last = tile.pop("last_days", None)
        req = {"log": self.log_name, **tile}
        if last is not None:
            req["window"] = [self.end_s - int(last) * DAY, self.end_s]
        return req

    def _component(self, name: str) -> Dict:
        return next(c for c in self.mix["components"] if c["name"] == name)

    def make(self, name: str, tile: Optional[int] = None) -> Optional[Item]:
        comp = self._component(name)
        if comp["kind"] == "tiles":
            return Item(name, self._tenant(), self.tile_request(comp, tile), tile=tile)
        nxt = self.windows[name].next()
        if nxt is None:
            return None
        days, window = nxt
        req = {"log": self.log_name, "sink": comp["sink"], "window": window}
        return Item(name, self._tenant(), req, days=days)

    # -- warm-up -------------------------------------------------------------
    def warmup(self) -> List[Item]:
        """Every tile once, then ``warmup.requests`` fresh windows of each
        window component; the timed stream never repeats them."""
        items: List[Item] = []
        n_win = int(self.mix.get("warmup", {}).get("requests", 0))
        for comp in self.mix["components"]:
            if comp["kind"] == "tiles":
                items += [self.make(comp["name"], k) for k in range(len(comp["tiles"]))]
            else:
                items += [self.make(comp["name"]) for _ in range(n_win)]
        return [i for i in items if i is not None]

    # -- the timed stream ------------------------------------------------------
    def closed_stream(self) -> Iterator[Item]:
        """Endless stream for a closed loop (one component kind at a time,
        drawn by share)."""
        comps = self.mix["components"]
        rng = _rng(self.seed, 3)
        shares = np.asarray([c["share"] for c in comps], dtype=np.float64)
        while True:
            comp = comps[int(rng.choice(len(comps), p=shares / shares.sum()))]
            tile = None
            if comp["kind"] == "tiles":
                tile = int(rng.choice(len(comp["tiles"]), p=zipf(comp)))
            item = self.make(comp["name"], tile)
            if item is None:
                return
            yield item

    def open_schedule(self, seconds: float, stream: int = 0) -> Iterator[Item]:
        """Due times and requests of an open loop lasting ``seconds``;
        ``stream`` draws an independent schedule from the same seed.  The
        due times and the order of components are drawn at once; each
        request is made when it is taken, so a long schedule holds few
        objects."""
        rate = float(self.mix["rate_qps"])
        n = int(round(rate * seconds))
        rng = _rng(self.seed, 4, stream)
        q = (np.arange(n) + 0.5) / n
        gaps = -np.log1p(-q) / rate
        gaps = gaps[rng.permutation(n)]
        due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
        comps = self.mix["components"]
        counts = _apportion(n, [c["share"] for c in comps])
        slots: List[tuple] = []
        for comp, cnt in zip(comps, counts):
            if comp["kind"] == "tiles":
                per = _apportion(cnt, zipf(comp).tolist())
                slots += [(comp["name"], k) for k, m in enumerate(per) for _ in range(m)]
            else:
                slots += [(comp["name"], None)] * cnt
        slots = [slots[i] for i in rng.permutation(len(slots))]
        return self._items(due.tolist(), slots, seconds)

    def _items(self, due: List[float], slots: List[tuple], seconds: float) -> Iterator[Item]:
        for t, (name, tile) in zip(due, slots):
            if t >= seconds:
                return
            item = self.make(name, tile)
            if item is None:
                return
            item.due = t
            yield item


def zipf(comp: Dict) -> np.ndarray:
    k = np.arange(1, len(comp["tiles"]) + 1, dtype=np.float64)
    w = k ** -float(comp["zipf"])
    return w / w.sum()

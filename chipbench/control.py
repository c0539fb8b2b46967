#!/usr/bin/env python3
"""The control of the correctness check: the plain reference put in the
program's place, computed one precision lower than the configuration
states.  The check must find it not correct.

Counts (Psi, histograms, process-map nodes and flows) are written out in
bfloat16, the step a kernel that kept its MXU result in bf16 would take;
counts above 256 lose their low bits.

    python3 chipbench/control.py --workload bpi16_clicks.fresh_windows \
        --seconds 5 --seeds 11 12 13

runs a short window of the cell's own traffic at the cell's own size per
seed, answered by the control, and prints each seed's checks.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from typing import Dict

import numpy as np

if __package__ in (None, ""):
    sys.path[:0] = [
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src"),
    ]
    __package__ = "chipbench"

from chipbench import reference  # noqa: E402


def bf16(x) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), back as int64."""
    f = np.ascontiguousarray(np.asarray(x, dtype=np.float32))
    bits = f.view(np.uint32).astype(np.uint64)
    bits = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    return bits.astype(np.uint32).view(np.float32).astype(np.int64)


class Response:
    def __init__(self, payload: Dict):
        self.status = 200
        self.payload = payload
        self.headers: Dict[str, str] = {}


class ControlApp:
    """Answers requests from the reference, one precision lower."""

    def __init__(self, ref: reference.Log):
        self.ref = ref
        self._answers: Dict[str, Dict] = {}  # a repeated request, answered once

    def _answer(self, q: Dict) -> Dict:
        ref, sink, window = self.ref, q.get("sink"), q.get("window")
        if sink == "dfg":
            return {"psi": bf16(reference.dfg(ref, window)).tolist(), "names": ref.names}
        if sink == "histogram":
            return {"counts": bf16(reference.histogram(ref, window)).tolist(),
                    "names": ref.names}
        if sink == "process_map":
            pm = reference.process_map(ref, float(q.get("top", 0.2)), q.get("edge_top"), window)
            pm["node_counts"] = bf16(pm["node_counts"]).tolist()
            pm["edges"] = [[s, d, int(bf16([c])[0])] for s, d, c in pm["edges"]]
            return pm
        raise ValueError(f"no control for sink {sink!r}")

    async def handle(self, request: Dict, tenant: str = "default") -> Response:
        key = json.dumps(request, sort_keys=True)
        if key not in self._answers:
            self._answers[key] = self._answer(request)
        payload = dict(self._answers[key], from_cache=False, backend="control", wall_s=0.0)
        return Response(payload)


def control_factory(name, log, reference_log):
    return ControlApp(reference_log())


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__,
                                 formatter_class=argparse.RawTextHelpFormatter)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from chipbench.harness import run_cell

    devnull = open(os.devnull, "w")
    for seed in args.seeds:
        res = run_cell(args.workload, seed, args.seconds, False,
                       app_factory=control_factory, out=devnull, err=devnull)
        print(json.dumps({"seed": seed, "correct": res["correct"],
                          "checks": res["checks"]}), flush=True)
    devnull.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())

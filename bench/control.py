"""Readings that set a cell's limits and declared costs, on the chip:

    python3 bench/control.py <cell> --seconds <s> --seeds 1 2 3 ...

Runs the cell once per seed in one process, and for each prints one JSON
line: the program's widest logit gap and, on the same sample of served
tokens, the control's (the reference with float8 matmul inputs), with
the verdict each gets by the rule that decides ``correct``; each
shape cell's and each phase's call times in the window: count, median,
99th percentile and maximum in ms (for the declared costs); and the run's
failures.  The benchmark's own runs never run the control.
"""

from __future__ import annotations

import argparse
import json
import sys

import numpy as np

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("workload")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = run.Cell.load(run.ROOT, args.workload)
    sys.path.insert(0, str(run.ROOT / "src"))
    run.use_compile_cache()
    devices = run.require_chips(cell.chips)
    for seed in args.seeds:
        calls: dict = {}
        res = run.serve(cell, seed, args.seconds, False, devices,
                        control=True, calls=calls)
        phases: dict = {}
        for k, v in calls.items():
            phases.setdefault(k.split(":")[0].split("@")[0], []).extend(v)
        pcts = {k: [len(v)] + [float(np.percentile(v, q)) * 1e3
                               for q in (50, 99, 100)]
                for k, v in sorted({**calls, **phases}.items())}
        print(json.dumps({"seed": seed, "checks": res["checks"],
                          "correct": res["correct"],
                          "control_correct": res["control_correct"],
                          "attempted": res["attempted"],
                          "failed": res["failed"],
                          "metrics": res["metrics"],
                          "calls_n_ms_p50_p99_max": pcts}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

#!/usr/bin/env python3
"""Measure a cell's run-to-run spread the way the bounds were set from.

    python benchmark/spread.py --workload W --seconds S \
        --seeds 2147483659,2147483693,... --sets 2 --out chiprun_out/spread-W.json

Runs the cell once per seed, ``--sets`` times over with the SAME seeds,
each run an ordinary ``run.py`` process (this parent never touches JAX).
For every end-to-end metric and each set: the median and the spread =
(third quartile - first quartile) / median, quartiles as
``statistics.quantiles(values, n=4)`` gives them.  A bound is about five
times the widest spread over the cells, never under 1 %.  The first run
of the call (it may compile) is reported apart for ``setup_s``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))
from sweep import run_once  # noqa: E402


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    seeds = [int(s) for s in args.seeds.split(",")]
    runs = []
    for k in range(args.sets):
        for seed in seeds:
            run = run_once(args.workload, seed, args.seconds)
            row = {"set": k, "seed": seed, "rc": run["rc"]}
            if "result" in run:
                res = run["result"]
                row.update(correct=res["correct"], attempted=res["attempted"],
                           failed=res["failed"], failures=run["failures"],
                           memory_peak_bytes=res["device"]["memory_peak_bytes"],
                           metrics={m: v["value"] for m, v in res["metrics"].items()})
            else:
                row["stderr"] = run["stderr"]
            runs.append(row)
            print(json.dumps(row), flush=True)
    summary: dict = {}
    good = [r for r in runs if "metrics" in r]
    for name in sorted({m for r in good for m in r["metrics"]}):
        per_set = []
        for k in range(args.sets):
            rows = [r for r in good if r["set"] == k]
            if name == "setup_s" and k == 0:
                rows = rows[1:]  # the call's first run compiles
            vals = [r["metrics"][name] for r in rows if name in r["metrics"]]
            per_set.append(dict(n=len(vals), median=statistics.median(vals) if vals else None,
                                spread=spread(vals), values=vals))
        summary[name] = per_set
        print(name, [(s["n"], s["median"], s["spread"]) for s in per_set], flush=True)
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(dict(
        workload=args.workload, seconds=args.seconds, seeds=seeds, runs=runs,
        summary=summary), indent=1))
    return 0 if len(good) == len(runs) and all(r["correct"] for r in good) else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Run one cell at several values of its parameters: the slots sweep and
the knee sweep.

    python benchmark/sweep.py --workload W --seconds 15 \
        --set slots=64,clients=64 --set slots=128,clients=128
    python benchmark/sweep.py --workload W --seconds 20 \
        --set rate_rps=3 --set rate_rps=4 --set rate_rps=5

Each point is one ordinary ``run.py`` process (``--override`` replaces
keys of the cell's parameter file for that run only), one after the
other - this parent never touches JAX, so each child has the chip to
itself.  Prints one row per point: the end-to-end metrics, the requests
in flight at the window's two edges (a backlog that grows through the
window is past the knee) and the memory peak.
"""

from __future__ import annotations

import argparse
import json
import subprocess
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent


def run_once(workload: str, seed: int, seconds: float,
             overrides: tuple[str, ...] = ()) -> dict:
    """One ``run.py --trace 0`` process -> its exit code and, when it
    printed one, its result line."""
    cmd = [sys.executable, str(BENCH / "run.py"), "--workload", workload,
           "--seed", str(seed), "--seconds", str(seconds), "--trace", "0"]
    for kv in overrides:
        cmd += ["--override", kv]
    proc = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
                          text=True)
    lines = proc.stdout.strip().splitlines()
    out = {"rc": proc.returncode}
    if proc.returncode == 0 and lines:
        out["result"] = json.loads(lines[-1])
        out["failures"] = [ln for ln in lines if "FAIL" in ln][:6]
    else:
        out["stderr"] = proc.stderr[-1500:]
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=15.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--set", action="append", required=True, dest="points",
                    metavar="KEY=V[,KEY=V]")
    args = ap.parse_args()
    ok = True
    for point in args.points:
        run = run_once(args.workload, args.seed, args.seconds,
                       tuple(point.split(",")))
        row = {"point": point, "rc": run["rc"]}
        if "result" in run:
            res = run["result"]
            detail = json.loads(
                (BENCH / "out" / f"{args.workload}-{args.seed}.json").read_text())
            row.update(correct=res["correct"], attempted=res["attempted"],
                       failed=res["failed"], failures=run["failures"],
                       memory_peak_mib=res["device"]["memory_peak_bytes"] / 2**20,
                       in_flight=detail["requests"]["in_flight"],
                       late_ms_p95=detail["requests"]["late_ms"][1],
                       **{k: v["value"] for k, v in res["metrics"].items()})
        else:
            row["stderr"] = run["stderr"]
            ok = False
        print(json.dumps(row), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

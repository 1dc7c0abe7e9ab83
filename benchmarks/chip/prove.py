#!/usr/bin/env python3
"""Two sets of runs of one cell, with the same seeds in both, and the
spread of every metric: how a bound is set and checked.

    python3 benchmarks/chip/prove.py --workload <cell> --runs 6 [--trace 1]

Runs `run.py` as the driver does (a new process per run), prints each
run's result line, and for each end-to-end metric the median and the
spread of each set: the distance between the first and third quartile
(`statistics.quantiles(values, n=4)`) as a share of the median. The
first run of the call is made apart and not counted: it may compile.
Writes `chiprun_out/bench/<cell>/prove.json`. Imports no jax.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
# large, as the driver's are: more than 32 signed bits hold
SEEDS = [2147483659, 2654435761, 3000000019, 3141592653, 3735928559,
         4000000007, 4123456789, 4294967291]


def one(workload: str, seed: int, seconds: str | None, trace: int) -> dict:
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--trace", str(trace)]
    if seconds:
        cmd += ["--seconds", seconds]
    t0 = time.monotonic()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
    lines = p.stdout.strip().splitlines()
    out = {"seed": seed, "rc": p.returncode, "trace": trace,
           "wall_s": time.monotonic() - t0}
    if p.returncode == 0 and lines:
        out["result"] = json.loads(lines[-1])
        out["notes"] = [ln for ln in lines[:-1] if ln.startswith('{"window"')
                        or ln.startswith("engine:")]
    else:
        out["stderr"] = p.stderr[-3000:]
    print(json.dumps(out), flush=True)
    return out


def spread(values: list[float]) -> float:
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--seconds", default=None)
    ap.add_argument("--trace", type=int, default=1,
                    help="1: end with one --trace 1 run")
    ap.add_argument("--first", type=int, default=1,
                    help="1: a run apart first, which may compile")
    a = ap.parse_args()
    seeds = SEEDS[:a.runs]
    report: dict = {"workload": a.workload, "runs": [], "sets": []}
    if a.first:
        report["first"] = one(a.workload, 1, a.seconds, 0)
    for _ in range(2):
        runs = [one(a.workload, s, a.seconds, 0) for s in seeds]
        report["runs"].append(runs)
        good = [r["result"] for r in runs if "result" in r]
        names = sorted({n for r in good for n in r["metrics"]})
        summary = {}
        for n in names:
            vals = [r["metrics"][n]["value"] for r in good
                    if n in r["metrics"]]
            if len(vals) >= 2:
                summary[n] = {"median": statistics.median(vals),
                              "spread": spread(vals), "values": vals}
        report["sets"].append({
            "ok_runs": len(good), "correct": all(r["correct"] for r in good),
            "failed": sum(r["failed"] for r in good), "metrics": summary})
    if a.trace:
        report["traced"] = one(a.workload, seeds[0], a.seconds, 1)
    widest = {}
    for n in report["sets"][0]["metrics"]:
        if n in report["sets"][1]["metrics"]:
            s1, s2 = (s["metrics"][n] for s in report["sets"])
            widest[n] = {"spread": max(s1["spread"], s2["spread"]),
                         "medians": [s1["median"], s2["median"]],
                         "second_over_first": s2["median"] / s1["median"]}
    report["widest"] = widest
    print(json.dumps({"widest": widest}), flush=True)
    out_dir = os.path.join(ROOT, "chiprun_out", "bench", a.workload)
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "prove.json"), "w") as f:
        json.dump(report, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Repeat one cell and report each metric's spread, as the bounds are set.

    python3 benchmarks/measure.py --workload <cell> [--runs 6] [--sets 2]
        [--seconds <run_seconds>] [--trace 0] [--seed0 <n>] [--control <name>]

Every run is a new process of ``run.py`` (this one never touches JAX, so the
chip is free for each child).  The sets use the same seeds.  A spread is the
distance between the first and the third quartile over the median.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main() -> int:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--runs", type=int, default=6)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--seconds", type=float, default=bench["run_seconds"])
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--seed0", type=int, default=2_200_000_000)
    ap.add_argument("--control", default="")
    args = ap.parse_args()
    sets, all_correct = [], True
    for k in range(args.sets):
        rows = []
        for r in range(args.runs):
            cmd = [*bench["command"], "--workload", args.workload, "--seed",
                   str(args.seed0 + 7919 * r), "--seconds", str(args.seconds),
                   "--trace", str(args.trace)]
            if args.control:
                cmd += ["--control", args.control]
            p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True)
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
                res["metrics"], res["correct"]
            except (IndexError, ValueError, KeyError):
                print(f"set {k} run {r}: exit {p.returncode}, no result\n"
                      f"{p.stdout[-2000:]}\n{p.stderr[-3000:]}", flush=True)
                return 1
            all_correct &= res["correct"]
            for line in lines[:-1]:
                print("   ", line[:600], flush=True)
            print(f"set {k} run {r} exit {p.returncode}: {lines[-1][:6000]}",
                  flush=True)
            rows.append({m: v["value"] for m, v in res["metrics"].items()})
        sets.append(rows)
    for name in sets[0][0]:
        for k, rows in enumerate(sets):
            vals = [row[name] for row in rows if name in row]
            if len(vals) >= 2:
                print(f"SPREAD {args.workload} {name} set {k}: median "
                      f"{statistics.median(vals):.6g} spread {spread(vals):.5f} "
                      f"values {[round(v, 5) for v in vals]}", flush=True)
    print(f"ALL_CORRECT {all_correct}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

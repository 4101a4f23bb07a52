#!/usr/bin/env python3
"""Run cells several times, one new process each, and print the spreads.

    python benchmark/measure.py --out chiprun_out/sets \
        --runs cosmoshub150.commit:0:11,12,13 blocksync1k.replay:1:7

Each ``--runs`` entry is ``<workload>:<trace>:<seed>[,<seed>...]``; the
runs go one after another (this process never imports JAX, so each
child has the chip to itself).  Every run's output is kept under
``--out``; the summary gives, per workload and metric, the values, the
median and the interquartile spread as a share of the median — the
figure the bounds in ``BENCHMARK.json`` are set from.
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
ROOT = os.path.dirname(HERE)


def spread(values: list[float]) -> float | None:
    if len(values) < 2:
        return None
    q1, _, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return (q3 - q1) / med if med else None


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--runs", nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--out", default="chiprun_out/measure")
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    seconds = args.seconds or manifest["run_seconds"]
    os.makedirs(args.out, exist_ok=True)
    results: dict[tuple, list[dict]] = {}
    failures = 0
    for entry in args.runs:
        workload, trace, seeds = entry.split(":")
        for seed in seeds.split(","):
            tag = f"{workload}.t{trace}.s{seed}"
            cmd = manifest["command"] + [
                "--workload", workload, "--seed", seed,
                "--seconds", str(seconds), "--trace", trace,
            ]
            t0 = time.perf_counter()
            proc = subprocess.run(cmd, cwd=ROOT, capture_output=True,
                                  text=True)
            wall = time.perf_counter() - t0
            with open(os.path.join(args.out, tag + ".out"), "w") as f:
                f.write(proc.stdout)
            with open(os.path.join(args.out, tag + ".err"), "w") as f:
                f.write(proc.stderr[-200_000:])
            last = proc.stdout.strip().splitlines()[-1:] or [""]
            try:
                line = json.loads(last[0])
            except json.JSONDecodeError:
                line = None
            ok = proc.returncode == 0 and line and line.get("correct")
            failures += not ok
            print(json.dumps({"run": tag, "rc": proc.returncode,
                              "wall_s": round(wall, 1), "line": line}),
                  flush=True)
            if not ok:
                print(proc.stderr[-3000:], file=sys.stderr, flush=True)
            if line:
                results.setdefault((workload, trace), []).append(line)
    for (workload, trace), lines in results.items():
        names = sorted({n for ln in lines for n in ln["metrics"]})
        for name in names:
            vals = [ln["metrics"][name]["value"] for ln in lines
                    if name in ln["metrics"]]
            later = vals[1:] if name == "setup_s" and len(vals) > 1 else vals
            print(json.dumps({
                "workload": workload, "trace": int(trace), "metric": name,
                "n": len(vals), "values": vals,
                "median": statistics.median(later),
                "iqr_share": spread(later),
                "note": ("first run left out" if later is not vals else None),
            }), flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

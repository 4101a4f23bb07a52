#!/usr/bin/env python3
"""Read the comparison's numbers for the program and for its control,
seed after seed, in one process on the chip.

    python benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 8

For each seed: a short window of the cell's own traffic at its own size
(long enough to pass the first tampered item: 64 items in) through the
program as shipped (the lower reading), then the same through the cell's
control — the driver's ``control``: the program's own
weaker verification mode put in the entry point's place, which breaks
one guarantee the configuration states (the upper reading).  One JSON
line a run; the last line says whether every program run was correct
and every control run was not.  Not part of a benchmark run: the
driver's check never calls it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run  # noqa: E402


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    args = ap.parse_args(argv)
    cell = run.load_cell(args.workload)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        devices = run.require_chip(cell["cell"]["chips"])
    except run.NoChip as exc:
        print(f"control.py: device check failed: {exc}", file=sys.stderr)
        return 2
    sound = True
    for seed in seeds:
        for side in ("program", "control"):
            line = run.run_cell(
                cell, run.plan_chain(cell, seed), args.seconds, False,
                devices,
                after_warm=(cell["driver"].control if side == "control"
                            else None),
            )
            print(json.dumps({
                "side": side, "seed": seed, "correct": line["correct"],
                "attempted": line["attempted"],
                "compared": {k: v["value"]
                             for k, v in line["compared"].items()},
            }), flush=True)
            if side == "program":
                sound &= line["correct"]
            else:  # failed by an answer, not by a window that saw nothing
                sound &= any(v["value"] > v["limit"]
                             for k, v in line["compared"].items()
                             if k != "unexercised_checks")
    print(json.dumps({"workload": args.workload, "seeds": seeds,
                      "program_correct_and_control_not": sound}),
          flush=True)
    return 0 if sound else 1


if __name__ == "__main__":
    sys.exit(main())

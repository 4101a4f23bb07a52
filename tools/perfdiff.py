"""perfdiff: compare two perf-ledger points and gate on regression.

    python tools/perfdiff.py OLD.json NEW.json [--threshold 0.10]
    python tools/perfdiff.py --selftest          # make perf-gate

Inputs are perf-ledger documents (tools/perfledger.py schema) or any
BENCH_ALL-shaped ``{"results": [...]}`` file; for each ``config``
present in both, the LATEST entry on each side is compared with a
noise-aware relative threshold:

- direction comes from the unit: throughput units (sigs/sec, ops/sec,
  tx/sec...) regress DOWN, latency units (ms, s, ns_per_op) regress
  UP;
- the default threshold (10%) sits above the run-to-run noise the
  bench history shows (repeat trials of the same config vary ~3-5% on
  this stack: bench.py takes best-of-3 precisely because single runs
  wobble) and well below any change worth a human's attention — the
  measured regressions that mattered were 3-5x, not 1.1x;
- values <= 0 on either side are skipped (a 0 means "the device was
  down", which the availability entries record separately — gating on
  it would page on every device outage instead of every code change).

Exit status: 0 clean, 1 when any compared config regressed past the
threshold, 2 on usage errors.  ``--selftest`` (what ``make perf-gate``
runs, standalone and in tier-1 via tests/test_health.py) proves the
gate's calibration against the committed fixture pair in
tests/data/perf_gate/: a seeded 20% regression MUST fail and seeded
noise-level (3%) deltas MUST pass — so the gate cannot silently decay
into always-green or always-red.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

DEFAULT_THRESHOLD = 0.10

#: units where SMALLER is better; everything else is throughput-like
LOWER_BETTER_UNITS = frozenset({"ms", "s", "seconds", "ns_per_op"})

FIXTURE_DIR = os.path.join(REPO, "tests", "data", "perf_gate")


def _latest_by_config(doc: dict) -> dict[str, dict]:
    """config -> last entry, from a ledger or BENCH_ALL-shaped doc."""
    rows = doc.get("entries")
    if rows is None:
        rows = doc.get("results", [])
    out: dict[str, dict] = {}
    for row in rows:
        cfg = row.get("config") or row.get("metric")
        if cfg is None or row.get("value") is None:
            continue
        out[cfg] = row  # later entries win: the ledger is append-order
    return out


def compare(
    old_doc: dict, new_doc: dict,
    threshold: float = DEFAULT_THRESHOLD,
    configs: list[str] | None = None,
) -> tuple[list[dict], list[dict]]:
    """Returns (regressions, comparisons): every config compared, and
    the subset whose delta crossed the threshold in the bad
    direction."""
    old = _latest_by_config(old_doc)
    new = _latest_by_config(new_doc)
    names = configs or sorted(set(old) & set(new))
    comparisons: list[dict] = []
    regressions: list[dict] = []
    for cfg in names:
        o, n = old.get(cfg), new.get(cfg)
        if o is None or n is None:
            continue
        try:
            ov, nv = float(o["value"]), float(n["value"])
        except (TypeError, ValueError):
            continue
        if ov <= 0 or nv <= 0:
            continue  # availability zeros, not perf points
        unit = n.get("unit") or o.get("unit") or ""
        lower_better = unit in LOWER_BETTER_UNITS
        # delta > 0 always means WORSE, whichever way the unit points
        delta = (nv - ov) / ov if lower_better else (ov - nv) / ov
        row = {
            "config": cfg, "unit": unit, "old": ov, "new": nv,
            "delta": round(delta, 4), "threshold": threshold,
            "regressed": delta > threshold,
        }
        comparisons.append(row)
        if row["regressed"]:
            regressions.append(row)
    return regressions, comparisons


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


#: the configs the stage-attribution rows explain: a regressed
#: ``height_latency_p95_<suffix>`` looks for sibling
#: ``height_stage_p95_<stage>_<suffix>`` rows (utils/critpath.py
#: taxonomy, appended by the same fleet smoke)
_LATENCY_PREFIX = "height_latency_p95_"
_STAGE_PREFIX = "height_stage_p95_"


def explain_stages(
    old_doc: dict, new_doc: dict, config: str
) -> list[dict]:
    """Attribute a ``height_latency_p95_*`` delta to its stage rows:
    for each critpath stage present on both sides, the absolute delta
    and its share of the latency regression — sorted worst first.
    Empty when ``config`` isn't a height-latency row or no stage rows
    exist (older ledgers), so callers can print-if-any."""
    if not config.startswith(_LATENCY_PREFIX):
        return []
    suffix = config[len(_LATENCY_PREFIX):]
    from cometbft_tpu.utils.critpath import STAGES

    old = _latest_by_config(old_doc)
    new = _latest_by_config(new_doc)
    try:
        lat_delta = float(new[config]["value"]) - float(
            old[config]["value"]
        )
    except (KeyError, TypeError, ValueError):
        lat_delta = 0.0
    out: list[dict] = []
    for stage in STAGES:
        cfg = f"{_STAGE_PREFIX}{stage}_{suffix}"
        o, n = old.get(cfg), new.get(cfg)
        if o is None or n is None:
            continue
        try:
            ov, nv = float(o["value"]), float(n["value"])
        except (TypeError, ValueError):
            continue
        delta = nv - ov
        out.append(
            {
                "stage": stage, "old": ov, "new": nv,
                "delta_ms": round(delta, 3),
                "share": (
                    round(delta / lat_delta, 4) if lat_delta else None
                ),
            }
        )
    out.sort(key=lambda r: -r["delta_ms"])
    return out


def _report(
    regressions: list[dict],
    comparisons: list[dict],
    old_doc: dict | None = None,
    new_doc: dict | None = None,
) -> None:
    for row in comparisons:
        mark = "REGRESSION" if row["regressed"] else "ok"
        print(
            f"perfdiff: {row['config']}: {row['old']:g} -> "
            f"{row['new']:g} {row['unit']} "
            f"({row['delta'] * 100:+.1f}% worse, threshold "
            f"{row['threshold'] * 100:.0f}%) {mark}",
            file=sys.stderr if row["regressed"] else sys.stdout,
        )
        if (
            row["regressed"]
            and old_doc is not None
            and new_doc is not None
        ):
            stages = explain_stages(old_doc, new_doc, row["config"])
            for s in stages:
                if s["delta_ms"] <= 0:
                    continue
                share = (
                    f" ({s['share'] * 100:.0f}% of the regression)"
                    if s["share"] is not None else ""
                )
                print(
                    f"perfdiff:   explained by {s['stage']}: "
                    f"{s['old']:g} -> {s['new']:g} ms "
                    f"(+{s['delta_ms']:g}ms){share}",
                    file=sys.stderr,
                )
    if not comparisons:
        print("perfdiff: no comparable configs", file=sys.stderr)


def selftest() -> int:
    """Prove the gate's calibration on the committed fixture pair:
    the seeded 20% regression must trip it, the seeded 3% noise must
    not.  This is what ``make perf-gate`` runs — deterministic (no
    live measurement), so it can gate ``make test``."""
    baseline = _load(os.path.join(FIXTURE_DIR, "baseline.json"))
    regressed = _load(os.path.join(FIXTURE_DIR, "regressed.json"))
    noise = _load(os.path.join(FIXTURE_DIR, "noise.json"))
    failures: list[str] = []
    regs, comps = compare(baseline, regressed)
    if not comps:
        failures.append("fixture pair produced no comparisons")
    # stage-attribution rows are seeded so ONE stage owns the latency
    # regression — the others hold steady by design, so the
    # every-config-must-trip check applies to the non-stage rows
    missed = [
        c["config"] for c in comps
        if not c["regressed"]
        and not c["config"].startswith(_STAGE_PREFIX)
    ]
    if missed:
        failures.append(
            f"seeded 20% regression NOT detected for: {missed}"
        )
    # the explanation path: the regressed latency row must be
    # attributable, and the seeded slow stage must rank first
    lat_cfg = "height_latency_p95_4node"
    if lat_cfg not in {r["config"] for r in regs}:
        failures.append(f"seeded {lat_cfg} regression not detected")
    stages = explain_stages(baseline, regressed, lat_cfg)
    if not stages:
        failures.append("stage rows produced no regression explanation")
    elif stages[0]["stage"] != "store_save":
        failures.append(
            "seeded store_save slowdown not named dominant "
            f"(got {stages[0]['stage']})"
        )
    regs_noise, comps_noise = compare(baseline, noise)
    if not comps_noise:
        failures.append("noise fixture produced no comparisons")
    if regs_noise:
        failures.append(
            "noise-level deltas tripped the gate: "
            f"{[r['config'] for r in regs_noise]}"
        )
    if failures:
        for f in failures:
            print(f"perf-gate selftest FAILED: {f}", file=sys.stderr)
        return 1
    print(
        f"perf-gate: ok — seeded 20% regression detected on "
        f"{len(comps)} config(s), {len(comps_noise)} noise-level "
        "delta(s) passed, store_save named dominant stage"
    )
    return 0


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("old", nargs="?", help="baseline ledger/BENCH file")
    ap.add_argument("new", nargs="?", help="candidate ledger/BENCH file")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="relative regression threshold (default 0.10)")
    ap.add_argument("--config", action="append", dest="configs",
                    help="limit to these config names (repeatable)")
    ap.add_argument("--selftest", action="store_true",
                    help="verify the gate against the seeded fixture "
                    "pair (make perf-gate)")
    args = ap.parse_args(argv)
    if args.selftest:
        return selftest()
    if not args.old or not args.new:
        ap.print_usage(sys.stderr)
        return 2
    try:
        old_doc, new_doc = _load(args.old), _load(args.new)
    except (OSError, ValueError) as exc:
        print(f"perfdiff: {exc}", file=sys.stderr)
        return 2
    regressions, comparisons = compare(
        old_doc, new_doc, threshold=args.threshold, configs=args.configs
    )
    _report(regressions, comparisons, old_doc, new_doc)
    return 1 if regressions else 0


if __name__ == "__main__":
    sys.exit(main())

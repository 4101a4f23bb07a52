"""perfledger: ONE merged record of every perf measurement this repo
has ever taken, with provenance.

Perf measurements used to live scattered across per-tool result files,
each with its own shape — comparing two runs meant re-reading several
formats by hand, and nothing could gate a regression.  This tool
normalizes them into ONE ledger file, the one ``CMT_TPU_PERF_LEDGER``
names (or ``--path``); there is no default location — a ledger belongs
to the machine it was measured on::

    {"schema": 1,
     "entries": [{"config", "value", "unit", "source", "measured",
                  "round"?, "dispatch_tier"?, "jit_compiles"?,
                  "steady_retraces"?, "platform"?, ...}, ...]}

Each entry is one measured point: what was measured (``config``), the
number (``value``/``unit``), where it came from (``source`` file or
tool), when, and the device-path provenance that makes the number
interpretable — the dispatch tier that actually ran, per-seam jit
compile counts, and steady-state retraces (a nonzero retrace means the
"steady state" wasn't).

Writers:
- ``bench.py`` and ``bench_all.py`` append every measured row
  automatically (source ``bench`` / ``bench_all``).
- ``python tools/perfledger.py --harvest`` back-fills from the
  result files the bench tools write at the repo root.

Readers: ``tools/perfdiff.py`` (the regression gate, ``make
perf-gate``) and the ``/debug/perf`` route, which serves the ledger
tail next to live tier health (cometbft_tpu/crypto/health.py).

Dedup key: (source, config, round, measured) — re-running a harvest
or a bench replaces its own point instead of duplicating it.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

SCHEMA = 1

#: provenance keys carried through from source rows verbatim when
#: present — everything a reader needs to interpret the number
PROVENANCE_KEYS = (
    "dispatch_tier", "dispatch_tiers", "jit_compiles", "steady_retraces",
    "warmup_compiles", "platform", "ndev", "per_chip_sigs_per_sec",
    "sigs_per_sec_per_chip", "sigs_per_sec", "latency_ms",
    "commits_per_sec", "nval", "batch", "note", "path", "vs_baseline",
    "target_ms", "rc",
    # attribution plane: the row's top-k leaf-frame hotspots sampled
    # while it was measured (utils/profiler.py) — what the number was
    # spending its host CPU on
    "hotspots",
)


def configured() -> bool:
    """Did the operator name a ledger (CMT_TPU_PERF_LEDGER)?  The
    best-effort writers record nothing when not."""
    from cometbft_tpu.crypto.health import perf_ledger_path

    return perf_ledger_path() is not None


def default_path() -> str:
    from cometbft_tpu.crypto.health import perf_ledger_path

    path = perf_ledger_path()
    if path is None:
        raise ValueError(
            "no perf ledger configured: set CMT_TPU_PERF_LEDGER or "
            "pass a path"
        )
    return path


def load(path: str | None = None) -> dict:
    path = path or default_path()
    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return {"schema": SCHEMA, "entries": []}
    doc.setdefault("schema", SCHEMA)
    doc.setdefault("entries", [])
    return doc


def entry_key(e: dict) -> tuple:
    return (
        e.get("source"), e.get("config"), e.get("round"), e.get("measured")
    )


def append(entries: list[dict], path: str | None = None) -> dict:
    """Atomically merge ``entries`` into the ledger.  A same-key entry
    REPLACES its predecessor and moves to the END of the list — append
    order IS recency (perfdiff's latest-per-config and the
    /debug/perf ledger tail both read positionally, so an in-place
    replace would leave a stale harvest entry looking newest)."""
    path = path or default_path()
    doc = load(path)
    merged: dict[tuple, dict] = {}  # insertion-ordered: last write last
    for e in entries:
        merged[entry_key(e)] = e
    doc["entries"] = [
        e for e in doc["entries"] if entry_key(e) not in merged
    ] + list(merged.values())
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)
    return doc


def tail(n: int = 10, path: str | None = None) -> list[dict]:
    return load(path)["entries"][-n:]


def make_entry(
    config: str, value, unit: str, source: str, row: dict | None = None,
    **extra,
) -> dict:
    """Normalize one measured point; ``row`` contributes whatever
    PROVENANCE_KEYS it carries."""
    e: dict = {"config": config, "value": value, "unit": unit,
               "source": source}
    row = row or {}
    e["measured"] = (
        extra.pop("measured", None)
        or row.get("measured")
        or row.get("measured_at")
    )
    for k in PROVENANCE_KEYS:
        if k in row and k not in e:
            e[k] = row[k]
    e.update(extra)
    return e


# -- bench-side helpers (called by bench.py / bench_all.py) ---------------

def headline_entry(result: dict, source: str = "bench") -> dict:
    """bench.py's headline JSON -> one ledger entry (provenance: tier
    and compile counts when the device path ran)."""
    e = make_entry(
        result.get("metric", "ed25519_batch_verify_throughput"),
        result.get("value"), result.get("unit", "sigs/sec"), source,
        row=result,
    )
    for k in ("generic_sigs_per_sec", "keyed_sigs_per_sec",
              "keyed_cols_impl", "partial", "error"):
        if k in result:
            e[k] = result[k]
    return e


def append_rows(
    rows: list[dict], source: str, path: str | None = None,
) -> None:
    """BENCH_ALL-shaped rows (config/value/unit + extras) -> ledger.
    Best-effort by design: the ledger must never fail a bench."""
    if path is None and not configured():
        return
    try:
        append(
            [
                make_entry(
                    r.get("config", r.get("metric", "unknown")),
                    r.get("value"), r.get("unit", ""), source, row=r,
                )
                for r in rows
            ],
            path,
        )
    except Exception as exc:  # noqa: BLE001 — provenance only
        print(f"perfledger append failed (ignored): {exc}",
              file=sys.stderr)


# -- the historical harvest ----------------------------------------------

def _read(path: str):
    try:
        with open(path) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def harvest(repo: str = REPO) -> list[dict]:
    """Normalize the result files the bench tools write at the repo
    root (BENCH_ALL.json, MULTICHIP_KEYED.json, BENCH_MICRO.json) into
    ledger entries (idempotent: stable keys, so re-harvesting replaces
    rather than duplicates)."""
    entries: list[dict] = []

    # BENCH_ALL.json / MULTICHIP_KEYED.json: config rows
    for name in ("BENCH_ALL.json", "MULTICHIP_KEYED.json"):
        doc = _read(os.path.join(repo, name))
        if not doc:
            continue
        for row in doc.get("results", []):
            entries.append(
                make_entry(
                    row.get("config", row.get("metric", "unknown")),
                    row.get("value"), row.get("unit", ""), name, row=row,
                )
            )
    # BENCH_MICRO.json: host micro-bench rows
    doc = _read(os.path.join(repo, "BENCH_MICRO.json"))
    if doc:
        for row in doc.get("results", []):
            entries.append(
                make_entry(
                    row.get("bench", "unknown"), row.get("ops_per_sec"),
                    "ops/sec", "BENCH_MICRO.json",
                    ns_per_op=row.get("ns_per_op"),
                )
            )
    return entries


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--path", help="ledger file (default: "
                    "CMT_TPU_PERF_LEDGER)")
    ap.add_argument("--harvest", action="store_true",
                    help="merge the bench tools' result files into "
                    "the ledger")
    ap.add_argument("--tail", type=int, metavar="N",
                    help="print the last N entries")
    args = ap.parse_args(argv)
    path = args.path or default_path()
    if args.harvest:
        doc = append(harvest(), path)
        print(f"perfledger: {len(doc['entries'])} entries in {path}",
              file=sys.stderr)
    if args.tail:
        print(json.dumps(tail(args.tail, path), indent=1))
    if not args.harvest and not args.tail:
        doc = load(path)
        print(f"perfledger: {len(doc['entries'])} entries in {path} "
              "(use --harvest / --tail N)", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

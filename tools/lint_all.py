"""lint_all: every static lint in ONE process, each file parsed once.

The six AST lints — lockcheck (guarded-by), jitcheck (device plane),
determcheck (replay determinism), hotpathcheck (critical-path
blocking), envcheck (knob registry), and trustcheck (wire-ingress
taint) — each walk the same ``cometbft_tpu`` tree.  Run as six
processes (`make lockcheck && make jitcheck && ...`) every one of
them re-reads, re-parses, and re-tokenizes every file.  Run here,
lintlib's content-keyed ``parse_cached`` / ``comments_by_line``
memos mean each file's AST is built once and shared: the first lint
pays the parse, the other five get cache hits.

This is the `make lint` umbrella.  The `make test` flow gets the
same six via the single ``metrics_lint main()`` gate (which also
checks the metrics series registry); this entrypoint exists for the
edit-lint loop where you want all verdicts in one fast command.

The wall time of the full six-lint pass is appended to the perf
ledger as ``lint_wall_seconds`` (source ``lint_all``) — perfdiff
treats ``seconds`` as lower-is-better, so `make perf-gate` catches a
lint that quietly goes quadratic on the growing tree the same way it
catches a verify regression.  Ledger writes are best-effort: the
lint verdict must never depend on ledger I/O.
"""

from __future__ import annotations

import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools import (  # noqa: E402 — path bootstrap above
    determcheck,
    envcheck,
    hotpathcheck,
    jitcheck,
    lintlib,
    lockcheck,
    trustcheck,
)

#: gate order: cheap registry lints first, call-graph walks last, so
#: the common "typo in a registry" failure reports in milliseconds
LINTS = (lockcheck, jitcheck, envcheck, determcheck, hotpathcheck,
         trustcheck)


def _record_wall(wall: float) -> None:
    """Best-effort ``lint_wall_seconds`` ledger row for perfdiff."""
    try:
        from tools import perfledger

        if not perfledger.configured():
            return
        perfledger.append([
            perfledger.make_entry(
                "lint_wall_seconds", round(wall, 3), "seconds",
                "lint_all",
                measured=time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
                note=f"{len(LINTS)} lints, shared-AST single pass",
            )
        ])
    except Exception as exc:  # the ledger must never fail the lint
        print(f"lint_all: ledger append failed (ignored): {exc}",
              file=sys.stderr)


def main(argv: list[str] | None = None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    t0 = time.perf_counter()
    rc = 0
    for lint in LINTS:
        if lint.main(list(argv)) != 0:
            rc = 1
    wall = time.perf_counter() - t0
    parsed = len(lintlib._PARSE_CACHE)
    print(
        f"lint_all: {len(LINTS)} lints "
        f"{'green' if rc == 0 else 'RED'} in {wall:.2f}s "
        f"({parsed} files parsed once, shared across lints)"
    )
    if rc == 0:
        _record_wall(wall)
    return rc


if __name__ == "__main__":
    sys.exit(main())

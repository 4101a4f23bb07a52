"""Share of the root spans' time that the named leaf spans cover, from
the program's span ring: what the stage spans still cannot see.

``params``: ``{"roots": [...], "leaves": [...], "last": 200}``.  Over
the ``last`` newest root spans: the union of the leaves that ran on the
root's own thread inside it, over the root's duration, summed.  A leaf
on another thread is another root's (or nobody's) and is not counted;
leaves that nest are counted once.  None under ``span_ms.MIN_PER``
roots, and where no leaf is in the ring at all."""

from benchmark.readers import span_ms


def covered_pct(events: list[dict], roots: list, leaves: list,
                last: int) -> float | None:
    cut_events, n = span_ms.tail(events, tuple(roots), last)
    if n < span_ms.MIN_PER:
        return None
    by_thread: dict[int, list] = {}
    for e in cut_events:
        if e["name"] in leaves:
            by_thread.setdefault(e["tid"], []).append(
                (e["ts"], e["ts"] + e["dur"])
            )
    if not by_thread:
        return None  # a program without these stage spans
    for spans in by_thread.values():
        spans.sort()
    total = covered = 0.0
    for root in cut_events:
        if root["name"] not in roots:
            continue
        lo, hi = root["ts"], root["ts"] + root["dur"]
        total += hi - lo
        end = lo
        for s, e in by_thread.get(root["tid"], ()):
            if s >= hi:
                break
            s, e = max(s, end), min(e, hi)
            if e > s:
                covered += e - s
                end = e
    return 100.0 * covered / total if total > 0 else None


def read(ctx: dict, params: dict) -> float | None:
    return covered_pct(span_ms.ring(), params["roots"], params["leaves"],
                       int(params.get("last", 200)))

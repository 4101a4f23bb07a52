"""Milliseconds a stage of the program takes per item, from the
program's own span ring (``cometbft_tpu/utils/trace.py`` ``TRACER``).

``params``: ``{"spans": [...], "per": "<span>", "last": 200}``.  The
ring's events from the start of the ``last``-th newest ``per`` span on;
the summed duration of the ``spans`` there over the count of ``per``
spans there.  So each metric is milliseconds of that stage per commit
or per launched batch, and the metrics of a cell add up against its
step.  Read after the window: the ring's newest events are the window's
steady state (the traced slice is its last stretch).  None under
``MIN_PER`` ``per`` spans, and for a program without these spans."""

MIN_PER = 10


def ring() -> list[dict]:
    """The program's span events, oldest first."""
    from cometbft_tpu.utils.trace import TRACER

    return TRACER.events()


def tail(events: list[dict], per: tuple, last: int) -> tuple[list, int]:
    """-> (the events that start at or after the start of the
    ``last``-th newest span named in ``per``, the count of ``per``
    spans among them)."""
    starts = sorted(e["ts"] for e in events if e["name"] in per)
    if not starts:
        return [], 0
    cut = starts[-last] if len(starts) >= last else starts[0]
    cut_events = [e for e in events if e["ts"] >= cut]
    return cut_events, sum(e["name"] in per for e in cut_events)


def per_item_ms(events: list[dict], spans: list, per: str,
                last: int) -> float | None:
    cut_events, n = tail(events, (per,), last)
    if n < MIN_PER:
        return None
    found = [e["dur"] for e in cut_events if e["name"] in spans]
    if not found:
        return None
    return sum(found) / n / 1e3  # the ring keeps microseconds


def read(ctx: dict, params: dict) -> float | None:
    return per_item_ms(ring(), params["spans"], params["per"],
                       int(params.get("last", 200)))

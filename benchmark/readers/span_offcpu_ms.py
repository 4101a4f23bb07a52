"""Milliseconds a stage of the program spends off its thread's CPU per
item, from the program's span ring (``cometbft_tpu/utils/trace.py``).

``params``: ``{"spans": [...], "less": [...], "per": "<span>", "last":
200}``.  A span's ``tdur`` is its thread's CPU time over the span, so
``dur - tdur`` is the time its thread was ready or blocked but not
running: the wait for the interpreter lock, the OS's preemption, I/O,
a device wait.  Over ``span_ms.tail``'s cut: the summed ``dur - tdur``
of the ``spans``, less the same summed over the ``less`` spans (a wait
inside them that is not the question, such as the device's), over the
count of ``per`` spans.  Events without ``tdur`` are skipped.  None
under ``span_ms.MIN_PER`` ``per`` spans, and where none of the
``spans`` has a ``tdur`` (a program that records none)."""

from benchmark.readers import span_ms


def offcpu_us(events: list[dict], names) -> tuple[float, int]:
    """-> (summed ``dur - tdur`` in microseconds, how many) of the
    events named in ``names`` that carry ``tdur``."""
    off = [e["dur"] - e["tdur"] for e in events
           if e["name"] in names and "tdur" in e]
    return sum(off), len(off)


def per_item_offcpu_ms(events: list[dict], spans: list, less: list,
                       per: str, last: int) -> float | None:
    cut_events, n = span_ms.tail(events, (per,), last)
    if n < span_ms.MIN_PER:
        return None
    off, found = offcpu_us(cut_events, spans)
    if not found:
        return None
    return (off - offcpu_us(cut_events, less)[0]) / n / 1e3


def read(ctx: dict, params: dict) -> float | None:
    return per_item_offcpu_ms(
        span_ms.ring(), params["spans"], params.get("less", []),
        params["per"], int(params.get("last", 200)),
    )

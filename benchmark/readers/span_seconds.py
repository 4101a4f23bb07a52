"""Seconds the program spent in its own span ``params["span"]`` during
set-up (``cometbft_tpu/utils/trace.py`` ring, read at the window's
start)."""


def read(ctx: dict, params: dict) -> float | None:
    spans = ctx["spans_s"].get(params["span"]) or []
    return sum(spans) if spans else None

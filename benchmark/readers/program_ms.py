"""Mean device time of one launch of the programs whose name matches
``params["pattern"]``: the sum of their events' device durations in the
traced slice over their launch count."""

import re


def matching(ctx: dict, pattern: str) -> tuple[int, float]:
    """-> (launches, device seconds) of the matching programs."""
    launches, seconds = 0, 0.0
    for name, p in (ctx["trace"] or {}).get("programs", {}).items():
        if re.search(pattern, name):
            launches += p["launches"]
            seconds += p["seconds"]
    return launches, seconds


def read(ctx: dict, params: dict) -> float | None:
    launches, seconds = matching(ctx, params["pattern"])
    if not launches:
        return None
    return 1e3 * seconds / launches

"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

``load`` turns the file into plain lists with nothing but JAX's own
``ProfileData``; ``reduce`` works on those lists, so a test can hand it
a trace written out by hand.  On a TPU the device planes are named
``/device:TPU:<n>``; each has a line of whole programs ("XLA Modules",
one event per launch, named ``jit_<program>(<fingerprint>)``) and a
line of single operations ("XLA Ops").  A verify program runs some
50,000 operations a launch (PR 25: 5.7 million events in a 2-second
slice, three minutes to walk in Python), so of the operation line only
the first ``MAX_OP_EVENTS`` are read — enough for the breakdown's "which
operations take the time" — and busy time is the union of the program
events' intervals (of the operation events' where a plane has no program
line), averaged over the device planes.  On the slice read in full the
two unions differed by 0.03% (PR 25): a program occupies the core from
its first operation to its last.  The window is the span the host's
annotations cover.
"""

from __future__ import annotations

import glob
import itertools
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"
MAX_OP_EVENTS = 200_000


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load(path: str, serialized: bytes | None = None) -> list[dict]:
    """-> [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]"""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(serialized)
            if serialized is not None else ProfileData.from_file(path))
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": [
                        (ev.name, float(ev.start_ns), float(ev.duration_ns))
                        for ev in itertools.islice(
                            line.events,
                            MAX_OP_EVENTS if line.name in OP_LINES else None,
                        )
                    ],
                }
                for line in plane.lines
            ],
        }
        for plane in data.planes
    ]


def union_seconds(intervals: list[tuple[float, float]]) -> float:
    """Length in seconds of the union of ``(start_ns, end_ns)``."""
    total = 0.0
    end = None
    for s, e in sorted(intervals):
        if end is None or s > end:
            total += e - s
            end = e
        elif e > end:
            total += e - end
            end = e
    return total / 1e9


def program_name(event_name: str) -> str:
    """``jit_verify_keyed_w8_b128(1234)`` -> ``verify_keyed_w8_b128``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def op_name(event_name: str) -> str:
    """An operation event carries its whole HLO text: ``%while.67 =
    (u32[]...) while(...)`` -> ``while.67``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _line(plane: dict, names: tuple) -> dict | None:
    for line in plane["lines"]:
        if line["name"] in names:
            return line
    return None


def _clip(events: list, lo: float | None, hi: float | None) -> list:
    """Events cut to ``[lo, hi]`` ns; those wholly outside dropped."""
    out = []
    for name, start, dur in events:
        s = start if lo is None else max(start, lo)
        e = start + dur if hi is None else min(start + dur, hi)
        if e > s:
            out.append((name, s, e - s))
    return out


def host_spans(planes: list[dict], prefixes: tuple) -> list[tuple]:
    """The host's annotations whose names start with one of
    ``prefixes``: [(name, start_ns, duration_ns)], by start."""
    out = []
    for plane in planes:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            out += [ev for ev in line["events"] if ev[0].startswith(prefixes)]
    return sorted(out, key=lambda ev: ev[1])


def reduce(planes: list[dict], annotations: tuple = ("entry.", "gen."),
           top: int = 10) -> dict:
    """-> {"devices", "window_s", "busy_s", "programs": {name:
    {"launches", "seconds"}}, "device_ops": [[name, seconds]],
    "idle_gaps": [[name, seconds]]}.

    The window runs from the first annotation's start to the last one's
    end; device events are clipped to it.  ``busy_s`` is averaged over
    the device planes.  An idle gap is a stretch of the window in which
    no program ran on the first device; its seconds go to the
    annotations that overlap it, each by its overlap (``host`` for what
    none covers).  ``device_ops``
    are the operations of the launches read (see MAX_OP_EVENTS), by
    their time there."""
    spans = host_spans(planes, annotations)
    lo = spans[0][1] if spans else None
    hi = max(s + d for _, s, d in spans) if spans else None
    devices = [p for p in planes if DEVICE_PLANE.match(p["name"])]
    busy, programs, ops = [], {}, {}
    first_intervals: list[tuple[float, float]] = []
    for n, plane in enumerate(devices):
        mods = _clip((_line(plane, MODULE_LINES) or {"events": []})["events"],
                     lo, hi)
        opl = _line(plane, OP_LINES)
        op_events = _clip(opl["events"], lo, hi) if opl else []
        base = mods or op_events
        intervals = [(s, s + d) for _, s, d in base]
        busy.append(union_seconds(intervals))
        if n == 0:
            first_intervals = intervals
        for name, _, dur in mods:
            p = programs.setdefault(program_name(name),
                                    {"launches": 0, "seconds": 0.0})
            p["launches"] += 1
            p["seconds"] += dur / 1e9
        for name, _, dur in op_events:
            name = op_name(name)
            ops[name] = ops.get(name, 0.0) + dur / 1e9
    if lo is None and first_intervals:
        lo = min(s for s, _ in first_intervals)
        hi = max(e for _, e in first_intervals)
    window_s = (hi - lo) / 1e9 if lo is not None else 0.0
    by_time = ops or {k: v["seconds"] for k, v in programs.items()}
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "programs": programs,
        "device_ops": [
            [k, v] for k, v in
            sorted(by_time.items(), key=lambda kv: -kv[1])[:top]
        ],
        "idle_gaps": _idle_gaps(first_intervals, spans, lo, hi, top),
    }


def _idle_gaps(intervals, spans, lo, hi, top: int) -> list:
    """Idle seconds of the window by what the host was doing: each gap
    shared out among the annotations that overlap it, what none covers
    under ``host``."""
    if lo is None:
        return []
    gaps, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    by_name: dict[str, float] = {}
    for g_lo, g_hi in gaps:
        left = g_hi - g_lo
        for name, s, d in spans:
            if s >= g_hi:
                break
            cover = min(g_hi, s + d) - max(g_lo, s)
            if cover > 0:
                by_name[name] = by_name.get(name, 0.0) + cover / 1e9
                left -= cover
        if left > 0:
            by_name["host"] = by_name.get("host", 0.0) + left / 1e9
    return [
        [k, v] for k, v in
        sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    ]


def describe(planes: list[dict]) -> list[dict]:
    """Planes and lines with their event counts: one look by hand
    before trusting the reduction on a new device."""
    return [
        {"plane": p["name"],
         "lines": {ln["name"]: len(ln["events"]) for ln in p["lines"]}}
        for p in planes
    ]

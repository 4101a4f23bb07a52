"""From a profiler trace (``.xplane.pb``) to the numbers the readers use.

``load`` turns the file into plain lists with nothing but JAX's own
``ProfileData``; ``reduce`` works on those lists, so a test can hand it
a trace written out by hand.  On a TPU the device planes are named
``/device:TPU:<n>``; each has a line of whole programs ("XLA Modules",
one event per launch, named ``jit_<program>(<fingerprint>)``) and a
line of single operations ("XLA Ops").  A verify program runs some
50,000 operations a launch (PR 25: 5.7 million events in a 2-second
slice, three minutes to walk in Python with their names), nearly all of
them inside a ``while``: of the operation line ``load`` keeps the
top-level events alone (``_top_level``: an event inside the one kept
before it is passed over with its name unread), ~3,100 a launch, so
the slice is read whole; ``MAX_OP_EVENTS`` caps what is KEPT, against a
line that nests nothing.  Busy time is the union of the program
events' intervals (of the operation events' where a plane has no program
line), averaged over the device planes.  On the slice read in full the
two unions differed by 0.03% (PR 25): a program occupies the core from
its first operation to its last.  The window is the span the harness's
own annotations (``entry.`` / ``gen.``) cover; the program's spans, which
stand in the same host plane since PR 26, only NAME the idle time.
"""

from __future__ import annotations

import glob
import heapq
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
MODULE_LINES = ("XLA Modules",)
OP_LINES = ("XLA Ops",)
HOST_PLANE = "/host:CPU"
#: top-level operation events KEPT of a line.  The commit cell's 0.5 s
#: slice holds 162,188 (52 launches of ~3,100; PR 35): room for a
#: commit six times shorter
MAX_OP_EVENTS = 1_000_000
#: the program's span names (``cometbft_tpu/utils/trace.py``), by prefix:
#: what an idle gap is named by, beside the harness's own annotations
PROGRAM_SPANS = ("verify_commit", "verify_queue/", "verify/", "batch_verify",
                 "device_", "light/", "blocksync/", "table_build")


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(
        os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")
    ))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def _top_level(events, cap: int) -> list:
    """The events of an operation line that do not lie inside the one
    kept before them, as ``(name, start_ns, duration_ns)``, at most
    ``cap``.  A loop's event comes before what runs inside it, so on a
    line in time order this keeps exactly the top level; on any other
    it keeps more, never less (``top_level_times`` sorts and decides).
    The name — an operation's whole HLO text — is read only of an event
    that is kept."""
    out: list[tuple] = []
    names: dict[str, str] = {}  # one string a distinct operation
    lo = hi = None
    for ev in events:
        start = float(ev.start_ns)
        end = start + float(ev.duration_ns)
        if hi is not None and lo <= start and end <= hi:
            continue
        name = ev.name
        out.append((names.setdefault(name, name), start, end - start))
        lo, hi = start, end
        if len(out) >= cap:
            break
    return out


def load(path: str, serialized: bytes | None = None) -> list[dict]:
    """-> [{"name", "lines": [{"name", "events": [(name, start_ns,
    duration_ns)]}]}]; of an operation line the top-level events."""
    from jax.profiler import ProfileData

    data = (ProfileData.from_serialized_xspace(serialized)
            if serialized is not None else ProfileData.from_file(path))
    return [
        {
            "name": plane.name,
            "lines": [
                {
                    "name": line.name,
                    "events": (
                        _top_level(line.events, MAX_OP_EVENTS)
                        if line.name in OP_LINES else
                        [(ev.name, float(ev.start_ns), float(ev.duration_ns))
                         for ev in line.events]
                    ),
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


def top_level_times(events: list) -> dict[str, float]:
    """Seconds by operation, each instant counted ONCE.  On a device's
    operation line a ``while`` event holds the events of what runs
    inside it; summed by name alone, nested or not, they read twice the
    launch (PR 27).  Here an event that lies inside another of the same
    line is LEFT OUT and a top-level event keeps its whole time, so the
    names of one launch add up to no more than its program's time.
    (The other way — ``parent/child`` names with the child's time taken
    off the parent's — was tried on the chip, PR 32: a loop's time
    scatters over hundreds of fusion names and 84% of a commit launch
    landed under ``other``.)  ``events``: [(name, start_ns,
    duration_ns)] of ONE line."""
    out: dict[str, float] = {}
    end = None
    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        if end is not None and start < end:
            continue  # inside the top-level event that began before it
        end = start + dur
        name = op_name(name)
        out[name] = out.get(name, 0.0) + dur / 1e9
    return out


def reduce(planes: list[dict], annotations: tuple = ("entry.", "gen."),
           naming: tuple = PROGRAM_SPANS, top: int = 10) -> dict:
    """-> {"devices", "window_s", "busy_s", "programs": {name:
    {"launches", "seconds"}}, "device_ops": [[name, seconds]],
    "idle_gaps": [[name, seconds]]}.

    The window runs from the first of the ``annotations`` to the end of
    the last; device events are clipped to it.  ``busy_s`` is averaged
    over the device planes.  An idle gap is a stretch of the window in
    which no program ran on the first device; every instant of it goes
    to ONE host annotation — of ``annotations`` or ``naming`` — the one
    that started last among those covering it, on any thread (``host``
    where none does), so the names' seconds add up to the gaps'.
    ``device_ops`` are the top-level operations of the window by their
    time there (``top_level_times``: what lies inside a loop is the
    loop's, each instant once), averaged over the device planes as
    ``busy_s`` is.  Both lists are cut to
    ``top`` entries, the last of them ``other`` with what the cut left
    out, so each list still adds up."""
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
        for name, sec in top_level_times(op_events).items():
            ops[name] = ops.get(name, 0.0) + sec / len(devices)
    if lo is None and first_intervals:
        lo = min(s for s, _ in first_intervals)
        hi = max(e for _, e in first_intervals)
    window_s = (hi - lo) / 1e9 if lo is not None else 0.0
    by_time = ops or {k: v["seconds"] for k, v in programs.items()}
    named = host_spans(planes, annotations + naming)
    return {
        "devices": len(devices),
        "window_s": window_s,
        "busy_s": sum(busy) / len(busy) if busy else 0.0,
        "programs": programs,
        "device_ops": _top(by_time, top),
        "idle_gaps": _top(_idle_gaps(first_intervals, named, lo, hi), top),
    }


def _top(by_name: dict[str, float], top: int) -> list:
    """The ``top`` largest as [[name, seconds]]; where more were found,
    the last entry is ``other``: the sum of what is not listed."""
    ranked = sorted(by_name.items(), key=lambda kv: -kv[1])
    if len(ranked) > top:
        rest = sum(v for _, v in ranked[top - 1:])
        ranked = ranked[:top - 1] + [("other", rest)]
    return [[k, v] for k, v in ranked]


def _idle_gaps(intervals, spans, lo, hi) -> dict[str, float]:
    """Idle seconds of the window by what the host was doing: each
    instant of a gap under the annotation that started last among those
    covering it (the innermost, and across threads the most recent),
    what none covers under ``host``.  ``spans`` sorted by start."""
    if lo is None:
        return {}
    gaps, end = [], lo
    for s, e in sorted(intervals):
        if s > end:
            gaps.append((end, s))
        end = max(end, e)
    if hi > end:
        gaps.append((end, hi))
    by_name: dict[str, float] = {}
    for g_lo, g_hi in gaps:
        over = []
        for name, s, d in spans:
            if s >= g_hi:
                break
            if s + d > g_lo:
                over.append((s, s + d, name))
        cuts = sorted({g_lo, g_hi} | {
            t for s, e, _ in over for t in (s, e) if g_lo < t < g_hi
        })
        # a sweep: the open spans in a heap, on top the latest start
        # (of two that start together, the shorter)
        open_spans: list[tuple] = []
        nxt = 0
        for a, b in zip(cuts, cuts[1:]):
            while nxt < len(over) and over[nxt][0] <= a:
                s, e, name = over[nxt]
                heapq.heappush(open_spans, (-s, e, name))
                nxt += 1
            while open_spans and open_spans[0][1] <= a:
                heapq.heappop(open_spans)
            name = open_spans[0][2] if open_spans else "host"
            by_name[name] = by_name.get(name, 0.0) + (b - a) / 1e9
    return by_name


def describe(planes: list[dict]) -> list[dict]:
    """Planes and lines with their event counts: one look by hand
    before trusting the reduction on a new device."""
    return [
        {"plane": p["name"],
         "lines": {ln["name"]: len(ln["events"]) for ln in p["lines"]}}
        for p in planes
    ]

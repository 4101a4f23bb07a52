"""The stage metrics (ISSUE 26): the three readers of the program's span
ring on rings written by hand, and a traced CPU rehearsal of each cell
that must report the span metrics and leave ``launch_overhead_ms.*``
out (no device plane, so no device time to take from the wall)."""

from __future__ import annotations

import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.readers import (  # noqa: E402
    launch_overhead, span_coverage, span_ms,
)

# the rehearsal's own tiny cells and its switch to the CPU kernels
from test_benchmark_rehearsal import (  # noqa: E402,F401
    CELLS, cpu_stands_in, drive,
)


def ev(name: str, ts: float, dur: float, tid: int = 1) -> dict:
    return {"name": name, "ts": ts, "dur": dur, "tid": tid}


def ring(n: int, step_us: float = 10_000.0) -> list[dict]:
    """``n`` steps, oldest first, each: a root of 8 ms on thread 1 with
    leaves of 1 ms and 3 ms inside it, a 2 ms leaf of the same name on
    thread 2, and a 0.5 ms span of another thread that ends first."""
    out = []
    for k in range(n):
        t = k * step_us
        out += [
            ev("feeder", t - 600, 500, tid=3),
            ev("leaf/a", t + 100, 1_000),
            ev("leaf/a", t + 200, 2_000, tid=2),
            ev("leaf/b", t + 2_000, 3_000),
            ev("root", t, 8_000),
        ]
    return out


def test_span_ms_sums_the_listed_spans_over_the_per_spans():
    events = ring(12)
    # both threads' leaf/a count: 1 + 2 ms a root
    assert span_ms.per_item_ms(events, ["leaf/a"], "root", 200) == (
        pytest.approx(3.0)
    )
    assert span_ms.per_item_ms(events, ["leaf/a", "leaf/b"], "root",
                               200) == pytest.approx(6.0)
    assert span_ms.per_item_ms(events, ["root"], "root", 200) == (
        pytest.approx(8.0)
    )
    assert span_ms.per_item_ms(events, ["no/such"], "root", 200) is None
    assert span_ms.per_item_ms(events, ["leaf/a"], "no/such", 200) is None


def test_span_ms_cuts_at_the_start_of_the_last_th_newest_per_span():
    events = ring(30)
    for e in events:  # the 20 oldest steps ran three times slower
        if e["ts"] < 20 * 10_000.0 - 1_000 and e["name"] == "leaf/b":
            e["dur"] *= 3
    assert span_ms.per_item_ms(events, ["leaf/b"], "root", 10) == (
        pytest.approx(3.0)
    )
    assert span_ms.per_item_ms(events, ["leaf/b"], "root", 200) == (
        pytest.approx((20 * 9.0 + 10 * 3.0) / 30)
    )
    # a span that started before the cut belongs to the step before:
    # the feeder of the cut's own step began 0.6 ms ahead of its root
    cut, n = span_ms.tail(events, ("root",), 10)
    assert n == 10
    assert sum(e["name"] == "feeder" for e in cut) == 9


def test_span_ms_reads_nothing_under_ten_per_spans():
    assert span_ms.per_item_ms(ring(9), ["leaf/a"], "root", 200) is None
    assert span_ms.per_item_ms(ring(10), ["leaf/a"], "root", 200) == (
        pytest.approx(3.0)
    )
    assert span_ms.per_item_ms([], ["leaf/a"], "root", 200) is None


def test_span_coverage_counts_leaves_on_the_roots_own_thread_once():
    events = ring(12)
    # thread 1: 1 ms + 3 ms of 8 ms; the 2 ms leaf/a on thread 2 is
    # not this root's
    assert span_coverage.covered_pct(
        events, ["root"], ["leaf/a", "leaf/b"], 200
    ) == pytest.approx(50.0)
    # leaves that nest or overlap are a union, and a leaf that runs
    # past its root is cut to it
    for k in range(12):
        t = k * 10_000.0
        events.append(ev("leaf/c", t + 2_500, 1_000))   # inside leaf/b
        events.append(ev("leaf/c", t + 7_000, 2_000))   # 1 ms inside
    assert span_coverage.covered_pct(
        events, ["root"], ["leaf/a", "leaf/b", "leaf/c"], 200
    ) == pytest.approx(100.0 * 5 / 8)
    # a program that has the root and none of the leaves: nothing to read
    assert span_coverage.covered_pct(
        events, ["root"], ["no/such"], 200
    ) is None
    assert span_coverage.covered_pct(ring(9), ["root"], ["leaf/a"],
                                     200) is None


def test_span_coverage_takes_two_roots_each_on_its_thread():
    events = []
    for k in range(6):
        t = k * 10_000.0
        events += [
            ev("stage/x", t + 100, 900, tid=7),
            ev("prepare", t, 1_000, tid=7),           # 90% covered
            ev("stage/x", t + 2_000, 1_000, tid=8),
            ev("launch", t + 2_000, 4_000, tid=8),    # 25% covered
        ]
    assert span_coverage.covered_pct(
        events, ["prepare", "launch"], ["stage/x"], 200
    ) == pytest.approx(100.0 * (900 + 1_000) / 5_000)


def test_launch_overhead_is_the_launch_wall_less_the_device_time(
        monkeypatch):
    events = [ev("batch_verify", k * 20_000.0, 14_000.0)
              for k in range(12)]
    monkeypatch.setattr(span_ms, "ring", lambda: events)
    params = {"pattern": "^verify_keyed_w", "last": 200}
    ctx = {"trace": {"programs": {
        "verify_keyed_w8_b128": {"launches": 4, "seconds": 0.050},
        "table_build_w8": {"launches": 1, "seconds": 9.0},
    }}}
    assert launch_overhead.read(ctx, params) == pytest.approx(1.5)
    # no device plane, or no launch of the program in the slice
    assert launch_overhead.read({"trace": {"programs": {}}}, params) is None
    assert launch_overhead.read({"trace": None}, params) is None
    monkeypatch.setattr(span_ms, "ring", lambda: events[:9])
    assert launch_overhead.read(ctx, params) is None


def test_the_readers_read_the_programs_own_ring(monkeypatch):
    from cometbft_tpu.utils.trace import SpanTracer
    from cometbft_tpu.utils import trace as trace_mod

    t = SpanTracer(capacity=256, enabled=True)
    monkeypatch.setattr(trace_mod, "TRACER", t)
    for _ in range(12):
        with t.span("root"):
            with t.span("leaf/a"):
                pass
    value = span_ms.read({}, {"spans": ["leaf/a"], "per": "root"})
    assert value is not None and 0 <= value < 1.0
    pct = span_coverage.read({}, {"roots": ["root"], "leaves": ["leaf/a"]})
    assert 0 < pct <= 100.0


def stage_metrics(cell: str) -> list[dict]:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    return [m for m in manifest["per_layer"]
            if m["source"] == "program_span" and m["name"] != "table_build_s"
            and cell in m["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_rehearsal_reports_the_stage_metrics(cpu_stands_in, name):
    line = drive(name, trace=True)
    assert line["correct"] is True
    mine = stage_metrics(name)
    assert len(mine) >= 5
    for m in mine:
        if m["name"].startswith("launch_overhead_ms."):
            assert m["name"] not in line["metrics"]  # no device plane
            continue
        got = line["metrics"][m["name"]]
        assert got["unit"] == m["unit"]
        if m["unit"] == "%":
            assert 0 < got["value"] <= 100.0, m["name"]
        else:
            assert got["value"] > 0, m["name"]
    # what was there still reads, the set-up spans among them
    assert line["metrics"]["table_build_s"]["value"] > 0
    assert line["metrics"]["device_sig_pct." + name.split(".")[1]][
        "value"] == 100.0

"""The off-CPU metrics: ``span_offcpu_ms`` on rings written by hand,
and the launch's metric specs read off the program's own ring after
real ``verify()`` calls (the generic tier's program swapped for an
all-true one, so nothing compiles).  No CPU number here is a device
number."""

from __future__ import annotations

import glob
import json
import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark.readers import span_offcpu_ms, span_ms  # noqa: E402

LAYER_METRICS = os.path.join(REPO, "benchmark", "layer_metrics")


def ev(name: str, ts: float, dur: float, tdur: float | None = None,
       tid: int = 1) -> dict:
    e = {"name": name, "ts": ts, "dur": dur, "tid": tid}
    if tdur is not None:
        e["tdur"] = tdur
    return e


def launches(n: int, fetch_tdur: float | None = 100.0) -> list[dict]:
    """``n`` launches 10 ms apart, oldest first: a 4 ms root that ran
    1.5 ms on its CPU, with a 2 ms fetch inside it that ran 0.1 ms."""
    out = []
    for k in range(n):
        t = k * 10_000.0
        out += [ev("fetch", t + 1_500, 2_000, fetch_tdur),
                ev("launch", t, 4_000, 1_500)]
    return out


@pytest.mark.parametrize("events, less, want", [
    # 2.5 ms of each 4 ms launch off its CPU
    (launches(12), [], 2.5),
    # less the fetch's 1.9 ms: the off-CPU time outside the device wait
    (launches(12), ["fetch"], 0.6),
    # a fetch without tdur is skipped, not counted as all off-CPU
    (launches(12, fetch_tdur=None), ["fetch"], 2.5),
    # the cut: only the 10 newest launches, whatever came before
    ([ev("launch", -50_000.0, 4_000, 0.0)] + launches(10), [], 2.5),
])
def test_offcpu_sums_dur_less_tdur_over_the_per_spans(events, less, want):
    assert span_offcpu_ms.per_item_offcpu_ms(
        events, ["launch"], less, "launch", 10
    ) == pytest.approx(want)


@pytest.mark.parametrize("events", [
    launches(9),  # under MIN_PER launches
    [{k: v for k, v in e.items() if k != "tdur"} for e in launches(12)],
    [],
])
def test_offcpu_reads_nothing_without_enough_or_without_tdur(events):
    """Under ``span_ms.MIN_PER`` per spans, or on a ring whose spans
    carry no thread time (a program without ``tdur``: the parent)."""
    assert span_ms.MIN_PER == 10
    assert span_offcpu_ms.per_item_offcpu_ms(
        events, ["launch"], ["fetch"], "launch", 200
    ) is None


def test_offcpu_counts_other_spans_per_item():
    """Spans other than ``per`` are summed and divided by the count of
    ``per`` spans: a pool routine's waits per applied step."""
    events = []
    for k in range(12):
        t = k * 200_000.0
        events += [ev("step", t, 150_000, 90_000),
                   ev("wait", t + 160_000, 20_000, 100),
                   ev("wait", t + 180_000, 20_000, 100)]
    assert span_offcpu_ms.per_item_offcpu_ms(
        events, ["wait"], [], "step", 24
    ) == pytest.approx(2 * 19.9)
    assert span_offcpu_ms.per_item_offcpu_ms(
        events, ["step"], [], "step", 24
    ) == pytest.approx(60.0)


def launch_specs() -> dict[str, dict]:
    """Every metric spec whose spans are the launch's own."""
    specs = {}
    for path in sorted(glob.glob(os.path.join(LAYER_METRICS, "*.json"))):
        with open(path) as f:
            spec = json.load(f)
        if spec["reader"] not in ("span_ms", "span_coverage",
                                  "span_offcpu_ms"):
            continue
        params = spec["params"]
        named = (params.get("spans", []) + params.get("roots", [])
                 + [params.get("per")])
        if "batch_verify" in named:
            specs[os.path.basename(path)[:-len(".json")]] = spec
    return specs


def test_the_launch_specs_read_the_programs_own_ring(monkeypatch):
    """Twelve launches through ``TpuBatchVerifier.verify``: each spec
    that reads the launch's spans finds a number, the five steps cover
    most of ``batch_verify``, and the off-CPU time is not negative by
    more than the thread clock's error: a step of that clock on each
    of the two spans a launch it reads (one span's ``tdur`` may read a
    whole step over its ``dur`` where the clock is sampled in ticks)."""
    import importlib
    import time

    import jax.numpy as jnp

    from cometbft_tpu.crypto import dispatch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519_verify as ev_mod
    from cometbft_tpu.utils import trace as trace_mod

    # a tier demoted by an earlier test in this process (a watchdog
    # trip's cool-down) would send these launches to the host
    dispatch.reset_for_tests()
    monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
    monkeypatch.setattr(
        ev_mod, "_compiled",
        lambda batch, bucket: lambda buf: jnp.ones(
            buf.shape[-1], dtype=bool
        ),
    )
    tracer = trace_mod.SpanTracer(capacity=4096, enabled=True)
    monkeypatch.setattr(ev_mod, "_tracer", tracer)
    monkeypatch.setattr(trace_mod, "TRACER", tracer)
    priv = ed.priv_key_from_secret(b"offcpu-specs")
    for k in range(12):
        bv = ev_mod.TpuBatchVerifier(device_min_batch=1)
        for i in range(4):
            msg = b"offcpu %d %d" % (k, i)
            bv.add(priv.pub_key(), msg, priv.sign(msg))
        ok, _ = bv.verify()
        assert ok
    c0 = c = time.thread_time()
    while c == c0:
        c = time.thread_time()
    clock_step_ms = (c - c0) * 1e3
    specs = launch_specs()
    assert {"launch_offcpu_ms.commit", "launch_offcpu_ms.replay"} <= set(
        specs
    )
    for name, spec in specs.items():
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"]
        )
        value = reader.read({}, spec["params"])
        assert value is not None, name
        assert value >= -2 * clock_step_ms, name
        if spec["reader"] == "span_coverage":
            assert 50 < value <= 100.0, name

"""The cell ``megacommit10k.commit`` rehearsed end to end on the CPU
backend at 24 validators, through ``run.run_cell`` as
``test_benchmark_rehearsal.py`` rehearses the other cells (same
stand-ins: a dispatch-threshold override lets the XLA-on-CPU kernels
play the chip; ``run.py`` has no such switch).  What the cell is for is
kept at the small size: 4-bit tables built in three chunks of 8 keys
(the cell: ten of 1,024), and a commit wider than one launch slice —
24 signatures pad to 32 lanes and run as two slices of 16 (the cell:
10,240 lanes in five slices of 2,048, lane 8,192 a seam), with the
tampered signatures at the first lanes, across the seam and at the last
lanes.  No CPU number here is a
device number.
"""

from __future__ import annotations

import copy
import json
import os
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import run  # noqa: E402
from tests.benchmark.test_benchmark_rehearsal import (  # noqa: E402,F401
    _break_verifier,
    cpu_stands_in,
    keeping,
)

CELL = "megacommit10k.commit"
N_VALS = 24
SLICE = 16
CHUNK = 8
SEED = 2**31 + 7
TINY = {
    "commits": 6, "warm": 3, "tamper_every": 2,
    "tamper_strata": [[0, 4], [14, 18], [20, 24]],
    "tamper_first_group": [0, 2],
}
LAYERS = {
    "device_sig_pct.mega", "commit_collect_ms.mega",
    "commit_sign_bytes_ms.mega", "commit_spec_lookup_ms.mega",
    "commit_record_ms.mega", "dispatch_plan_ms.mega",
    "dispatch_pack_ms.mega", "launch_call_ms.mega", "fetch_wait_ms.mega",
    "commit_span_coverage_pct.mega", "table_build_s", "table_place_s.mega",
}
#: read from the device plane of the trace: silent on the CPU
DEVICE_LAYERS = {
    "keyed_kernel_ms.mega", "keyed_kernel_roofline.mega",
    "launch_overhead_ms.mega",
}


@pytest.fixture(scope="module")
def mega_shapes(cpu_stands_in):
    from cometbft_tpu.ops import ed25519_verify as EV
    from cometbft_tpu.ops import precompute as PR

    mp = pytest.MonkeyPatch()
    mp.setattr(PR, "KEY8_MAX", 0)  # 4-bit pages, as above 256 keys
    mp.setattr(PR, "BUILD_CHUNK", CHUNK)
    mp.setattr(EV, "MAX_LAUNCH", SLICE)
    yield
    mp.undo()


def drive(trace: bool = False, after_warm=None, seen: dict | None = None,
          **params) -> dict:
    """One run past the look for a chip; the window ends with the
    chain, so the counts below are exact.  ``seen["state"]``: the
    driver's state, for a look at it after the window."""
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.utils.trace import TRACER

    PR.TABLE_CACHE.clear()
    TRACER.clear()  # the ring is the process's: other files' spans out
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], validators=N_VALS)
    cell["traffic"] = copy.deepcopy(cell["traffic"])
    cell["traffic"]["params"].update(TINY, **params)
    cell["traffic"].update(reference_sample=8, trace_seconds=60.0)
    return run.run_cell(cell, run.plan_chain(cell, SEED, sign_workers=1),
                        60.0, trace, jax.devices()[:1],
                        after_warm=keeping(seen, after_warm))


def test_the_cell_end_to_end(mega_shapes, capfd):
    seen = {}
    line = drive(seen=seen)
    assert line["correct"] is True
    assert line["attempted"] == 6 and line["failed"] == 0
    # p50 and set-up, and not the 95th percentile of forty samples
    assert set(line["metrics"]) == {"commit_verify_p50_ms", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # named, never a chip's
    assert all(v["value"] == 0 == v["limit"]
               for v in line["compared"].values())
    out, _ = capfd.readouterr()
    phases = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert [p["phase"] for p in phases] == ["start", "setup", "window"]
    setup, window = phases[1:]
    # the table came in three chunks, each placed before the next
    assert len(setup["table_build_spans_s"]) == N_VALS // CHUNK
    # every commit one verdict; one tampered in each stratum, each
    # scanned by the reference to the index the program named
    assert window["samples"] == 6 and window["chain_ran_out"] is True
    assert window["rejected"] == 3 == window["reference_scans"]
    assert window["compiles_in_window"]["count"] == 0
    # all of it on the keyed tier, 24 signatures a batch, never demoted
    assert set(window["counters"]["batches"]) == {"keyed/32"}
    assert window["counters"]["transitions"] == 0
    assert window["counters"]["queue_lane_sigs"] == {}  # no queue lane
    # each commit let go of once its verdict was in; the comparison
    # still had the whole chain's plain data
    state = seen["state"]
    assert state.commits == [None] * 6
    assert all(len(it.sigs) == N_VALS for it in state.chain.items)


def test_a_traced_rehearsal_reports_the_layers(mega_shapes):
    """The stage readers want ten commits in the ring: 12 here."""
    from cometbft_tpu.utils.trace import TRACER

    line = drive(trace=True, commits=12)
    assert line["correct"] is True and line["attempted"] == 12
    assert set(line["metrics"]) == LAYERS
    listed = {m["name"] for m in run.load_cell(CELL)["per_layer"]}
    assert listed == LAYERS | DEVICE_LAYERS
    assert line["metrics"]["device_sig_pct.mega"]["value"] == 100.0
    assert line["metrics"]["commit_span_coverage_pct.mega"]["value"] > 50
    assert 0 < (line["metrics"]["table_place_s.mega"]["value"]
                ) and line["metrics"]["table_build_s"]["value"] > 0
    # lanes against signatures, as PERF.md states them from the spans
    launches = [e["args"] for e in TRACER.events()
                if e["name"] == "device_launch"]
    assert launches and {a["batch"] for a in launches} == {2 * SLICE}
    assert {e["args"]["batch"] for e in TRACER.events()
            if e["name"] == "batch_verify"} == {N_VALS}


def test_a_verdict_altered_where_it_is_produced_is_not_correct(
    mega_shapes, monkeypatch
):
    """One verdict bit of every other device batch flipped to false,
    in the second slice: a valid commit is rejected, or a rejection
    names the wrong index."""
    calls = []

    def alter(results):
        calls.append(1)
        if len(calls) % 2 == 0:
            results[SLICE + 1] = False
        return results

    line = drive(after_warm=lambda state: _break_verifier(monkeypatch, alter))
    assert line["correct"] is False
    assert (line["compared"]["schedule_mismatches"]["value"]
            + line["compared"]["reference_index_mismatches"]["value"]) >= 1

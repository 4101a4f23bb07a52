"""The cell ``lightsync10k.stride100`` rehearsed end to end on the CPU
backend at 12 validators, through ``run.run_cell`` as
``test_benchmark_rehearsal.py`` rehearses the other cells (same
stand-ins: a dispatch-threshold override lets the XLA-on-CPU kernels
play the chip; ``run.py`` has no such switch).  The light lane's batch
target is cut to 16 signatures, so that the look-ahead (9 signatures a
header, D = 4) cuts its submissions across headers as it does at 1,024.
No CPU number here is a device number.
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

from benchmark import run, trace_reduce  # noqa: E402
from tests.benchmark.test_benchmark_rehearsal import (  # noqa: E402,F401
    cpu_stands_in,
    keeping,
)

CELL = "lightsync10k.stride100"
N_VALS = 12  # trusting check 5 signatures, self-commit check 9
SEED = 2**31 + 7
#: 8 warm-up targets + 40 in the window, 9 signatures each: 27 full
#: 16-signature submissions and no short one at the end
TINY = {
    "headers": 40, "warm": 9, "stride": 100, "tamper_every": 8,
    "tamper_strata": [[5, 9], [0, 5], [9, 12]],
}
LAYERS = {
    "device_sig_pct.light", "light_fetch_ms.light",
    "light_verify_ahead_ms.light", "light_witness_store_ms.light",
    "light_ahead_wait_ms.light", "commit_sign_bytes_ms.light",
    "commit_spec_lookup_ms.light", "queue_prehash_ms.light",
    "queue_resolve_ms.light", "fetch_wait_ms.light",
    "light_span_coverage_pct.light", "queue_batch_sigs.light",
    "table_build_s",
}
#: read from the device plane of the trace: silent on the CPU
DEVICE_LAYERS = {
    "keyed_kernel_ms.light", "keyed_kernel_roofline.light",
    "launch_overhead_ms.light",
}


@pytest.fixture(scope="module")
def light_batch_16(cpu_stands_in):
    mp = pytest.MonkeyPatch()
    mp.setenv("CMT_TPU_LIGHT_BATCH", "16")
    yield
    mp.undo()


def drive(trace: bool = False, after_warm=None,
          seen: dict | None = None) -> dict:
    """One run past the look for a chip; the window ends with the
    chain, so the counts below are exact.  ``seen["state"]``: the
    driver's state, for a look at it after the window."""
    cell = run.load_cell(CELL)
    cell["config"] = dict(cell["config"], validators=N_VALS)
    cell["traffic"] = copy.deepcopy(cell["traffic"])
    cell["traffic"]["params"].update(TINY)
    cell["traffic"].update(reference_sample=8, trace_seconds=60.0)
    return run.run_cell(cell, run.plan_chain(cell, SEED, sign_workers=1),
                        60.0, trace, jax.devices()[:1],
                        after_warm=keeping(seen, after_warm))


def test_the_cell_end_to_end(light_batch_16, capfd):
    seen = {}
    line = drive(seen=seen)
    assert line["correct"] is True
    assert line["attempted"] == 40 and line["failed"] == 0
    assert set(line["metrics"]) == {"replay_blocks_per_s", "setup_s"}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # named, never a chip's
    assert all(v["value"] == 0 == v["limit"]
               for v in line["compared"].values())
    out, _ = capfd.readouterr()
    phases = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert [p["phase"] for p in phases] == ["start", "setup", "window"]
    window = phases[-1]
    # every header one verdict; 5 tampered, 4 of them within what the
    # two checks read, the fifth beyond it and accepted
    assert window["samples"] == 40 and window["chain_ran_out"] is True
    assert window["rejected"] == 4 == window["reference_scans"]
    assert window["compiles_in_window"]["count"] == 0
    # the look-ahead's full batches, and nothing on the host rung but
    # the single signatures the cache could not vouch for
    assert set(window["counters"]["batches"]) == {"keyed/16"}
    queue = window["counters"]["queue"]
    assert queue["launched_sigs"] == 16 * queue["launched_batches"]
    assert window["counters"]["queue_lane_sigs"] == {
        "light_client": queue["launched_sigs"]
    }
    assert window["counters"]["queue_lane_batches"] == {
        "light_client": queue["launched_batches"]
    }
    # the driver let go of every target with a verdict, the providers
    # of every block below the last trusted header; the comparison
    # still had the whole chain's plain data
    state = seen["state"]
    assert state.commits == [None] * 40
    items = state.chain.items
    trusted = state.client.latest_trusted().height
    assert trusted == max(
        items[k].height for k in range(40)
        if not any(i < state.checked for i in items[k].bad)
    )
    assert sorted(state.served) == list(state.serving) == [
        it.height for it in items if it.height >= trusted
    ]
    assert len(items) == 40 and all(len(it.sigs) == N_VALS for it in items)


def test_a_traced_rehearsal_reports_the_layers(light_batch_16):
    line = drive(trace=True)
    assert line["correct"] is True
    assert set(line["metrics"]) == LAYERS
    listed = {m["name"] for m in run.load_cell(CELL)["per_layer"]}
    assert listed == LAYERS | DEVICE_LAYERS
    assert line["metrics"]["queue_batch_sigs.light"]["value"] == 16
    assert line["metrics"]["device_sig_pct.light"]["value"] == 100.0
    assert line["metrics"]["light_span_coverage_pct.light"]["value"] > 50
    assert line["metrics"]["light_ahead_wait_ms.light"]["value"] > 0
    # the idle time under the program's own span names, adding up
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(line["device"]["window_s"])
    program = {k for k in gaps if k.startswith(trace_reduce.PROGRAM_SPANS)}
    assert len(program) >= 6 and any(k.startswith("light/") for k in program)
    assert gaps.get("entry.light_verify", 0.0) < 0.5 * sum(gaps.values())


def test_the_control_comes_out_not_correct(light_batch_16):
    """The trusting check alone accepts a bit flipped between one
    third and two thirds."""
    line = drive(after_warm=run.load_cell(CELL)["driver"].control)
    assert line["correct"] is False
    assert line["compared"]["schedule_mismatches"]["value"] >= 1
    assert line["compared"]["reference_verdict_mismatches"]["value"] >= 1


def test_half_of_a_light_lane_batch_left_out_is_not_correct(
    light_batch_16, monkeypatch
):
    """The second half of every device batch reported valid unchecked:
    the verify-ahead caches a tampered signature as proven and the
    client accepts its header."""
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

    real = TpuBatchVerifier.execute

    def broken(self, plan):
        ok, results = real(self, plan)
        results = list(results)
        half = len(results) // 2
        results = results[:half] + [True] * (len(results) - half)
        return all(results), results

    line = drive(after_warm=lambda state: monkeypatch.setattr(
        TpuBatchVerifier, "execute", broken
    ))
    assert line["correct"] is False
    assert line["compared"]["schedule_mismatches"]["value"] >= 1

"""The benchmark's drivers rehearsed end to end on the CPU backend at a
tiny size, through ``run.py``'s own functions with the look for a chip
skipped HERE (a dispatch-threshold override makes the XLA-on-CPU kernels
stand in for the chip; ``run.py`` has no such switch).  Also here: the
control and the planted faults must each turn ``correct`` false, and the
trace reduction must find a known busy union in a trace written by hand.

Sized like tests/test_chip_smoke.py's phases (12 validators: every
batch is at most 16 lanes of one key set) so that the programs compiled
for one serve the other from the shared compile cache.  No CPU number
here is a device number: the timings these runs print are discarded.
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

N_VALS = 12  # full commit 12, verify_commit_light 9: both 16 lanes
SEED = 2**31 + 7  # one seed: one key set, one table build for the file
TINY = {
    "cosmoshub150.commit": {
        "commits": 12, "warm": 2, "tamper_every": 4,
        "tamper_strata": [[9, 12], [0, 4], [4, 9]],
    },
    "blocksync1k.replay": {
        "blocks": 12, "warm": 3, "tamper_every": 4,
        "tamper_strata": [[5, 9], [0, 5]], "tamper_first_group": [1, 4],
    },
}
CELLS = sorted(TINY)


@pytest.fixture(scope="module")
def cpu_stands_in():
    """What lets the device path run here, all put back afterwards."""
    from cometbft_tpu import metrics as M
    from cometbft_tpu.crypto import dispatch
    from cometbft_tpu.ops import precompute as PR

    mp = pytest.MonkeyPatch()
    mp.setenv("CMT_TPU_ROUTE", "0")
    mp.setenv("CMT_TPU_DEVICE_MIN_BATCH", "2")
    mp.setenv("CMT_TPU_DISABLE_MESH_VERIFY", "1")
    mp.setenv("CMT_TPU_VERIFY_PREFETCH", "1")
    PR.TABLE_CACHE.clear()
    dispatch.reset_for_tests()
    yield
    mp.undo()
    PR.TABLE_CACHE.clear()
    dispatch.reset_for_tests()
    M.install_crypto_metrics(None)


def tiny_cell(name: str) -> dict:
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], validators=N_VALS)
    cell["traffic"] = copy.deepcopy(cell["traffic"])
    cell["traffic"]["params"].update(TINY[name])
    cell["traffic"].update(reference_sample=8, trace_seconds=60.0)
    return cell


def drive(name: str, trace: bool = False, after_warm=None,
          seconds: float = 60.0) -> dict:
    """One run past the look for a chip; the window ends with the
    chain, so the counts below are exact."""
    cell = tiny_cell(name)
    return run.run_cell(cell, run.plan_chain(cell, SEED, sign_workers=1),
                        seconds, trace, jax.devices()[:1],
                        after_warm=after_warm)


@pytest.mark.parametrize("name", CELLS)
def test_a_run_end_to_end(cpu_stands_in, name, capfd):
    line = drive(name)
    assert list(line) == ["correct", "attempted", "failed", "metrics",
                          "device", "compared"]
    assert line["correct"] is True
    assert line["attempted"] == 12 and line["failed"] == 0
    cell = run.load_cell(name)
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert all(m["value"] > 0 for m in line["metrics"].values())
    assert line["device"]["platform"] == "cpu"  # named, never a chip's
    assert all(v["value"] == 0 == v["limit"]
               for v in line["compared"].values())
    out, err = capfd.readouterr()
    phases = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    assert [p["phase"] for p in phases] == ["start", "setup", "window"]
    assert all(p["platform"] == "cpu" and p["count"] == 1 for p in phases)
    window = phases[-1]
    assert window["samples"] == 12 and window["chain_ran_out"] is True
    assert window["rejected"] == 3 == window["reference_scans"]
    assert window["compiles_in_window"]["count"] == 0
    assert set(window["counters"]["batches"]) == {"keyed/16"}
    assert err.rstrip().splitlines()[-1].startswith("compared ")
    json.dumps(line)


def test_a_traced_run_reports_the_layers(cpu_stands_in):
    """On the CPU backend the trace has no device plane: the readers
    of the device trace find nothing and are left out; the counters'
    and spans' readers report."""
    line = drive("blocksync1k.replay", trace=True)  # all of it traced
    assert line["correct"] is True
    assert line["metrics"]["queue_batch_sigs.replay"]["value"] == N_VALS
    assert line["metrics"]["device_sig_pct.replay"]["value"] == 100.0
    assert line["metrics"]["table_build_s"]["value"] > 0
    assert not any(k.startswith("keyed_kernel") for k in line["metrics"])
    assert line["device"]["busy_s"] == 0.0 and line["device"]["window_s"] > 0
    assert line["breakdown"]["device_ops"] == []
    assert line["breakdown"]["idle_gaps"][0][0].startswith("entry.")
    assert list(line)[-1] == "compared"


@pytest.mark.parametrize("name", CELLS)
def test_the_control_comes_out_not_correct(cpu_stands_in, name):
    """The program's own weaker mode in the entry point's place."""
    line = drive(name, after_warm=run.load_cell(name)["driver"].control)
    assert line["correct"] is False
    assert line["compared"]["schedule_mismatches"]["value"] >= 1
    assert line["compared"]["reference_verdict_mismatches"]["value"] >= 1


def _break_verifier(monkeypatch, alter):
    """``alter(results) -> results`` on every device batch's verdicts,
    where they are produced."""
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

    real = TpuBatchVerifier.execute

    def broken(self, plan):
        ok, results = real(self, plan)
        results = alter(list(results))
        return all(results), results

    monkeypatch.setattr(TpuBatchVerifier, "execute", broken)


@pytest.mark.parametrize("name", CELLS)
def test_half_of_the_batch_left_out_is_not_correct(
    cpu_stands_in, monkeypatch, name
):
    """The second half of every batch reported valid unchecked: the
    tampered signatures there are accepted."""
    def after_warm(state):
        _break_verifier(
            monkeypatch,
            lambda r: r[:len(r) // 2] + [True] * (len(r) - len(r) // 2),
        )

    line = drive(name, after_warm=after_warm)
    assert line["correct"] is False
    assert line["compared"]["schedule_mismatches"]["value"] >= 1


def test_an_answer_altered_where_it_is_produced_is_not_correct(
    cpu_stands_in, monkeypatch
):
    """One verdict bit of every fifth device batch flipped to false: a
    valid commit is rejected, or a rejection names the wrong index."""
    calls = []

    def alter(results):
        calls.append(1)
        if len(calls) % 5 == 0:
            results[1] = False
        return results

    line = drive("cosmoshub150.commit",
                 after_warm=lambda state: _break_verifier(monkeypatch, alter))
    assert line["correct"] is False
    assert (line["compared"]["schedule_mismatches"]["value"]
            + line["compared"]["reference_index_mismatches"]["value"]) >= 1


def test_a_host_verdict_altered_is_not_correct_in_the_replay(
    cpu_stands_in, monkeypatch
):
    """The replay survives a prefetch bit flipped to false (negatives
    are never cached: the step verifies again).  Its answer to a
    tampered block is produced by the host rung's single-signature
    check of what the cache could not vouch for: that one altered to
    "valid" accepts the block."""
    from cometbft_tpu.crypto.ed25519 import Ed25519PubKey

    def after_warm(state):
        monkeypatch.setattr(Ed25519PubKey, "verify_signature",
                            lambda self, msg, sig: True)

    line = drive("blocksync1k.replay", after_warm=after_warm)
    assert line["correct"] is False
    assert line["compared"]["reference_verdict_mismatches"]["value"] >= 1


# -- the trace reduction on a trace written by hand -----------------------

_TRACE = """
planes {
  id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000 }
    events { metadata_id: 1 offset_ps: 10000000 duration_ps: 4000000 }
    events { metadata_id: 4 offset_ps: 30000000 duration_ps: 4000000 }
  }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 2 offset_ps: 0 duration_ps: 1000000 }
    events { metadata_id: 3 offset_ps: 500000 duration_ps: 2000000 }
    events { metadata_id: 2 offset_ps: 10000000 duration_ps: 3000000 }
    events { metadata_id: 2 offset_ps: 30000000 duration_ps: 3000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "jit_verify_keyed_w8_b128(123)" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.1 = s32[26,256]{0,1} fusion(s32[] %p)" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.2 = s32[4]{0} copy(s32[4]{0} %q)" } }
  event_metadata { key: 4 value { id: 4 name: "jit_table_build_w8(9)" } }
}
planes {
  id: 2 name: "/host:CPU"
  lines { id: 7 name: "python" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 6000000 }
    events { metadata_id: 2 offset_ps: 6000000 duration_ps: 10000000 }
    events { metadata_id: 3 offset_ps: 1000000 duration_ps: 1000000 }
  }
  event_metadata { key: 1 value { id: 1 name: "entry.verify_commit" } }
  event_metadata { key: 2 value { id: 2 name: "gen.next" } }
  event_metadata { key: 3 value { id: 3 name: "unrelated" } }
}
"""


def test_trace_reduce_finds_a_known_busy_union():
    from jax.profiler import ProfileData

    planes = trace_reduce.load(
        "", serialized=ProfileData.text_proto_to_serialized_xspace(_TRACE)
    )
    got = trace_reduce.reduce(planes, ("entry.", "gen."))
    # the window: the annotations' span, 1,000 ns to 17,000 ns; the
    # launch at 31,000 ns lies outside it and is not counted
    assert got["devices"] == 1
    assert got["window_s"] == pytest.approx(16e-6)
    # programs: [1000, 5000] and [11000, 15000]
    assert got["busy_s"] == pytest.approx(8e-6)
    assert got["programs"] == {
        "verify_keyed_w8_b128": {"launches": 2,
                                 "seconds": pytest.approx(8e-6)},
    }
    assert got["device_ops"] == [["fusion.1", pytest.approx(4e-6)],
                                 ["copy.2", pytest.approx(2e-6)]]
    # idle: 5,000..11,000 (entry.verify_commit to 7,000, then gen.next)
    # and 15,000..17,000 (gen.next)
    assert got["idle_gaps"] == [["gen.next", pytest.approx(6e-6)],
                                ["entry.verify_commit", pytest.approx(2e-6)]]
    assert got["busy_s"] + sum(s for _, s in got["idle_gaps"]) == (
        pytest.approx(got["window_s"])
    )


def test_trace_reduce_without_a_device_plane_reports_no_busy_time():
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("entry.verify_commit", 0.0, 5e3)]},
    ]}]
    got = trace_reduce.reduce(planes)
    assert got["devices"] == 0 and got["busy_s"] == 0.0
    assert got["programs"] == {} and got["window_s"] == pytest.approx(5e-6)

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


def keeping(seen: dict | None, after_warm=None):
    """An ``after_warm`` that also keeps the driver's state, in
    ``seen["state"]``."""
    def keep(state):
        if seen is not None:
            seen["state"] = state
        if after_warm is not None:
            after_warm(state)

    return keep


def drive(name: str, trace: bool = False, after_warm=None,
          seconds: float = 60.0, seen: dict | None = None) -> dict:
    """One run past the look for a chip; the window ends with the
    chain, so the counts below are exact.  ``seen["state"]``: the
    driver's state, for a look at it after the window."""
    cell = tiny_cell(name)
    return run.run_cell(cell, run.plan_chain(cell, SEED, sign_workers=1),
                        seconds, trace, jax.devices()[:1],
                        after_warm=keeping(seen, after_warm))


@pytest.mark.parametrize("name", CELLS)
def test_a_run_end_to_end(cpu_stands_in, name, capfd):
    seen = {}
    line = drive(name, seen=seen)
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
    # the queue's launches by lane: the commit loop bypasses the queue
    lanes = window["counters"]["queue_lane_sigs"]
    assert lanes == ({"prefetch": window["counters"]["queue"]["launched_sigs"]}
                     if name == "blocksync1k.replay" else {})
    assert err.rstrip().splitlines()[-1].startswith("compared ")
    json.dumps(line)
    # every item with an outcome was let go of inside the window, and
    # the comparison still had the whole chain's plain data to read
    state = seen["state"]
    assert state.commits == [None] * 12
    assert len(state.chain.items) == 12
    assert all(len(it.sigs) == N_VALS for it in state.chain.items)


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
    # no device plane: the whole window is one gap, named by the
    # program's spans where one covers an instant, by the harness's
    # ``entry.`` annotations only where none does
    gaps = dict(line["breakdown"]["idle_gaps"])
    assert sum(gaps.values()) == pytest.approx(line["device"]["window_s"])
    program = {k for k in gaps if k.startswith(trace_reduce.PROGRAM_SPANS)}
    assert len(program) >= 6 and "blocksync/prefetch_items" in program
    assert sum(gaps[k] for k in program) > 0.5 * sum(gaps.values())
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
    events { metadata_id: 3 offset_ps: 1500000 duration_ps: 2000000 }
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


def test_device_ops_count_each_instant_once():
    """A ``while`` event holds the fusions that run inside it: they are
    left out and the loop keeps its whole time, so one launch's
    operations add up to no more than its program's time (summed by
    name alone, nested or not, the first launch read 13 of 10 us)."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_verify_keyed_w8_b128(1)", 0.0, 10e3),
                ("jit_verify_keyed_w8_b128(1)", 20e3, 10e3),
            ]},
            {"name": "XLA Ops", "events": [
                # the loop first or last in the line: the order is by time
                ("%fusion.2 = s32[4] fusion(%b)", 5e3, 2e3),
                ("%while.67 = (u32[]) while(%t)", 1e3, 8e3),
                ("%fusion.1 = s32[4] fusion(%a)", 2e3, 2e3),
                ("%copy.9 = s32[4] copy(%c)", 9e3, 1e3),
                ("%while.67 = (u32[]) while(%t)", 21e3, 8e3),
                ("%fusion.1 = s32[4] fusion(%a)", 22e3, 3e3),
                ("%fusion.2 = s32[4] fusion(%b)", 25e3, 4e3),
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "python", "events": [("entry.verify_commit", 0.0, 30e3)]},
        ]},
    ]
    got = trace_reduce.reduce(planes)
    assert got["device_ops"] == [["while.67", pytest.approx(16e-6)],
                                 ["copy.9", pytest.approx(1e-6)]]
    assert sum(s for _, s in got["device_ops"]) <= got["busy_s"]
    assert got["busy_s"] == pytest.approx(20e-6)
    assert got["programs"]["verify_keyed_w8_b128"]["launches"] == 2
    # a loop inside a loop is the outer loop's
    assert trace_reduce.top_level_times([
        ("%while.1 = while()", 0.0, 10e3), ("%while.2 = while()", 1e3, 6e3),
        ("%fusion.3 = fusion()", 2e3, 4e3), ("%fusion.3 = fusion()", 10e3, 1e3),
    ]) == {"while.1": pytest.approx(10e-6), "fusion.3": pytest.approx(1e-6)}


def test_load_reads_the_top_level_of_an_operation_line_before_the_cap(
    monkeypatch
):
    """Four launches, each a ``while`` holding two fusions and a copy
    after it.  ``load`` passes over what lies inside a loop before it
    counts against ``MAX_OP_EVENTS``: capped at 6 KEPT events it reads
    three launches whole, where the first 6 events of the line are one
    launch and a half (the mega cell's slice was read so: two of its
    four launches, PR 33)."""
    from jax.profiler import ProfileData

    launches = "".join(
        f"""
    events {{ metadata_id: 1 offset_ps: {t}000000 duration_ps: 8000000 }}
    events {{ metadata_id: 2 offset_ps: {t + 1}000000 duration_ps: 3000000 }}
    events {{ metadata_id: 2 offset_ps: {t + 4}000000 duration_ps: 3000000 }}
    events {{ metadata_id: 3 offset_ps: {t + 8}000000 duration_ps: 1000000 }}"""
        for t in (0, 20, 40, 60)
    )
    trace = f"""
planes {{
  id: 1 name: "/device:TPU:0"
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000{launches}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%while.67 = (u32[]) while(%t)" }} }}
  event_metadata {{ key: 2 value {{ id: 2 name: "%fusion.1 = s32[4] fusion(%a)" }} }}
  event_metadata {{ key: 3 value {{ id: 3 name: "%copy.9 = s32[4] copy(%c)" }} }}
}}
"""
    serialized = ProfileData.text_proto_to_serialized_xspace(trace)
    whole = trace_reduce.load("", serialized=serialized)
    events = whole[0]["lines"][0]["events"]
    assert [trace_reduce.op_name(n) for n, _, _ in events] == (
        ["while.67", "copy.9"] * 4
    )
    got = trace_reduce.reduce(whole)
    assert got["device_ops"] == [["while.67", pytest.approx(32e-6)],
                                 ["copy.9", pytest.approx(4e-6)]]
    assert got["busy_s"] == pytest.approx(36e-6)  # no program line: the ops'
    monkeypatch.setattr(trace_reduce, "MAX_OP_EVENTS", 6)
    capped = trace_reduce.load("", serialized=serialized)
    assert len(capped[0]["lines"][0]["events"]) == 6
    assert trace_reduce.reduce(capped)["device_ops"] == [
        ["while.67", pytest.approx(24e-6)], ["copy.9", pytest.approx(3e-6)],
    ]


def test_idle_gaps_are_named_by_one_annotation_an_instant():
    """Nested and cross-thread annotations over two gaps whose shares
    are known: an instant goes to the annotation that started last
    among those covering it; the names' seconds add up to the gaps'."""
    planes = [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ("jit_verify_keyed_w8_b128(1)", 10e3, 10e3),
                ("jit_verify_keyed_w8_b128(1)", 60e3, 10e3),
            ]},
        ]},
        {"name": "/host:CPU", "lines": [
            {"name": "caller", "events": [
                ("entry.verify_commit", 0.0, 50e3),
                ("verify_commit", 2e3, 40e3),
                ("verify_commit/sign_bytes", 4e3, 4e3),
                ("gen.next", 50e3, 2e3),
                ("entry.verify_commit", 52e3, 48e3),
                ("not_a_program_span", 55e3, 40e3),
            ]},
            {"name": "launcher", "events": [
                ("verify_queue/launch", 30e3, 25e3),
                ("device_fetch", 90e3, 5e3),
            ]},
        ]},
    ]
    got = trace_reduce.reduce(planes)
    assert got["window_s"] == pytest.approx(100e-6)
    assert got["busy_s"] == pytest.approx(20e-6)
    # gaps: 0-10, 20-60, 70-100 us
    assert dict(got["idle_gaps"]) == {
        # 0-2; 52-60 (it started after the launcher's span, which
        # still runs to 55); 70-90; 95-100
        "entry.verify_commit": pytest.approx((2 + 8 + 20 + 5) * 1e-6),
        "verify_commit": pytest.approx((2 + 2 + 10) * 1e-6),  # 2-4, 8-10, 20-30
        "verify_commit/sign_bytes": pytest.approx(4e-6),  # 4-8
        # 30-50: the other thread's, begun later than the caller's two
        "verify_queue/launch": pytest.approx(20e-6),
        "gen.next": pytest.approx(2e-6),  # 50-52
        "device_fetch": pytest.approx(5e-6),  # 90-95
    }
    assert sum(s for _, s in got["idle_gaps"]) + got["busy_s"] == (
        pytest.approx(got["window_s"])
    )
    # the harness's prefixes alone: the old names, the same total
    old = trace_reduce.reduce(planes, naming=())
    assert dict(old["idle_gaps"]) == {
        "entry.verify_commit": pytest.approx(78e-6),
        "gen.next": pytest.approx(2e-6),
    }
    assert (old["window_s"], old["busy_s"]) == (got["window_s"],
                                                got["busy_s"])


def test_a_cut_list_ends_in_other_and_still_adds_up():
    by_name = {f"op.{i}": float(20 - i) for i in range(14)}
    got = trace_reduce._top(by_name, 10)
    assert len(got) == 10 and got[0] == ["op.0", 20.0]
    assert got[-1] == ["other", sum(20.0 - i for i in range(9, 14))]
    assert sum(v for _, v in got) == sum(by_name.values())
    assert trace_reduce._top({"a": 1.0}, 10) == [["a", 1.0]]


def test_trace_reduce_without_a_device_plane_reports_no_busy_time():
    planes = [{"name": "/host:CPU", "lines": [
        {"name": "python", "events": [("entry.verify_commit", 0.0, 5e3)]},
    ]}]
    got = trace_reduce.reduce(planes)
    assert got["devices"] == 0 and got["busy_s"] == 0.0
    assert got["programs"] == {} and got["window_s"] == pytest.approx(5e-6)

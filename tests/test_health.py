"""Device-health plane tests (docs/observability.md "Device-health
plane"): launch watchdog, tier prober, the steps of a launch in the
span ring, the /debug index + /debug/perf surfaces, and the perf ledger + regression
gate (tools/perfledger.py, tools/perfdiff.py).

``make health-smoke`` runs the TestHealthSmoke class standalone;
``make perf-gate`` runs tools/perfdiff.py --selftest against the same
fixture pair TestPerfDiff pins here.
"""

from __future__ import annotations

import json
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

from cometbft_tpu.crypto import health as H
from cometbft_tpu.metrics import (
    HealthMetrics,
    health_metrics,
    install_health_metrics,
)
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.metrics import Registry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def hm():
    """A fresh, registry-backed health sink installed for the test."""
    metrics = HealthMetrics(Registry())
    install_health_metrics(metrics)
    try:
        yield metrics
    finally:
        install_health_metrics(None)


def counter_value(metric, **labels) -> float:
    return metric.labels(**labels).get()


def hist_count(metric, **labels) -> int:
    return metric.labels(**labels)._count


def flight_events_since(since_total: int) -> list[dict]:
    """Events recorded after a ``FLIGHT.recorded_total`` mark.
    Wrap-proof: a positional ``len(FLIGHT.events())`` mark goes stale
    the moment the bounded ring fills (``events()[mark:]`` is then
    always empty), which a full tier-1 run's event volume reaches.
    The ``new <= 0`` guard matters: ``events[-0:]`` is the WHOLE ring,
    not the empty tail."""
    events = FLIGHT.events()
    new = FLIGHT.recorded_total - since_total
    if new <= 0:
        return []
    return events[-min(new, len(events)):]


def flight_kinds(since_total: int) -> list[str]:
    return [ev["kind"] for ev in flight_events_since(since_total)]


class TestEnvKnobs:
    def test_interval_default_and_zero(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_HEALTH_INTERVAL", raising=False)
        assert H.health_interval_from_env() == H.DEFAULT_HEALTH_INTERVAL_S
        monkeypatch.setenv("CMT_TPU_HEALTH_INTERVAL", "0")
        assert H.health_interval_from_env() == 0.0

    def test_interval_invalid_fails_loudly(self, monkeypatch):
        monkeypatch.setenv("CMT_TPU_HEALTH_INTERVAL", "sixty")
        with pytest.raises(ValueError, match="CMT_TPU_HEALTH_INTERVAL"):
            H.health_interval_from_env()
        monkeypatch.setenv("CMT_TPU_HEALTH_INTERVAL", "-5")
        with pytest.raises(ValueError, match="CMT_TPU_HEALTH_INTERVAL"):
            H.health_interval_from_env()

    def test_budget_validated(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_LAUNCH_BUDGET_S", raising=False)
        assert H.launch_budget_from_env() == H.DEFAULT_LAUNCH_BUDGET_S
        monkeypatch.setenv("CMT_TPU_LAUNCH_BUDGET_S", "0")
        with pytest.raises(ValueError, match="CMT_TPU_LAUNCH_BUDGET_S"):
            H.launch_budget_from_env()
        monkeypatch.setenv("CMT_TPU_LAUNCH_BUDGET_S", "abc")
        with pytest.raises(ValueError, match="CMT_TPU_LAUNCH_BUDGET_S"):
            H.launch_budget_from_env()

    def test_prober_refuses_nonpositive_interval(self):
        with pytest.raises(ValueError, match="positive interval"):
            H.HealthProber(interval_s=0)


class TestLaunchWatchdog:
    def test_hung_launch_trips_counter_and_flight(self, hm):
        """The acceptance case: a launch sleeping past the budget
        raises the hang counter + flight event WITHIN the budget and
        never deadlocks the launching thread."""
        wd = H.LaunchWatchdog(budget_s=0.05)
        mark = FLIGHT.recorded_total
        try:
            tripped_at = None
            with wd.watch(tier="fake", batch=64):
                # poll so we can assert the trip happened DURING the
                # hang (within ~budget), not at disarm time
                deadline = time.monotonic() + 2.0
                while time.monotonic() < deadline:
                    if counter_value(hm.device_hangs_total) >= 1:
                        tripped_at = time.monotonic()
                        break
                    time.sleep(0.005)
            assert tripped_at is not None, "watchdog never fired"
            assert counter_value(hm.device_hangs_total) == 1
            kinds = flight_kinds(mark)
            assert "crypto/device_hang" in kinds
            # the launch returned afterwards: recovery is recorded
            assert "crypto/device_hang_recovered" in kinds
            ev = [
                e for e in flight_events_since(mark)
                if e["kind"] == "crypto/device_hang"
            ][0]
            assert ev["tier"] == "fake" and ev["batch"] == 64
        finally:
            wd.stop()

    def test_fast_launch_does_not_trip(self, hm):
        wd = H.LaunchWatchdog(budget_s=5.0)
        try:
            with wd.watch(tier="fake"):
                time.sleep(0.01)
            assert counter_value(hm.device_hangs_total) == 0
            assert wd.snapshot()["active_launches"] == []
        finally:
            wd.stop()

    def test_concurrent_launches_trip_independently(self, hm):
        wd = H.LaunchWatchdog(budget_s=0.05)
        try:
            def slow():
                with wd.watch(tier="slow"):
                    time.sleep(0.2)

            def fast():
                with wd.watch(tier="fast"):
                    time.sleep(0.01)

            threads = [
                threading.Thread(target=slow),
                threading.Thread(target=fast),
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=5)
            assert counter_value(hm.device_hangs_total) == 1
        finally:
            wd.stop()

    def test_snapshot_reports_active_launch(self, hm):
        wd = H.LaunchWatchdog(budget_s=60)
        try:
            token = wd.arm("keyed", batch=128)
            snap = wd.snapshot()
            assert snap["budget_s"] == 60
            assert [a["tier"] for a in snap["active_launches"]] == ["keyed"]
            assert wd.disarm(token) is False
        finally:
            wd.stop()


class TestHealthProber:
    def test_schedule_respects_interval(self, hm):
        """~N probes in N intervals — the CMT_TPU_HEALTH_INTERVAL
        contract (satellite acceptance)."""
        calls: list[float] = []
        prober = H.HealthProber(
            interval_s=0.08,
            tiers={"fake": lambda: calls.append(time.monotonic()) or True},
        )
        prober.start()
        try:
            time.sleep(0.42)
        finally:
            prober.stop()
        # 0.42s / 0.08s = ~5 ticks; wide bounds for a loaded box
        assert 2 <= len(calls) <= 8, calls
        assert counter_value(hm.tier_healthy, tier="fake") == 1.0
        assert hist_count(hm.tier_probe_seconds, tier="fake") == len(calls)
        n_after = prober.snapshot()["probes_total"]
        time.sleep(0.2)  # stopped prober must not keep probing
        assert prober.snapshot()["probes_total"] == n_after

    def test_failed_probe_marks_unhealthy_and_recovers(self, hm):
        state = {"ok": False}

        def flaky():
            if not state["ok"]:
                raise RuntimeError("device wedged")
            return True

        prober = H.HealthProber(interval_s=60, tiers={"keyed": flaky})
        mark = FLIGHT.recorded_total
        assert prober.probe_once() == {"keyed": False}
        assert counter_value(hm.tier_healthy, tier="keyed") == 0.0
        assert counter_value(
            hm.tier_probe_failures_total, tier="keyed"
        ) == 1
        assert "crypto/tier_unhealthy" in flight_kinds(mark)
        snap = prober.snapshot()["tiers"]["keyed"]
        assert snap["consecutive_failures"] == 1
        assert "device wedged" in snap["error"]
        # recovery flips the gauge back and records the transition
        state["ok"] = True
        assert prober.probe_once() == {"keyed": True}
        assert counter_value(hm.tier_healthy, tier="keyed") == 1.0
        assert "crypto/tier_recovered" in flight_kinds(mark)

    def test_misverify_counts_as_unhealthy(self, hm):
        prober = H.HealthProber(
            interval_s=60, tiers={"generic": lambda: False}
        )
        assert prober.probe_once() == {"generic": False}
        assert counter_value(hm.tier_healthy, tier="generic") == 0.0

    def test_probes_run_under_the_watchdog(self, hm):
        wd = H.LaunchWatchdog(budget_s=0.05)
        prober = H.HealthProber(
            interval_s=60,
            tiers={"hung": lambda: time.sleep(0.15) or True},
            watchdog=wd,
        )
        try:
            mark = FLIGHT.recorded_total
            prober.probe_once()
            deadline = time.monotonic() + 2
            while (
                time.monotonic() < deadline
                and counter_value(hm.device_hangs_total) < 1
            ):
                time.sleep(0.01)
            assert counter_value(hm.device_hangs_total) == 1
            hang = [
                e for e in flight_events_since(mark)
                if e["kind"] == "crypto/device_hang"
            ][0]
            assert hang["tier"] == "probe:hung"
        finally:
            wd.stop()

    def test_wedged_probe_does_not_wedge_the_loop(self, hm):
        """The r03/r04 case the plane exists for: a probe stuck in a
        wedged runtime is abandoned at probe_timeout_s, the tier is
        marked unhealthy, and the NEXT round (including other tiers)
        still runs — failing fast while the stuck worker lives."""
        release = threading.Event()

        def wedged():
            release.wait(5)
            return True

        prober = H.HealthProber(
            interval_s=60,
            tiers={"keyed": wedged, "host": lambda: True},
            probe_timeout_s=0.05,
        )
        t0 = time.monotonic()
        results = prober.probe_once()
        assert time.monotonic() - t0 < 2  # loop NOT blocked for 5s
        assert results == {"keyed": False, "host": True}
        snap = prober.snapshot()
        assert snap["hung_probes"] == ["keyed"]
        assert "timeout" in snap["tiers"]["keyed"]["error"]
        assert counter_value(hm.tier_healthy, tier="keyed") == 0.0
        assert counter_value(hm.tier_healthy, tier="host") == 1.0
        # while the worker is still stuck the tier fails FAST
        assert prober.probe_once()["keyed"] is False
        assert "still hung" in prober.snapshot()["tiers"]["keyed"]["error"]
        # once the wedge clears, the next round probes normally again
        release.set()
        deadline = time.monotonic() + 2
        while (
            time.monotonic() < deadline
            and prober.snapshot()["hung_probes"]
        ):
            time.sleep(0.01)
        assert prober.probe_once()["keyed"] is True
        assert counter_value(hm.tier_healthy, tier="keyed") == 1.0

    def test_default_tiers_on_cpu_are_host_only(self):
        # tier-1 runs on the cpu backend: the XLA-on-CPU path is a
        # tier no dispatch chooses, so no DEVICE tier is probed
        # (they join on a real accelerator — see default_tier_probes).
        # bls_native appears exactly when the native BLS library is
        # already loaded in this process (suite order dependent —
        # test_bls* loads it), never triggering the first-use build.
        from cometbft_tpu.crypto import bls_native

        probes = set(H.default_tier_probes())
        expected = {"host"}
        if bls_native.loaded():
            expected.add("bls_native")
        assert probes == expected


class TestHealthSmoke:
    """`make health-smoke`: boot the prober against the host tier and
    assert the healthy gauge + a probe histogram sample + the debug
    surfaces."""

    def test_host_tier_probe_end_to_end(self, hm):
        prober = H.HealthProber(interval_s=0.15)  # default tiers
        prober.start()
        try:
            deadline = time.monotonic() + 30
            while (
                time.monotonic() < deadline
                and prober.snapshot()["probes_total"] == 0
            ):
                time.sleep(0.05)
        finally:
            prober.stop()
        assert counter_value(hm.tier_healthy, tier="host") == 1.0
        assert hist_count(hm.tier_probe_seconds, tier="host") >= 1
        snap = prober.snapshot()
        assert snap["tiers"]["host"]["healthy"] is True
        assert snap["tiers"]["host"]["last_probe_s"] > 0

    def test_debug_perf_and_index_routes(self, hm, tmp_path, monkeypatch):
        from cometbft_tpu.utils.metrics import MetricsServer

        ledger = tmp_path / "perf_ledger.json"
        ledger.write_text(json.dumps({
            "schema": 1,
            "entries": [
                {"config": "keyed", "value": 103453.0,
                 "unit": "sigs/sec", "source": "fixture"},
            ],
        }))
        monkeypatch.setenv("CMT_TPU_PERF_LEDGER", str(ledger))
        prober = H.HealthProber(
            interval_s=60, tiers={"host": lambda: True}
        )
        prober.start()
        try:
            prober.probe_once()
            srv = MetricsServer(Registry(), "127.0.0.1:0")
            srv.start()
            try:
                base = f"http://127.0.0.1:{srv.port}"
                perf = json.loads(
                    urllib.request.urlopen(
                        base + "/debug/perf", timeout=5
                    ).read()
                )
                # tier health + last probe latency for every
                # available tier (acceptance criterion)
                assert perf["prober"]["tiers"]["host"]["healthy"] is True
                assert perf["prober"]["tiers"]["host"]["last_probe_s"] >= 0
                assert "budget_s" in perf["watchdog"]
                # a launch's timing is the span ring's (/trace), not a
                # second bookkeeping here
                assert "utilization" not in perf
                assert set(perf) == {"device", "prober", "watchdog",
                                     "ledger"}
                assert perf["ledger"]["tail"][-1]["config"] == "keyed"
                assert perf["device"]["status"] in (
                    "uninitialized", "ready", "failed"
                )
                assert {"ndev", "platform", "kind"} <= set(perf["device"])
                index = json.loads(
                    urllib.request.urlopen(
                        base + "/debug", timeout=5
                    ).read()
                )
                paths = [e["path"] for e in index["endpoints"]]
                for expected in ("/trace", "/debug/flight",
                                 "/debug/perf", "/metrics"):
                    assert expected in paths
                assert "wire" in paths  # the RPC-side routes are listed
            finally:
                srv.stop()
        finally:
            prober.stop()

    def test_debug_perf_rpc_route(self, hm):
        from cometbft_tpu.inspect import _INSPECT_ROUTES
        from cometbft_tpu.rpc.core import Environment

        assert "debug/perf" in _INSPECT_ROUTES
        payload = Environment().routes()["debug/perf"]()
        assert "watchdog" in payload and "utilization" not in payload
        assert {"device", "prober", "ledger"} <= set(payload)


class TestVerifierHealthHooks:
    """The TpuBatchVerifier.verify seam: each step of a launch is a
    span of its own in the ring, and a hung launch trips the watchdog
    without deadlocking the verifier."""

    def _verifier(self, run_generic):
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

        class FakeDeviceVerifier(TpuBatchVerifier):
            def _run_generic(self, pub, sig, msgs):
                self._last_tier = "generic"
                return run_generic(pub, sig, msgs)

        priv = ed.priv_key_from_secret(b"health-hook-test")
        bv = FakeDeviceVerifier(device_min_batch=1)
        for i in range(2):
            msg = b"hook msg %d" % i
            bv.add(priv.pub_key(), msg, priv.sign(msg))
        return bv

    def test_verify_records_queue_wait_and_busy(self, hm, monkeypatch):
        """One ``verify()`` on the generic tier (its program swapped for
        an all-true one, so nothing compiles): ``batch_verify`` holds
        its five steps, in order, on its own thread — the arm, the
        pack, the launch, the fetch and the settle.  The root and its
        fetch, the two spans a metric reads the thread's CPU time off,
        carry it beside their wall time; the other steps read no
        thread clock."""
        import jax.numpy as jnp

        from cometbft_tpu.ops import ed25519_verify as ev
        from cometbft_tpu.utils.trace import TRACER

        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        monkeypatch.setattr(
            ev, "_compiled",
            lambda batch, bucket: lambda buf: jnp.ones(
                buf.shape[-1], dtype=bool
            ),
        )
        bv = self._verifier(
            lambda pub, sig, msgs: ev._finish(
                ev.verify_arrays_async(pub, sig, msgs)
            )
        )
        TRACER.clear()
        ok, bits = bv.verify()
        assert ok and bits == [True, True]
        events = TRACER.events()
        (root,) = [e for e in events if e["name"] == "batch_verify"]
        steps = sorted(
            (e for e in events
             if e["args"].get("parent") == "batch_verify"),
            key=lambda e: e["ts"],
        )
        assert [e["name"] for e in steps] == [
            "batch_verify/arm", "verify/pack", "device_launch",
            "device_fetch", "batch_verify/settle",
        ]
        end = root["ts"] + root["dur"]
        for prev, step in zip([None] + steps, steps):
            assert step["tid"] == root["tid"]
            assert root["ts"] <= step["ts"]
            assert step["ts"] + step["dur"] <= end + 0.2
            if prev is not None:  # one after another, never nested
                assert prev["ts"] + prev["dur"] <= step["ts"] + 0.2
            assert ("tdur" in step) == (step["name"] == "device_fetch")
        (fetch,) = [e for e in steps if e["name"] == "device_fetch"]
        assert fetch["tdur"] >= 0 and root["tdur"] >= 0
        assert root["args"]["ok"] is True

    def test_hung_verify_trips_watchdog_within_budget(
        self, hm, monkeypatch
    ):
        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        wd = H.LaunchWatchdog(budget_s=0.05)
        monkeypatch.setattr(H, "WATCHDOG", wd)
        try:
            mark = FLIGHT.recorded_total

            def hung_run(pub, sig, msgs):
                time.sleep(0.2)  # past the 0.05s budget
                return np.ones(len(msgs), dtype=bool)

            bv = self._verifier(hung_run)
            ok, _ = bv.verify()  # must complete — no deadlock
            assert ok
            assert counter_value(hm.device_hangs_total) == 1
            kinds = flight_kinds(mark)
            assert "crypto/device_hang" in kinds
            assert "crypto/device_hang_recovered" in kinds
        finally:
            wd.stop()


class TestPerfLedger:
    def _import(self):
        import sys

        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from tools import perfledger

        return perfledger

    def test_append_replaces_same_key(self, tmp_path):
        pl = self._import()
        path = str(tmp_path / "ledger.json")
        e = pl.make_entry("cfg", 100.0, "sigs/sec", "src", measured="t1")
        pl.append([e], path)
        pl.append([dict(e, value=110.0)], path)
        doc = pl.load(path)
        assert len(doc["entries"]) == 1
        assert doc["entries"][0]["value"] == 110.0
        # a different measured stamp is a NEW trajectory point
        pl.append([dict(e, measured="t2", value=120.0)], path)
        assert len(pl.load(path)["entries"]) == 2
        assert pl.tail(1, path)[0]["value"] == 120.0

    def test_replaced_entry_moves_to_the_end(self, tmp_path):
        """Append order IS recency: re-measuring a config already in
        the ledger must make it the LATEST point, even when older
        entries (e.g. a harvest) were appended after its first
        write — perfdiff and the /debug/perf tail read positionally."""
        pl = self._import()
        path = str(tmp_path / "ledger.json")
        bench = pl.make_entry(
            "verify_commit_150", 50.0, "ms", "bench_all", measured="d1"
        )
        pl.append([bench], path)
        pl.append(
            [pl.make_entry("other", 1.0, "ms", "harvest")], path
        )
        # same key re-measured: must land LAST, not update in place
        pl.append([dict(bench, value=40.0)], path)
        entries = pl.load(path)["entries"]
        assert len(entries) == 2
        assert entries[-1]["config"] == "verify_commit_150"
        assert entries[-1]["value"] == 40.0

    def test_harvest_normalizes_the_result_files(self, tmp_path):
        """Harvest the result files the bench tools write (here: a
        checkout holding a BENCH_ALL.json and a BENCH_MICRO.json):
        every entry has config/value/unit/source with its provenance
        carried through, and re-harvesting is idempotent."""
        pl = self._import()
        repo = tmp_path / "checkout"
        repo.mkdir()
        (repo / "BENCH_ALL.json").write_text(json.dumps({"results": [
            {"config": "verify_commit_150", "value": 2.5, "unit": "ms",
             "dispatch_tier": "keyed", "platform": "tpu",
             "measured": "d1"},
            {"config": "blocksync_replay_1kval", "value": 90000.0,
             "unit": "sigs/sec", "dispatch_tier": "keyed",
             "measured": "d1"},
        ]}))
        (repo / "BENCH_MICRO.json").write_text(json.dumps({"results": [
            {"bench": "wal_write", "ops_per_sec": 1000.0,
             "ns_per_op": 1e6},
        ]}))
        entries = pl.harvest(str(repo))
        assert len(entries) == 3
        for e in entries:
            assert e["config"] and e["source"]
        by_cfg = {e["config"]: e for e in entries}
        assert by_cfg["verify_commit_150"]["dispatch_tier"] == "keyed"
        assert by_cfg["verify_commit_150"]["source"] == "BENCH_ALL.json"
        assert by_cfg["wal_write"]["unit"] == "ops/sec"
        path = str(tmp_path / "ledger.json")
        pl.append(entries, path)
        n = len(pl.load(path)["entries"])
        pl.append(pl.harvest(str(repo)), path)
        assert len(pl.load(path)["entries"]) == n  # idempotent
        assert pl.harvest(str(tmp_path / "empty")) == []

    def test_headline_entry_carries_provenance(self):
        pl = self._import()
        e = pl.headline_entry({
            "metric": "ed25519_batch_verify_throughput",
            "value": 56810.6, "unit": "sigs/sec", "platform": "cpu",
            "jit_compiles": {"keyed": 2}, "steady_retraces": {},
            "keyed_sigs_per_sec": 56810.6,
        })
        assert e["jit_compiles"] == {"keyed": 2}
        assert e["platform"] == "cpu"
        assert e["keyed_sigs_per_sec"] == 56810.6

    def test_health_tail_reads_env_path(self, tmp_path, monkeypatch):
        ledger = tmp_path / "l.json"
        ledger.write_text(json.dumps({
            "schema": 1,
            "entries": [{"config": f"c{i}", "value": i} for i in range(5)],
        }))
        monkeypatch.setenv("CMT_TPU_PERF_LEDGER", str(ledger))
        assert H.perf_ledger_path() == str(ledger)
        tail = H.perf_ledger_tail(2)
        assert [e["config"] for e in tail] == ["c3", "c4"]
        monkeypatch.setenv(
            "CMT_TPU_PERF_LEDGER", str(tmp_path / "missing.json")
        )
        assert H.perf_ledger_tail() == []  # absent ledger: empty, no raise
        # no ledger configured: nothing is read — least of all a file
        # shipped in the checkout — and the tools say so
        monkeypatch.delenv("CMT_TPU_PERF_LEDGER")
        assert H.perf_ledger_path() is None
        assert H.perf_ledger_tail() == []
        with pytest.raises(ValueError, match="CMT_TPU_PERF_LEDGER"):
            self._import().default_path()


class TestPerfDiff:
    FIXTURES = os.path.join(REPO, "tests", "data", "perf_gate")

    def _import(self):
        import sys

        if REPO not in sys.path:
            sys.path.insert(0, REPO)
        from tools import perfdiff

        return perfdiff

    def _load(self, name):
        with open(os.path.join(self.FIXTURES, name)) as f:
            return json.load(f)

    def test_seeded_20pct_regression_fails_gate(self):
        pd = self._import()
        regs, comps = pd.compare(
            self._load("baseline.json"), self._load("regressed.json")
        )
        assert {r["config"] for r in regs} == {
            "keyed_batch_verify", "blocksync_replay_1kval",
            "verify_commit_10000",
            # attribution-plane rows: the seeded store_save slowdown
            # regresses the height-latency SLO row AND its stage row
            "height_latency_p95_4node",
            "height_stage_p95_store_save_4node",
        }
        # latency regressed UP, throughput DOWN — both flagged worse
        assert all(r["delta"] > 0.10 for r in regs)
        # the device-down zero row is skipped, not gated
        assert "device_down_round" not in {c["config"] for c in comps}

    def test_noise_level_deltas_pass(self):
        pd = self._import()
        regs, comps = pd.compare(
            self._load("baseline.json"), self._load("noise.json")
        )
        assert regs == []
        # 3 original rows + height_latency_p95_4node + 10 stage rows
        assert len(comps) == 14

    def test_cli_exit_codes(self, capsys):
        pd = self._import()
        base = os.path.join(self.FIXTURES, "baseline.json")
        assert pd.main(
            [base, os.path.join(self.FIXTURES, "regressed.json")]
        ) == 1
        assert pd.main(
            [base, os.path.join(self.FIXTURES, "noise.json")]
        ) == 0
        assert pd.main([]) == 2  # usage error
        capsys.readouterr()

    def test_selftest_is_green(self, capsys):
        pd = self._import()
        assert pd.selftest() == 0
        assert "perf-gate: ok" in capsys.readouterr().out

    def test_direction_comes_from_unit(self):
        pd = self._import()
        mk = lambda v, u: {"entries": [
            {"config": "c", "value": v, "unit": u, "source": "t"}
        ]}
        # throughput: higher new value is an improvement
        regs, _ = pd.compare(mk(100, "sigs/sec"), mk(200, "sigs/sec"))
        assert regs == []
        # latency: higher new value is a regression
        regs, _ = pd.compare(mk(100, "ms"), mk(200, "ms"))
        assert len(regs) == 1

    def test_threshold_is_tunable(self):
        pd = self._import()
        base = self._load("baseline.json")
        noise = self._load("noise.json")
        regs, _ = pd.compare(base, noise, threshold=0.01)
        assert regs, "1% threshold must flag the 3% noise"

"""Failover dispatch ladder tests (crypto/dispatch.py).

Covers the ISSUE 9 acceptance set: deterministic chaos-plan parsing and
scheduling (seeded schedules are reproducible, mislaunch is one-shot,
shard_loss only faults the mesh tiers), the demotion/promotion state
machine under a fake clock (exponential cool-down, half-open trials,
probe-streak hysteresis, no thrash on a flapping tier), the execute
seam's ladder walk with typed TierFault escalation (chaos faults fall
tier by tier to the host/python floor with exact verdicts preserved),
the launch_hang fault reproducing the r04 watchdog signature end to
end, zero steady-state retraces under a sealed CMT_TPU_JITGUARD while
the ladder demotes and re-promotes on the forced-8-device CPU mesh,
the /debug/dispatch surfaces, race-mode hammering of the new guarded
classes, the host-or-device decision table of ``TpuBatchVerifier.
_plan()`` and the static BLS walks, the ladder's batch counter up to
the benchmark's reader of it, and the tier-1 chaos liveness drive: a single-validator node
under CMT_TPU_CHAOS=1 commits >= 20 consecutive heights through an
injected device loss and recovery while the flight recorder shows the
demotion chain and the later re-promotion (`make chaos-smoke` runs the
liveness subset standalone).
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from cometbft_tpu.crypto import dispatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.metrics import (
    CryptoMetrics,
    HealthMetrics,
    install_crypto_metrics,
    install_health_metrics,
)
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.metrics import Registry

# benchmark/ (the counter's reader) sits beside tests/, not on the path
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture
def cm():
    """Fresh registry-backed crypto + health sinks, uninstalled after."""
    crypto = CryptoMetrics(Registry())
    health = HealthMetrics(Registry())
    install_crypto_metrics(crypto)
    install_health_metrics(health)
    try:
        yield crypto
    finally:
        install_crypto_metrics(None)
        install_health_metrics(None)


@pytest.fixture
def dispatch_env():
    """Returns a setter for the ladder/chaos env knobs; whatever a test
    sets, the originals are restored and the singletons re-read the
    CLEAN env after (monkeypatch can't give that ordering: its undo
    runs after fixture teardown, which would re-seed the process-wide
    LADDER/CHAOS with the test's knobs)."""
    knobs = (
        "CMT_TPU_CHAOS", "CMT_TPU_CHAOS_PLAN", "CMT_TPU_DEMOTE_AFTER",
        "CMT_TPU_PROMOTE_AFTER", "CMT_TPU_COOLDOWN_S",
        "CMT_TPU_COOLDOWN_MAX_S",
    )
    saved = {k: os.environ.get(k) for k in knobs}

    def set_env(**kv: str) -> None:
        for key, val in kv.items():
            assert key in knobs, key
            os.environ[key] = val
        dispatch.reset_for_tests()

    try:
        yield set_env
    finally:
        for key, val in saved.items():
            if val is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = val
        dispatch.reset_for_tests()


def counter_value(metric, **labels) -> float:
    return metric.labels(**labels).get()


def flight_events_since(since_total: int) -> list[dict]:
    """Wrap-proof flight tail after a FLIGHT.recorded_total mark
    (tests/test_health.py rationale: positional marks go stale once
    the bounded ring fills)."""
    events = FLIGHT.events()
    new = FLIGHT.recorded_total - since_total
    if new <= 0:
        return []
    return events[-min(new, len(events)):]


def transitions_since(mark: int) -> list[dict]:
    return [
        ev for ev in flight_events_since(mark)
        if ev["kind"] == "crypto/dispatch_transition"
    ]


class Clock:
    """Explicit test clock for the ladder state machine."""

    def __init__(self) -> None:
        self.t = 0.0

    def __call__(self) -> float:
        return self.t


def make_ladder(clock, **kw):
    kw.setdefault("demote_after", 3)
    kw.setdefault("promote_after", 2)
    kw.setdefault("cooldown_s", 1.0)
    kw.setdefault("cooldown_max_s", 8.0)
    return dispatch.DispatchLadder(clock=clock, **kw)


# -- chaos plan ----------------------------------------------------------


class TestChaosPlanParse:
    def test_explicit_windows(self):
        plan = dispatch.ChaosPlan.parse(
            "device_loss@0-2.5; mislaunch@4-5 ;shard_loss@6-7"
        )
        assert plan.windows == [
            (0.0, 2.5, "device_loss"),
            (4.0, 5.0, "mislaunch"),
            (6.0, 7.0, "shard_loss"),
        ]

    def test_seeded_schedule_is_deterministic(self):
        spec = "seed=7,on=2,off=5,n=6,kinds=device_loss|mislaunch"
        a = dispatch.ChaosPlan.parse(spec)
        b = dispatch.ChaosPlan.parse(spec)
        assert a.windows == b.windows
        assert len(a.windows) == 6
        assert {k for _, _, k in a.windows} <= {
            "device_loss", "mislaunch"
        }
        # a different seed produces a different schedule
        c = dispatch.ChaosPlan.parse(spec.replace("seed=7", "seed=8"))
        assert c.windows != a.windows

    def test_default_drill_spec_parses(self, dispatch_env):
        dispatch_env(CMT_TPU_CHAOS="1")  # no explicit plan
        assert dispatch.CHAOS.enabled()
        assert dispatch.CHAOS.plan.windows

    def test_disabled_without_env(self, dispatch_env):
        dispatch_env(CMT_TPU_COOLDOWN_S="0.5")  # chaos not set
        assert not dispatch.CHAOS.enabled()
        dispatch.CHAOS.inject("keyed")  # no-op, must not raise

    @pytest.mark.parametrize("bad", [
        "volcano@0-2",            # unknown kind
        "device_loss@5-2",        # end before start
        "device_loss@-1-2",       # negative start
        "device_loss",            # no window
        "",                       # empty plan
        "seed=1,warp=9",          # unknown seeded param
    ])
    def test_parse_errors_fail_loudly(self, bad):
        with pytest.raises(ValueError, match="CMT_TPU_CHAOS_PLAN"):
            dispatch.ChaosPlan.parse(bad)


class TestChaosPlanSchedule:
    def test_applies_scope(self):
        plan = dispatch.ChaosPlan.parse("shard_loss@0-1")
        # shard loss: one chip gone — only the mesh tiers fault
        assert plan.applies("shard_loss", "keyed_mesh")
        assert plan.applies("shard_loss", "generic_mesh")
        assert not plan.applies("shard_loss", "keyed")
        assert not plan.applies("shard_loss", "generic")
        # the host/python floor is never chaos'd, for any kind
        for kind in dispatch.CHAOS_KINDS:
            assert not plan.applies(kind, "host")
            assert not plan.applies(kind, "python")
        assert plan.applies("device_loss", "generic")

    def test_fault_at_windows_and_gaps(self):
        plan = dispatch.ChaosPlan.parse("device_loss@1-2")
        fired: set[int] = set()
        assert plan.fault_at("keyed", 0.5, fired) is None
        assert plan.fault_at("keyed", 1.5, fired) == (0, "device_loss")
        assert plan.fault_at("keyed", 2.0, fired) is None  # end-exclusive
        assert plan.fault_at("host", 1.5, fired) is None

    def test_mislaunch_is_one_shot(self):
        plan = dispatch.ChaosPlan.parse("mislaunch@0-10")
        fired: set[int] = set()
        idx, kind = plan.fault_at("generic", 1.0, fired)
        assert kind == "mislaunch"
        fired.add(idx)
        # same window never fires twice: the fault was transient
        assert plan.fault_at("generic", 2.0, fired) is None


# -- the ladder state machine --------------------------------------------


class TestLadderStateMachine:
    def test_fault_demotes_with_exponential_cooldown(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["keyed", "generic"])
        assert ladder.current_tier() == "keyed"
        mark = FLIGHT.recorded_total
        ladder.tier_fault("keyed", reason="launch:RuntimeError", batch=4)
        assert not ladder.active("keyed")
        assert ladder.current_tier() == "generic"
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "keyed", "to": "generic",
               "reason": "launch:RuntimeError"},
        ) == 1
        evs = transitions_since(mark)
        assert evs and evs[0]["transition"] == "demote"
        assert evs[0]["tier"] == "keyed" and evs[0]["to"] == "generic"
        # cool-down doubles per repeat offense, capped at the max
        st = ladder.snapshot()["tiers"]["keyed"]
        assert st["cooldown_remaining_s"] == pytest.approx(1.0)
        assert st["next_cooldown_s"] == 2.0
        for expect in (4.0, 8.0, 8.0):
            clock.t += 100.0  # past cool-down: half-open re-admission
            ladder.tier_fault("keyed", reason="launch:RuntimeError")
            assert ladder.snapshot()["tiers"]["keyed"][
                "next_cooldown_s"
            ] == expect

    def test_half_open_trial_success_promotes(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["generic"])
        ladder.tier_fault("generic", reason="watchdog")
        assert not ladder.active("generic")
        # cool-down still running: the tier stays inadmissible
        clock.t = 0.5
        assert not ladder.active("generic")
        assert ladder.current_tier() == "host"
        # expiry re-admits for a trial; a successful batch promotes
        clock.t = 1.5
        assert ladder.active("generic")
        mark = FLIGHT.recorded_total
        ladder.note_batch("generic")
        assert ladder.snapshot()["tiers"]["generic"]["demoted"] is False
        assert ladder.current_tier() == "generic"
        assert counter_value(
            cm.dispatch_promotions_total, tier="generic"
        ) == 1
        evs = transitions_since(mark)
        assert [e["transition"] for e in evs] == ["promote"]
        assert evs[0]["reason"] == "trial_success"

    def test_probe_streak_hysteresis(self, cm):
        clock = Clock()
        ladder = make_ladder(clock, demote_after=3, promote_after=2)
        ladder.admissible(["keyed"])
        # two failures + a success: streak resets, no demotion
        ladder.note_probe("keyed", False)
        ladder.note_probe("keyed", False)
        ladder.note_probe("keyed", True)
        assert ladder.active("keyed")
        # three consecutive failures demote with reason probe_failures
        for _ in range(3):
            ladder.note_probe("keyed", False)
        assert not ladder.active("keyed")
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "keyed", "to": "host",
               "reason": "probe_failures"},
        ) == 1
        # healthy canaries before cool-down expiry do NOT promote
        ladder.note_probe("keyed", True)
        ladder.note_probe("keyed", True)
        assert ladder.snapshot()["tiers"]["keyed"]["demoted"] is True
        # after expiry, M consecutive healthy canaries promote
        clock.t = 2.0
        ladder.note_probe("keyed", True)
        ladder.note_probe("keyed", True)
        assert ladder.snapshot()["tiers"]["keyed"]["demoted"] is False
        assert counter_value(
            cm.dispatch_promotions_total, tier="keyed"
        ) == 1

    def test_flapping_tier_cooldown_caps_no_thrash(self, cm):
        """A tier that keeps faulting right after each re-admission
        gets exponentially rarer chances: its cool-down grows to the
        cap and STAYS there (through promotions too), so the ladder
        can never enter a tight demote/promote thrash loop."""
        clock = Clock()
        ladder = make_ladder(clock, cooldown_s=1.0, cooldown_max_s=8.0)
        ladder.admissible(["generic"])
        last = 0.0
        for _ in range(6):
            ladder.tier_fault("generic", reason="launch:OSError")
            st = ladder.snapshot()["tiers"]["generic"]
            assert st["next_cooldown_s"] >= last
            last = st["next_cooldown_s"]
            clock.t += st["cooldown_remaining_s"] + 0.01
            ladder.note_batch("generic")  # trial success -> promote
            # promotion does NOT reset the elevated cool-down
            assert ladder.snapshot()["tiers"]["generic"][
                "next_cooldown_s"
            ] == last
        assert last == 8.0

    def test_inflight_success_inside_cooldown_does_not_promote(
        self, cm
    ):
        """A launch already in flight when the watchdog demoted its
        tier can return late-but-successfully INSIDE the cool-down;
        that is not trial evidence and must not cancel the demotion
        (the r04 overrun-then-return shape would otherwise keep the
        slow tier in rotation forever)."""
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["keyed"])
        ladder.tier_fault("keyed", reason="watchdog")
        clock.t = 0.5  # cool-down (1.0 s) still running
        ladder.note_batch("keyed")
        assert ladder.snapshot()["tiers"]["keyed"]["demoted"] is True
        assert counter_value(
            cm.dispatch_promotions_total, tier="keyed"
        ) == 0
        # past expiry the same success IS the half-open trial
        clock.t = 1.5
        ladder.note_batch("keyed")
        assert ladder.snapshot()["tiers"]["keyed"]["demoted"] is False

    def test_duplicate_fault_records_signal_without_double_backoff(
        self, cm
    ):
        """The watchdog-then-exception pair: the second signal lands
        in the counters and the trail, but the exponential back-off
        advances once per offense — even when the stalled call's
        exception arrives after the cool-down expired."""
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["generic"])
        ladder.tier_fault("generic", reason="watchdog")
        assert ladder.snapshot()["tiers"]["generic"][
            "next_cooldown_s"
        ] == 2.0
        clock.t = 1.5  # past cooldown_until: the time-window dup
        # heuristic alone would re-escalate; the explicit pairing wins
        ladder.tier_fault(
            "generic", reason="chaos:launch_hang", duplicate=True
        )
        st = ladder.snapshot()["tiers"]["generic"]
        assert st["demotions"] == 2  # both signals recorded
        assert st["next_cooldown_s"] == 2.0  # back-off advanced ONCE
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host",
               "reason": "chaos:launch_hang"},
        ) == 1

    def test_failing_canary_past_cooldown_consumes_the_trial(self, cm):
        """An active prober that keeps reporting a demoted tier dead
        re-closes it at cool-down expiry (doubled cool-down), so a
        production batch is never the guinea pig for a tier the
        canaries already know is down."""
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["keyed"])
        ladder.tier_fault("keyed", reason="watchdog")
        clock.t = 0.5  # still cooling down: duplicate evidence only
        ladder.note_probe("keyed", False)
        assert ladder.snapshot()["tiers"]["keyed"]["demotions"] == 1
        clock.t = 1.5
        assert ladder.active("keyed")  # half-open
        ladder.note_probe("keyed", False)
        st = ladder.snapshot()["tiers"]["keyed"]
        assert st["demotions"] == 2
        assert not ladder.active("keyed")
        assert st["next_cooldown_s"] == 4.0  # doubled again
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "keyed", "to": "host",
               "reason": "probe_failures"},
        ) == 1

    def test_floor_never_demoted(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.tier_fault("python", reason="launch:ValueError")
        assert ladder.active("python")
        assert ladder.snapshot()["tiers"]["python"]["demoted"] is False
        # even with everything else down, current_tier has a floor
        for tier in ("keyed_mesh", "keyed", "generic_mesh", "generic",
                     "host"):
            ladder.admissible([tier])
            ladder.tier_fault(tier, reason="watchdog")
        assert ladder.current_tier() == "python"

    def test_watchdog_fault_reason_and_probe_prefix_scope(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["generic"])
        ladder.watchdog_fault("generic")
        assert not ladder.active("generic")
        assert ladder.snapshot()["tiers"]["generic"][
            "last_reason"
        ] == "watchdog"
        ladder.watchdog_fault("python")  # floor: no-op
        ladder.watchdog_fault("not-a-tier")  # unknown: no-op
        assert ladder.current_tier() == "host"

    def test_current_tier_gauge_is_one_hot(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["keyed", "generic"])
        ladder.note_batch("keyed")

        def one_hot() -> dict[str, float]:
            return {
                t: counter_value(cm.dispatch_current_tier, tier=t)
                for t in dispatch.TIER_ORDER
            }

        hot = one_hot()
        assert hot["keyed"] == 1.0 and sum(hot.values()) == 1.0
        ladder.tier_fault("keyed", reason="watchdog")
        hot = one_hot()
        assert hot["generic"] == 1.0 and sum(hot.values()) == 1.0

    def test_note_batch_counts_at_single_decision_point(self, cm):
        """crypto_dispatch_tier accounting is unified: every batch —
        device tier or host-only factory route — lands in note_batch."""
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.note_batch("host")
        ladder.note_batch("host")
        ladder.note_batch("keyed")
        assert counter_value(cm.dispatch_tier, tier="host") == 2
        assert counter_value(cm.dispatch_tier, tier="keyed") == 1

    def test_snapshot_and_transition_trail(self, cm):
        clock = Clock()
        ladder = make_ladder(clock)
        ladder.admissible(["generic"])
        ladder.tier_fault("generic", reason="chaos:device_loss")
        snap = ladder.snapshot()
        assert snap["order"] == list(dispatch.TIER_ORDER)
        assert snap["current"] == "host"
        assert snap["policy"]["demote_after"] == 3
        assert snap["transitions"][-1]["kind"] == "demote"
        assert snap["transitions"][-1]["reason"] == "chaos:device_loss"
        assert snap["tiers"]["generic"]["demotions"] == 1


class TestEnvValidation:
    @pytest.mark.parametrize("var,reader", [
        ("CMT_TPU_DEMOTE_AFTER", dispatch.demote_after_from_env),
        ("CMT_TPU_PROMOTE_AFTER", dispatch.promote_after_from_env),
        ("CMT_TPU_COOLDOWN_S", dispatch.cooldown_from_env),
        ("CMT_TPU_COOLDOWN_MAX_S", dispatch.cooldown_max_from_env),
    ])
    def test_knobs_fail_loudly(self, var, reader, monkeypatch):
        monkeypatch.delenv(var, raising=False)
        assert reader() > 0
        monkeypatch.setenv(var, "abc")
        with pytest.raises(ValueError, match=var):
            reader()
        monkeypatch.setenv(var, "0")
        with pytest.raises(ValueError, match=var):
            reader()


# -- the execute seam's ladder walk --------------------------------------


def _fill(bv, n: int, tag: bytes = b"dl", tamper: set[int] = frozenset()):
    priv = ed.priv_key_from_secret(tag)
    for i in range(n):
        msg = tag + b"-%d" % i
        sig = priv.sign(msg)
        if i in tamper:
            sig = sig[:-1] + bytes([sig[-1] ^ 1])
        bv.add(priv.pub_key(), msg, sig)
    return bv


def _fake_ok(bv):
    """Fake device runner: every lane verifies, no XLA involved."""
    return lambda tier, plan: np.ones(plan.n, dtype=bool)


@pytest.fixture
def verifier_cls(monkeypatch):
    monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

    return TpuBatchVerifier


class TestExecuteLadderWalk:
    def test_healthy_tier_serves_and_is_accounted(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        dispatch_env(CMT_TPU_COOLDOWN_S="0.05")
        bv = _fill(verifier_cls(device_min_batch=1), 3)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        ok, results = bv.verify()
        assert ok and results == [True, True, True]
        assert bv._last_tier == "generic"
        assert counter_value(cm.dispatch_tier, tier="generic") == 1

    def test_chaos_device_loss_falls_to_floor_with_exact_verdicts(
        self, cm, dispatch_env, verifier_cls
    ):
        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="device_loss@0-3600",
            CMT_TPU_COOLDOWN_S="30",
        )
        mark = FLIGHT.recorded_total
        bv = _fill(verifier_cls(device_min_batch=1), 3, tamper={1})
        ok, results = bv.verify()
        # the walk ended on a host-side tier with EXACT verdicts: the
        # injected loss cost availability of the device, never
        # correctness
        assert ok is False and results == [True, False, True]
        assert bv._last_tier in ("host", "python")
        assert not dispatch.LADDER.active("generic")
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host",
               "reason": "chaos:device_loss"},
        ) == 1
        evs = transitions_since(mark)
        assert [e["transition"] for e in evs] == ["demote"]

    def test_plan_reports_ladder_demoted_reason(
        self, cm, dispatch_env, verifier_cls
    ):
        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        dispatch.LADDER.admissible(["generic"])
        dispatch.LADDER.tier_fault("generic", reason="watchdog")
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        plan = bv.plan()
        assert plan.route == "host"
        assert plan.reason == "ladder_demoted"
        assert plan.tiers == ["host", "python"]
        ok, results = bv.execute(plan)
        assert ok and results == [True, True]
        assert counter_value(cm.dispatch_tier, tier="host") == 1

    def test_tier_demoted_between_plan_and_execute_is_skipped(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        """The verify queue parks plans; a tier demoted while a plan
        waits must be skipped mid-walk without a fresh fault."""
        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        launched = []
        monkeypatch.setattr(
            bv, "_run_tier",
            lambda tier, plan: launched.append(tier)
            or np.ones(plan.n, dtype=bool),
        )
        plan = bv.plan()
        assert plan.tiers[0] == "generic"
        demotions_before = counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host", "reason": "watchdog"},
        )
        dispatch.LADDER.tier_fault("generic", reason="watchdog")
        ok, _ = bv.execute(plan)
        assert ok and launched == []  # generic never attempted
        assert bv._last_tier == "host"
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host", "reason": "watchdog"},
        ) == demotions_before + 1  # only the explicit fault, no double

    def test_recovery_trial_promotes_through_execute(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="device_loss@0-0.3",
            CMT_TPU_COOLDOWN_S="0.05",
            CMT_TPU_COOLDOWN_MAX_S="0.3",
        )
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        ok, _ = bv.verify()
        assert ok and bv._last_tier == "host"
        # active("generic") flips back True once the 0.05 s cool-down
        # expires (half-open trial), so assert the demotion through the
        # counter instead of racing the clock
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host",
               "reason": "chaos:device_loss"},
        ) == 1
        time.sleep(0.7)  # past the window AND the cool-down
        mark = FLIGHT.recorded_total
        bv2 = _fill(verifier_cls(device_min_batch=1), 2, tag=b"dl2")
        monkeypatch.setattr(bv2, "_run_tier", _fake_ok(bv2))
        ok, _ = bv2.verify()
        assert ok and bv2._last_tier == "generic"
        assert dispatch.LADDER.current_tier() == "generic"
        assert counter_value(
            cm.dispatch_promotions_total, tier="generic"
        ) == 1
        promotes = [
            e for e in transitions_since(mark)
            if e["transition"] == "promote"
        ]
        assert promotes and promotes[0]["reason"] == "trial_success"

    def test_mislaunch_is_transient(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="mislaunch@0-3600",
            CMT_TPU_COOLDOWN_S="0.05",
        )
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        ok, _ = bv.verify()
        assert ok and bv._last_tier == "host"  # one transient fault
        time.sleep(0.1)
        bv2 = _fill(verifier_cls(device_min_batch=1), 2, tag=b"ml2")
        monkeypatch.setattr(bv2, "_run_tier", _fake_ok(bv2))
        ok, _ = bv2.verify()
        # the window's one shot is spent: the trial succeeds, promotes
        assert ok and bv2._last_tier == "generic"
        assert dispatch.CHAOS.snapshot()["hits"] == {"mislaunch": 1}

    def test_launch_hang_trips_watchdog_then_demotes(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        """The r04 signature end to end: the injected hang sleeps past
        the watchdog budget INSIDE the armed watch, so the overrun
        fires (hang counter + watchdog demotion) before the stalled
        launch returns, and the chaos fault then re-demotes."""
        from cometbft_tpu.crypto import health as _health
        from cometbft_tpu.metrics import health_metrics as _hm

        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="launch_hang@0-3600",
            CMT_TPU_COOLDOWN_S="30",
        )
        monkeypatch.setattr(_health.WATCHDOG, "_budget", 0.15)
        hangs0 = counter_value(_hm().device_hangs_total)
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        t0 = time.perf_counter()
        ok, results = bv.verify()
        assert ok and results == [True, True]  # the floor still answers
        assert time.perf_counter() - t0 < 5.0
        deadline = time.time() + 5
        while time.time() < deadline and (
            counter_value(_hm().device_hangs_total) == hangs0
        ):
            time.sleep(0.01)
        assert counter_value(_hm().device_hangs_total) == hangs0 + 1
        snap = dispatch.LADDER.snapshot()["tiers"]["generic"]
        assert snap["demoted"] is True
        # both signals recorded: the watchdog demotion AND the chaos
        # fault's re-demotion (order fixed: the watchdog fires first)
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host", "reason": "watchdog"},
        ) == 1
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host",
               "reason": "chaos:launch_hang"},
        ) == 1
        # one offense, one back-off step: the escalation knew the
        # watchdog had already demoted this launch's tier
        assert snap["next_cooldown_s"] == 60.0

    def test_launch_hang_sleeps_inside_batch_verify_arm(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        """The chaos hook runs inside ``batch_verify/arm``, under the
        armed watchdog: the hang is that span's (asleep: off the
        launch's thread's CPU), it trips the watchdog and the ladder,
        and the failed attempt has no ``batch_verify/settle``."""
        from cometbft_tpu.crypto import health as _health
        from cometbft_tpu.metrics import health_metrics as _hm
        from cometbft_tpu.utils.trace import TRACER

        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="launch_hang@0-3600",
            CMT_TPU_COOLDOWN_S="30",
        )
        monkeypatch.setattr(_health.WATCHDOG, "_budget", 0.1)
        hangs0 = counter_value(_hm().device_hangs_total)
        bv = _fill(verifier_cls(device_min_batch=1), 2)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        TRACER.clear()
        ok, _ = bv.verify()
        assert ok and bv._last_tier == "host"
        deadline = time.time() + 5
        while time.time() < deadline and (
            counter_value(_hm().device_hangs_total) == hangs0
        ):
            time.sleep(0.01)
        assert counter_value(_hm().device_hangs_total) == hangs0 + 1
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic", "to": "host", "reason": "watchdog"},
        ) == 1
        events = TRACER.events()
        (arm,) = [e for e in events if e["name"] == "batch_verify/arm"]
        (root,) = [e for e in events if e["name"] == "batch_verify"]
        assert arm["args"]["error"] == root["args"]["error"] == "ChaosFault"
        assert arm["args"]["parent"] == "batch_verify"
        assert arm["tid"] == root["tid"]
        assert arm["dur"] >= 0.1 * 1.25 * 1e6 - 1e3  # the injected sleep
        assert root["tdur"] < 0.5 * root["dur"]
        assert not [e for e in events if e["name"] == "batch_verify/settle"]

    def test_shard_loss_faults_only_mesh_tiers(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="shard_loss@0-3600",
            CMT_TPU_COOLDOWN_S="30",
        )

        class MeshLike(verifier_cls):
            def _generic_tiers(self):
                return ["generic_mesh", "generic"]

        bv = _fill(MeshLike(device_min_batch=1), 2)
        monkeypatch.setattr(bv, "_run_tier", _fake_ok(bv))
        ok, _ = bv.verify()
        # one chip gone: the mesh tier faults, the single-device rung
        # one below it serves the batch
        assert ok and bv._last_tier == "generic"
        assert not dispatch.LADDER.active("generic_mesh")
        assert dispatch.LADDER.active("generic")
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "generic_mesh", "to": "generic",
               "reason": "chaos:shard_loss"},
        ) == 1

    def test_host_fault_falls_to_python_floor(self, cm, dispatch_env,
                                              monkeypatch):
        dispatch_env(CMT_TPU_COOLDOWN_S="30")

        def boom(self):
            raise RuntimeError("native lib crashed")

        monkeypatch.setattr(ed.CpuBatchVerifier, "verify", boom)
        bv = dispatch.LadderHostVerifier()
        priv = ed.priv_key_from_secret(b"floor")
        good, bad = b"good", b"bad"
        bv.add(priv.pub_key(), good, priv.sign(good))
        bv.add(priv.pub_key(), bad, priv.sign(good))  # wrong msg
        ok, results = bv.verify()
        assert ok is False and results == [True, False]
        assert not dispatch.LADDER.active("host")
        assert dispatch.LADDER.current_tier() == "python"
        assert counter_value(cm.dispatch_tier, tier="python") == 1
        assert counter_value(
            cm.dispatch_demotions_total,
            **{"from": "host", "to": "python",
               "reason": "launch:RuntimeError"},
        ) == 1

    def test_ladder_host_verifier_records_per_batch(self, cm,
                                                    dispatch_env):
        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        for i in range(2):
            bv = dispatch.LadderHostVerifier()
            _fill(bv, 2, tag=b"lhv%d" % i)
            ok, _ = bv.verify()
            assert ok
        assert counter_value(cm.dispatch_tier, tier="host") == 2


# -- the host-or-device decision (ops/ed25519_verify.py _plan) -----------

THRESHOLD = 128  # the accelerator's ACCELERATOR_MIN_BATCH, made explicit
HOST_WALK = ["host", "python"]


class FakeTables:
    """TABLE_CACHE stand-in for plan()-only tests: no EC build, no XLA.
    Records which of the two probes the plan made."""

    class Entry:
        def key_ids(self, pubs):
            return np.zeros(len(pubs), dtype=np.int32)

    def __init__(self, warm: bool, build="entry") -> None:
        self.warm = warm
        self.build = build
        self.calls: list[str] = []

    def peek(self, pubs):
        self.calls.append("peek")
        return self.Entry() if self.warm else None

    def lookup_or_build(self, pubs):
        self.calls.append("lookup_or_build")
        if self.build == "raise":
            raise RuntimeError("table build failed")
        return self.Entry() if self.build == "entry" else None


def _unsigned(bv, n: int, msg: bytes = b"plan-only"):
    """n entries that are only ever planned, never verified."""
    pub = ed.priv_key_from_secret(b"plan").pub_key()
    for _ in range(n):
        bv.add(pub, msg, bytes(64))
    return bv


def _table_row(n: int, warm: bool):
    """The comment block over DEVICE_MIN_BATCH, as data: (route,
    reason, tiers, table probes) for a batch of n under THRESHOLD."""
    if n >= THRESHOLD:
        return ("device", "batch_size",
                ["keyed", "generic"] + HOST_WALK, ["lookup_or_build"])
    if n >= 64:
        if warm:
            return ("device", "keyed_warm", ["keyed"] + HOST_WALK,
                    ["peek"])
        return "host", "batch_size", HOST_WALK, ["peek"]
    return "host", "batch_size", HOST_WALK, []


class TestPlanDecisionTable:
    @pytest.fixture(autouse=True)
    def clean(self, cm, dispatch_env, monkeypatch):
        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        monkeypatch.delenv("CMT_TPU_DISABLE_PRECOMPUTE", raising=False)
        monkeypatch.delenv("CMT_TPU_DEVICE_MIN_BATCH", raising=False)
        self.cm = cm

    def plan(self, monkeypatch, n, tables, msg=b"plan-only", **kw):
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

        monkeypatch.setattr(PR, "TABLE_CACHE", tables)
        return _unsigned(TpuBatchVerifier(**kw), n, msg).plan()

    @pytest.mark.parametrize("warm", [True, False], ids=["warm", "cold"])
    @pytest.mark.parametrize(
        "n", [1, 2, 63, 64, 100, 127, 128, 150, 256, 1000, 1024]
    )
    def test_size_against_the_two_constants(self, n, warm, monkeypatch):
        tables = FakeTables(warm)
        plan = self.plan(monkeypatch, n, tables,
                         device_min_batch=THRESHOLD)
        route, reason, tiers, probes = _table_row(n, warm)
        assert (plan.route, plan.reason) == (route, reason)
        assert plan.tiers == tiers
        assert tables.calls == probes
        assert (plan.entry is not None) == ("keyed" in tiers)
        assert counter_value(
            self.cm.dispatch_decisions, route=route, reason=reason
        ) == 1

    def test_empty_batch_plans_nothing(self, monkeypatch):
        plan = self.plan(monkeypatch, 0, FakeTables(True),
                         device_min_batch=THRESHOLD)
        assert plan.route == "empty" and plan.tiers == []

    @pytest.mark.parametrize("n", [100, 150])
    def test_message_over_the_largest_bucket(self, n, monkeypatch):
        from cometbft_tpu.ops.ed25519_verify import _BUCKETS

        tables = FakeTables(True)
        plan = self.plan(monkeypatch, n, tables,
                         msg=bytes(_BUCKETS[-1] + 1),
                         device_min_batch=THRESHOLD)
        assert (plan.route, plan.reason) == ("host", "msg_too_large")
        assert plan.tiers == HOST_WALK and tables.calls == []

    @pytest.mark.parametrize("n", [100, 150, 1024])
    def test_cpu_backend_never_dispatches(self, n, monkeypatch):
        from cometbft_tpu.ops import ed25519_verify as EV

        assert EV.runtime_device_min_batch() == EV.NO_DEVICE_DISPATCH
        tables = FakeTables(True)
        plan = self.plan(monkeypatch, n, tables)  # the backend's rule
        assert (plan.route, plan.reason) == ("host", "cpu_backend")
        assert plan.tiers == HOST_WALK and tables.calls == []

    @pytest.mark.parametrize("demoted,route,reason,tiers,probes", [
        (("keyed", "generic"), "host", "ladder_demoted", HOST_WALK, []),
        (("keyed",), "device", "batch_size", ["generic"] + HOST_WALK,
         []),
        (("generic",), "device", "batch_size", ["keyed"] + HOST_WALK,
         ["lookup_or_build"]),
    ], ids=["both", "keyed", "generic"])
    def test_demoted_tiers_leave_the_walk(
        self, demoted, route, reason, tiers, probes, monkeypatch
    ):
        for tier in demoted:
            dispatch.LADDER.tier_fault(tier, reason="watchdog")
        tables = FakeTables(True)
        plan = self.plan(monkeypatch, 150, tables,
                         device_min_batch=THRESHOLD)
        assert (plan.route, plan.reason) == (route, reason)
        assert plan.tiers == tiers
        # a dead keyed tier must not stall the plan behind a build
        assert tables.calls == probes

    @pytest.mark.parametrize("n,route,tiers", [
        (100, "host", HOST_WALK),
        (150, "device", ["generic"] + HOST_WALK),
    ])
    def test_disable_precompute_skips_the_tables(
        self, n, route, tiers, monkeypatch
    ):
        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        tables = FakeTables(True)
        plan = self.plan(monkeypatch, n, tables,
                         device_min_batch=THRESHOLD)
        assert (plan.route, plan.reason) == (route, "batch_size")
        assert plan.tiers == tiers and tables.calls == []

    @pytest.mark.parametrize("build,faulted", [
        ("none", False), ("raise", True),
    ])
    def test_no_tables_leaves_generic(self, build, faulted, monkeypatch):
        """Out of table policy (None) is no fault; a lookup that
        raises faults the keyed tier.  Either way the batch goes on."""
        plan = self.plan(monkeypatch, 150, FakeTables(False, build),
                         device_min_batch=THRESHOLD)
        assert plan.route == "device" and plan.entry is None
        assert plan.tiers == ["generic"] + HOST_WALK
        assert dispatch.LADDER.active("keyed") is not faulted


class TestBlsStaticWalks:
    class Pub:
        def type(self):
            from cometbft_tpu.crypto import bls12381

            return bls12381.KEY_TYPE

    def verifier(self, mode: str):
        from cometbft_tpu.crypto import bls12381, bls_dispatch

        v = bls_dispatch.BlsLadderVerifier()
        sig = bytes(bls12381.SIGNATURE_SIZE)
        if mode == "aggregate":
            v.set_aggregate([self.Pub()] * 3, b"m", sig)
        else:
            for _ in range(3):
                v.add(self.Pub(), b"m", sig)
        return v

    @pytest.mark.parametrize("mode,native,tiers", [
        ("aggregate", True, ["bls_native", "python"]),
        ("batch", True, ["bls_native", "host", "python"]),
        ("aggregate", False, ["python"]),
        ("batch", False, ["host", "python"]),
    ])
    def test_walks(self, mode, native, tiers, cm, dispatch_env,
                   monkeypatch):
        from cometbft_tpu.crypto import bls_dispatch

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        monkeypatch.setattr(
            bls_dispatch.bls_native, "available", lambda: native
        )
        plan = self.verifier(mode).plan()
        assert plan.mode == mode and plan.tiers == tiers

    def test_demoted_native_leaves_the_walk(self, cm, dispatch_env,
                                            monkeypatch):
        from cometbft_tpu.crypto import bls_dispatch

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        monkeypatch.setattr(
            bls_dispatch.bls_native, "available", lambda: True
        )
        dispatch.LADDER.tier_fault("bls_native", reason="watchdog")
        assert self.verifier("batch").plan().tiers == HOST_WALK


# -- the batch counter (LADDER.cost_snapshot) and its readers ------------


class TestShapeBucket:
    def test_pow2_ceiling(self):
        assert dispatch.shape_bucket(0) == 1
        assert dispatch.shape_bucket(1) == 1
        assert dispatch.shape_bucket(2) == 2
        assert dispatch.shape_bucket(3) == 4
        assert dispatch.shape_bucket(64) == 64
        assert dispatch.shape_bucket(150) == 256
        assert dispatch.shape_bucket(10_000) == 16384

    def test_capped(self):
        assert dispatch.shape_bucket(1 << 30) == dispatch.MAX_SHAPE_BUCKET


class TestBatchCounter:
    @pytest.mark.parametrize("family,tier,n,bucket", [
        ("ed25519", "keyed", 150, 256),
        ("ed25519", "keyed_mesh", 1000, 1024),
        ("ed25519", "generic", 1 << 21, dispatch.MAX_SHAPE_BUCKET),
        ("ed25519", "host", 1, 1),
        ("bls", "host", 3, 4),
        ("bls", "bls_native", 8, 8),
        ("bls_agg", "bls_native", 150, 256),
    ])
    def test_one_row_per_family_tier_bucket(
        self, family, tier, n, bucket, cm
    ):
        ladder = make_ladder(Clock())
        for samples in (1, 2):
            ladder.note_batch(tier, batch=n, seconds=0.01, family=family)
            assert ladder.cost_snapshot() == {"table": [{
                "family": family, "tier": tier, "bucket": bucket,
                "samples": samples,
            }]}

    @pytest.mark.parametrize("tier,batch,seconds", [
        ("python", 150, 0.01),   # the floor is not in the table
        ("keyed", 0, 0.01),      # no shape
        ("keyed", 150, None),    # no measured wall
        ("keyed", 150, 0.0),
        ("warp", 150, 0.01),     # not a rung
    ])
    def test_not_counted(self, tier, batch, seconds, cm):
        ladder = make_ladder(Clock())
        ladder.note_batch(tier, batch=batch, seconds=seconds)
        assert ladder.cost_snapshot() == {"table": []}
        # the per-tier metric counts every call all the same
        assert counter_value(cm.dispatch_tier, tier=tier) == 1

    def test_rows_sorted_and_reset_empties(self, cm, dispatch_env):
        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        for tier, n in (("keyed", 1000), ("host", 2), ("keyed", 150)):
            dispatch.LADDER.note_batch(tier, batch=n, seconds=0.01)
        assert [
            (r["tier"], r["bucket"])
            for r in dispatch.LADDER.cost_snapshot()["table"]
        ] == [("host", 2), ("keyed", 256), ("keyed", 1024)]
        dispatch.LADDER.reset()
        assert dispatch.LADDER.cost_snapshot() == {"table": []}

    def test_faulted_tier_not_counted_answering_rung_is(
        self, cm, dispatch_env, verifier_cls
    ):
        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="device_loss@0-3600",
            CMT_TPU_COOLDOWN_S="30",
        )
        bv = _fill(verifier_cls(device_min_batch=1), 3)
        ok, _ = bv.verify()
        assert ok and bv._last_tier == "host"
        assert dispatch.LADDER.cost_snapshot()["table"] == [{
            "family": "ed25519", "tier": "host", "bucket": 4,
            "samples": 1,
        }]

    def test_coalesced_submission_counts_once_at_buffer_shape(
        self, cm, dispatch_env, verifier_cls, monkeypatch
    ):
        """The queue's collector hands plan() the COALESCED buffer: one
        8-sig submission is one batch in bucket 8, not eight bucket-1
        fragments."""
        from cometbft_tpu.crypto import verify_queue as vq

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        monkeypatch.setattr(
            verifier_cls, "_run_generic",
            lambda self, pub, sig, msgs: np.ones(len(msgs), dtype=bool),
        )
        priv = ed.priv_key_from_secret(b"qshape")
        msg = b"qshape-msg"
        sig = priv.sign(msg)
        q = vq.VerifyQueue(
            verifier_factory=lambda pk: verifier_cls(device_min_batch=1),
            use_cache=False,
        )
        q.start()
        try:
            futs = q.submit_many([(priv.pub_key(), msg, sig)] * 8)
            assert all(f.result(30) for f in futs)
        finally:
            q.stop()
        assert dispatch.LADDER.cost_snapshot()["table"] == [{
            "family": "ed25519", "tier": "generic", "bucket": 8,
            "samples": 1,
        }]


class TestBenchmarkReadsTheCounter:
    """benchmark/observe.py and readers/device_share.py read the
    counter by name (``cost_snapshot``, ``ROUTE_FAMILY_ED25519``):
    ``device_sig_pct.*`` must read what it read before."""

    DEVICE = ["keyed_mesh", "keyed", "generic_mesh", "generic"]

    def test_counters_key_batches_by_tier_and_bucket(
        self, cm, dispatch_env
    ):
        from benchmark import observe

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        was = observe.counters()
        assert was["batches"] == {}
        note = dispatch.LADDER.note_batch
        note("keyed", batch=150, seconds=0.004)
        note("keyed", batch=150, seconds=0.004)
        note("keyed", batch=1000, seconds=0.014)
        note("host", batch=1, seconds=0.0001)
        note("python", batch=150, seconds=0.3)
        # another family's rows are not the ed25519 cells' business
        note("bls_native", batch=150, seconds=0.01, family="bls_agg")
        now = observe.counters()
        assert now["batches"] == {
            "host/1": 1, "keyed/256": 2, "keyed/1024": 1,
        }
        assert now["tiers"]["python"] == 1
        assert observe.delta(now, was)["batches"] == now["batches"]
        assert now["transitions"] == 0

    @pytest.mark.parametrize("batches,pct", [
        ([("keyed", 150), ("keyed", 1000)], 100.0),
        ([("host", 150), ("host", 1)], 0.0),
        ([("keyed", 150), ("host", 150), ("host", 150), ("host", 150)],
         25.0),
        ([("keyed", 1000), ("host", 1)], 100.0 * 1024 / 1025),
        ([], None),
    ], ids=["device", "host", "quarter", "warmup_host", "none"])
    def test_device_share_reads_the_ladder(
        self, batches, pct, cm, dispatch_env
    ):
        from benchmark import observe
        from benchmark.readers import device_share

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        for tier, n in batches:
            dispatch.LADDER.note_batch(tier, batch=n, seconds=0.01)
        ctx = {"counters": observe.counters()}
        assert device_share.read(
            ctx, {"device_tiers": self.DEVICE}
        ) == pct


# -- race-mode harness over the new guarded classes ----------------------


class TestDispatchRaceMode:
    @pytest.fixture(autouse=True)
    def race_mode(self, monkeypatch):
        monkeypatch.setattr(cmtsync, "_RACE", True)
        cmtsync._reset_race_state()
        yield
        cmtsync._reset_race_state()

    def test_ladder_hammer_clean_under_race_mode(self, cm):
        """The ladder, hammered from multiple threads through its
        locked API (the chaos drive's real concurrency: launcher
        faults, prober verdicts, batch accounting, /debug snapshots),
        must not trip the race checker."""
        from cometbft_tpu.utils.sync import RaceError

        clock = Clock()
        ladder = cmtsync.guarded(dispatch.DispatchLadder)(
            demote_after=2, promote_after=1, cooldown_s=0.001,
            cooldown_max_s=0.01, clock=clock,
        )
        errs: list[BaseException] = []

        def worker(seed: int):
            try:
                for i in range(30):
                    tier = ("keyed", "generic")[i % 2]
                    ladder.tier_fault(tier, reason="launch:OSError")
                    ladder.note_probe(tier, i % 3 == 0)
                    ladder.note_batch("host")
                    ladder.active(tier)
                    ladder.snapshot()
            except RaceError as e:  # pragma: no cover
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(4)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs

    def test_chaos_hammer_clean_under_race_mode(self, dispatch_env):
        from cometbft_tpu.utils.sync import RaceError

        dispatch_env(
            CMT_TPU_CHAOS="1",
            CMT_TPU_CHAOS_PLAN="mislaunch@0-0.001",
        )
        chaos = cmtsync.guarded(dispatch.Chaos)()
        errs: list[BaseException] = []

        def worker():
            try:
                for _ in range(50):
                    try:
                        chaos.inject("keyed")
                    except dispatch.ChaosFault:
                        pass
                    chaos.snapshot()
            except RaceError as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs


# -- /debug/dispatch surfaces --------------------------------------------


class TestDebugDispatchSurfaces:
    def test_payload_shape(self, cm, dispatch_env):
        dispatch_env(
            CMT_TPU_CHAOS="1", CMT_TPU_CHAOS_PLAN="device_loss@1-2"
        )
        dispatch.LADDER.admissible(["generic"])
        dispatch.LADDER.tier_fault("generic", reason="watchdog")
        payload = dispatch.debug_dispatch_payload()
        assert payload["ladder"]["current"] == "host"
        assert payload["ladder"]["tiers"]["generic"]["demoted"] is True
        assert payload["chaos"]["enabled"] is True
        assert payload["chaos"]["windows"] == [
            {"kind": "device_loss", "start_s": 1.0, "end_s": 2.0}
        ]
        assert set(payload) == {"ladder", "chaos", "batches"}
        assert payload["batches"] == []
        dispatch.LADDER.note_batch("host", batch=150, seconds=0.01)
        assert dispatch.debug_dispatch_payload()["batches"] == [
            {"family": "ed25519", "tier": "host", "bucket": 256,
             "samples": 1}
        ]
        json.dumps(payload)  # must be JSON-serializable as served

    def test_debug_dispatch_http_and_index(self, cm, dispatch_env):
        from cometbft_tpu.utils.metrics import MetricsServer

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        dispatch.LADDER.admissible(["keyed"])
        dispatch.LADDER.tier_fault("keyed", reason="probe_failures")
        dispatch.LADDER.note_batch(
            "bls_native", batch=8, seconds=0.01,
            family=dispatch.ROUTE_FAMILY_BLS_AGG,
        )
        srv = MetricsServer(Registry(), "127.0.0.1:0")
        srv.start()
        try:
            base = f"http://127.0.0.1:{srv.port}"
            body = json.loads(urllib.request.urlopen(
                base + "/debug/dispatch", timeout=5
            ).read())
            assert body["ladder"]["tiers"]["keyed"]["demoted"] is True
            assert body["ladder"]["transitions"][-1]["kind"] == "demote"
            assert body["chaos"]["enabled"] is False
            assert body["batches"] == [
                {"family": "bls_agg", "tier": "bls_native", "bucket": 8,
                 "samples": 1}
            ]
            index = json.loads(urllib.request.urlopen(
                base + "/debug", timeout=5
            ).read())
            paths = [e["path"] for e in index["endpoints"]]
            assert "/debug/dispatch" in paths
        finally:
            srv.stop()

    def test_debug_dispatch_rpc_route(self, cm, dispatch_env):
        from cometbft_tpu.inspect import _INSPECT_ROUTES
        from cometbft_tpu.rpc.core import Environment

        dispatch_env(CMT_TPU_COOLDOWN_S="30")
        assert "debug/dispatch" in _INSPECT_ROUTES
        payload = Environment().routes()["debug/dispatch"]()
        assert set(payload) == {"ladder", "chaos", "batches"}


# -- sealed JITGUARD through ladder transitions --------------------------


class TestJitguardLadderTransitions:
    def test_zero_steady_state_retraces_across_demote_promote(
        self, cm, dispatch_env, monkeypatch
    ):
        """Acceptance: warm the generic mesh + single-device rungs on
        the forced-8-device CPU mesh, seal the jitguard, then force a
        full demote -> fallback-launch -> re-promote cycle: ladder
        transitions must not introduce new compile keys."""
        from cometbft_tpu.ops import jitguard
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        dispatch_env(
            CMT_TPU_COOLDOWN_S="2", CMT_TPU_COOLDOWN_MAX_S="8"
        )
        monkeypatch.setattr(jitguard, "_ENABLED", True)
        jitguard.reset()

        def run(bv):
            ok, results = bv.verify()
            assert ok and all(results)
            return bv._last_tier

        def batches(tag: bytes, suffixes):
            # 8 lanes (pow2, one device-shard each on the 8-dev mesh):
            # the smallest shape that exercises both generic rungs —
            # the ~43 ms/sig XLA-on-CPU kernel makes wide batches the
            # tier-1 wall-clock cost here, not the compile.  Batches
            # are signed up-front so the signing wall can't eat the
            # demotion cool-down before the fallback launch.
            return [
                _fill(
                    ShardedTpuBatchVerifier(device_min_batch=1), 8,
                    tag=tag + suffix,
                )
                for suffix in suffixes
            ]

        try:
            # pre-seal: compile each rung once (mesh, then the
            # single-device fallback the demotion walks to)
            warm_mesh, warm_single = batches(
                b"warm", (b"-mesh", b"-single")
            )
            assert run(warm_mesh) == "generic_mesh"
            dispatch.LADDER.tier_fault(
                "generic_mesh", reason="chaos:shard_loss"
            )
            assert run(warm_single) == "generic"
            dispatch.reset_for_tests()  # same cool-down both cycles
            before = dict(jitguard.compile_counts())
            jitguard.seal()
            # sealed: a full demote -> fallback-launch -> trial-promote
            # cycle on the same shapes must add zero compile keys
            mesh, single, trial = batches(
                b"sealed", (b"-mesh", b"-single", b"-trial")
            )
            assert run(mesh) == "generic_mesh"
            dispatch.LADDER.tier_fault(
                "generic_mesh", reason="chaos:shard_loss"
            )
            # inside the cool-down: the batch runs one rung down
            assert run(single) == "generic"
            time.sleep(2.1)  # past the cool-down: next batch trials
            assert run(trial) == "generic_mesh"
            assert dispatch.LADDER.current_tier() == "generic_mesh"
            assert jitguard.compile_counts() == before
        finally:
            jitguard.reset()


# -- the tier-1 chaos liveness drive -------------------------------------


class TestChaosLivenessNode:
    def test_node_commits_through_device_loss_and_recovery(
        self, tmp_path, dispatch_env, monkeypatch
    ):
        """ISSUE 9 acceptance: under CMT_TPU_CHAOS=1 with a seeded
        device-loss-then-recovery plan, a single-validator node commits
        >= 20 consecutive heights with zero failed commits, the flight
        recorder shows the demotion chain (keyed_mesh -> ... -> host)
        and the later re-promotion, and crypto_dispatch_current_tier
        returns to the original (best) tier."""
        import jax

        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.config import test_config
        from cometbft_tpu.crypto import batch as cbatch
        from cometbft_tpu.node import Node
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.privval import FilePV
        from cometbft_tpu.types.genesis import (
            GenesisDoc,
            GenesisValidator,
        )

        # the forced-8-device CPU mesh stands in for the accelerator:
        # the device plane comes up in-process (the node would do the
        # same at start) and the factory hands out the sharded
        # (keyed_mesh-capable) verifier deterministically
        ndev = len(jax.devices())
        assert ndev > 1
        assert cbatch.init_device_plane()["ndev"] == ndev
        monkeypatch.setenv("CMT_TPU_DEVICE_MIN_BATCH", "1")
        pv = FilePV(ed.priv_key_from_secret(b"chaos-liveness-val"))
        # pre-warm the validator key's comb tables: the chaos window
        # opens at node start, and the one-time table build must not
        # eat it (nor stall height 1 behind EC page building)
        assert PR.TABLE_CACHE.lookup_or_build(
            [pv.pub_key.bytes()]
        ) is not None
        dispatch_env(
            CMT_TPU_CHAOS="1",
            # loss-then-recovery: every device-tier launch in the
            # first 3 plan-seconds faults, then the plan goes quiet
            CMT_TPU_CHAOS_PLAN="device_loss@0-3",
            CMT_TPU_COOLDOWN_S="0.25",
            CMT_TPU_COOLDOWN_MAX_S="1.0",
        )
        gen = GenesisDoc(
            chain_id="chaos-liveness",
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=(GenesisValidator(pv.pub_key, 10),),
        )
        cfg = test_config(str(tmp_path))
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        cfg.ensure_dirs()
        mark = FLIGHT.recorded_total
        node = Node(cfg, app=KVStoreApp(), genesis=gen,
                    priv_validator=pv)
        node.start()
        try:
            heights: list[int] = []
            # harvest the flight tail INCREMENTALLY: a fast node
            # commits hundreds of heights while the cold keyed_mesh
            # program compiles during recovery, and that event volume
            # wraps the bounded ring past the early demotion chain
            events: list[dict] = []
            deadline = time.time() + 240
            target = 21  # >= 20 committed heights
            while time.time() < deadline:
                events += flight_events_since(mark)
                mark = FLIGHT.recorded_total
                h = node.height()
                if not heights or h > heights[-1]:
                    heights.append(h)
                if h >= target and any(
                    e.get("transition") == "promote"
                    and e.get("tier") == "keyed_mesh"
                    for e in events
                ):
                    break
                time.sleep(0.05)
            events += flight_events_since(mark)
            assert heights[-1] >= target, (
                f"only committed {heights[-1]} heights under chaos "
                f"(trail: {dispatch.LADDER.snapshot()['transitions']})"
            )
            # committed heights strictly increase across the injected
            # loss and recovery — consensus never failed a commit
            assert all(
                b > a for a, b in zip(heights, heights[1:])
            )
            evs = [
                e for e in events
                if e["kind"] == "crypto/dispatch_transition"
            ]
            demotes = [e for e in evs if e["transition"] == "demote"]
            promotes = [e for e in evs if e["transition"] == "promote"]
            # the chain walked the whole ladder to the host floor...
            assert {e["tier"] for e in demotes} >= {
                "keyed_mesh", "keyed", "generic_mesh", "generic"
            }
            assert any(e["to"] == "host" for e in demotes)
            assert all(
                e["reason"] == "chaos:device_loss" for e in demotes
            )
            # ...and recovered: the best tier was genuinely re-promoted
            # (not just half-open past its cool-down) and the ladder is
            # back where it started
            assert any(e["tier"] == "keyed_mesh" for e in promotes)
            snap = dispatch.LADDER.snapshot()
            assert snap["tiers"]["keyed_mesh"]["demoted"] is False
            assert dispatch.LADDER.current_tier() == "keyed_mesh"
            assert not any(
                e["kind"] == "consensus/panic" for e in events
            )
            # the metrics surface agrees: one-hot current tier back on
            # keyed_mesh, with the demotion/promotion counters live
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{node.metrics_server.port}/metrics",
                timeout=5,
            ).read().decode()
            hot = {}
            for line in body.splitlines():
                if line.startswith(
                    "cometbft_crypto_dispatch_current_tier{"
                ):
                    tier = line.split('tier="')[1].split('"')[0]
                    hot[tier] = float(line.split()[-1])
            assert hot["keyed_mesh"] == 1.0
            assert sum(hot.values()) == 1.0
            assert "cometbft_crypto_dispatch_demotions_total" in body
            assert "cometbft_crypto_dispatch_promotions_total" in body
            # post-mortem surface: the transition trail is served
            snap = json.loads(urllib.request.urlopen(
                f"http://127.0.0.1:{node.metrics_server.port}"
                "/debug/dispatch",
                timeout=5,
            ).read())
            assert snap["chaos"]["enabled"] is True
            assert snap["chaos"]["hits"].get("device_loss", 0) >= 1
            assert snap["ladder"]["transitions"]
        finally:
            node.stop()

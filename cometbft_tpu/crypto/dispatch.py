"""Failover dispatch ladder — health-driven tier demotion/promotion.

Two of five bench rounds lost the accelerator mid-run (r03: a failed
backend init, r04: hung launches), and in r05 the native host Pippenger
verifier outran the generic device path — yet until this module,
fallback was a scatter of ``except Exception`` blocks with no runtime
demotion, no promotion back, and no proof that consensus stays live
through device loss.  Committee-based consensus only keeps its
finality guarantees if signature verification stays *available*, not
just fast (arXiv:2302.00418, arXiv:2010.07031).  This module is the
one first-class owner of that availability decision:

**The ladder.**  Six tiers in strict preference order::

    keyed_mesh > keyed > generic_mesh > generic > host > python

(the sharded keyed kernel over the full device mesh, the single-device
keyed kernel, the sharded generic kernel, the single-device generic
kernel, the native host Pippenger/RLC batch verifier, and the pure
per-signature Python floor).  ``TpuBatchVerifier.plan()`` asks the
ladder which of a batch's *eligible* tiers are currently admissible;
``execute()`` walks them top-down, so the VerifyQueue launcher and
``ShardedTpuBatchVerifier`` inherit the same policy through the one
seam.  The ``python`` floor is never demoted — consensus liveness is
the invariant the whole ladder exists to protect.

**Demotion** is immediate and evidence-driven: a launch failure, a
watchdog overrun (``crypto/health.py`` LaunchWatchdog), or
``CMT_TPU_DEMOTE_AFTER`` consecutive HealthProber canary failures
demotes the tier with an exponential cool-down
(``CMT_TPU_COOLDOWN_S`` base, doubling per repeat offense up to
``CMT_TPU_COOLDOWN_MAX_S`` — a flapping tier gets exponentially rarer
chances, never a thrash loop).

**Promotion** closes the loop the PR 7 prober measures but nothing
consumed: a demoted tier is re-admitted after ``CMT_TPU_PROMOTE_AFTER``
consecutive healthy canaries once its cool-down has expired.  In
processes with no prober running, cool-down expiry re-admits the tier
for a half-open *trial*: the next batch may select it, and one success
promotes (one failure re-demotes at double the cool-down).

**Chaos mode** (``CMT_TPU_CHAOS=1``): a seeded, deterministic fault
plan (``CMT_TPU_CHAOS_PLAN``) injected at the execute seam — device
loss, launch hang past the watchdog budget, transient mis-launch,
mesh shard loss — so tier-1 can prove consensus keeps committing
heights while the ladder demotes and re-promotes (`make chaos-smoke`,
tests/test_dispatch.py).  Chaos never faults the host/python floor.

Every transition emits a ``crypto/dispatch_transition`` flight event
and feeds ``crypto_dispatch_demotions_total{from,to,reason}`` /
``crypto_dispatch_promotions_total{tier}`` /
``crypto_dispatch_current_tier{tier}`` (one-hot).  ``/debug/dispatch``
(metrics server and JSON-RPC route, inspect mode included) serves the
ladder state, cool-downs, the recent transition trail, the chaos plan
and the per-(family, tier, pow2-bucket) batch counts.  Which tiers a
batch is ELIGIBLE for is not decided here: that is
``TpuBatchVerifier._plan()`` (ops/ed25519_verify.py, the table over
``DEVICE_MIN_BATCH``) and ``BlsLadderVerifier.plan()``.  Policy
documentation: docs/dispatch_ladder.md.

This module deliberately imports no jax: host-only nodes (device
verification disabled) route through it without touching the device
stack.
"""

from __future__ import annotations

import os
import random
import time
from collections import Counter, deque

from cometbft_tpu.crypto import ed25519 as _ed
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.env import flag_from_env
from cometbft_tpu.utils.flight import ring_size_from_env as _int_env
from cometbft_tpu.utils.log import default_logger

#: the full ladder, best tier first (docs/dispatch_ladder.md) — the
#: canonical order every surface (health probes, docs, /debug) shares.
#: ``bls_native`` is the BLS12-381 family's top rung (the native C++
#: pairing backend, crypto/bls_dispatch.py): an ed25519 batch never
#: runs there and a BLS batch never runs on the device tiers, but both
#: families share the ONE availability state machine, so a faulting
#: native BLS library demotes exactly like a faulting device — with
#: cool-down, half-open trials, and probe-driven promotion inherited.
TIER_ORDER = (
    "keyed_mesh", "keyed", "generic_mesh", "generic", "bls_native",
    "host", "python",
)
#: tiers that launch on the accelerator
DEVICE_TIERS = frozenset(
    ("keyed_mesh", "keyed", "generic_mesh", "generic")
)
#: tiers backed by the native BLS12-381 pairing library
BLS_TIERS = frozenset(("bls_native",))
#: tiers the chaos plan may fault: everything above the host/python
#: floor — the accelerator tiers AND the native BLS backend (a
#: crashing ctypes library is exactly the kind of loss the ladder
#: exists to absorb); the floor itself is never chaos'd
CHAOS_TIERS = DEVICE_TIERS | BLS_TIERS
#: tiers that shard over the multi-chip mesh (shard-loss chaos scope)
MESH_TIERS = frozenset(("keyed_mesh", "generic_mesh"))
#: the floor: pure per-signature Python verification — never demoted,
#: never chaos-faulted; consensus liveness rests on it
FLOOR_TIER = "python"

DEFAULT_DEMOTE_AFTER = 3
DEFAULT_PROMOTE_AFTER = 2
DEFAULT_COOLDOWN_S = 30.0
DEFAULT_COOLDOWN_MAX_S = 600.0
#: transition-trail ring depth served at /debug/dispatch
TRANSITION_RING = 64

#: shape-bucket ceiling (anything larger shares the top bucket)
MAX_SHAPE_BUCKET = 1 << 20
#: batch-counter families: "host" means ed25519 CPU-batch for an
#: ed25519 walk but pure-RLC BLS for a BLS batch walk, and a BLS
#: aggregate (one pairing covers N signers) is not N independent
#: pairings — so the counter keys on (family, tier, bucket) and
#: same-name rungs in different families never share a row.  The
#: ``ROUTE_FAMILY_*`` names and ``DispatchLadder.cost_snapshot`` date
#: from the deleted cost router; they are kept because
#: ``benchmark/observe.py`` reads them (the rename is a ``benchmark``
#: issue's: ROADMAP Design 2).
ROUTE_FAMILY_ED25519 = "ed25519"
ROUTE_FAMILY_BLS = "bls"
ROUTE_FAMILY_BLS_AGG = "bls_agg"


def shape_bucket(n: int) -> int:
    """The pow2 ceiling bucket a batch of ``n`` signatures falls in —
    the shape key of the ladder's batch counter (a 2-sig evidence
    check and a 10k-sig commit must never share a row)."""
    if n <= 1:
        return 1
    return min(1 << (n - 1).bit_length(), MAX_SHAPE_BUCKET)


def _float_env(var: str, default: float, minimum: float) -> float:
    """Validated float env knob (fail-loudly, same contract as
    flight.ring_size_from_env / health._float_env)."""
    raw = os.environ.get(var)
    if raw is None or raw.strip() == "":
        return default
    try:
        value = float(raw)
    except ValueError:
        raise ValueError(
            f"{var} must be a number >= {minimum}, got {raw!r}"
        ) from None
    if value < minimum:
        raise ValueError(f"{var} must be >= {minimum}, got {value}")
    return value


def demote_after_from_env() -> int:
    """Consecutive canary-probe failures that demote a tier."""
    return _int_env("CMT_TPU_DEMOTE_AFTER", DEFAULT_DEMOTE_AFTER, 1)


def promote_after_from_env() -> int:
    """Consecutive healthy canaries that re-admit a demoted tier."""
    return _int_env("CMT_TPU_PROMOTE_AFTER", DEFAULT_PROMOTE_AFTER, 1)


def cooldown_from_env() -> float:
    """Base demotion cool-down seconds (doubles per repeat offense)."""
    return _float_env("CMT_TPU_COOLDOWN_S", DEFAULT_COOLDOWN_S, 0.001)


def cooldown_max_from_env() -> float:
    """Cool-down ceiling for repeat offenders."""
    return _float_env(
        "CMT_TPU_COOLDOWN_MAX_S", DEFAULT_COOLDOWN_MAX_S, 0.001
    )


class TierUnavailable(RuntimeError):
    """A tier cannot serve this batch at all (capability/policy), as
    opposed to failing at runtime — the ladder skips it without
    demotion."""

    def __init__(self, tier: str, reason: str = "") -> None:
        super().__init__(f"tier {tier} unavailable: {reason}")
        self.tier = tier
        self.reason = reason


class TierFault(RuntimeError):
    """A tier failed at runtime (launch failure, device loss) — the
    typed escalation the execute walk converts into a demotion."""

    def __init__(self, tier: str, reason: str = "") -> None:
        super().__init__(f"tier {tier} fault: {reason}")
        self.tier = tier
        self.reason = reason


class ChaosFault(TierFault):
    """A fault injected by the chaos plan (CMT_TPU_CHAOS)."""


def fault_reason(exc: BaseException) -> str:
    """Bounded-cardinality reason label for an escalation exception."""
    if isinstance(exc, ChaosFault):
        return f"chaos:{exc.reason}"
    if isinstance(exc, (TierFault, TierUnavailable)):
        return exc.reason or type(exc).__name__
    return f"launch:{type(exc).__name__}"


# -- the chaos plan ------------------------------------------------------

#: fault kinds the plan may schedule (docs/dispatch_ladder.md):
#: device_loss — every device-tier launch in the window raises;
#: launch_hang — the launch sleeps past the watchdog budget, THEN
#:   raises (the watchdog fires first — the r04 signature);
#: mislaunch   — exactly ONE launch in the window raises (transient);
#: shard_loss  — only the *_mesh tiers raise (one chip gone: the
#:   single-device tiers still work).
CHAOS_KINDS = ("device_loss", "launch_hang", "mislaunch", "shard_loss")


class ChaosPlan:
    """A deterministic fault schedule: windows of (start_s, end_s,
    kind) over seconds-since-chaos-epoch.  Spec grammar (entries
    separated by ``;``):

    - ``kind@START-END`` — an explicit window, e.g.
      ``device_loss@0-2.5``.
    - ``seed=N[,on=S][,off=S][,n=K][,kinds=a|b]`` — K pseudo-random
      fault windows generated from ``random.Random(N)``: quiet gaps
      ~``off`` seconds, faults ~``on`` seconds, kinds drawn from the
      ``|``-list.  Same spec string -> identical schedule, always.
    """

    def __init__(self, windows: list[tuple[float, float, str]]) -> None:
        self.windows = sorted(windows)
        for start, end, kind in self.windows:
            if kind not in CHAOS_KINDS:
                raise ValueError(
                    f"CMT_TPU_CHAOS_PLAN: unknown fault kind {kind!r} "
                    f"(one of {'|'.join(CHAOS_KINDS)})"
                )
            if not (0 <= start < end):
                raise ValueError(
                    f"CMT_TPU_CHAOS_PLAN: bad window {start}-{end}"
                )

    @classmethod
    def parse(cls, spec: str) -> "ChaosPlan":
        windows: list[tuple[float, float, str]] = []
        for entry in spec.split(";"):
            entry = entry.strip()
            if not entry:
                continue
            if entry.startswith("seed="):
                windows.extend(cls._seeded(entry))
                continue
            try:
                kind, span = entry.split("@", 1)
                a, b = span.split("-", 1)
                windows.append((float(a), float(b), kind.strip()))
            except ValueError:
                raise ValueError(
                    f"CMT_TPU_CHAOS_PLAN: cannot parse entry {entry!r} "
                    "(want kind@start-end or seed=N,...)"
                ) from None
        if not windows:
            raise ValueError("CMT_TPU_CHAOS_PLAN: empty plan")
        return cls(windows)

    @staticmethod
    def _seeded(entry: str) -> list[tuple[float, float, str]]:
        params = {"on": 2.0, "off": 6.0, "n": 4.0}
        kinds: list[str] = ["device_loss"]
        seed = 0
        for part in entry.split(","):
            key, _, val = part.partition("=")
            key = key.strip()
            if key == "seed":
                seed = int(val)
            elif key == "kinds":
                kinds = [k for k in val.split("|") if k]
            elif key in params:
                params[key] = float(val)
            else:
                raise ValueError(
                    f"CMT_TPU_CHAOS_PLAN: unknown seeded param {key!r}"
                )
        rng = random.Random(seed)
        windows: list[tuple[float, float, str]] = []
        t = 0.0
        for _ in range(int(params["n"])):
            t += params["off"] * (0.5 + rng.random())
            dur = params["on"] * (0.5 + rng.random())
            kind = kinds[rng.randrange(len(kinds))]
            windows.append((t, t + dur, kind))
            t += dur
        return windows

    def applies(self, kind: str, tier: str) -> bool:
        if tier not in CHAOS_TIERS:
            return False  # the host/python floor is never chaos'd
        if kind == "shard_loss":
            return tier in MESH_TIERS
        return True

    def fault_at(
        self, tier: str, t: float, fired: set[int]
    ) -> tuple[int, str] | None:
        """The (window index, kind) faulting ``tier`` at plan time
        ``t``, honoring one-shot semantics for ``mislaunch`` via the
        caller-owned ``fired`` set — pure apart from that set, so unit
        tests drive it with explicit clocks."""
        for idx, (start, end, kind) in enumerate(self.windows):
            if not (start <= t < end):
                continue
            if not self.applies(kind, tier):
                continue
            if kind == "mislaunch" and idx in fired:
                continue
            return idx, kind
        return None


@cmtsync.guarded
class Chaos:
    """The chaos injector: no-op unless ``CMT_TPU_CHAOS=1``.  The plan
    clock starts at the first injection check (or ``start()``), so a
    node's chaos windows are relative to when traffic begins."""

    _GUARDED_BY = {"_epoch": "_mtx", "_fired": "_mtx", "_hits": "_mtx"}

    def __init__(self) -> None:
        self._mtx = cmtsync.Mutex()
        self._epoch: float | None = None
        self._fired: set[int] = set()
        self._hits: dict[str, int] = {}
        self.plan: ChaosPlan | None = None
        self.reload()

    def reload(self) -> None:
        """Re-read the env (tests toggle chaos per-case; production
        reads it once at process start)."""
        plan = None
        if flag_from_env("CMT_TPU_CHAOS"):
            spec = os.environ.get(
                "CMT_TPU_CHAOS_PLAN",  # env ok: free-form fault plan — ChaosPlan.parse validates fail-loudly naming the variable
                # default drill: seeded loss-then-recovery cycles
                "seed=0,on=2,off=8,n=8,kinds=device_loss|mislaunch",
            )
            plan = ChaosPlan.parse(spec)
        with self._mtx:
            self.plan = plan
            self._epoch = None
            self._fired = set()
            self._hits = {}

    def enabled(self) -> bool:
        return self.plan is not None

    def start(self) -> None:
        """Pin the chaos epoch now (node assembly calls this when it
        logs the armed plan; otherwise the first inject() pins it)."""
        with self._mtx:
            if self._epoch is None:
                self._epoch = time.monotonic()

    def inject(self, tier: str, probe: bool = False) -> None:
        """The execute-seam (and probe-seam) injection point: raises
        ChaosFault when the plan schedules a fault for ``tier`` now.
        ``launch_hang`` sleeps past the watchdog budget first (so the
        watchdog demotes — the r04 signature) except on the probe
        seam, where the prober's own timeout plays that role."""
        plan = self.plan
        if plan is None or tier not in CHAOS_TIERS:
            return
        with self._mtx:
            if self._epoch is None:
                self._epoch = time.monotonic()
            t = time.monotonic() - self._epoch
            hit = plan.fault_at(tier, t, self._fired)
            if hit is None:
                return
            idx, kind = hit
            if kind == "mislaunch":
                self._fired.add(idx)
            self._hits[kind] = self._hits.get(kind, 0) + 1
        if kind == "launch_hang" and not probe:
            from cometbft_tpu.crypto import health as _health

            time.sleep(_health.WATCHDOG.budget_s * 1.25)
        raise ChaosFault(tier, kind)

    def snapshot(self) -> dict:
        plan = self.plan
        with self._mtx:
            elapsed = (
                round(time.monotonic() - self._epoch, 3)
                if self._epoch is not None else None
            )
            hits = dict(self._hits)
        return {
            "enabled": plan is not None,
            "elapsed_s": elapsed,
            "hits": hits,
            "windows": (
                [
                    {"kind": k, "start_s": a, "end_s": b}
                    for a, b, k in plan.windows
                ]
                if plan is not None else []
            ),
        }


# -- the ladder ----------------------------------------------------------


@cmtsync.guarded
class DispatchLadder:
    """The process-wide tier-availability state machine (module
    docstring).  All verifier seams consult the one ``LADDER``
    singleton, so a tier demoted under consensus traffic is equally
    demoted for blocksync prefetch, probes, and benches."""

    _GUARDED_BY = {
        "_state": "_mtx",
        "_known": "_mtx",
        "_transitions": "_mtx",
        "_gauge_set": "_mtx",
        "_batches": "_mtx",
    }

    def __init__(
        self,
        demote_after: int | None = None,
        promote_after: int | None = None,
        cooldown_s: float | None = None,
        cooldown_max_s: float | None = None,
        clock=time.monotonic,
        logger=None,
    ) -> None:
        self._mtx = cmtsync.Mutex()
        self._clock = clock
        self.logger = logger or default_logger().with_fields(
            module="crypto.dispatch"
        )
        self.demote_after = (
            demote_after if demote_after is not None
            else demote_after_from_env()
        )
        self.promote_after = (
            promote_after if promote_after is not None
            else promote_after_from_env()
        )
        self.cooldown_s = (
            cooldown_s if cooldown_s is not None else cooldown_from_env()
        )
        self.cooldown_max_s = (
            cooldown_max_s if cooldown_max_s is not None
            else cooldown_max_from_env()
        )
        # tier -> mutable state dict (guarded by _mtx)
        self._state: dict[str, dict] = {}
        self._known: set[str] = {"host", FLOOR_TIER}
        self._transitions: deque = deque(maxlen=TRANSITION_RING)
        # the one-hot gauge only changes on transitions and _known
        # growth — not per batch, so the hot path skips the rewrite
        self._gauge_set = False
        # (family, tier, pow2 bucket) -> batches that ran there
        self._batches: Counter = Counter()

    # -- state helpers (call under _mtx) ---------------------------------

    def _st(self, tier: str) -> dict:  # holds _mtx
        st = self._state.get(tier)
        if st is None:
            st = {
                "demoted": False,
                "fail_streak": 0,      # consecutive probe failures
                "ok_streak": 0,        # healthy canaries while demoted
                "cooldown_until": 0.0,
                "next_cooldown_s": self.cooldown_s,
                "demotions": 0,
                "promotions": 0,
                "last_reason": None,
            }
            self._state[tier] = st
        return st

    def _active_locked(self, tier: str) -> bool:  # holds _mtx
        if tier == FLOOR_TIER:
            return True
        st = self._state.get(tier)
        if st is None or not st["demoted"]:
            return True
        # half-open trial: cool-down expiry re-admits the tier for the
        # next batch (a success promotes, a failure re-demotes at
        # double the cool-down) — so processes with no prober running
        # still recover
        return self._clock() >= st["cooldown_until"]

    def _current_locked(self) -> str:  # holds _mtx
        for tier in TIER_ORDER:
            if tier in self._known and self._active_locked(tier):
                return tier
        return FLOOR_TIER

    def _next_active_below_locked(self, tier: str) -> str:  # holds _mtx
        try:
            idx = TIER_ORDER.index(tier)
        except ValueError:
            return FLOOR_TIER
        for t in TIER_ORDER[idx + 1:]:
            # cross-family rungs never serve each other's batches: a
            # demoted DEVICE tier's work falls to host/python, never
            # to the BLS pairing backend that happens to sit between
            # them in the shared order — the demotion event's ``to``
            # label must name where the batch actually goes
            if tier in DEVICE_TIERS and t in BLS_TIERS:
                continue
            if (t in self._known or t in ("host", FLOOR_TIER)) and (
                self._active_locked(t)
            ):
                return t
        return FLOOR_TIER

    # -- public queries ---------------------------------------------------

    def active(self, tier: str) -> bool:
        """Is ``tier`` currently admissible (not demoted, or past its
        cool-down for a half-open trial)?"""
        with self._mtx:
            return self._active_locked(tier)

    def admissible(self, tiers: list[str]) -> list[str]:
        """Filter an eligibility list to currently-admissible tiers,
        preserving ladder order; also registers them as known (the
        current-tier gauge tracks the best tier this process could
        run, not the whole universe)."""
        with self._mtx:
            refresh = not self._gauge_set or any(
                t not in self._known for t in tiers
            )
            self._known.update(tiers)
            out = [t for t in tiers if self._active_locked(t)]
        if refresh:
            self._set_current_gauge()
        return out

    def current_tier(self) -> str:
        with self._mtx:
            return self._current_locked()

    def cost_snapshot(self) -> dict:
        """The batch counter as ``{"table": [...]}``: one row per
        (family, tier, pow2 bucket) with ``samples`` batches.  The
        python floor is not in it (``note_batch``).  Read by
        /debug/dispatch, chip_smoke.py's Probe and the benchmark's
        ``device_sig_pct.*`` — whence the name (ROUTE_FAMILY_* above)."""
        with self._mtx:
            rows = sorted(self._batches.items())
        return {
            "table": [
                {
                    "family": family, "tier": tier, "bucket": bucket,
                    "samples": count,
                }
                for (family, tier, bucket), count in rows
            ]
        }

    # -- evidence ---------------------------------------------------------

    def note_batch(
        self, tier: str, batch: int = 0, seconds: float | None = None,
        family: str = ROUTE_FAMILY_ED25519,
    ) -> None:
        """The ONE per-batch accounting point: every batch-verify call
        records the tier it ACTUALLY ran on here (host-only factory
        verifiers and device verifiers alike — PR 6's split accounting
        unified), and a successful batch on a trial-re-admitted tier
        promotes it.  A batch with a shape and a measured wall
        (``batch`` >= 1, ``seconds`` > 0) on any tier but the python
        floor is also counted per (family, tier, pow2 bucket) inside
        the same critical section (``cost_snapshot``)."""
        _crypto_metrics().dispatch_tier.labels(tier=tier).inc()
        promote = False
        with self._mtx:
            refresh = not self._gauge_set or tier not in self._known
            self._known.add(tier)
            if (
                tier != FLOOR_TIER and tier in TIER_ORDER
                and batch >= 1 and seconds is not None and seconds > 0
            ):
                self._batches[family, tier, shape_bucket(batch)] += 1
            st = self._st(tier)
            st["fail_streak"] = 0
            if st["demoted"] and self._clock() >= st["cooldown_until"]:
                # only a half-open trial admits a batch onto a demoted
                # tier AFTER its cool-down — that success is the
                # promotion evidence.  A launch that was already in
                # flight when the tier was demoted (watchdog overrun)
                # also lands here, still INSIDE the cool-down; its
                # success must not cancel the demotion.
                promote = True
        if promote:
            self._promote(tier, reason="trial_success")
        elif refresh:
            self._set_current_gauge()

    def tier_fault(
        self, tier: str, reason: str, batch: int = 0,
        duplicate: bool = False,
    ) -> None:
        """A runtime failure on ``tier`` (launch failure, chaos fault,
        table-build error): demote immediately with exponential
        cool-down.  No-op for the python floor.  ``duplicate`` marks
        evidence for an offense already demoted (the launch's watchdog
        fired before its exception escalated here)."""
        if tier == FLOOR_TIER:
            return
        now = self._clock()
        with self._mtx:
            self._known.add(tier)
            st = self._st(tier)
            was_demoted = st["demoted"]
            # a fault on a tier already demoted and still cooling down
            # is duplicate evidence of the SAME offense (the watchdog
            # demotes a wedged launch before its exception escalates
            # here — ``duplicate`` pins the pairing per launch even
            # when the stall outlives the cool-down): both signals are
            # recorded, but the exponential back-off advances once per
            # offense, not once per signal
            dup = duplicate or (
                was_demoted and now < st["cooldown_until"]
            )
            st["demoted"] = True
            st["ok_streak"] = 0
            st["last_reason"] = reason
            if dup:
                cooldown = max(st["cooldown_until"] - now, 0.0)
            else:
                st["cooldown_until"] = now + st["next_cooldown_s"]
                cooldown = st["next_cooldown_s"]
                st["next_cooldown_s"] = min(
                    st["next_cooldown_s"] * 2, self.cooldown_max_s
                )
            st["demotions"] += 1
            to = self._next_active_below_locked(tier)
        self._emit(
            "demote", tier, to, reason,
            cooldown_s=cooldown, batch=batch,
            redemoted=was_demoted,
        )

    def watchdog_fault(self, tier: str) -> None:
        """A launch watchdog overrun on ``tier`` (crypto/health.py):
        the launch is wedged past its budget — demote now, before the
        stalled call even returns."""
        if tier in TIER_ORDER and tier != FLOOR_TIER:
            self.tier_fault(tier, reason="watchdog")

    def note_probe(self, tier: str, ok: bool) -> None:
        """Canary-probe evidence from the HealthProber: N consecutive
        failures demote; M consecutive successes (after cool-down)
        promote a demoted tier."""
        if tier not in TIER_ORDER or tier == FLOOR_TIER:
            return
        demote = promote = False
        now = self._clock()
        with self._mtx:
            self._known.add(tier)
            st = self._st(tier)
            if ok:
                st["fail_streak"] = 0
                if st["demoted"]:
                    st["ok_streak"] += 1
                    if (
                        st["ok_streak"] >= self.promote_after
                        and now >= st["cooldown_until"]
                    ):
                        promote = True
            else:
                st["ok_streak"] = 0
                if not st["demoted"]:
                    st["fail_streak"] += 1
                    if st["fail_streak"] >= self.demote_after:
                        demote = True
                elif now >= st["cooldown_until"]:
                    # a failing canary past cool-down consumes the
                    # half-open trial: the tier re-closes at doubled
                    # cool-down, so a production batch never has to
                    # discover what the prober already knows is dead
                    demote = True
        if demote:
            self.tier_fault(tier, reason="probe_failures")
        elif promote:
            self._promote(tier, reason="probes")

    # -- transitions ------------------------------------------------------

    def _promote(self, tier: str, reason: str) -> None:
        with self._mtx:
            st = self._st(tier)
            if not st["demoted"]:
                return
            st["demoted"] = False
            st["fail_streak"] = 0
            st["ok_streak"] = 0
            st["promotions"] += 1
            st["last_reason"] = reason
            # next_cooldown_s stays elevated: a tier that faults again
            # soon after promotion pays the doubled cool-down — the
            # anti-thrash half of the hysteresis
            to = self._current_locked()
        _crypto_metrics().dispatch_promotions_total.labels(
            tier=tier
        ).inc()
        self._emit("promote", tier, to, reason)

    def _emit(self, kind: str, frm: str, to: str, reason: str,
              **fields) -> None:
        event = {
            "kind": kind, "from": frm, "to": to, "reason": reason,
            "at": time.time(),
        }
        event.update(fields)
        with self._mtx:
            self._transitions.append(event)
        if kind == "demote":
            _crypto_metrics().dispatch_demotions_total.labels(
                **{"from": frm, "to": to, "reason": reason}
            ).inc()
        FLIGHT.record(
            "crypto/dispatch_transition", transition=kind, tier=frm,
            to=to, reason=reason,
        )
        log = self.logger.error if kind == "demote" else self.logger.info
        log(
            f"dispatch ladder {kind}", tier=frm, to=to, reason=reason,
            **{k: v for k, v in fields.items() if k != "at"},
        )
        self._set_current_gauge()

    def _set_current_gauge(self) -> None:
        with self._mtx:
            current = self._current_locked()
            self._gauge_set = True
        gauge = _crypto_metrics().dispatch_current_tier
        for tier in TIER_ORDER:
            gauge.labels(tier=tier).set(1.0 if tier == current else 0.0)

    # -- introspection / tests -------------------------------------------

    def snapshot(self) -> dict:
        now = self._clock()
        with self._mtx:
            tiers = {}
            for tier in TIER_ORDER:
                st = self._state.get(tier)
                if st is None:
                    tiers[tier] = {
                        "known": tier in self._known,
                        "demoted": False,
                    }
                    continue
                tiers[tier] = {
                    "known": tier in self._known,
                    "demoted": st["demoted"],
                    "fail_streak": st["fail_streak"],
                    "ok_streak": st["ok_streak"],
                    "cooldown_remaining_s": round(
                        max(st["cooldown_until"] - now, 0.0), 3
                    ),
                    "next_cooldown_s": st["next_cooldown_s"],
                    "demotions": st["demotions"],
                    "promotions": st["promotions"],
                    "last_reason": st["last_reason"],
                }
            return {
                "order": list(TIER_ORDER),
                "current": self._current_locked(),
                "policy": {
                    "demote_after": self.demote_after,
                    "promote_after": self.promote_after,
                    "cooldown_s": self.cooldown_s,
                    "cooldown_max_s": self.cooldown_max_s,
                },
                "tiers": tiers,
                "transitions": list(self._transitions),
            }

    def reset(self) -> None:
        """Tests only: wipe all tier state and the batch counter and
        re-read the env knobs."""
        with self._mtx:
            self._state.clear()
            self._known = {"host", FLOOR_TIER}
            self._transitions.clear()
            self._gauge_set = False
            self._batches.clear()
        self.demote_after = demote_after_from_env()
        self.promote_after = promote_after_from_env()
        self.cooldown_s = cooldown_from_env()
        self.cooldown_max_s = cooldown_max_from_env()


#: process-wide singletons — every verifier seam, the watchdog, and
#: the prober feed/consult the same ladder (mirrors health.WATCHDOG)
LADDER = DispatchLadder()
CHAOS = Chaos()


def chaos_enabled() -> bool:
    return CHAOS.enabled()


def reset_for_tests() -> None:
    """Wipe ladder state and re-read chaos/policy env — test isolation
    for suites that toggle CMT_TPU_CHAOS / the policy knobs."""
    LADDER.reset()
    CHAOS.reload()


# -- the host-only ladder verifier ---------------------------------------


class LadderHostVerifier(_ed.CpuBatchVerifier):
    """The BatchVerifier ``crypto/batch.py`` hands out when the
    operator disabled device verification: the host tier
    with the ladder's python floor under it.  Records
    ``crypto_dispatch_tier`` per BATCH at verify time — the same
    decision point device verifiers use — so tier counts are
    comparable across the whole ladder (PR 6's factory-time vs
    batch-time split, unified).  Deliberately jax-free."""

    def verify(self) -> tuple[bool, list[bool]]:
        if not self._entries:
            return False, []
        n = len(self._entries)
        if LADDER.active("host"):
            try:
                t0 = time.perf_counter()
                ok, results = super().verify()
                LADDER.note_batch(
                    "host", batch=n,
                    seconds=time.perf_counter() - t0,
                )
                return ok, results
            except Exception as exc:  # noqa: BLE001 — typed escalation:
                # a native-lib fault demotes the host tier to the
                # python floor instead of vanishing into a bare except
                LADDER.tier_fault(
                    "host", reason=fault_reason(exc), batch=n
                )
        t0 = time.perf_counter()
        results = [
            pk.verify_signature(msg, sig)
            for pk, msg, sig in self._entries
        ]
        LADDER.note_batch(
            FLOOR_TIER, batch=n, seconds=time.perf_counter() - t0
        )
        return all(results), results


# -- the /debug/dispatch payload -----------------------------------------


def debug_dispatch_payload() -> dict:
    """Everything ``/debug/dispatch`` serves: ladder order + per-tier
    state (demoted, cool-downs, streaks), the recent transition trail,
    the chaos plan (docs/dispatch_ladder.md) and the batch counter
    (``batches``: per (family, tier, pow2 bucket), the batches that
    ran there)."""
    return {
        "ladder": LADDER.snapshot(),
        "chaos": CHAOS.snapshot(),
        "batches": LADDER.cost_snapshot()["table"],
    }


__all__ = [
    "BLS_TIERS",
    "CHAOS",
    "CHAOS_KINDS",
    "CHAOS_TIERS",
    "DEVICE_TIERS",
    "FLOOR_TIER",
    "LADDER",
    "MESH_TIERS",
    "TIER_ORDER",
    "Chaos",
    "ChaosFault",
    "ChaosPlan",
    "DispatchLadder",
    "LadderHostVerifier",
    "TierFault",
    "TierUnavailable",
    "chaos_enabled",
    "cooldown_from_env",
    "cooldown_max_from_env",
    "debug_dispatch_payload",
    "demote_after_from_env",
    "fault_reason",
    "promote_after_from_env",
    "ROUTE_FAMILY_BLS",
    "ROUTE_FAMILY_BLS_AGG",
    "ROUTE_FAMILY_ED25519",
    "reset_for_tests",
    "shape_bucket",
]

"""Device-health plane: tier probes and the launch watchdog.

Two of five bench rounds silently lost the accelerator mid-run (r03: a
failed backend init; r04: two 600 s hung attempts) — nothing in the process
noticed until a human read the driver's rc=124.  This module turns
those failure modes into signals the dispatch ladder (ROADMAP item 5)
and an operator can act on, two instruments in one plane:

- **LaunchWatchdog** — bounds every real device launch at the
  ``TpuBatchVerifier.verify`` seam.  A launch that outlives its budget
  (``CMT_TPU_LAUNCH_BUDGET_S``, default 240 s — comfortably above the
  96 s cold-compile measured in r01 and far below the 600 s hangs of
  r04) increments ``crypto_device_hangs_total``, records a
  ``crypto/device_hang`` flight event, and logs a structured line —
  the stalled thread itself cannot be interrupted (the hang lives in C
  under the runtime), so the watchdog converts a silent stall into an
  observable one and records the recovery if the launch ever returns.
- **HealthProber** — a background thread issuing periodic lightweight
  canary verifies against each AVAILABLE dispatch tier (keyed_mesh /
  keyed / generic / host), every ``CMT_TPU_HEALTH_INTERVAL`` seconds
  (default 60; 0 disables).  Each probe feeds
  ``crypto_tier_probe_seconds{tier}`` and ``crypto_tier_healthy{tier}``
  AND the dispatch ladder (``crypto/dispatch.py``): N consecutive
  canary failures demote the tier, M consecutive healthy canaries
  promote it back — the loop this plane measures is now closed.
  Device tiers are probed only when the device plane is ready
  (crypto/batch.init_device_plane ran) and is a real accelerator:
  probing the XLA-on-CPU path would measure a tier no dispatch ever
  chooses.

Surfaces: ``/debug/perf`` on the metrics server and the ``debug/perf``
JSON-RPC route (inspect mode included) serve ``debug_perf_payload()``
— current tier health, last probe latencies, watchdog state, and the
perf-ledger tail (CMT_TPU_PERF_LEDGER, tools/perfledger.py).  Where a
launch's time goes is not here: the span ring (``utils/trace.py``,
served at ``/trace``) has each launch's ``batch_verify`` and its steps,
each with its wall time (``dur``), the root and its fetch also with
their thread's CPU time (``tdur``).  Documented in
docs/observability.md ("Device-health plane").
"""

from __future__ import annotations

import json
import os
import threading
import time
from contextlib import contextmanager

from cometbft_tpu.metrics import health_metrics as _health_metrics
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.service import BaseService

DEFAULT_LAUNCH_BUDGET_S = 240.0
DEFAULT_HEALTH_INTERVAL_S = 60.0

#: the probe-able dispatch-ladder tiers in demotion order — a strict
#: subset of crypto/dispatch.TIER_ORDER (the python floor needs no
#: canary: it is never demoted)
TIERS = (
    "keyed_mesh", "keyed", "generic_mesh", "generic", "bls_native",
    "host",
)


def _float_env(var: str, default: float, minimum: float) -> float:
    """Validated float env knob (same fail-loudly contract as
    flight.ring_size_from_env, documented together): a float
    >= ``minimum``, anything else raises naming the variable."""
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


def launch_budget_from_env() -> float:
    """Watchdog budget per device launch, seconds (> 0)."""
    return _float_env(
        "CMT_TPU_LAUNCH_BUDGET_S", DEFAULT_LAUNCH_BUDGET_S, 0.001
    )


def health_interval_from_env() -> float:
    """Probe cadence, seconds; 0 disables the prober entirely."""
    return _float_env(
        "CMT_TPU_HEALTH_INTERVAL", DEFAULT_HEALTH_INTERVAL_S, 0.0
    )


class LaunchWatchdog:
    """Bounds device launches: one shared daemon thread tracks every
    armed launch's deadline; overruns are counted + flight-recorded
    (the launch itself cannot be interrupted — see module docstring).

    ``watch()`` is the seam-side API::

        with WATCHDOG.watch(tier="keyed", batch=n):
            out = self._run_keyed(...)

    Arm/disarm are O(1) dict ops under one mutex; the thread sleeps
    until the nearest deadline (or indefinitely when no launch is in
    flight), so an idle process pays nothing.
    """

    def __init__(
        self, budget_s: float | None = None, logger: Logger | None = None
    ):
        self._budget = budget_s
        self.logger = logger or default_logger().with_fields(
            module="crypto.health"
        )
        self._mtx = cmtsync.Mutex()
        self._wake = threading.Event()
        # guarded by _mtx: token -> {t0, deadline, tier, batch, fired}
        self._active: dict[int, dict] = {}
        self._next_token = 0  # guarded by _mtx
        self._thread: threading.Thread | None = None  # guarded by _mtx
        self._stop = False

    @property
    def budget_s(self) -> float:
        if self._budget is None:
            self._budget = launch_budget_from_env()
        return self._budget

    # -- seam API --------------------------------------------------------

    def arm(
        self, tier: str, batch: int = 0, budget_s: float | None = None
    ) -> int:
        deadline = time.monotonic() + (
            budget_s if budget_s is not None else self.budget_s
        )
        with self._mtx:
            self._next_token += 1
            token = self._next_token
            self._active[token] = {
                "t0": time.monotonic(),
                "deadline": deadline,
                "tier": tier,
                "batch": batch,
                "fired": False,
            }
            if self._thread is None or not self._thread.is_alive():
                self._stop = False
                self._thread = threading.Thread(
                    target=self._loop, name="crypto-watchdog", daemon=True
                )
                self._thread.start()
        self._wake.set()
        return token

    def disarm(self, token: int) -> bool:
        """Returns True when the watchdog had already fired for this
        launch (i.e. it recovered after being declared hung)."""
        with self._mtx:
            entry = self._active.pop(token, None)
        if entry is None:
            return False
        if entry["fired"]:
            stalled = time.monotonic() - entry["t0"]
            FLIGHT.record(
                "crypto/device_hang_recovered", tier=entry["tier"],
                batch=entry["batch"], stalled_s=round(stalled, 3),
            )
            self.logger.error(
                "device launch recovered after watchdog trip",
                tier=entry["tier"], stalled_s=round(stalled, 3),
            )
        return entry["fired"]

    @contextmanager
    def watch(self, tier: str, batch: int = 0,
              budget_s: float | None = None):
        """Yields a state box whose ``fired`` flag is filled at exit:
        callers that demote on escalation can tell whether THIS
        launch's overrun already demoted the tier (dispatch ladder
        duplicate-offense pairing)."""
        token = self.arm(tier, batch=batch, budget_s=budget_s)
        state = {"fired": False}
        try:
            yield state
        finally:
            state["fired"] = self.disarm(token)

    # -- the watchdog thread ---------------------------------------------

    def _loop(self) -> None:
        while True:
            with self._mtx:
                if self._stop:
                    return
                pending = [
                    e["deadline"]
                    for e in self._active.values()
                    if not e["fired"]
                ]
            timeout = None
            if pending:
                timeout = max(min(pending) - time.monotonic(), 0.0)
            self._wake.wait(timeout)
            self._wake.clear()
            now = time.monotonic()
            expired: list[dict] = []
            with self._mtx:
                if self._stop:
                    return
                for entry in self._active.values():
                    if not entry["fired"] and entry["deadline"] <= now:
                        entry["fired"] = True
                        expired.append(dict(entry))
            for entry in expired:  # record outside the lock
                elapsed = now - entry["t0"]
                _health_metrics().device_hangs_total.inc()
                FLIGHT.record(
                    "crypto/device_hang", tier=entry["tier"],
                    batch=entry["batch"], elapsed_s=round(elapsed, 3),
                    budget_s=round(entry["deadline"] - entry["t0"], 3),
                )
                self.logger.error(
                    "device launch exceeded watchdog budget — device "
                    "wedged or compile runaway (launch cannot be "
                    "interrupted; recovery will be logged if it ever "
                    "returns)",
                    tier=entry["tier"], batch=entry["batch"],
                    elapsed_s=round(elapsed, 3),
                )
                # the overrun demotes the wedged tier NOW, before the
                # stalled call returns (if it ever does) — the r04
                # failure mode becomes a ladder transition, not just a
                # counter.  Probe watchdogs carry a "probe:" prefix;
                # the hang is the underlying tier's either way.
                try:
                    from cometbft_tpu.crypto import dispatch as _disp

                    tier = entry["tier"]
                    if tier.startswith("probe:"):
                        tier = tier[len("probe:"):]
                    _disp.LADDER.watchdog_fault(tier)
                except Exception as exc:  # noqa: BLE001 — the
                    # watchdog thread must survive a ladder hiccup
                    self.logger.error(
                        "watchdog demotion failed", err=repr(exc)
                    )

    def stop(self) -> None:
        """Tests only: stop the shared thread (a fresh arm restarts
        it)."""
        with self._mtx:
            self._stop = True
            thread = self._thread
        self._wake.set()
        if thread is not None:
            thread.join(timeout=5)
        with self._mtx:
            self._thread = None

    def snapshot(self) -> dict:
        with self._mtx:
            active = [
                {
                    "tier": e["tier"],
                    "batch": e["batch"],
                    "elapsed_s": round(time.monotonic() - e["t0"], 3),
                    "fired": e["fired"],
                }
                for e in self._active.values()
            ]
        return {"budget_s": self.budget_s, "active_launches": active}


class HealthProber(BaseService):
    """Background canary prober over the available dispatch tiers.

    ``tiers`` maps tier name -> zero-arg callable returning truthy on
    a correct verify; None builds the default probes lazily at the
    first tick (host always; device tiers only when a real accelerator
    backend is already live in-process — see module docstring).  The
    first probe fires one full interval after start, so short-lived
    nodes (tests, localnet children) pay nothing.
    """

    def __init__(
        self,
        interval_s: float | None = None,
        tiers: dict | None = None,
        logger: Logger | None = None,
        watchdog: LaunchWatchdog | None = None,
        probe_timeout_s: float | None = None,
    ):
        super().__init__(
            name="health-prober",
            logger=logger or default_logger().with_fields(
                module="crypto.health"
            ),
        )
        self.interval_s = (
            interval_s if interval_s is not None
            else health_interval_from_env()
        )
        if self.interval_s <= 0:
            raise ValueError(
                "HealthProber needs a positive interval "
                "(CMT_TPU_HEALTH_INTERVAL=0 means: don't start one)"
            )
        self._tiers = tiers
        self._watchdog = watchdog if watchdog is not None else WATCHDOG
        self._probe_timeout = probe_timeout_s
        self._state_mtx = cmtsync.Mutex()
        self._state: dict[str, dict] = {}  # guarded by _state_mtx
        self.probes_total = 0  # guarded by _state_mtx
        # tier -> still-running probe worker (guarded by _state_mtx):
        # a tier whose previous canary is STILL stuck fails fast
        # instead of piling a new stuck thread per interval
        self._inflight: dict[str, threading.Thread] = {}
        self._thread: threading.Thread | None = None

    @property
    def probe_timeout_s(self) -> float:
        """How long one canary may run before it is declared hung —
        the watchdog launch budget unless overridden."""
        if self._probe_timeout is not None:
            return self._probe_timeout
        return self._watchdog.budget_s

    # -- lifecycle -------------------------------------------------------

    def on_start(self) -> None:
        global _ACTIVE_PROBER
        _ACTIVE_PROBER = self
        self._thread = threading.Thread(
            target=self._loop, name="health-prober", daemon=True
        )
        self._thread.start()

    def on_stop(self) -> None:
        global _ACTIVE_PROBER
        if _ACTIVE_PROBER is self:
            _ACTIVE_PROBER = None
        if self._thread is not None:
            self._thread.join(timeout=5)
            self._thread = None

    def _loop(self) -> None:
        # quit_event().wait doubles as the schedule: one probe per
        # interval, first probe one interval after start
        while not self.quit_event().wait(self.interval_s):
            try:
                self.probe_once()
            except Exception as exc:  # noqa: BLE001 — prober must
                # outlive any single bad probe round
                self.logger.error("probe round failed", err=repr(exc))

    # -- probing ---------------------------------------------------------

    def _tier_probes(self) -> dict:
        if self._tiers is not None:
            return self._tiers  # caller-pinned set (tests)
        # re-evaluated EVERY round, not cached: tier availability
        # grows during the process lifetime (a jax backend initializes
        # on the first device batch, the native BLS library loads on
        # the first aggregate commit), and a probe set frozen at the
        # first round would leave late-arriving tiers canary-less —
        # demoted once, they could then only recover through
        # half-open production batches paying the retry the prober
        # exists to absorb.  The capability checks inside
        # default_tier_probes are cheap reads (no imports, no builds).
        return default_tier_probes()

    def _run_probe(self, tier: str, probe) -> tuple[bool, str | None,
                                                    float]:
        """One canary in a bounded worker thread: a probe stuck in C
        under a wedged runtime cannot be interrupted, so the prober
        ABANDONS it at probe_timeout_s (the daemon worker parks on the
        stuck call) and reports the tier hung — the loop, and every
        other tier's schedule, keeps running.  While the stuck worker
        lives, the tier fails fast instead of stacking workers."""
        with self._state_mtx:
            prev = self._inflight.get(tier)
        if prev is not None and prev.is_alive():
            return False, "previous probe still hung", 0.0
        box: dict = {}

        def run() -> None:
            from cometbft_tpu.crypto import dispatch as _disp

            t0 = time.perf_counter()
            try:
                # probes are real device launches: the watchdog bounds
                # them exactly like production batches — and the chaos
                # plan faults canaries exactly like production batches
                # (probe=True skips the launch_hang sleep: the prober's
                # own timeout plays the watchdog's role on this seam)
                _disp.CHAOS.inject(tier, probe=True)
                with self._watchdog.watch(tier=f"probe:{tier}"):
                    box["ok"] = bool(probe())
            except Exception as exc:  # noqa: BLE001 — a dead tier is
                box["err"] = f"{type(exc).__name__}: {exc}"  # a result
            box["dt"] = time.perf_counter() - t0

        worker = threading.Thread(
            target=run, name=f"probe-{tier}", daemon=True
        )
        t0 = time.perf_counter()
        worker.start()
        worker.join(self.probe_timeout_s)
        if worker.is_alive():
            with self._state_mtx:
                self._inflight[tier] = worker
            return (
                False,
                f"probe exceeded {self.probe_timeout_s:g}s timeout",
                time.perf_counter() - t0,
            )
        with self._state_mtx:
            self._inflight.pop(tier, None)
        return (
            box.get("ok", False), box.get("err"),
            box.get("dt", time.perf_counter() - t0),
        )

    def probe_once(self) -> dict[str, bool]:
        """One canary round over every available tier; returns
        tier -> healthy.  Exposed for tests and `make health-smoke`."""
        from cometbft_tpu.crypto import dispatch as _disp

        hm = _health_metrics()
        results: dict[str, bool] = {}
        for tier, probe in self._tier_probes().items():
            ok, err, dt = self._run_probe(tier, probe)
            hm.tier_probe_seconds.labels(tier=tier).observe(dt)
            hm.tier_healthy.labels(tier=tier).set(1.0 if ok else 0.0)
            # canary evidence drives the dispatch ladder: N consecutive
            # failures demote the tier, M consecutive successes (past
            # its cool-down) promote it back (crypto/dispatch.py)
            _disp.LADDER.note_probe(tier, ok)
            with self._state_mtx:
                prev = self._state.get(tier, {})
                self._state[tier] = {
                    "healthy": ok,
                    "last_probe_s": round(dt, 6),
                    "last_probe_at": time.time(),
                    "consecutive_failures": (
                        0 if ok else prev.get("consecutive_failures", 0) + 1
                    ),
                    "error": err,
                }
                self.probes_total += 1
                was_healthy = prev.get("healthy")
            if not ok:
                hm.tier_probe_failures_total.labels(tier=tier).inc()
                FLIGHT.record(
                    "crypto/tier_unhealthy", tier=tier,
                    probe_s=round(dt, 3), err=err or "mis-verified",
                )
                self.logger.error(
                    "dispatch tier failed its canary probe", tier=tier,
                    probe_s=round(dt, 3), err=err or "mis-verified",
                )
            elif was_healthy is False:
                FLIGHT.record(
                    "crypto/tier_recovered", tier=tier,
                    probe_s=round(dt, 3),
                )
                self.logger.info(
                    "dispatch tier recovered", tier=tier
                )
            results[tier] = ok
        return results

    def snapshot(self) -> dict:
        with self._state_mtx:
            return {
                "interval_s": self.interval_s,
                "probe_timeout_s": self.probe_timeout_s,
                "probes_total": self.probes_total,
                "hung_probes": sorted(
                    t for t, w in self._inflight.items() if w.is_alive()
                ),
                "tiers": {t: dict(s) for t, s in self._state.items()},
            }


#: the currently running prober (set by HealthProber.on_start), read
#: by debug_perf_payload — None when no prober is running
_ACTIVE_PROBER: HealthProber | None = None


def _canary_fixture():
    """Two signed 64-byte messages, built once per process (signing is
    slow on the pure-Python fallback; the canary must stay cheap)."""
    global _CANARY
    if _CANARY is None:
        from cometbft_tpu.crypto import ed25519 as ed

        privs = [
            ed.priv_key_from_secret(b"health-canary-%d" % i)
            for i in range(2)
        ]
        msgs = [b"health canary %d" % i for i in range(2)]
        _CANARY = [
            (p.pub_key(), m, p.sign(m)) for p, m in zip(privs, msgs)
        ]
    return _CANARY


_CANARY = None


def default_tier_probes() -> dict:
    """tier name -> canary callable, for every tier AVAILABLE in this
    process right now.  Host is always available; device tiers only
    when the device plane came up on a real accelerator (the
    XLA-on-CPU path is a tier no dispatch chooses — see
    ops/ed25519_verify.runtime_device_min_batch)."""
    from cometbft_tpu.crypto import batch as _batch

    probes: dict = {"host": _probe_host}
    # the native BLS tier is probed only when the library ALREADY
    # loaded in this process: the prober must never trigger the
    # first-use g++ build (~10 s) for a tier no verify has asked for
    # — the same already-initialized gate the device tiers use
    from cometbft_tpu.crypto import bls_native as _bls_native

    if _bls_native.loaded():
        probes["bls_native"] = _probe_bls_native
    dev = _batch.device_status()
    if dev["status"] != "ready" or dev["platform"] == "cpu":
        return probes
    probes["generic"] = _probe_generic
    probes["keyed"] = _probe_keyed
    if dev["ndev"] > 1:
        probes["keyed_mesh"] = _probe_keyed_mesh
        probes["generic_mesh"] = _probe_generic_mesh
    return probes


def _probe_host() -> bool:
    from cometbft_tpu.crypto import ed25519 as ed

    bv = ed.CpuBatchVerifier()
    for pub, msg, sig in _canary_fixture():
        bv.add(pub, msg, sig)
    ok, bits = bv.verify()
    return ok and all(bits)


_BLS_CANARY = None


def _probe_bls_native() -> bool:
    """Native-BLS canary, PINNED to the native backend (the PR 9
    lesson: a canary that re-enters the ladder reports the FALLBACK's
    health as promotion evidence for the dead tier) — one fixed
    signature verified via bls_native.verify directly."""
    global _BLS_CANARY
    from cometbft_tpu.crypto import bls12381 as _bls
    from cometbft_tpu.crypto import bls_native as _bls_native

    if _BLS_CANARY is None:
        priv = _bls.priv_key_from_secret(b"cometbft-tpu-bls-canary")
        msg = b"bls-tier-canary"
        _BLS_CANARY = (
            priv.pub_key().bytes(), msg, _bls_native.sign(
                priv.bytes(), msg
            ),
        )
    pk, msg, sig = _BLS_CANARY
    return bool(_bls_native.verify(pk, msg, sig))


def _probe_arrays():
    import numpy as np

    fixture = _canary_fixture()
    pub = np.stack([
        np.frombuffer(p.bytes(), dtype=np.uint8) for p, _, _ in fixture
    ] * 4)
    sig = np.stack([
        np.frombuffer(s, dtype=np.uint8) for _, _, s in fixture
    ] * 4)
    msgs = [m for _, m, _ in fixture] * 4
    return pub, sig, msgs


def _probe_generic() -> bool:
    from cometbft_tpu.ops.ed25519_verify import verify_arrays

    pub, sig, msgs = _probe_arrays()
    return bool(verify_arrays(pub, sig, msgs).all())


def _probe_keyed() -> bool:
    """Keyed-tier canary: verifies against the prober's own tiny
    key-set tables (built once; table policy may decline a 2-key set,
    in which case the probe falls back to reporting the generic path's
    health under the keyed label rather than failing a healthy
    device)."""
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.ops.ed25519_verify import (
        _finish,
        verify_arrays_keyed_async,
    )

    pub, sig, msgs = _probe_arrays()
    pubs_b = [p.bytes() for p, _, _ in _canary_fixture()]
    entry = PR.TABLE_CACHE.lookup_or_build(pubs_b)
    if entry is None:  # out of table policy: not a device failure
        return _probe_generic()
    key_ids = entry.key_ids([bytes(p) for p in pub])
    out = _finish(
        verify_arrays_keyed_async(entry, key_ids, pub, sig, msgs)
    )
    return bool(out.all())


def _probe_keyed_mesh() -> bool:
    """Mesh-tier canary PINNED to the keyed_mesh runner: a canary must
    exercise its own tier, not walk the dispatch ladder — a demoted
    tier's canary routed one rung down would report the FALLBACK's
    health as promotion evidence for the dead tier."""
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

    bv = ShardedTpuBatchVerifier(device_min_batch=0)
    if not bv._mesh_capable():
        return _probe_keyed()
    pub, sig, msgs = _probe_arrays()
    pubs_b = [p.bytes() for p, _, _ in _canary_fixture()]
    entry = PR.TABLE_CACHE.lookup_or_build(pubs_b)
    if entry is None:  # out of table policy: not a device failure
        return _probe_generic_mesh()
    key_ids = entry.key_ids([bytes(p) for p in pub])
    out = bv._run_keyed_mesh(entry, key_ids, pub, sig, msgs)
    return bool(out.all())


def _probe_generic_mesh() -> bool:
    """Sharded-generic canary, pinned to its runner for the same
    reason as the keyed_mesh probe."""
    from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

    bv = ShardedTpuBatchVerifier(device_min_batch=0)
    pub, sig, msgs = _probe_arrays()
    out = bv._run_generic_mesh(pub, sig, msgs)
    return bool(out.all())


#: process-wide singleton — the verifier seam and probers all feed
#: the same watchdog state every surface reads (mirrors
#: utils/flight.FLIGHT)
WATCHDOG = LaunchWatchdog()


# -- the /debug/perf payload ---------------------------------------------

def perf_ledger_path() -> str | None:
    """The perf ledger ``CMT_TPU_PERF_LEDGER`` names (the merged perf
    trajectory tools/perfledger.py maintains), or None: the node reads
    a ledger only when the operator points it at one."""
    return os.environ.get("CMT_TPU_PERF_LEDGER") or None  # env ok: free-form filesystem path — no parse to fail


def perf_ledger_tail(n: int = 10) -> list[dict]:
    """Last ``n`` ledger entries (empty when no ledger is configured
    or it does not exist yet)."""
    path = perf_ledger_path()
    if path is None:
        return []
    try:
        with open(path) as f:
            doc = json.load(f)
        entries = doc.get("entries", [])
        return entries[-n:] if n else entries
    except (OSError, ValueError):
        return []


def debug_perf_payload(ledger_tail_n: int = 10) -> dict:
    """Everything ``/debug/perf`` serves: tier health + last probe
    latencies, watchdog state, device-plane status, and the
    perf-ledger tail.  A launch's timing is the span ring's
    (``/trace``: ``batch_verify`` and its steps, ``dur``; the root
    and its fetch also ``tdur``)."""
    from cometbft_tpu.crypto import batch as _batch

    prober = _ACTIVE_PROBER
    return {
        "device": _batch.device_status(),
        "prober": (
            prober.snapshot()
            if prober is not None
            else {"running": False, "tiers": {}}
        ),
        "watchdog": WATCHDOG.snapshot(),
        "ledger": {
            "path": perf_ledger_path(),
            "tail": perf_ledger_tail(ledger_tail_n),
        },
    }


__all__ = [
    "DEFAULT_HEALTH_INTERVAL_S",
    "DEFAULT_LAUNCH_BUDGET_S",
    "TIERS",
    "WATCHDOG",
    "HealthProber",
    "LaunchWatchdog",
    "debug_perf_payload",
    "default_tier_probes",
    "health_interval_from_env",
    "launch_budget_from_env",
    "perf_ledger_path",
    "perf_ledger_tail",
]

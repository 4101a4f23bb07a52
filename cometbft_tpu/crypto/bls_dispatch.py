"""BLS12-381 verification through the failover dispatch ladder.

"Performance of EdDSA and BLS Signatures in Committee-Based Consensus"
(arXiv:2302.00418) quantifies the trade this module closes: a commit
carrying a BLS *aggregate* signature verifies with one pairing-product
check — e(agg_pk, H(m)) == e(g1, agg_sig), two pair-works, one final
exponentiation — where the same commit as N independent signatures
costs an N-signature batch.  Until this module, the BLS plane sat
OUTSIDE the dispatch ladder: ``crypto/batch.py`` handed out a bare
``BlsBatchVerifier`` whose native-vs-python selection was an
unaccounted ``if available()`` with no demotion when the ctypes
library faults, no ``crypto_dispatch_tier`` sample, no watchdog, no
chaos coverage.

:class:`BlsLadderVerifier` gives BLS the exact seam the ed25519 plane
has had since PR 8/9 — ``plan()`` computes the batch's eligible tiers
and filters them through ``dispatch.LADDER.admissible()``;
``execute()`` walks them top-down with typed ``TierFault`` escalation:

- ``bls_native`` — the C++ pairing backend (crypto/bls_native.py):
  RLC batch check for independent triples, one pairing-product for
  aggregates.  Runs under the LaunchWatchdog and inside the chaos
  injection scope (``dispatch.CHAOS_TIERS``), and a fault demotes it
  through the same cool-down/half-open/probe state machine as a lost
  device.
- ``host`` — the pure tower-field RLC batch (one shared Miller loop;
  batch mode only).
- ``python`` — the floor: per-signature (batch mode) or one
  pure-python pairing-product (aggregate mode).  Never demoted,
  never faulted; re-raises, exactly like the ed25519 floor.

Every batch lands in ``crypto_dispatch_tier{tier}`` via
``LADDER.note_batch`` — the one per-batch accounting point — so BLS
verifies are no longer invisible to ``/debug/dispatch``.

**Aggregate-pubkey cache.**  Same-message aggregate verification
needs the G1 sum of the signers' pubkeys.  Validator sets are stable
across many commits, so the sum is cached in a bounded LRU keyed by
SHA-256 over the concatenated pubkeys: a warm serving plane pays ONE
pairing-product per commit and zero EC aggregation (cold native
aggregation ~40 ms at 150 keys, python ~350 ms — the cache is what
makes the ``bls_aggregate_150val`` ledger row beat the ed25519
``verify_commit_150`` batch baseline).
"""

from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict

from cometbft_tpu.crypto import BatchVerifier
from cometbft_tpu.crypto import bls12381 as _bls
from cometbft_tpu.crypto import bls_native
from cometbft_tpu.crypto import dispatch as _failover
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import ring_size_from_env as _int_env
from cometbft_tpu.utils.trace import TRACER as _tracer

#: the BLS family's top ladder rung (dispatch.TIER_ORDER)
BLS_NATIVE_TIER = "bls_native"

DEFAULT_AGG_PK_CACHE_CAP = 1024


def agg_pk_cache_capacity_from_env() -> int:
    """Aggregate-pubkey cache capacity in entries (>= 16); each entry
    is one (validator-set, signer-subset) pair's 96-byte G1 sum."""
    return _int_env("CMT_TPU_BLS_AGG_PK_CACHE", DEFAULT_AGG_PK_CACHE_CAP, 16)


@cmtsync.guarded
class AggPubKeyCache:
    """Bounded LRU of SHA-256(pk_0 || ... || pk_n-1) -> 96-byte G1
    pubkey sum.  Pure EC facts — a sum of points never goes stale — so
    capacity is the only eviction policy.  The key binds the exact
    ordered signer list, so two different signer subsets of one
    validator set never share an entry."""

    _GUARDED_BY = {"_map": "_mtx"}

    def __init__(self, capacity: int | None = None) -> None:
        self.capacity = (
            capacity if capacity is not None
            else agg_pk_cache_capacity_from_env()
        )
        self._mtx = cmtsync.Mutex()
        self._map: OrderedDict[bytes, bytes] = OrderedDict()

    def aggregate(self, pub_bytes: list[bytes]) -> bytes:
        """The cached G1 sum for this exact signer list, computing and
        memoizing on miss (native-accelerated when the backend exports
        ``cmt_bls_aggregate_pubkeys``).  Raises ValueError on
        malformed/identity inputs, which is never cached."""
        key = hashlib.sha256(b"".join(pub_bytes)).digest()
        with self._mtx:
            hit = self._map.get(key)
            if hit is not None:
                self._map.move_to_end(key)
                return hit
        agg = _bls.aggregate_pub_keys_bytes(pub_bytes)
        with self._mtx:
            self._map[key] = agg
            self._map.move_to_end(key)
            while len(self._map) > self.capacity:
                self._map.popitem(last=False)
        return agg

    def __len__(self) -> int:
        with self._mtx:
            return len(self._map)

    def clear(self) -> None:
        with self._mtx:
            self._map.clear()


#: process-wide singleton — every BLS aggregate verification shares
#: the one pubkey-sum cache (mirrors dispatch.LADDER / health.WATCHDOG)
AGG_PK_CACHE = AggPubKeyCache()


class _BlsPlan:
    """Host-phase output of :meth:`BlsLadderVerifier.plan`: the
    routing decision plus everything ``execute()`` needs — mirrors
    ops/ed25519_verify._VerifyPlan so the verify queue's collector can
    run it off-thread."""

    __slots__ = (
        "n", "mode", "tiers", "items", "agg_pubs", "agg_msgs",
        "agg_sig", "same_msg", "t_plan",
    )

    def __init__(self) -> None:
        self.n = 0
        self.mode = "empty"  # empty | batch | aggregate
        self.tiers: list[str] = []
        self.items: list[tuple] = []
        self.agg_pubs: list = []
        self.agg_msgs: list[bytes] = []
        self.agg_sig = b""
        self.same_msg = False
        self.t_plan = 0.0


class BlsLadderVerifier(BatchVerifier):
    """BatchVerifier provider for bls12_381 keys, dispatch-ladder
    routed (module docstring).  Two modes:

    - **batch** (``add()`` triples): independent (pubkey, msg, sig)
      verification — RLC on the native/host tiers, per-signature
      verdicts on the floor.
    - **aggregate** (``set_aggregate()``): ONE aggregate signature
      over the signer list — the commit shape
      ``types/validation._verify`` selects when the commit actually
      carries ``agg_signature``.  All-or-nothing verdict.
    """

    def __init__(self) -> None:
        self._items: list[tuple] = []
        self._agg: tuple[list, list[bytes], bytes, bool] | None = None
        # ladder tier the last batch ACTUALLY ran on (ed25519 parity)
        self._last_tier: str | None = None

    def add(self, pub_key, msg: bytes, sig: bytes) -> None:
        if pub_key.type() != _bls.KEY_TYPE:
            raise TypeError("BlsLadderVerifier requires bls12_381 keys")
        if len(sig) != _bls.SIGNATURE_SIZE:
            raise ValueError("malformed signature size")
        if self._agg is not None:
            raise ValueError("verifier is in aggregate mode")
        self._items.append((pub_key, bytes(msg), bytes(sig)))

    def set_aggregate(
        self, pub_keys: list, msgs, agg_sig: bytes
    ) -> None:
        """Aggregate mode: ``msgs`` is ONE bytes (same-message fast
        aggregate — the aggregate-commit shape) or a list of per-signer
        messages (distinct-message aggregate)."""
        if self._items:
            raise ValueError("verifier already has batch items")
        if len(agg_sig) != _bls.SIGNATURE_SIZE:
            raise ValueError("malformed aggregate signature size")
        if not pub_keys:
            raise ValueError("aggregate needs at least one signer")
        for pk in pub_keys:
            if pk.type() != _bls.KEY_TYPE:
                raise TypeError(
                    "BlsLadderVerifier requires bls12_381 keys"
                )
        same = isinstance(msgs, (bytes, bytearray))
        msg_list = (
            [bytes(msgs)] if same else [bytes(m) for m in msgs]
        )
        if not same and len(msg_list) != len(pub_keys):
            raise ValueError("one message per signer required")
        self._agg = (list(pub_keys), msg_list, bytes(agg_sig), same)

    def __len__(self) -> int:
        if self._agg is not None:
            return len(self._agg[0])
        return len(self._items)

    # -- the plan()/execute() seam ---------------------------------------

    def plan(self) -> _BlsPlan:
        """Host phase: ladder tier selection.  Eligibility is a pure
        capability check — the native tier exists only when the C++
        backend loads (never triggered here: a cold process must not
        pay the first-use g++ build on the plan path unless a verify
        is actually about to need it, which it is)."""
        plan = _BlsPlan()
        plan.t_plan = time.perf_counter()
        if self._agg is not None:
            plan.mode = "aggregate"
            plan.agg_pubs, plan.agg_msgs, plan.agg_sig, plan.same_msg = (
                self._agg
            )
            plan.n = len(plan.agg_pubs)
        elif self._items:
            plan.mode = "batch"
            plan.items = self._items
            plan.n = len(self._items)
        else:
            return plan
        ladder = _failover.LADDER
        eligible = (
            [BLS_NATIVE_TIER] if bls_native.available() else []
        )
        admissible = ladder.admissible(eligible)
        _crypto_metrics().dispatch_decisions.labels(
            route="bls", reason=plan.mode
        ).inc()
        # aggregates offer no host rung (host == python for a
        # pairing-product); a batch has the pure-RLC host rung
        if plan.mode == "aggregate":
            plan.tiers = admissible + [_failover.FLOOR_TIER]
        else:
            plan.tiers = admissible + ["host", _failover.FLOOR_TIER]
        return plan

    def execute(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        """Walk the plan's tiers top-down: chaos injection + watchdog
        around the native tier, typed fault escalation demoting a
        failing tier through ``dispatch.LADDER`` (the batch continues
        one rung down), the python floor re-raising — a pure-python
        pairing error is a bug, not an availability problem."""
        if plan.mode == "empty":
            return False, []
        ladder = _failover.LADDER
        last_exc: BaseException | None = None
        self._last_tier = None
        tiers = plan.tiers or [_failover.FLOOR_TIER]
        for tier in tiers:
            if tier not in ("host", _failover.FLOOR_TIER) and (
                not ladder.active(tier)
            ):
                continue  # demoted since plan time (queue parked it)
            t_tier = time.perf_counter()
            try:
                if tier == BLS_NATIVE_TIER:
                    ok, results = self._run_native(plan)
                elif tier == "host":
                    ok, results = self._run_host(plan)
                else:
                    ok, results = self._run_python(plan)
            except Exception as exc:  # noqa: BLE001 — the escalation
                # seam (ed25519_verify.execute parity): any tier
                # failure demotes and walks one rung down; the floor
                # re-raises
                if tier == _failover.FLOOR_TIER:
                    raise
                last_exc = exc
                ladder.tier_fault(
                    tier, reason=_failover.fault_reason(exc),
                    batch=plan.n,
                    duplicate=getattr(
                        exc, "_ladder_watchdog_fired", False
                    ),
                )
                continue
            self._last_tier = tier
            # counted in the BLS family matching the plan's mode: the
            # host rung here is pure-RLC BLS, not the ed25519 one
            ladder.note_batch(
                tier, batch=plan.n,
                seconds=time.perf_counter() - t_tier,
                family=(
                    _failover.ROUTE_FAMILY_BLS_AGG
                    if plan.mode == "aggregate"
                    else _failover.ROUTE_FAMILY_BLS
                ),
            )
            return ok, results
        raise last_exc if last_exc is not None else RuntimeError(
            "BLS dispatch ladder exhausted without a floor tier"
        )

    def verify(self) -> tuple[bool, list[bool]]:
        return self.execute(self.plan())

    # -- per-tier runners -------------------------------------------------

    def _run_native(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        """The C++ backend under the full health seam: span + chaos
        injection + launch watchdog (a wedged ctypes call becomes a
        signal inside the budget, and the watchdog demotes this tier
        before the stall returns — the r04 shape, inherited)."""
        from cometbft_tpu.crypto import health as _health

        wd = None
        try:
            with _tracer.span(
                "batch_verify", cat="crypto", thread_clock=True,
                kernel=f"bls_{plan.mode}", batch=plan.n,
            ) as sp:
                with _health.WATCHDOG.watch(
                    tier=BLS_NATIVE_TIER, batch=plan.n
                ) as wd:
                    _failover.CHAOS.inject(BLS_NATIVE_TIER)
                    if plan.mode == "aggregate":
                        ok, results = self._native_aggregate(plan)
                    else:
                        ok, results = self._native_batch(plan)
                sp.set(ok=ok, tier=BLS_NATIVE_TIER)
            return ok, results
        except Exception as exc:
            if wd is not None and wd["fired"]:
                exc._ladder_watchdog_fired = True
            raise

    def _native_aggregate(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        sig = plan.agg_sig
        if plan.same_msg:
            # ONE pairing-product: e(sum pk_i, H(m)) == e(g1, sig).
            # The pubkey sum comes from the LRU (cold: native EC adds;
            # warm: free) — a ValueError from a malformed signer is a
            # VERDICT (invalid aggregate), not a tier fault
            try:
                agg_pk = AGG_PK_CACHE.aggregate(
                    [pk.bytes() for pk in plan.agg_pubs]
                )
            except ValueError:
                return False, [False] * plan.n
            ok = bls_native.verify(
                agg_pk, _bls._digest_msg(plan.agg_msgs[0]), sig
            )
        else:
            ok = bls_native.aggregate_verify(
                [pk.bytes() for pk in plan.agg_pubs],
                [_bls._digest_msg(m) for m in plan.agg_msgs],
                sig,
            )
        return ok, [ok] * plan.n

    def _native_batch(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        weights = [os.urandom(15) + b"\x01" for _ in range(plan.n)]
        ok = bls_native.batch_verify(
            [pk.bytes() for pk, _, _ in plan.items],
            [_bls._digest_msg(m) for _, m, _ in plan.items],
            [s for _, _, s in plan.items],
            weights,
        )
        if ok:
            return True, [True] * plan.n
        # the RLC check says "something is invalid" — per-signature
        # re-verify for the exact verdict vector (reference behavior)
        results = [
            bls_native.verify(
                pk.bytes(), _bls._digest_msg(m), s
            )
            for pk, m, s in plan.items
        ]
        return all(results), results

    def _run_host(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        """The pure tower-field RLC batch — one shared Miller loop
        (batch mode only; plan() gives aggregates no host rung)."""
        if _bls.batch_verify_rlc_python(plan.items):
            return True, [True] * plan.n
        results = [
            pk.verify_signature_python(m, s)
            for pk, m, s in plan.items
        ]
        return all(results), results

    def _run_python(self, plan: _BlsPlan) -> tuple[bool, list[bool]]:
        """The floor: pure per-signature verification (batch) or one
        pure pairing-product (aggregate) — never the native backend,
        which is exactly the tier being fallen back FROM."""
        if plan.mode == "aggregate":
            if plan.same_msg:
                ok = _bls.fast_aggregate_verify_python(
                    plan.agg_pubs, plan.agg_msgs[0], plan.agg_sig
                )
            else:
                ok = _bls.aggregate_verify_python(
                    plan.agg_pubs, plan.agg_msgs, plan.agg_sig
                )
            return ok, [ok] * plan.n
        results = [
            pk.verify_signature_python(m, s)
            for pk, m, s in plan.items
        ]
        return all(results), results


def reset_for_tests() -> None:
    """Wipe the aggregate-pubkey cache (suites that tamper with keys)."""
    AGG_PK_CACHE.clear()


__all__ = [
    "AGG_PK_CACHE",
    "AggPubKeyCache",
    "BLS_NATIVE_TIER",
    "BlsLadderVerifier",
    "agg_pk_cache_capacity_from_env",
    "reset_for_tests",
]

"""Pipelined verify-ahead: the async double-buffered verify queue.

BENCH_r02 measured 171 ms sync latency per device launch while the
pipelined bench mode showed 1.6x over sync, and the PR 7 utilization
plane shows the device idle between commits — the gap to the BASELINE
north star is launch overlap, not kernel speed (ROADMAP item 2).  This
module closes it at the ``TpuBatchVerifier`` seam: a process-wide
``VerifyQueue`` accepts verification requests from any caller
(consensus ``VoteSet.add_vote``, blocksync replay prefetch, and the
mempool CheckTx ingest lane — ROADMAP item 4's admission plane,
``CListMempool._verify_tx_signature``), coalesces
them into device-sized batches, and keeps **two buffers in flight**:

- a *collector* thread drains pending requests, computes the SHA-512
  cache-key prehash, and runs the verifier's host phase
  (``TpuBatchVerifier.plan()`` — dispatch routing, key-table lookup,
  input packing) for buffer N+1 **while** buffer N's device launch is
  in flight on the
- *launcher* thread, which executes prepared batches through the
  failover dispatch ladder (``crypto/dispatch.py``: keyed_mesh ->
  keyed -> generic_mesh -> generic -> host -> python; the verifier's
  ``execute()`` walks the plan's admissible tiers top-down, demoting
  a faulting tier and continuing one rung down — a tier demoted
  between plan time and launch time is skipped mid-walk) and
  delivers completion futures back to callers.

Mixed-priority scheduling: consensus-vote requests **preempt**
blocksync/prefetch batches still in the queue — the collector always
prepares pending consensus work first, and the launcher always picks a
prepared consensus batch over a prepared prefetch batch.

**Speculative-result cache.**  Every verification that PASSES lands
in a bounded LRU keyed by SHA-512(pubkey || signature || message) —
the message is the vote's sign bytes, so the key is the
(vote-sign-bytes digest, pubkey) pair the speculative plane needs,
deliberately bound to the *signature* as well: a cached verdict must
never answer for a different signature over the same bytes.  Only
POSITIVE verdicts are memoized (SpeculativeCache docstring): a
transient device fault mis-verifying a valid signature must cost one
rejection and heal on retry, never poison the cache.
``VoteSet.add_vote`` submits signatures on receipt, so
``verify_commit`` at finalize time is mostly a cache hit instead of a
10k-sig synchronous launch (types/validation.py consults
``cached_result``); blocksync submits the next
``CMT_TPU_VERIFY_PREFETCH`` blocks' commit signatures while the
current block applies.  Fall-back is STRICT: on a cache miss, queue
unavailability, a failed future, or a wait timeout, callers run the
exact synchronous verify they ran before this module existed — the
queue is an accelerator, never a correctness dependency.  And a
consensus-priority caller never WAITS behind in-flight work: when the
queue is busy, ``verify_or_fallback`` verifies inline (pre-queue
latency) and still feeds the cache.

Env knobs (validated fail-loudly, same contract as the ring vars in
utils/flight.py):

- ``CMT_TPU_VERIFY_PREFETCH`` — blocksync prefetch depth in blocks
  (default 8; 0 disables prefetch).
- ``CMT_TPU_SPEC_CACHE`` — speculative-result cache capacity in
  entries (default 65536, >= 1024; ~152 B/entry, so the default is
  ~10 MB and covers a fully speculated 10k-validator commit 6x over).
- ``CMT_TPU_VERIFY_QUEUE=0`` — node assembly skips the queue entirely
  (every caller takes the synchronous path, exactly as before).
- ``CMT_TPU_CHECKTX_BATCH`` — ingest-lane accumulation target in
  signatures (default 256, >= 1): concurrent mempool CheckTx
  submissions coalesce until this many are pending, then release as
  ONE buffer (one DispatchLadder launch).
- ``CMT_TPU_CHECKTX_WAIT_MS`` — ingest accumulation deadline in
  milliseconds (default 5, >= 0): the oldest pending CheckTx
  signature never waits longer than this for the batch to fill.
- ``CMT_TPU_LIGHT_BATCH`` / ``CMT_TPU_LIGHT_WAIT_MS`` — the same two
  bounds for the ``light_client`` serving lane (defaults 1024 / 10):
  concurrent light-client header syncs coalesce into single ladder
  launches through the SAME ``_LaneBatcher`` machinery the ingest
  lane uses.

The ``ingest`` lane (ROADMAP item 4, the mempool admission plane) is
the lowest priority: every other lane strictly preempts it at buffer
granularity, and its requests additionally accumulate behind the
micro-batcher gate above — mempool admission soaks up device idle
time between commits without ever delaying a vote.  The
``light_client`` lane (ISSUE 13, the header serving plane) sits
between prefetch and ingest with its own micro-batcher: external
clients syncing header ranges must never delay live votes or the
node's own replay, but they outrank admission.

Observability: ``crypto_verify_queue_*`` metrics (CryptoMetrics),
``verify_queue/prepare`` + ``verify_queue/launch`` spans (the overlap
is visible as prepare-of-N+1 nesting inside launch-of-N wall time —
docs/observability.md "reading an overlap trace") with their stages
inside (``verify_queue/submit`` on the caller, ``/prehash`` in prepare,
``/resolve`` in launch, and the two handoffs ``/pending_wait`` and
``/prepared_wait`` recorded at the pops), and the launcher
feeds ``crypto_host_device_overlap_ratio`` with the share of each
launch wall covered by concurrent host prep.
"""

from __future__ import annotations

import hashlib
import threading
import time
from collections import OrderedDict, deque

from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.metrics import health_metrics as _health_metrics
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import ring_size_from_env as _int_env
from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.service import BaseService
from cometbft_tpu.utils.trace import TRACER as _tracer

#: request priorities (metric label values); consensus preempts
#: prefetch, both preempt the ``light_client`` serving lane, and all
#: three strictly preempt the mempool ``ingest`` lane at both the
#: collector and the launcher (buffer granularity — a prepared
#: consensus buffer launches before a parked light/ingest buffer).
#: light_client sits between prefetch and ingest: header serving for
#: external clients must never delay live votes or the node's own
#: block replay, but it IS revenue traffic — admission soaks up
#: whatever idle remains below it.
PRIORITY_CONSENSUS = "consensus"
PRIORITY_PREFETCH = "prefetch"
PRIORITY_LIGHT = "light_client"
PRIORITY_INGEST = "ingest"
_PRIORITIES = (
    PRIORITY_CONSENSUS, PRIORITY_PREFETCH, PRIORITY_LIGHT,
    PRIORITY_INGEST,
)

DEFAULT_PREFETCH_DEPTH = 8
DEFAULT_SPEC_CACHE_CAP = 65536
#: ingest micro-batcher: accumulate concurrent CheckTx submissions
#: until this many signatures are pending (one DispatchLadder launch
#: instead of one per RPC thread) ...
DEFAULT_CHECKTX_BATCH = 256
#: ... or until the OLDEST pending ingest request has waited this many
#: milliseconds — the admission-latency bound a half-full batch pays
DEFAULT_CHECKTX_WAIT_MS = 5
#: light_client lane micro-batcher (same accumulate/deadline/release
#: machinery as ingest, via the shared _LaneBatcher): concurrent
#: header-verification requests coalesce until this many signatures
#: are pending ...
DEFAULT_LIGHT_BATCH = 1024
#: ... or the OLDEST pending light request has waited this long — a
#: looser bound than CheckTx (10 ms vs 5): header sync is bulk
#: traffic, and a wider window is what turns 10k concurrent clients'
#: 150-sig commits into full-device launches
DEFAULT_LIGHT_WAIT_MS = 10
#: largest coalesced batch — matches ops/ed25519_verify.MAX_LAUNCH's
#: default so one queue batch is one device launch
DEFAULT_MAX_BATCH = 8192
#: how long a caller waits on a future before the strict sync
#: fallback; generous because a pure-Python host tier can take seconds
#: per large prefetch batch ahead of a consensus request
DEFAULT_WAIT_S = 120.0


def prefetch_depth_from_env() -> int:
    """Blocksync verify-prefetch depth in blocks; 0 disables."""
    return _int_env("CMT_TPU_VERIFY_PREFETCH", DEFAULT_PREFETCH_DEPTH, 0)


def spec_cache_capacity_from_env() -> int:
    """Speculative-result cache capacity in entries (>= 1024: smaller
    caches evict a large commit mid-verify and the speculative plane
    silently degrades to all-miss)."""
    return _int_env("CMT_TPU_SPEC_CACHE", DEFAULT_SPEC_CACHE_CAP, 1024)


def checktx_batch_from_env() -> int:
    """Ingest-lane accumulation target in signatures (>= 1; 1 disables
    coalescing — every CheckTx submission releases immediately)."""
    return _int_env("CMT_TPU_CHECKTX_BATCH", DEFAULT_CHECKTX_BATCH, 1)


def checktx_wait_ms_from_env() -> int:
    """Ingest-lane accumulation deadline in milliseconds (>= 0; 0
    releases every pending ingest batch immediately, whatever its
    size)."""
    return _int_env("CMT_TPU_CHECKTX_WAIT_MS", DEFAULT_CHECKTX_WAIT_MS, 0)


def light_batch_from_env() -> int:
    """Light-client lane accumulation target in signatures (>= 1; 1
    disables coalescing)."""
    return _int_env("CMT_TPU_LIGHT_BATCH", DEFAULT_LIGHT_BATCH, 1)


def light_wait_ms_from_env() -> int:
    """Light-client lane accumulation deadline in milliseconds (>= 0;
    0 releases every pending light batch immediately)."""
    return _int_env("CMT_TPU_LIGHT_WAIT_MS", DEFAULT_LIGHT_WAIT_MS, 0)


class QueueUnavailable(RuntimeError):
    """The queue is stopped/draining; callers must verify
    synchronously."""


class VerifyFuture:
    """Completion handle for one submitted (pubkey, msg, sig) request.

    ``result()`` returns the verification bit or raises: the waiter
    treats ANY raise (failed launch, drain, timeout) as "queue
    unavailable" and falls back to synchronous verification."""

    __slots__ = ("_event", "_result", "_error")

    def __init__(self) -> None:
        self._event = threading.Event()
        self._result: bool | None = None
        self._error: BaseException | None = None

    def _resolve(self, result: bool) -> None:
        if not self._event.is_set():
            self._result = result
            self._event.set()

    def _fail(self, exc: BaseException) -> None:
        # first writer wins: a drain-timeout _fail must not clobber a
        # verdict a slow launcher delivered concurrently (and vice
        # versa — the waiter's strict sync fallback covers the rest)
        if not self._event.is_set():
            self._error = exc
            self._event.set()

    def done(self) -> bool:
        return self._event.is_set()

    def result(self, timeout: float | None = DEFAULT_WAIT_S) -> bool:
        if not self._event.wait(timeout):
            raise QueueUnavailable("verify future timed out")
        if self._error is not None:
            raise QueueUnavailable(
                f"verify batch failed: {self._error!r}"
            ) from self._error
        return bool(self._result)


def cache_key(pub: bytes, msg: bytes, sig: bytes) -> bytes:
    """SHA-512 over pubkey || signature || sign-bytes — the host
    prehash the collector runs for buffer N+1 while buffer N launches.
    Binding the signature (not just the (digest, pubkey) pair) is
    load-bearing: two distinct signatures over the same vote bytes
    must never share a cached verdict."""
    h = hashlib.sha512()
    h.update(pub)
    h.update(sig)
    h.update(msg)
    return h.digest()


@cmtsync.guarded
class SpeculativeCache:
    """Bounded LRU of cache_key -> True: PROOFS OF VALIDITY only.
    A positive verdict is a pure fact about the (pubkey, sign-bytes,
    signature) triple — height- and validator-set-independent, never
    stale — so capacity is the only eviction policy.  Negative
    verdicts are deliberately NEVER stored: a transient device fault
    mis-verifying one signature must cost one rejected attempt (the
    pre-queue behavior — the retry re-verifies fresh), not a
    permanently poisoned cache entry that rejects a valid commit
    forever.  Invalid signatures therefore re-verify on every consult,
    which is the attacker paying, not us."""

    _GUARDED_BY = {"_map": "_mtx"}

    def __init__(self, capacity: int | None = None) -> None:
        self.capacity = (
            capacity if capacity is not None
            else spec_cache_capacity_from_env()
        )
        self._mtx = cmtsync.Mutex()
        self._map: OrderedDict[bytes, bool] = OrderedDict()

    def lookup(self, key: bytes) -> bool | None:
        with self._mtx:
            if key not in self._map:
                return None
            self._map.move_to_end(key)
            return self._map[key]

    def store(self, key: bytes, ok: bool) -> None:
        if not ok:
            return  # negative verdicts are never memoized (class doc)
        with self._mtx:
            self._map[key] = True
            self._map.move_to_end(key)
            while len(self._map) > self.capacity:
                self._map.popitem(last=False)

    def __len__(self) -> int:
        with self._mtx:
            return len(self._map)


class _Request:
    __slots__ = ("pub_key", "msg", "sig", "future", "key", "t")

    def __init__(self, pub_key, msg: bytes, sig: bytes) -> None:
        self.pub_key = pub_key
        self.msg = msg
        self.sig = sig
        self.future = VerifyFuture()
        self.key: bytes | None = None  # prehash, set by the collector
        #: arrival time (perf_counter, the span ring's clock), stamped
        #: by submit_many when the request is enqueued — the ingest
        #: micro-batcher's accumulation deadline is measured from the
        #: OLDEST pending request, so a half-full batch never waits
        #: past the bound; verify_queue/pending_wait starts here
        self.t = 0.0


class _LaneBatcher:
    """The accumulate/deadline/release gate an accumulating lane puts
    in front of the collector (PR 10's CheckTx micro-batcher,
    EXTRACTED so the ingest and light_client lanes share one
    implementation instead of two drifting copies): a pending lane
    releases when it reaches the ``batch_target`` size, when the
    OLDEST pending request has waited ``wait_s``, or on drain — never
    before, so concurrent submissions coalesce into one DispatchLadder
    launch instead of one launch per caller thread.  Stateless apart
    from its two bounds; all timing reads the requests' arrival
    stamps, so unit tests drive it with explicit clocks."""

    __slots__ = ("batch_target", "wait_s")

    def __init__(self, batch_target: int, wait_ms: int) -> None:
        self.batch_target = batch_target
        self.wait_s = wait_ms / 1000.0

    def ready(
        self, lane: deque, draining: bool, now: float | None = None
    ) -> bool:
        if not lane:
            return False
        if draining or len(lane) >= self.batch_target:
            return True
        now = time.perf_counter() if now is None else now
        return now - lane[0].t >= self.wait_s

    def cut(self, pending: int) -> int:
        """How many of ``pending`` requests one release takes: exactly
        ``batch_target`` once that many are pending — what is over
        waits for the next buffer, under its own arrival stamps and
        deadline, instead of padding this launch to the next
        power-of-two bucket — so a lane that releases by size always
        launches the one shape it has compiled.  A release by deadline
        or drain, under the target, takes them all."""
        return min(pending, self.batch_target)

    def deadline_wait(
        self, lane: deque, now: float | None = None
    ) -> float | None:
        """Seconds until the oldest pending request's accumulation
        deadline (None when the lane is empty) — the collector sleeps
        no longer than the NEAREST deadline across all batched lanes,
        so the wait bounds stay real."""
        if not lane:
            return None
        now = time.perf_counter() if now is None else now
        return max(0.001, self.wait_s - (now - lane[0].t))


class _Prepared:
    """One prepared buffer: requests grouped per key type with their
    host-phase artifacts, ready for the launcher."""

    __slots__ = ("priority", "reqs", "groups", "prep_seconds", "t_ready")

    def __init__(self, priority: str) -> None:
        self.priority = priority
        self.reqs: list[_Request] = []
        #: list of (reqs, verifier | None, plan | None); verifier None
        #: means per-signature host verification in the launcher
        self.groups: list[tuple] = []
        self.prep_seconds = 0.0
        #: perf_counter when the collector parked this buffer;
        #: verify_queue/prepared_wait runs from here to the launcher's pop
        self.t_ready = 0.0


@cmtsync.guarded
class VerifyQueue(BaseService):
    """The double-buffered verify queue (module docstring).

    ``verifier_factory(pub_key)`` builds the per-batch verifier
    (default: crypto/batch.create_batch_verifier — the production
    dispatch ladder).  ``launch`` overrides the launch phase entirely
    (tests gate it to prove the overlap deterministically): a callable
    ``launch(items) -> list[bool]`` over ``(pub_key, msg, sig)``
    tuples.  ``use_cache=False`` disables the speculative cache
    (benches re-verify the same batch honestly)."""

    _GUARDED_BY = {
        "_pending": "_qmtx",
        "_prepared": "_qmtx",
        "_preparing_lane": "_qmtx",
        "_draining": "_qmtx",
        "_launch_active": "_qmtx",
        "_launch_t0": "_qmtx",
        "_overlap_accum": "_qmtx",
        "_prep_since": "_qmtx",
        "_overlap_seconds": "_qmtx",
        "_launch_wall_seconds": "_qmtx",
        "_stats": "_qmtx",
        "_last_overlap": "_qmtx",
    }

    def __init__(
        self,
        verifier_factory=None,
        launch=None,
        max_batch: int = DEFAULT_MAX_BATCH,
        spec_cache: SpeculativeCache | None = None,
        use_cache: bool = True,
        checktx_batch: int | None = None,
        checktx_wait_ms: int | None = None,
        light_batch: int | None = None,
        light_wait_ms: int | None = None,
        logger: Logger | None = None,
    ) -> None:
        super().__init__(
            name="verify-queue",
            logger=logger or default_logger().with_fields(
                module="crypto.verify_queue"
            ),
        )
        self._factory = verifier_factory
        self._launch = launch
        self._max_batch = max_batch
        #: per-lane micro-batcher gates (module docstring): pending
        #: ingest/light requests accumulate until the lane's size
        #: target is reached or its oldest request hits the wait
        #: deadline, then release as ONE buffer.  Lanes absent here
        #: (consensus, prefetch) release immediately.
        self._batchers: dict[str, _LaneBatcher] = {
            PRIORITY_INGEST: _LaneBatcher(
                checktx_batch if checktx_batch is not None
                else checktx_batch_from_env(),
                checktx_wait_ms if checktx_wait_ms is not None
                else checktx_wait_ms_from_env(),
            ),
            PRIORITY_LIGHT: _LaneBatcher(
                light_batch if light_batch is not None
                else light_batch_from_env(),
                light_wait_ms if light_wait_ms is not None
                else light_wait_ms_from_env(),
            ),
        }
        self.cache = (
            (spec_cache or SpeculativeCache()) if use_cache else None
        )
        self._qmtx = cmtsync.Mutex()
        self._collector_wake = threading.Event()
        self._launcher_wake = threading.Event()
        self._pending: dict[str, deque[_Request]] = {
            p: deque() for p in _PRIORITIES
        }
        #: prepared buffers awaiting launch, at most ONE per priority:
        #: with the one the launcher holds, that is the double buffer
        self._prepared: dict[str, deque[_Prepared]] = {
            p: deque() for p in _PRIORITIES
        }
        #: the lane being prepared, from the moment _next_pending pops
        #: a batch until the collector parks (or abandons) its
        #: prepared buffer; None when idle.  Lane-aware (not a bool)
        #: so busy() can ignore an INGEST buffer mid-prepare while
        #: still covering the consensus/prefetch prep window — without
        #: it busy() goes dark for the whole prep phase and a consensus
        #: vote parks behind the prefetch batch being prepared
        self._preparing_lane: str | None = None
        self._draining = False
        self._launch_active = 0
        self._launch_t0 = 0.0
        self._overlap_accum = 0.0
        #: start (or accounted-until watermark) of the prep currently
        #: running on the collector, None when idle — lets a launch
        #: that ends MID-prep credit the overlap accrued so far (a
        #: prep outliving the launch it overlapped must not count 0)
        self._prep_since: float | None = None
        self._overlap_seconds = 0.0
        self._launch_wall_seconds = 0.0
        self._last_overlap: float | None = None
        self._stats = {
            "submitted": {p: 0 for p in _PRIORITIES},
            "cache_resolved": 0,
            "prepared_batches": 0,
            "launched_batches": 0,
            "launched_sigs": 0,
            "launched_batches_by_lane": {p: 0 for p in _PRIORITIES},
            "launched_sigs_by_lane": {p: 0 for p in _PRIORITIES},
            "failed_batches": 0,
        }
        self._collector_thread: threading.Thread | None = None
        self._launcher_thread: threading.Thread | None = None

    # -- submission ------------------------------------------------------

    def accepting(self) -> bool:
        with self._qmtx:
            draining = self._draining
        return self.is_running() and not draining

    def busy(self) -> bool:
        """True while work a consensus vote could get stuck behind is
        pending, prepared, preparing, or launching.  Latency-sensitive
        callers (a live consensus vote) use this to verify INLINE
        instead of parking — priority preemption reorders queued
        buffers but can never interrupt the launch already on the
        device.

        QUEUED ingest and light_client work (accumulating requests, a
        parked buffer, a buffer mid-prepare) is deliberately
        excluded: it is exactly what consensus preempts, so a mempool
        under sustained admission load — or a serving plane under 10k
        syncing light clients — must not push every live vote
        onto the inline path by itself.  Such a launch ALREADY ON
        THE DEVICE still counts — it cannot be interrupted, and
        waiting a full launch wall behind it is what this check
        exists to avoid; while admission keeps the device saturated,
        live votes therefore verify inline at pre-queue latency (the
        designed degradation — never a stall)."""
        with self._qmtx:
            return bool(
                self._launch_active
                or self._preparing_lane in (
                    PRIORITY_CONSENSUS, PRIORITY_PREFETCH
                )
                or any(
                    self._pending[p] or self._prepared[p]
                    for p in (PRIORITY_CONSENSUS, PRIORITY_PREFETCH)
                )
            )

    def submit_many(
        self, items, priority: str = PRIORITY_CONSENSUS
    ) -> list[VerifyFuture]:
        """Enqueue ``(pub_key, msg, sig)`` tuples; returns one future
        per item.  Raises QueueUnavailable when stopped/draining."""
        if priority not in _PRIORITIES:
            raise ValueError(f"unknown priority {priority!r}")
        with _tracer.span(
            "verify_queue/submit", cat="crypto", priority=priority,
        ) as sp:
            reqs = [_Request(pk, bytes(m), bytes(s)) for pk, m, s in items]
            sp.set(batch=len(reqs))
            arrived = time.perf_counter()
            for r in reqs:
                r.t = arrived
            with self._qmtx:
                if self._draining or not self.is_running():
                    raise QueueUnavailable("verify queue is not accepting")
                self._pending[priority].extend(reqs)
                self._stats["submitted"][priority] += len(reqs)
                depth = len(self._pending[priority])
            cm = _crypto_metrics()
            cm.verify_queue_submitted.labels(priority=priority).inc(
                len(reqs)
            )
            cm.verify_queue_depth.labels(priority=priority).set(depth)
            self._collector_wake.set()
            return [r.future for r in reqs]

    def submit(self, pub_key, msg, sig,
               priority: str = PRIORITY_CONSENSUS) -> VerifyFuture:
        return self.submit_many([(pub_key, msg, sig)], priority)[0]

    # -- lifecycle -------------------------------------------------------

    def on_start(self) -> None:
        self._collector_thread = threading.Thread(
            target=self._collector, name="verify-queue-collect",
            daemon=True,
        )
        self._launcher_thread = threading.Thread(
            target=self._launcher, name="verify-queue-launch",
            daemon=True,
        )
        self._collector_thread.start()
        self._launcher_thread.start()

    def on_stop(self) -> None:
        """Drain: stop accepting, let the collector prepare what is
        already pending and the launcher finish every prepared buffer,
        then fail any leftovers so no caller blocks forever."""
        with self._qmtx:
            self._draining = True
        self._collector_wake.set()
        self._launcher_wake.set()
        for t in (self._collector_thread, self._launcher_thread):
            if t is not None:
                t.join(timeout=DEFAULT_WAIT_S)
        leftovers: list[_Request] = []
        with self._qmtx:
            for p in _PRIORITIES:
                leftovers.extend(self._pending[p])
                self._pending[p].clear()
                for prep in self._prepared[p]:
                    leftovers.extend(prep.reqs)
                self._prepared[p].clear()
        for r in leftovers:
            r.future._fail(QueueUnavailable("queue stopped"))
        if _installed() is self:
            install_queue(None)

    # -- the collector (host phase: buffer N+1) --------------------------

    def _batcher_deadline_wait(self) -> float:
        """How long the collector may sleep before the NEAREST pending
        accumulation deadline across the batched lanes expires (holds
        no lock — called from the collector's idle loop only)."""
        wait = 0.05
        now = time.perf_counter()
        with self._qmtx:
            for p, gate in self._batchers.items():
                remaining = gate.deadline_wait(self._pending[p], now)
                if remaining is not None:
                    wait = min(wait, remaining)
        return max(0.001, wait)

    def _next_pending(self) -> tuple[list[_Request] | None, str | None]:
        """Pop the next batch worth of requests: consensus first, then
        prefetch, then light_client, then ingest (strict preemption),
        and only for a priority lane whose prepared slot is free (the
        double-buffer bound).  The batched lanes (ingest,
        light_client) additionally hold until their micro-batch
        accumulation gate opens (``_LaneBatcher.ready``).  Sets
        ``_preparing_lane`` under the same lock as the pop so busy() never
        misses the batch between dequeue and the prepared-slot
        append."""
        with self._qmtx:
            for p in _PRIORITIES:
                gate = self._batchers.get(p)
                if gate is not None and not gate.ready(
                    self._pending[p], self._draining
                ):
                    continue
                if self._pending[p] and not self._prepared[p]:
                    take = min(len(self._pending[p]), self._max_batch)
                    if gate is not None:
                        take = gate.cut(take)
                    reqs = [
                        self._pending[p].popleft() for _ in range(take)
                    ]
                    self._preparing_lane = p
                    _crypto_metrics().verify_queue_depth.labels(
                        priority=p
                    ).set(len(self._pending[p]))
                    return reqs, p
        return None, None

    def _idle_done(self) -> bool:
        with self._qmtx:
            if not self._draining:
                return False
            return not any(self._pending.values())

    def _collector(self) -> None:
        while True:
            reqs, priority = self._next_pending()
            if reqs is None:
                if self._idle_done():
                    return
                # sleep no longer than the nearest accumulation
                # deadline across the batched lanes — the default
                # CheckTx wait bound (5 ms) is finer than the idle
                # poll interval
                self._collector_wake.wait(self._batcher_deadline_wait())
                self._collector_wake.clear()
                continue
            # handoff: the oldest request's arrival to this pop
            _tracer.add_complete(
                "verify_queue/pending_wait", reqs[0].t,
                time.perf_counter() - reqs[0].t, cat="crypto",
                args={"priority": priority, "batch": len(reqs)},
            )
            try:
                try:
                    prep = self._prepare(reqs, priority)
                except Exception as exc:  # noqa: BLE001 — fall back
                    self.logger.error(
                        "verify-queue prepare failed", err=repr(exc)
                    )
                    for r in reqs:
                        r.future._fail(exc)
                    continue
                if not prep.reqs:
                    continue  # every request was a cache hit
                prep.t_ready = time.perf_counter()
                with self._qmtx:
                    self._prepared[priority].append(prep)
                    self._stats["prepared_batches"] += 1
                    inflight = self._launch_active + sum(
                        len(d) for d in self._prepared.values()
                    )
                _crypto_metrics().verify_queue_inflight.set(inflight)
                self._launcher_wake.set()
            finally:
                # clear AFTER the prepared-slot append (or abandon):
                # between pop and here busy() sees _preparing_lane,
                # after the append it sees the prepared buffer — no
                # window
                with self._qmtx:
                    self._preparing_lane = None

    def _prepare(self, reqs: list[_Request], priority: str) -> _Prepared:
        """Host phase for one buffer: cache-key prehash, speculative
        dedupe, then the verifier's plan() (dispatch routing + input
        packing) — all of it overlapping whatever launch is in
        flight."""
        t0 = time.perf_counter()
        with self._qmtx:
            self._prep_since = t0
        prep = _Prepared(priority)
        cm = _crypto_metrics()
        try:
            with _tracer.span(
                "verify_queue/prepare", cat="crypto", batch=len(reqs),
                priority=priority,
            ) as prep_span:
                work: list[_Request] = []
                with _tracer.span("verify_queue/prehash", cat="crypto"):
                    for r in reqs:
                        r.key = cache_key(r.pub_key.bytes(), r.msg, r.sig)
                        cached = (
                            self.cache.lookup(r.key)
                            if self.cache is not None else None
                        )
                        if cached is not None:
                            cm.verify_queue_spec_cache.labels(
                                result="hit"
                            ).inc()
                            r.future._resolve(cached)
                            continue
                        if self.cache is not None:
                            cm.verify_queue_spec_cache.labels(
                                result="miss"
                            ).inc()
                        work.append(r)
                if work:
                    with self._qmtx:
                        self._stats["cache_resolved"] += (
                            len(reqs) - len(work)
                        )
                    prep.reqs = work
                    cm.verify_queue_batch_size.observe(len(work))
                    if self._launch is not None:
                        prep.groups = [(work, None, None)]
                    else:
                        prep.groups = self._build_groups(work)
                else:
                    with self._qmtx:
                        self._stats["cache_resolved"] += len(reqs)
                # stage mark for the attribution plane: how much of
                # this prepare was the speculative cache resolving
                # (critpath's verify_spec) vs real plan/pack work
                prep_span.set(
                    hits=len(reqs) - len(work), misses=len(work)
                )
            prep.prep_seconds = time.perf_counter() - t0
        finally:
            # overlap accounting: host prep that ran while a launch was
            # in flight is exactly the wall time the pipeline bought.
            # The _prep_since watermark may have been advanced by a
            # launch that ENDED mid-prep (it credited the overlap up to
            # its end), so accrue only from the watermark forward.  In
            # a finally so a raising prepare (malformed signature in
            # plan/pack) can't leave a stale watermark that every later
            # launch end mistakes for a live prep, pinning the
            # cumulative overlap ratio near 1.0.
            now = time.perf_counter()
            with self._qmtx:
                since = (
                    self._prep_since if self._prep_since is not None
                    else t0
                )
                if self._launch_active:
                    self._overlap_accum += max(
                        0.0, now - max(since, self._launch_t0)
                    )
                self._prep_since = None
        return prep

    def _build_groups(self, work: list[_Request]) -> list[tuple]:
        from cometbft_tpu.crypto import batch as crypto_batch

        by_type: dict[str, list[_Request]] = {}
        for r in work:
            by_type.setdefault(r.pub_key.type(), []).append(r)
        factory = self._factory
        groups: list[tuple] = []
        for reqs in by_type.values():
            pk0 = reqs[0].pub_key
            verifier = None
            # every group — single-signature ones included — routes
            # through the verifier seam so the dispatch ladder
            # (crypto/dispatch.py) is the ONE decision + accounting
            # point: a 1-sig group still plans (host route at
            # production thresholds, device when the ladder says so)
            # and lands in crypto_dispatch_tier; the per-sig fallback
            # below covers only unsupported key types and factory
            # failures.  The submission's COALESCED shape carries
            # through plan() untouched — plan() sees the micro-batched
            # size the launch will actually have, not the per-caller
            # fragment sizes, so an ingest lane full of 1-sig CheckTx
            # requests is routed (and counted) as the 256-sig buffer
            # it coalesced into
            if crypto_batch.supports_batch_verifier(pk0):
                try:
                    verifier = (
                        factory(pk0) if factory is not None
                        else crypto_batch.create_batch_verifier(pk0)
                    )
                except Exception:  # noqa: BLE001 — per-sig fallback
                    verifier = None
            plan = None
            if verifier is not None:
                for r in reqs:
                    verifier.add(r.pub_key, r.msg, r.sig)
                plan_fn = getattr(verifier, "plan", None)
                if plan_fn is not None:
                    plan = plan_fn()
            groups.append((reqs, verifier, plan))
        return groups

    # -- the launcher (device phase: buffer N) ---------------------------

    def _next_prepared(self) -> _Prepared | None:
        with self._qmtx:
            for p in _PRIORITIES:
                if self._prepared[p]:
                    return self._prepared[p].popleft()
        return None

    def _launch_done(self) -> bool:
        with self._qmtx:
            if not self._draining:
                return False
            if any(self._prepared.values()) or any(
                self._pending.values()
            ):
                return False
        t = self._collector_thread
        return t is None or not t.is_alive()

    def _launcher(self) -> None:
        while True:
            prep = self._next_prepared()
            if prep is None:
                if self._launch_done():
                    return
                self._launcher_wake.wait(0.05)
                self._launcher_wake.clear()
                continue
            # handoff: the collector's append to this pop
            _tracer.add_complete(
                "verify_queue/prepared_wait", prep.t_ready,
                time.perf_counter() - prep.t_ready, cat="crypto",
                args={"priority": prep.priority},
            )
            self._collector_wake.set()  # slot freed: prep buffer N+1
            self._execute(prep)

    def _execute(self, prep: _Prepared) -> None:
        t0 = time.perf_counter()
        with self._qmtx:
            self._launch_active += 1
            if self._launch_active == 1:
                self._launch_t0 = t0
                self._overlap_accum = 0.0
        try:
            with _tracer.span(
                "verify_queue/launch", cat="crypto",
                batch=len(prep.reqs), priority=prep.priority,
            ):
                for reqs, verifier, plan in prep.groups:
                    self._execute_group(reqs, verifier, plan)
        finally:
            now = time.perf_counter()
            wall = max(now - t0, 0.0)
            with self._qmtx:
                self._launch_active -= 1
                if self._prep_since is not None:
                    # a prep is STILL running: credit its overlap with
                    # this launch now and advance its watermark so its
                    # own end-of-prep accrual can't double count
                    self._overlap_accum += max(
                        0.0, now - max(self._prep_since, t0)
                    )
                    self._prep_since = now
                overlap = min(self._overlap_accum, wall)
                self._overlap_accum = 0.0
                self._overlap_seconds += overlap
                self._launch_wall_seconds += wall
                self._stats["launched_batches"] += 1
                self._stats["launched_sigs"] += len(prep.reqs)
                self._stats["launched_batches_by_lane"][prep.priority] += 1
                self._stats["launched_sigs_by_lane"][prep.priority] += len(
                    prep.reqs
                )
                # CUMULATIVE ratio: overlapped host-prep seconds over
                # total launch wall — a final buffer with nothing
                # behind it dilutes rather than zeroes the signal
                ratio = (
                    min(
                        self._overlap_seconds
                        / self._launch_wall_seconds,
                        1.0,
                    )
                    if self._launch_wall_seconds > 0 else 0.0
                )
                self._last_overlap = ratio
                inflight = self._launch_active + sum(
                    len(d) for d in self._prepared.values()
                )
            cm = _crypto_metrics()
            cm.verify_queue_inflight.set(inflight)
            cm.verify_queue_launched.labels(priority=prep.priority).inc()
            cm.verify_queue_launched_sigs.labels(
                priority=prep.priority
            ).inc(len(prep.reqs))
            _health_metrics().host_device_overlap_ratio.set(ratio)

    def _execute_group(self, reqs, verifier, plan) -> None:
        try:
            if self._launch is not None:
                results = self._launch(
                    [(r.pub_key, r.msg, r.sig) for r in reqs]
                )
            elif verifier is not None:
                if plan is not None:
                    ok, results = verifier.execute(plan)
                else:
                    ok, results = verifier.verify()
            else:
                # per-signature host fallback (unsupported key types,
                # factory failures): one ladder accounting sample at
                # the decision point — crypto_dispatch_tier covers
                # every verify, not just batch-seam launches.
                # Deliberately shape-blind (no batch/seconds): these
                # are whatever key types fell through, and timing
                # them would pollute the host tier's cost estimates
                from cometbft_tpu.crypto.dispatch import (
                    LADDER as _ladder,
                )

                _ladder.note_batch("host")
                results = [
                    r.pub_key.verify_signature(r.msg, r.sig)
                    for r in reqs
                ]
            results = list(results)
        except Exception as exc:  # noqa: BLE001 — strict sync fallback
            self.logger.error(
                "verify-queue launch failed", err=repr(exc),
                batch=len(reqs),
            )
            with self._qmtx:
                self._stats["failed_batches"] += 1
            for r in reqs:
                r.future._fail(exc)
            return
        if len(results) != len(reqs):
            # a malformed verifier/launch result must fail the batch
            # IMMEDIATELY (callers take the strict sync fallback), not
            # leave zip-truncated futures dangling until the 120 s
            # wait times out — on the consensus path, with locks held
            exc = RuntimeError(
                f"launch returned {len(results)} results for "
                f"{len(reqs)} requests"
            )
            self.logger.error(
                "verify-queue launch result mismatch", err=str(exc)
            )
            with self._qmtx:
                self._stats["failed_batches"] += 1
            for r in reqs:
                r.future._fail(exc)
            return
        with _tracer.span("verify_queue/resolve", cat="crypto"):
            for r, bit in zip(reqs, results):
                bit = bool(bit)
                if self.cache is not None and r.key is not None:
                    self.cache.store(r.key, bit)
                r.future._resolve(bit)

    # -- introspection ---------------------------------------------------

    def stats(self) -> dict:
        with self._qmtx:
            out = {
                "submitted": dict(self._stats["submitted"]),
                "cache_resolved": self._stats["cache_resolved"],
                "prepared_batches": self._stats["prepared_batches"],
                "launched_batches": self._stats["launched_batches"],
                "launched_sigs": self._stats["launched_sigs"],
                "launched_batches_by_lane": dict(
                    self._stats["launched_batches_by_lane"]
                ),
                "launched_sigs_by_lane": dict(
                    self._stats["launched_sigs_by_lane"]
                ),
                "failed_batches": self._stats["failed_batches"],
                "pending": {
                    p: len(d) for p, d in self._pending.items()
                },
                "prepared": {
                    p: len(d) for p, d in self._prepared.items()
                },
                "overlap_ratio": self._last_overlap,
                "draining": self._draining,
            }
        out["cache_entries"] = len(self.cache) if self.cache else 0
        return out


# -- the process-wide queue + speculative helpers ------------------------

_install_mtx = cmtsync.Mutex()
_QUEUE: VerifyQueue | None = None


def install_queue(queue: VerifyQueue | None) -> None:
    """Install the process-wide queue (node assembly) or uninstall
    with None (node stop does this via VerifyQueue.on_stop)."""
    global _QUEUE
    with _install_mtx:
        _QUEUE = queue


def _installed() -> VerifyQueue | None:
    return _QUEUE


def lane_batch_target(priority: str) -> int | None:
    """The accumulation target, in signatures, of ``priority``'s
    micro-batcher on the installed queue — what a caller that feeds a
    batched lane from its own look-ahead (``light/client.py``) cuts
    its submissions to, so that each releases at once and fills its
    launch.  None when no queue is accepting or the lane releases
    immediately (consensus, prefetch)."""
    q = _QUEUE
    if q is None or not q.accepting():
        return None
    gate = q._batchers.get(priority)
    return gate.batch_target if gate is not None else None


def speculation_active() -> bool:
    """True while a queue is installed and accepting — the gate every
    speculative consult (types/validation.py) and submission
    (vote_set, blocksync, consensus) checks first.  With no queue
    installed, every caller behaves exactly as before this module
    existed."""
    q = _QUEUE
    return q is not None and q.accepting()


def cached_result(
    pub: bytes, msg: bytes, sig: bytes, key: bytes | None = None
) -> bool | None:
    """Speculative-cache consult: True when this exact (pubkey,
    sign-bytes, signature) triple already verified VALID, None
    otherwise (caller verifies synchronously — negative verdicts are
    never cached, see SpeculativeCache).  Pass ``key`` (a precomputed
    ``cache_key``) to skip the SHA-512 prehash — a consult-then-record
    caller (validation._verify_group over a cold 10k-sig commit)
    hashes each triple once, not twice."""
    q = _QUEUE
    if q is None or q.cache is None:
        return None
    result = q.cache.lookup(
        key if key is not None else cache_key(pub, msg, sig)
    )
    _crypto_metrics().verify_queue_spec_cache.labels(
        result="hit" if result is not None else "miss"
    ).inc()
    return result


def record_result(
    pub: bytes, msg: bytes, sig: bytes, ok: bool,
    key: bytes | None = None,
) -> None:
    """Feed a synchronously obtained verdict into the cache so repeat
    verifications (evidence re-checks, light-client retries) skip the
    launch.  ``key`` as in ``cached_result``."""
    q = _QUEUE
    if q is not None and q.cache is not None:
        q.cache.store(
            key if key is not None else cache_key(pub, msg, sig),
            bool(ok),
        )


def _verify_inline(q: VerifyQueue | None, items) -> list[bool]:
    """The pre-queue synchronous path, cache-aware: speculated triples
    resolve from the cache, fresh verdicts feed it (True only) so
    ``verify_commit`` still hits even for inline-verified votes."""
    out: list[bool] = []
    for pk, msg, sig in items:
        key = None
        if q is not None:
            pkb = pk.bytes()
            key = cache_key(pkb, msg, sig)
            if cached_result(pkb, msg, sig, key=key) is True:
                out.append(True)
                continue
        ok = pk.verify_signature(msg, sig)
        if key is not None and ok:
            record_result(pkb, msg, sig, ok, key=key)
        out.append(ok)
    return out


def verify_or_fallback(
    items, priority: str = PRIORITY_CONSENSUS,
    timeout: float = DEFAULT_WAIT_S,
) -> list[bool]:
    """Verify ``(pub_key, msg, sig)`` tuples through the queue as ONE
    batched submission, with the strict synchronous fallback: any
    queue problem (not installed, draining, failed batch, timeout)
    degrades that item to the exact ``pub_key.verify_signature`` call
    the caller made before the queue existed.

    Consensus-priority requests NEVER park behind in-flight work:
    when the queue is busy (a prefetch launch on the device, buffers
    queued), a live vote's couple of signatures verify inline — the
    pre-queue latency — and the verdicts still land in the
    speculative cache.  Preemption reorders queued buffers; it cannot
    interrupt a launch, so waiting here could cost a full prefetch
    launch wall on the consensus hot path."""
    q = _QUEUE
    if q is None:
        return [
            pk.verify_signature(msg, sig) for pk, msg, sig in items
        ]
    if priority == PRIORITY_CONSENSUS and q.busy():
        return _verify_inline(q, items)
    try:
        futs = q.submit_many(items, priority)
    except QueueUnavailable:
        return _verify_inline(q, items)
    out: list[bool] = []
    # one SHARED deadline across the whole submission: the futures
    # resolve together (one batch), so per-future timeouts would
    # multiply a wedged launcher's stall by len(items) — with the
    # VoteSet mutex held, in the worst caller
    deadline = time.monotonic() + timeout
    for (pk, msg, sig), fut in zip(items, futs):
        try:
            out.append(
                fut.result(max(0.0, deadline - time.monotonic()))
            )
        except QueueUnavailable:
            out.append(pk.verify_signature(msg, sig))
    return out


def checktx_verify_or_fallback(
    items, timeout: float = DEFAULT_WAIT_S,
) -> tuple[list[bool], int]:
    """Mempool admission: verify ``(pub_key, msg, sig)`` tuples through
    the queue's low-priority ``ingest`` lane — the micro-batcher
    coalesces concurrent CheckTx calls into single DispatchLadder
    launches — with the same STRICT sync fallback the vote path has:
    queue off, draining, a failed batch, or a wait timeout degrades to
    the inline ``pub_key.verify_signature`` call, never a stall and
    never a dropped tx.

    Unlike consensus, ingest callers DO park behind in-flight work
    (no ``busy()`` bypass): admission is latency-tolerant by design,
    and waiting is what lets the accumulator fill.  Verdicts land in
    the speculative cache, so a tx re-submitted across peers (or hit
    again at recheck) resolves without a second launch.

    Returns ``(results, n_inline)`` — how many of the items actually
    degraded to the inline path, so the caller's batched/inline route
    metrics report what verified each signature, not what was merely
    attempted."""
    q = _QUEUE
    if q is None:
        return _verify_inline(None, items), len(items)
    try:
        futs = q.submit_many(items, PRIORITY_INGEST)
    except QueueUnavailable:
        return _verify_inline(q, items), len(items)
    out: list[bool] = []
    n_inline = 0
    # one shared deadline, same rationale as verify_or_fallback
    deadline = time.monotonic() + timeout
    for (pk, msg, sig), fut in zip(items, futs):
        try:
            out.append(
                fut.result(max(0.0, deadline - time.monotonic()))
            )
        except QueueUnavailable:
            out.append(pk.verify_signature(msg, sig))
            n_inline += 1
    return out, n_inline


def light_verify_or_fallback(
    items, timeout: float = DEFAULT_WAIT_S,
) -> tuple[list[bool], int]:
    """Light-client header serving: verify ``(pub_key, msg, sig)``
    tuples through the ``light_client`` lane — the shared micro-batcher
    coalesces CONCURRENT header syncs into single DispatchLadder
    launches — with the same STRICT sync fallback and
    ``(results, n_inline)`` contract as ``checktx_verify_or_fallback``.
    Light callers, like ingest, DO park behind in-flight work: serving
    latency is bulk-tolerant, and waiting is what fills the batch."""
    q = _QUEUE
    if q is None:
        return _verify_inline(None, items), len(items)
    try:
        futs = q.submit_many(items, PRIORITY_LIGHT)
    except QueueUnavailable:
        return _verify_inline(q, items), len(items)
    out: list[bool] = []
    n_inline = 0
    deadline = time.monotonic() + timeout
    for (pk, msg, sig), fut in zip(items, futs):
        try:
            out.append(
                fut.result(max(0.0, deadline - time.monotonic()))
            )
        except QueueUnavailable:
            out.append(pk.verify_signature(msg, sig))
            n_inline += 1
    return out, n_inline


# -- the submission-lane context (types/validation routing) --------------

_LANE_TLS = threading.local()


class submission_lane:
    """While active on this thread, ``types/validation._verify`` routes
    its batch signature verification through the queue at the given
    priority instead of building a synchronous batch verifier — the
    seam the light serving plane (light/serve.py) uses so that a full
    ``verify_commit_light`` keeps its tally/address semantics while
    its crypto rides the ``light_client`` micro-batcher.  ``_verify``
    captures the lane ONCE at entry (its key-type groups may run on
    executor threads where this thread-local is invisible).  Nests
    safely; no-op when no queue is installed."""

    def __init__(self, priority: str) -> None:
        if priority not in _PRIORITIES:
            raise ValueError(f"unknown priority {priority!r}")
        self._priority = priority
        self._prev: str | None = None

    def __enter__(self) -> "submission_lane":
        self._prev = getattr(_LANE_TLS, "lane", None)
        _LANE_TLS.lane = self._priority
        return self

    def __exit__(self, *exc) -> None:
        _LANE_TLS.lane = self._prev


def active_submission_lane() -> str | None:
    """The lane a ``submission_lane`` context has pinned on this
    thread, or None — None also when no queue is accepting, so the
    validation path degrades to its exact pre-lane behavior."""
    lane = getattr(_LANE_TLS, "lane", None)
    if lane is None:
        return None
    q = _QUEUE
    if q is None or not q.accepting():
        return None
    return lane


def submit_speculative(items, priority: str) -> list | None:
    """Submit ``(pub_key, msg, sig)`` tuples whose verdicts are wanted
    in the speculative cache, not by the caller: one future per item,
    for a caller that sleeps until its batch has been answered (the
    light client's verify-ahead), or None when the queue is down —
    speculation is never worth an error."""
    q = _QUEUE
    if q is None:
        return None
    try:
        return q.submit_many(items, priority)
    except QueueUnavailable:
        return None


def submit_prefetch(items) -> int:
    """Fire-and-forget prefetch submission (blocksync replay, the
    consensus proposal's last_commit): results land in the speculative
    cache for the verify_commit that follows.  Returns the number of
    requests actually enqueued (0 when the queue is down — prefetch is
    never worth an error)."""
    if submit_speculative(items, PRIORITY_PREFETCH) is None:
        return 0
    return len(items)


__all__ = [
    "DEFAULT_CHECKTX_BATCH",
    "DEFAULT_CHECKTX_WAIT_MS",
    "DEFAULT_LIGHT_BATCH",
    "DEFAULT_LIGHT_WAIT_MS",
    "DEFAULT_MAX_BATCH",
    "DEFAULT_PREFETCH_DEPTH",
    "DEFAULT_SPEC_CACHE_CAP",
    "PRIORITY_CONSENSUS",
    "PRIORITY_INGEST",
    "PRIORITY_LIGHT",
    "PRIORITY_PREFETCH",
    "QueueUnavailable",
    "active_submission_lane",
    "checktx_batch_from_env",
    "checktx_verify_or_fallback",
    "checktx_wait_ms_from_env",
    "light_batch_from_env",
    "light_verify_or_fallback",
    "light_wait_ms_from_env",
    "SpeculativeCache",
    "VerifyFuture",
    "VerifyQueue",
    "cache_key",
    "cached_result",
    "install_queue",
    "lane_batch_target",
    "prefetch_depth_from_env",
    "record_result",
    "spec_cache_capacity_from_env",
    "speculation_active",
    "submission_lane",
    "submit_prefetch",
    "submit_speculative",
    "verify_or_fallback",
]

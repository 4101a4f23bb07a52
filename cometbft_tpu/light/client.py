"""Light client (reference: light/client.go:133).

Verifies headers against a trusted root using sequential or skipping
(bisection) verification, cross-checks every newly verified header
against witness providers (fork detection, light/detector.go), and
persists trusted blocks.  The 10k-header verification benchmark
(BASELINE.json) exercises this plane's batch-verify calls.
"""

from __future__ import annotations

from collections import deque
from collections.abc import Callable, Iterable, Iterator
from dataclasses import dataclass
from fractions import Fraction

from cometbft_tpu.crypto import verify_queue as _vq
from cometbft_tpu.light.provider import Provider, ProviderError
from cometbft_tpu.light.store import LightStore
from cometbft_tpu.light.verifier import (
    DEFAULT_TRUST_LEVEL,
    ErrNewValSetCantBeTrusted,
    VerificationError,
    verify as _verify,
    verify_adjacent,
)
from cometbft_tpu.types.evidence import LightClientAttackEvidence
from cometbft_tpu.types.light_block import LightBlock, LightBlockError
from cometbft_tpu.types.validation import commit_check_triples
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.time import now_ns
from cometbft_tpu.utils.trace import TRACER as _tracer

SEQUENTIAL = "sequential"   # client.go:44
SKIPPING = "skipping"       # client.go:50

DEFAULT_PRUNING_SIZE = 1000  # client.go:60
DEFAULT_MAX_CLOCK_DRIFT_NS = 10 * 10**9


class LightClientError(Exception):
    pass


class ErrLightClientAttack(LightClientError):
    """(light/errors.go ErrLightClientAttack) — divergence between the
    primary and a witness was detected and evidence submitted."""


class NoWitnessesError(LightClientError):
    pass


@dataclass(frozen=True)
class TrustOptions:
    """(light/client.go:77 TrustOptions) — the subjective root of trust."""

    period_ns: int
    height: int
    hash: bytes

    def validate(self) -> None:
        if self.period_ns <= 0:
            raise LightClientError("trusting period must be positive")
        if self.height <= 0:
            raise LightClientError("trust height must be positive")
        if len(self.hash) != 32:
            raise LightClientError("trust hash must be 32 bytes")


#: what a target's verdict can be besides "verified": the primary had
#: no such block, the block is malformed, the verifier rejected it,
#: the client could not place it.  (A detected attack is not a
#: verdict on one header: it ends the catch-up.)
_VERDICTS = (
    ProviderError, LightBlockError, VerificationError, LightClientError,
)

#: light-lane batches a catch-up keeps fetched and submitted ahead of
#: the header it is verifying — the queue's own double buffer: one
#: the launcher holds, one the collector prepares
_AHEAD_BATCHES = 2


class _VerifyAhead:
    """One catch-up's look-ahead (``Client.verify_light_blocks_at_heights``).

    Fetches the targets in order, up to D ahead of the one being
    verified, and submits the signatures their commit checks will look
    at (``commit_check_triples`` to past two thirds of each block's
    OWN set, which its header binds; with the sets in agreement the
    trusting check's signatures are among them) to the verify queue's
    ``light_client`` lane, cut into submissions of exactly the lane's
    batch target: each releases at once and fills its launch, a
    header's signatures riding two launches where the cut falls inside
    it.  Only the last targets' remainder goes out short and waits the
    lane's deadline.  D = ``_AHEAD_BATCHES`` batch targets over the
    signatures a header needs (21 for 150 equal validators and the
    default 1,024), so the caller verifies header n from cached
    verdicts while the queue prepares and launches the batch that
    holds n + D.

    Nothing here judges a header: the cache holds positive verdicts
    only, so a signature that failed, a queue that stopped, a commit
    that does not line up all leave the verifier's strict path to
    decide as it would without a look-ahead.  ``target`` None: no
    look-ahead, the targets are fetched one at a time."""

    def __init__(self, client: "Client", heights: list[int],
                 target: int | None) -> None:
        self._client = client
        self._todo = deque(heights)
        #: fetched and not yet handed out:
        #: (height, LightBlock | the fetch's exception, already trusted)
        self._fetched: deque[tuple] = deque()
        self._target = target
        #: signatures the last header built needed; sizes D
        self._per_header: int | None = None
        #: triples built and not yet submitted, the first of them at
        #: ``_base`` in the catch-up's stream of triples
        self._triples: list[tuple] = []
        self._base = 0
        #: (stream index of a header's last triple, its height), for
        #: the headers whose last triple is not submitted yet
        self._marks: deque[tuple[int, int]] = deque()
        self._last_future: dict[int, _vq.VerifyFuture] = {}

    def next(self) -> tuple:
        """-> (height, LightBlock | exception, already trusted, the
        future of the last signature submitted for it or None)."""
        self._top_up()
        height, got, trusted = self._fetched.popleft()
        return height, got, trusted, self._last_future.pop(height, None)

    def _depth(self) -> int:
        """D: how many targets may be fetched and not yet handed out."""
        if self._target is None or self._per_header is None:
            return 1  # no look-ahead; or the first header, which sizes it
        return -(-_AHEAD_BATCHES * self._target // self._per_header)

    def _top_up(self) -> None:
        # twice at the start: the first header's signature count sets D
        while self._todo and len(self._fetched) < self._depth():
            fresh = self._fetch_to_depth()
            if self._target is not None and fresh:
                self._submit_ahead(fresh)

    def _fetch_to_depth(self) -> list[LightBlock]:
        """-> the light blocks fetched now that still need verifying."""
        fresh = []
        while self._todo and len(self._fetched) < self._depth():
            height = self._todo.popleft()
            got = self._client.store.get(height)
            trusted = got is not None
            if not trusted:
                try:
                    got = self._client._fetch(height)
                except _VERDICTS as exc:
                    got = exc
                else:
                    fresh.append(got)
            self._fetched.append((height, got, trusted))
        return fresh

    def _submit_ahead(self, lbs: list[LightBlock]) -> None:
        with _tracer.span(
            "light/verify_ahead", cat="light", headers=len(lbs),
        ) as sp:
            built = 0
            for lb in lbs:
                vals = lb.validator_set
                triples = commit_check_triples(
                    self._client.chain_id, vals, lb.commit,
                    vals.total_voting_power() * 2 // 3,
                )
                self._per_header = max(1, len(triples or ()))
                if not triples:
                    continue
                built += len(triples)
                self._triples.extend(triples)
                self._marks.append(
                    (self._base + len(self._triples) - 1, lb.height)
                )
            while self._target and len(self._triples) >= self._target:
                self._submit(self._target)
            if not self._todo:
                self._submit(len(self._triples))
            sp.set(sigs=built)

    def _submit(self, n: int) -> None:
        if not n or self._target is None:
            return
        chunk, self._triples = self._triples[:n], self._triples[n:]
        futures = _vq.submit_speculative(chunk, _vq.PRIORITY_LIGHT)
        if futures is None:
            # the queue went away: the rest of the catch-up verifies
            # on the strict path, one header at a time
            self._target = None
            self._triples.clear()
            self._marks.clear()
            return
        end = self._base + n
        while self._marks and self._marks[0][0] < end:
            index, height = self._marks.popleft()
            self._last_future[height] = futures[index - self._base]
        self._base = end


class Client:
    """(light/client.go:133 Client)"""

    def __init__(
        self,
        chain_id: str,
        trust_options: TrustOptions | None,
        primary: Provider,
        witnesses: list[Provider],
        trusted_store: LightStore,
        verification_mode: str = SKIPPING,
        trust_level: Fraction = DEFAULT_TRUST_LEVEL,
        trust_period_ns: int = 7 * 24 * 3600 * 1_000_000_000,
        max_clock_drift_ns: int = DEFAULT_MAX_CLOCK_DRIFT_NS,
        pruning_size: int = DEFAULT_PRUNING_SIZE,
        logger: Logger | None = None,
    ):
        if trust_options is not None:
            trust_options.validate()
        self.chain_id = chain_id
        self.trust_options = trust_options
        # the trusting period outlives the root of trust: resume mode
        # (trust_options=None, NewClientFromTrustedStore) still expires
        # stored headers against it
        self.trust_period_ns = (
            trust_options.period_ns if trust_options is not None
            else trust_period_ns
        )
        self.primary = primary
        self.witnesses = list(witnesses)
        self.store = trusted_store
        self.mode = verification_mode
        self.trust_level = trust_level
        self.max_clock_drift_ns = max_clock_drift_ns
        self.pruning_size = pruning_size
        self.logger = logger or default_logger().with_fields(module="light")
        self._mtx = cmtsync.Mutex()
        self._initialize()

    # -- initialization (client.go:265 initializeWithTrustOptions) -------

    def _initialize(self) -> None:
        existing = self.store.latest()
        if existing is not None:
            return  # already have a trust root (client.go checkTrustedHeaderUsingOptions simplified: keep store)
        if self.trust_options is None:
            # NewClientFromTrustedStore semantics (light/client.go:233,
            # cmd light.go:189 "continue from latest state"): without a
            # root of trust there is nothing subjective to anchor to
            raise LightClientError(
                "trusted store is empty and no trust options given "
                "(supply --trusted-height/--trusted-hash on first run)"
            )
        lb = self.primary.light_block(self.trust_options.height)
        lb.validate_basic(self.chain_id)
        if lb.hash() != self.trust_options.hash:
            raise LightClientError(
                f"primary's header hash {lb.hash().hex()[:12]} != "
                f"trust hash {self.trust_options.hash.hex()[:12]}"
            )
        # +2/3 of ITS validator set signed it
        from cometbft_tpu.light.verifier import _verify_self_commit

        _verify_self_commit(lb, self.chain_id)
        self._compare_with_witnesses(lb)
        self.store.save(lb)

    # -- public API -------------------------------------------------------

    def trusted_light_block(self, height: int) -> LightBlock | None:
        return self.store.get(height)

    def latest_trusted(self) -> LightBlock | None:
        return self.store.latest()

    def update(self, now: int | None = None) -> LightBlock | None:
        """Verify the primary's latest header (client.go:486 Update)."""
        latest = self.primary.light_block(0)
        trusted = self.store.latest()
        if trusted is not None and latest.height <= trusted.height:
            return None
        return self.verify_light_block_at_height(latest.height, now)

    def verify_light_block_at_height(
        self, height: int, now: int | None = None
    ) -> LightBlock:
        """(client.go:473 VerifyLightBlockAtHeight) — a catch-up of
        one target: nothing is ahead of it, so nothing is submitted
        ahead and both commit checks run where they always have."""
        ((_, lb, err),) = self.verify_light_blocks_at_heights([height], now)
        if err is not None:
            raise err
        return lb

    def verify_light_blocks_at_heights(
        self,
        heights: Iterable[int],
        now: int | Callable[[LightBlock], int] | None = None,
    ) -> Iterator[tuple[int, LightBlock | None, Exception | None]]:
        """Verify a known, strictly ascending list of heights — a
        relayer's or a wallet's catch-up — and yield, in order, one
        ``(height, light block, None)`` for each target verified and
        trusted, or ``(height, None, error)`` for each rejected: a
        rejected target is never stored, and the catch-up goes on
        from the last trusted header to the next target.  With more
        than one target and a verify queue running, the targets are
        fetched and their commits' signatures submitted to the
        queue's ``light_client`` lane ahead of their verification
        (:class:`_VerifyAhead`); each target is then verified exactly
        as ``verify_light_block_at_height`` verifies it.

        ``now`` is the time each target is verified at: None for the
        clock, an integer for all targets, or a function of the
        fetched light block.  Nothing runs until the iterator is
        advanced, and it may be left unfinished.  A detected attack
        (``ErrLightClientAttack``) ends the catch-up by raising."""
        heights = list(heights)
        if any(h <= 0 for h in heights):
            raise LightClientError("height must be positive")
        if any(b <= a for a, b in zip(heights, heights[1:])):
            raise LightClientError("heights must be strictly ascending")
        if callable(now):
            now_of = now
        else:
            def now_of(_lb):
                return now_ns() if now is None else now
        return self._catch_up(heights, now_of)

    def _catch_up(
        self, heights: list[int], now_of: Callable[[LightBlock], int]
    ) -> Iterator[tuple]:
        ahead = _VerifyAhead(
            self, heights,
            _vq.lane_batch_target(_vq.PRIORITY_LIGHT)
            if len(heights) > 1 else None,
        )
        for _ in heights:
            with _tracer.span(
                "light/verify", cat="light", thread_clock=True,
            ) as root:
                height, got, trusted, future = ahead.next()
                root.set(height=height)
                lb = err = None
                try:
                    if isinstance(got, Exception):
                        raise got
                    if not trusted:
                        self._await_ahead(future)
                        with self._mtx:
                            self._verify_light_block(
                                got, now_of(got), root
                            )
                    lb = got
                except ErrLightClientAttack:
                    raise
                except _VERDICTS as exc:
                    err = exc
            yield height, lb, err

    def _fetch(self, height: int) -> LightBlock:
        with _tracer.span("light/fetch", cat="light", height=height):
            lb = self.primary.light_block(height)
            lb.validate_basic(self.chain_id)
            if lb.height != height:
                raise LightClientError(
                    f"primary returned height {lb.height}, wanted {height}"
                )
            return lb

    @staticmethod
    def _await_ahead(future) -> None:
        """Asleep until the queue has answered for the last signature
        submitted ahead for this target.  Whatever the answer, or
        none (the queue stopped, the batch failed), the verifier
        decides: it finds positive verdicts cached or verifies."""
        if future is None:
            return
        with _tracer.span(
            "light/ahead_wait", cat="light", thread_clock=True,
        ):
            try:
                future.result()
            except _vq.QueueUnavailable:
                pass

    def verify_header(self, header, now: int | None = None) -> LightBlock:
        """Verify a caller-supplied header by fetching its light block
        (client.go:520 VerifyHeader)."""
        lb = self.verify_light_block_at_height(header.height, now)
        if lb.hash() != header.hash():
            raise LightClientError(
                "header differs from the verified header at that height"
            )
        return lb

    # -- verification strategies -----------------------------------------

    def _verify_light_block(self, new: LightBlock, now: int, root) -> None:
        """``root``: the target's ``light/verify`` span."""
        with _tracer.span("light/store", cat="light"):
            served = self.store.stats()["anchor_memory"]
            trusted = self.store.light_block_before(new.height)
            served = self.store.stats()["anchor_memory"] - served
        root.set(anchor="memory" if served else "store")
        if trusted is None:
            # target below our first trusted block: backwards verification
            first = self.store.first()
            if first is None:
                raise LightClientError("store has no trust root")
            root.set(mode="backwards", trusted_height=first.height)
            self._verify_backwards(first, new)
            self._finalize_verified(new)
            return
        root.set(
            mode="adjacent" if new.height == trusted.height + 1
            else "non_adjacent",
            trusted_height=trusted.height,
        )
        if self.mode == SEQUENTIAL:
            self._verify_sequential(trusted, new, now)
        else:
            self._verify_skipping(trusted, new, now)
        self._finalize_verified(new)

    def _finalize_verified(self, new: LightBlock) -> None:
        with _tracer.span("light/witness", cat="light"):
            self._compare_with_witnesses(new)
        with _tracer.span("light/store", cat="light"):
            self.store.save(new)
            if self.store.size() > self.pruning_size:
                self.store.prune(self.pruning_size)

    def _verify_sequential(
        self, trusted: LightBlock, new: LightBlock, now: int
    ) -> None:
        """(client.go:612 verifySequential) — fetch and verify every
        intermediate header."""
        current = trusted
        for h in range(trusted.height + 1, new.height + 1):
            nxt = (
                new if h == new.height else self.primary.light_block(h)
            )
            nxt.validate_basic(self.chain_id)
            verify_adjacent(
                current, nxt, self.chain_id,
                self.trust_period_ns, now,
                self.max_clock_drift_ns,
            )
            if h != new.height:
                self.store.save(nxt)
            current = nxt

    def _verify_skipping(
        self, trusted: LightBlock, new: LightBlock, now: int
    ) -> None:
        """(client.go:705 verifySkipping) — bisection: try the jump; on
        insufficient trusted power, verify the midpoint first."""
        verified = [trusted]
        pending = [new]
        depth_guard = 0
        while pending:
            depth_guard += 1
            if depth_guard > 10_000:
                raise LightClientError("bisection did not converge")
            base = verified[-1]
            target = pending[-1]
            try:
                _verify(
                    base, target, self.chain_id,
                    self.trust_period_ns, now,
                    self.trust_level, self.max_clock_drift_ns,
                )
                verified.append(target)
                pending.pop()
                if target.height != new.height:
                    self.store.save(target)
            except ErrNewValSetCantBeTrusted:
                pivot = (base.height + target.height) // 2
                if pivot in (base.height, target.height):
                    raise LightClientError(
                        "cannot bisect further — chain not verifiable "
                        "within the trusting period"
                    ) from None
                mid = self.primary.light_block(pivot)
                mid.validate_basic(self.chain_id)
                pending.append(mid)

    def _verify_backwards(self, trusted: LightBlock, new: LightBlock) -> None:
        """(client.go:790 backwards) — hash-link each header back from
        the trusted block to the target."""
        current = trusted
        for h in range(trusted.height - 1, new.height - 1, -1):
            prev = new if h == new.height else self.primary.light_block(h)
            prev.validate_basic(self.chain_id)
            if current.header.last_block_id.hash != prev.hash():
                raise VerificationError(
                    f"header {h} does not hash-link to header {h + 1}"
                )
            current = prev

    # -- fork detection (light/detector.go) ------------------------------

    def _make_attack_evidence(
        self, conflicting: LightBlock, common: LightBlock, trusted: LightBlock
    ) -> LightClientAttackEvidence:
        """(detector.go newLightClientAttackEvidence) — ``common`` is
        the latest trusted block both sides agree on; ``trusted`` is the
        header we believe at the conflicting height.  Total power and
        the byzantine list come from the common-height validator set and
        the actual conflicting signatures, so full nodes' checks pass."""
        from dataclasses import replace

        ev = LightClientAttackEvidence(
            conflicting_block=conflicting,
            common_height=common.height,
            total_voting_power=common.validator_set.total_voting_power(),
            timestamp_ns=common.time_ns,
        )
        byz = ev.get_byzantine_validators(
            common.validator_set, trusted.signed_header
        )
        return replace(
            ev, byzantine_validators=tuple(v.address for v in byz)
        )

    def _compare_with_witnesses(self, lb: LightBlock) -> None:
        """(detector.go:33 detectDivergence) — any witness serving a
        different header at this height implies an attack on one side;
        we can't tell which, so build evidence against each side and
        report it to the other."""
        for witness in self.witnesses:
            try:
                w_lb = witness.light_block(lb.height)
            except Exception:  # noqa: BLE001 — witness down: skip
                continue
            if w_lb.hash() == lb.hash():
                continue
            common = self.store.light_block_before(lb.height)
            if common is None:
                self.logger.error(
                    "divergence detected but no trusted block below the "
                    "conflicting height — cannot build attack evidence",
                    height=lb.height,
                )
            else:
                # witness's block is the fraud → tell the primary
                ev_w = self._make_attack_evidence(w_lb, common, lb)
                # primary's block is the fraud → tell the witness
                ev_p = self._make_attack_evidence(lb, common, w_lb)
                for target, ev in ((self.primary, ev_w), (witness, ev_p)):
                    try:
                        target.report_evidence(ev)
                    except Exception:  # noqa: BLE001
                        pass
            raise ErrLightClientAttack(
                f"witness header {w_lb.hash().hex()[:12]} conflicts with "
                f"primary {lb.hash().hex()[:12]} at height {lb.height}"
            )


__all__ = [
    "Client",
    "ErrLightClientAttack",
    "LightClientError",
    "NoWitnessesError",
    "SEQUENTIAL",
    "SKIPPING",
    "TrustOptions",
]

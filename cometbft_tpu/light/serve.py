"""Batched light-client serving plane — the millions-of-users workload
(ROADMAP item 1; "Practical Light Clients for Committee-Based
Blockchains", arXiv:2410.03347, defines the traffic shape: huge
numbers of light clients concurrently syncing header ranges).

A full node serving light clients re-verifies each requested header's
commit before vouching for it.  Naively that is one synchronous batch
launch per header per client — 10k clients syncing the same 100-header
range would pay 1M launches for 100 headers' worth of distinct work.
This module removes both multiplicities:

- **Cross-client coalescing.**  ``LightHeaderServer.sync_range``
  verifies commits through ``types/validation`` inside a
  ``verify_queue.submission_lane("light_client")`` context, so the
  signatures of CONCURRENT requests ride the VerifyQueue's
  ``light_client`` lane and its micro-batcher
  (``CMT_TPU_LIGHT_BATCH`` / ``CMT_TPU_LIGHT_WAIT_MS``) coalesces
  them into single DispatchLadder launches — strictly preempted by
  consensus and prefetch, so serving load can never delay a live
  vote.  BLS aggregate commits (types/block.py) verify with one
  pairing-product through the same validation seam.

- **Repeat-sync elimination.**  Verified headers land in the
  :class:`HeaderRangeCache` — a bounded LRU over heights, trusting-
  period aware — and the speculative-result cache keeps the
  underlying signature verdicts, so a fully cached repeat sync
  performs ZERO launches (pinned by tests/test_light_serve.py).

Observability: the ``light_*`` family (metrics/LightMetrics —
cache hit/miss/eviction, serve latency/volume) next to the queue's
``crypto_verify_queue_*{priority="light_client"}`` series; env knobs
validated fail-loudly via the shared ring-size contract.
"""

from __future__ import annotations

import time
from collections import OrderedDict

from cometbft_tpu.metrics import light_metrics as _light_metrics
from cometbft_tpu.crypto import verify_queue as _vq
from cometbft_tpu.types.block import BlockID
from cometbft_tpu.types.validation import (
    commit_check_triples,
    verify_commit_light,
)
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.flight import FLIGHT
from cometbft_tpu.utils.flight import ring_size_from_env as _int_env
from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.time import now_ns
from cometbft_tpu.utils.trace import TRACER as _tracer

DEFAULT_CACHE_CAP = 8192
#: default trusting period: 7 days (light/client.py default)
DEFAULT_TRUST_PERIOD_NS = 7 * 24 * 3600 * 1_000_000_000
#: largest height span one sync request may ask for — a bound, not a
#: knob: an unbounded range is a griefing vector (one request pinning
#: the serving thread for the whole chain)
MAX_RANGE = 1024


def header_cache_capacity_from_env() -> int:
    """Verified-header cache capacity in headers (>= 16; smaller
    caches thrash on a single client's range and the repeat-sync
    elimination silently degrades to all-miss)."""
    return _int_env("CMT_TPU_LIGHT_CACHE", DEFAULT_CACHE_CAP, 16)


class LightServeError(Exception):
    pass


@cmtsync.guarded
class HeaderRangeCache:
    """Bounded LRU of height -> (verified header hash, header time).

    An entry means "this exact header at this height carried a valid
    +2/3 commit of its own validator set" — a pure fact, EXCEPT that
    light clients only accept headers inside their trusting period,
    so entries expire ``trust_period_ns`` after the header's own
    timestamp: serving a stale hit would vouch for a header the
    client's own rules reject (trust-period-aware eviction, counted
    under reason="expired"; capacity pressure evicts oldest-used
    first under reason="lru").  Reads and writes are mutex-guarded —
    the serving plane consults this from many RPC threads at once,
    hammered under CMT_TPU_RACE=1 in tests/test_light_serve.py."""

    _GUARDED_BY = {"_map": "_mtx"}

    def __init__(
        self,
        capacity: int | None = None,
        trust_period_ns: int = DEFAULT_TRUST_PERIOD_NS,
        clock=now_ns,
    ) -> None:
        self.capacity = (
            capacity if capacity is not None
            else header_cache_capacity_from_env()
        )
        if self.capacity < 1:
            raise ValueError("header cache capacity must be >= 1")
        if trust_period_ns <= 0:
            raise ValueError("trusting period must be positive")
        self.trust_period_ns = trust_period_ns
        self._clock = clock
        self._mtx = cmtsync.Mutex()
        self._map: OrderedDict[int, tuple[bytes, int]] = OrderedDict()

    def get(self, height: int, now: int | None = None) -> bytes | None:
        """The verified header hash at ``height``, or None on miss or
        trust-period expiry (the expired entry is evicted)."""
        lm = _light_metrics()
        now = self._clock() if now is None else now
        expired = False
        with self._mtx:
            ent = self._map.get(height)
            if ent is not None:
                if now > ent[1] + self.trust_period_ns:
                    del self._map[height]
                    expired = True
                    ent = None
                else:
                    self._map.move_to_end(height)
        if expired:
            lm.header_cache_evictions.labels(reason="expired").inc()
            lm.header_cache_entries.set(len(self))
        if ent is None:
            lm.header_cache.labels(result="miss").inc()
            return None
        lm.header_cache.labels(result="hit").inc()
        return ent[0]

    def put(
        self, height: int, header_hash: bytes, header_time_ns: int
    ) -> None:
        lm = _light_metrics()
        evicted = 0
        with self._mtx:
            self._map[height] = (bytes(header_hash), header_time_ns)
            self._map.move_to_end(height)
            while len(self._map) > self.capacity:
                self._map.popitem(last=False)
                evicted += 1
            size = len(self._map)
        if evicted:
            lm.header_cache_evictions.labels(reason="lru").inc(evicted)
        lm.header_cache_entries.set(size)

    def __len__(self) -> int:
        with self._mtx:
            return len(self._map)

    def clear(self) -> None:
        with self._mtx:
            self._map.clear()
        _light_metrics().header_cache_entries.set(0)

    def stats(self) -> dict:
        with self._mtx:
            return {
                "entries": len(self._map),
                "capacity": self.capacity,
                "trust_period_ns": self.trust_period_ns,
            }


class LightHeaderServer:
    """The serving plane (module docstring): verified header ranges
    from a light-block :class:`~cometbft_tpu.light.provider.Provider`
    (a node's own stores via ``NodeProvider`` in production, a
    fixture provider in benches), with the header cache in front and
    the ``light_client`` verify-queue lane underneath."""

    def __init__(
        self,
        chain_id: str,
        provider,
        cache: HeaderRangeCache | None = None,
        trust_period_ns: int = DEFAULT_TRUST_PERIOD_NS,
        logger: Logger | None = None,
    ) -> None:
        self.chain_id = chain_id
        self.provider = provider
        self.cache = cache or HeaderRangeCache(
            trust_period_ns=trust_period_ns
        )
        self.logger = logger or default_logger().with_fields(
            module="light.serve"
        )

    def sync_range(
        self,
        from_height: int,
        to_height: int,
        now: int | None = None,
    ) -> dict:
        """Serve heights [from_height, to_height]: each header's own
        +2/3 commit is verified (``verify_commit_light`` — aggregate
        or batch by what the commit carries) unless the cache already
        vouches for that height, and every freshly verified header is
        cached.  Raises LightServeError on bad ranges or missing
        blocks; crypto failures propagate as the validation errors
        they are."""
        if from_height < 1 or to_height < from_height:
            raise LightServeError(
                f"bad range [{from_height}, {to_height}]"
            )
        if to_height - from_height + 1 > MAX_RANGE:
            raise LightServeError(
                f"range wider than {MAX_RANGE} headers"
            )
        lm = _light_metrics()
        t0 = time.perf_counter()
        now = now_ns() if now is None else now
        headers: list[dict] = []
        hits = 0
        try:
            with _tracer.span(
                "light/serve_range", cat="light",
                from_height=from_height, to_height=to_height,
            ) as sp:
                # the lane context makes validation route signature
                # batches through the queue's light_client
                # micro-batcher (no queue installed -> exact sync
                # behavior).  Two phases: collect every uncached
                # height's light block first and PRIME the lane with
                # ALL their signatures as one submission — a lone
                # client cold-syncing a wide range fills the batch
                # from its own work and pays the accumulation
                # deadline once, not once per header — then verify
                # each height (phase-1 verdicts answer from the
                # speculative cache).
                with _vq.submission_lane(_vq.PRIORITY_LIGHT):
                    entries: list[tuple] = []
                    for h in range(from_height, to_height + 1):
                        cached = self.cache.get(h, now)
                        if cached is not None:
                            hits += 1
                            entries.append((h, cached, None))
                        else:
                            entries.append(
                                (h, None, self._fetch_height(h))
                            )
                    self._prime_lane(
                        [lb for _, _, lb in entries if lb is not None]
                    )
                    for h, cached_hash, lb in entries:
                        if lb is None:
                            headers.append(
                                {"height": h,
                                 "hash": cached_hash.hex(),
                                 "cached": True}
                            )
                        else:
                            headers.append(self._verify_block(lb))
                sp.set(headers=len(headers), cache_hits=hits)
        except Exception:
            lm.serve_requests.labels(result="error").inc()
            raise
        wall = time.perf_counter() - t0
        lm.serve_requests.labels(result="ok").inc()
        lm.serve_headers.inc(len(headers))
        lm.serve_seconds.observe(wall)
        return {
            "chain_id": self.chain_id,
            "from_height": from_height,
            "to_height": to_height,
            "headers": headers,
            "cache_hits": hits,
            "elapsed_ms": round(wall * 1e3, 3),
        }

    def _fetch_height(self, height: int):
        lb = self.provider.light_block(height)
        lb.validate_basic(self.chain_id)
        if lb.height != height:
            raise LightServeError(
                f"provider returned height {lb.height}, wanted {height}"
            )
        return lb

    def _prime_lane(self, lbs: list) -> None:
        """Phase 1: every uncached height's per-signature work rides
        the light lane as ONE submission (``light_verify_or_fallback``
        waits for the coalesced launch; verdicts land in the
        speculative cache, so phase 2's ``verify_commit_light`` is
        cache hits).  Well-formedness is NOT judged here — a
        commit that does not line up with its set primes nothing
        (``commit_check_triples``) and phase 2 reports the precise
        error.  Aggregate-covered signatures are skipped:
        their proof is the commit-level pairing, cached under its own
        key at first verification.  Primes every commit-flag
        signature where phase 2's early-break stops at +2/3 — a
        bounded overshoot that buys the single coalesced launch."""
        if not lbs or not _vq.speculation_active():
            return
        items = []
        for lb in lbs:
            items.extend(
                commit_check_triples(
                    self.chain_id, lb.validator_set, lb.commit
                ) or ()
            )
        if items:
            _vq.light_verify_or_fallback(items)

    def _verify_block(self, lb) -> dict:
        height = lb.height
        sh = lb.signed_header
        block_id = BlockID(
            hash=sh.header.hash(),
            part_set_header=sh.commit.block_id.part_set_header,
        )
        verify_commit_light(
            self.chain_id, lb.validator_set, block_id, sh.height,
            sh.commit,
        )
        self.cache.put(height, lb.hash(), lb.time_ns)
        FLIGHT.record(
            "light/header_verified", height=height,
            sigs=sh.commit.size(),
            aggregate=bool(sh.commit.agg_signature),
        )
        return {
            "height": height, "hash": lb.hash().hex(), "cached": False,
        }


__all__ = [
    "DEFAULT_CACHE_CAP",
    "DEFAULT_TRUST_PERIOD_NS",
    "HeaderRangeCache",
    "LightHeaderServer",
    "LightServeError",
    "MAX_RANGE",
    "header_cache_capacity_from_env",
]

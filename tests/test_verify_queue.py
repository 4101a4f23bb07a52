"""Pipelined verify-ahead queue tests (crypto/verify_queue.py).

Covers the ISSUE 8 acceptance set: the deterministic double-buffer
overlap proof with a gated fake launcher (buffer N+1's host prep
completes while buffer N's launch is in flight), speculative-hit/miss
equivalence against synchronous ``verify_commit`` (valid, tampered and
absent-validator commits), priority preemption ordering (consensus
batches launch ahead of queued prefetch batches), queue drain on stop,
zero steady-state retraces under a sealed CMT_TPU_JITGUARD on the
forced-8-device CPU mesh, the fail-loudly env validation, the
blocksync prefetch submission, and the ``bench.py --pipelined`` round
trip (``make pipeline-smoke`` runs the RoundTrip/Overlap/PipelinedBench
subset standalone).
"""

from __future__ import annotations

import os
import threading
import time
from dataclasses import replace
from types import SimpleNamespace

import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import verify_queue as vq
from cometbft_tpu.metrics import (
    CryptoMetrics,
    HealthMetrics,
    install_crypto_metrics,
    install_health_metrics,
)
from cometbft_tpu.types import PRECOMMIT_TYPE, VoteSet
from cometbft_tpu.types import validation
from cometbft_tpu.types.block import (
    BLOCK_ID_FLAG_ABSENT,
    CommitSig,
)
from cometbft_tpu.types.vote import Vote
from cometbft_tpu.types.vote_set import VoteSetError
from cometbft_tpu.utils.metrics import Registry

from tests.helpers import (
    CHAIN_ID,
    make_block_id,
    make_commit,
    make_val_set,
    signed_vote,
)


@pytest.fixture
def live_metrics():
    cm = CryptoMetrics(Registry())
    hm = HealthMetrics(Registry())
    install_crypto_metrics(cm)
    install_health_metrics(hm)
    yield cm, hm
    install_crypto_metrics(None)
    install_health_metrics(None)


@pytest.fixture
def queue_guard():
    """Whatever a test installs, the process-wide slot is clean
    after."""
    yield
    q = vq._installed()
    if q is not None and q.is_running():
        q.stop()
    vq.install_queue(None)


def _items(n: int, nkeys: int = 4, tag: bytes = b"vqt"):
    privs = [
        ed.priv_key_from_secret(tag + b"%d" % i) for i in range(nkeys)
    ]
    out = []
    for i in range(n):
        m = tag + b"-msg-%d" % i
        k = privs[i % nkeys]
        out.append((k.pub_key(), m, k.sign(m)))
    return out


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


class TestVerifyQueueRoundTrip:
    def test_round_trip_valid_and_tampered(self, live_metrics,
                                           queue_guard):
        q = vq.VerifyQueue()
        q.start()
        items = _items(8)
        futs = q.submit_many(items)
        assert all(f.result(30) for f in futs)
        pk, m, s = items[0]
        assert q.submit(pk, b"tampered", s).result(30) is False
        st = q.stats()
        assert st["launched_sigs"] == 9
        assert st["failed_batches"] == 0
        q.stop()

    def test_speculative_cache_resolves_repeat_without_launch(
        self, live_metrics, queue_guard
    ):
        q = vq.VerifyQueue()
        q.start()
        items = _items(4)
        [f.result(30) for f in q.submit_many(items)]
        launched = q.stats()["launched_sigs"]
        futs = q.submit_many(items)  # identical triples: all cache hits
        assert all(f.result(30) for f in futs)
        _wait(
            lambda: q.stats()["cache_resolved"] >= 4,
            msg="cache-resolved count",
        )
        assert q.stats()["launched_sigs"] == launched
        q.stop()

    def test_submitted_and_depth_metrics(self, live_metrics,
                                         queue_guard):
        cm, _ = live_metrics
        q = vq.VerifyQueue()
        q.start()
        [f.result(30) for f in q.submit_many(_items(3))]
        sub = {
            k[0]: c.get()
            for k, c in cm.verify_queue_submitted.children().items()
        }
        assert sub.get("consensus") == 3
        q.stop()

    def test_submit_after_stop_raises_and_fallback_verifies(
        self, live_metrics, queue_guard
    ):
        q = vq.VerifyQueue()
        q.start()
        vq.install_queue(q)
        q.stop()
        items = _items(2)
        with pytest.raises(vq.QueueUnavailable):
            q.submit_many(items)
        assert not vq.speculation_active()
        # strict fallback: correct verdicts with the queue down
        assert vq.verify_or_fallback(items) == [True, True]
        pk, m, s = items[0]
        assert vq.verify_or_fallback([(pk, b"x", s)]) == [False]


class TestOverlap:
    """The deterministic double-buffer proof: buffer N+1's host prep
    (prehash + pack) completes while buffer N's launch is gated
    in flight."""

    def test_prepare_overlaps_inflight_launch(self, live_metrics,
                                              queue_guard):
        _, hm = live_metrics
        started = threading.Event()
        release = threading.Event()

        def gated_launch(items):
            started.set()
            assert release.wait(30), "test gate never released"
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=gated_launch)
        q.start()
        items = _items(8)
        futs_a = q.submit_many(items[:4])
        assert started.wait(10), "buffer N never launched"
        # buffer N is IN FLIGHT (gated); buffer N+1 must fully
        # prepare meanwhile — that is the pipeline
        futs_b = q.submit_many(items[4:])
        _wait(
            lambda: q.stats()["prepared_batches"] >= 2,
            msg="buffer N+1 prepared during buffer N's launch",
        )
        st = q.stats()
        assert st["prepared"]["consensus"] == 1  # parked, ready
        assert st["launched_batches"] == 0      # N still in flight
        assert not any(f.done() for f in futs_a)
        release.set()
        assert all(f.result(30) for f in futs_a + futs_b)
        st = q.stats()
        assert st["launched_batches"] == 2
        # overlap accounting: prep of N+1 ran inside N's launch wall
        assert st["overlap_ratio"] is not None
        assert st["overlap_ratio"] > 0
        assert hm.host_device_overlap_ratio.labels().get() > 0
        q.stop()


class TestPriorityPreemption:
    def test_consensus_batch_launches_before_queued_prefetch(
        self, live_metrics, queue_guard
    ):
        order: list[bytes] = []
        release = threading.Event()
        started = threading.Event()

        def gated_launch(items):
            order.append(items[0][1])  # first msg marks the batch
            started.set()
            assert release.wait(30)
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=gated_launch)
        q.start()
        p1 = _items(2, tag=b"pref1")
        p2 = _items(2, tag=b"pref2")
        c1 = _items(2, tag=b"cons1")
        futs = list(q.submit_many(p1, vq.PRIORITY_PREFETCH))
        assert started.wait(10)  # p1 is in flight (gated)
        futs += q.submit_many(p2, vq.PRIORITY_PREFETCH)
        _wait(
            lambda: q.stats()["prepared"]["prefetch"] == 1,
            msg="prefetch buffer parked",
        )
        futs += q.submit_many(c1, vq.PRIORITY_CONSENSUS)
        _wait(
            lambda: q.stats()["prepared"]["consensus"] == 1,
            msg="consensus buffer parked",
        )
        release.set()
        assert all(f.result(30) for f in futs)
        # consensus preempts the earlier-submitted prefetch batch
        assert order == [p1[0][1], c1[0][1], p2[0][1]]
        q.stop()


class TestBusyBypass:
    """A live consensus vote must never park behind an in-flight
    prefetch launch — preemption reorders queued buffers, it cannot
    interrupt the device."""

    def test_consensus_verifies_inline_while_prefetch_launches(
        self, live_metrics, queue_guard
    ):
        release = threading.Event()
        started = threading.Event()

        def gated_launch(items):
            started.set()
            assert release.wait(30)
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=gated_launch)
        q.start()
        vq.install_queue(q)
        try:
            pf = q.submit_many(
                _items(4, tag=b"busypf"), vq.PRIORITY_PREFETCH
            )
            assert started.wait(10)  # prefetch launch gated in flight
            assert q.busy()
            items = _items(2, tag=b"busyc")
            t0 = time.monotonic()
            out = vq.verify_or_fallback(items)
            elapsed = time.monotonic() - t0
            assert out == [True, True]
            assert elapsed < 5, (
                "consensus vote waited behind the gated launch"
            )
            assert not any(f.done() for f in pf)  # launch still gated
            # the inline path fed the speculative cache
            pk, m, s = items[0]
            assert vq.cached_result(pk.bytes(), m, s) is True
            release.set()
            assert all(f.result(30) for f in pf)
        finally:
            q.stop()


class TestBusyDuringPrepare:
    """busy() must cover the window where the collector has popped a
    batch from pending but not yet parked the prepared buffer — a
    multi-thousand-sig prefetch prep (prehash + pack) is hundreds of
    milliseconds a consensus vote must not park behind."""

    def test_busy_covers_prepare_window(self, live_metrics,
                                        queue_guard):
        entered = threading.Event()
        release = threading.Event()

        class GatedKey:
            def bytes(self):
                entered.set()
                assert release.wait(30), "test gate never released"
                return b"\x00" * 32

        q = vq.VerifyQueue(launch=lambda items: [True] * len(items))
        q.start()
        try:
            futs = q.submit_many(
                [(GatedKey(), b"m", b"s")], vq.PRIORITY_PREFETCH
            )
            assert entered.wait(10), "collector never entered prepare"
            # the batch is in neither pending, prepared, nor a launch
            st = q.stats()
            assert st["pending"]["prefetch"] == 0
            assert st["prepared"]["prefetch"] == 0
            assert st["launched_batches"] == 0
            assert q.busy(), "busy() missed the batch being prepared"
            release.set()
            assert futs[0].result(30) is True
            _wait(lambda: not q.busy(), msg="queue idle after launch")
        finally:
            release.set()
            q.stop()

    def test_failed_prepare_clears_overlap_watermark(
        self, live_metrics, queue_guard
    ):
        class BadKey:
            def bytes(self):
                raise RuntimeError("malformed key")

        q = vq.VerifyQueue(launch=lambda items: [True] * len(items))
        q.start()
        try:
            fut = q.submit(BadKey(), b"m", b"s")
            with pytest.raises(vq.QueueUnavailable):
                fut.result(30)
            _wait(lambda: not q.busy(), msg="failed prepare abandoned")
            # a later launch with no concurrent prep must credit ZERO
            # overlap: a stale watermark from the raising prepare would
            # count the full launch wall as phantom overlap and pin the
            # cumulative ratio near 1.0
            futs = q.submit_many(_items(2, tag=b"pfail"))
            assert all(f.result(30) for f in futs)
            _wait(
                lambda: q.stats()["launched_batches"] >= 1,
                msg="launch after failed prepare",
            )
            assert (q.stats()["overlap_ratio"] or 0.0) == 0.0
        finally:
            q.stop()


class TestSharedDeadline:
    def test_fallback_wait_is_one_shared_timeout(
        self, live_metrics, queue_guard
    ):
        """A wedged launcher stalls a waiting caller for ONE timeout,
        not timeout x len(items): the futures resolve together (one
        batch), so after the first timeout the rest must fall back
        immediately."""
        release = threading.Event()

        def wedged_launch(items):
            assert release.wait(60)
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=wedged_launch)
        q.start()
        vq.install_queue(q)
        try:
            items = _items(3, tag=b"deadline")
            t0 = time.monotonic()
            sync_cost_baseline = [
                pk.verify_signature(m, s) for pk, m, s in items
            ]
            sync_cost = time.monotonic() - t0
            assert sync_cost_baseline == [True, True, True]
            t0 = time.monotonic()
            out = vq.verify_or_fallback(
                items, vq.PRIORITY_PREFETCH, timeout=1.0
            )
            elapsed = time.monotonic() - t0
            assert out == [True, True, True]  # strict sync fallback
            # per-future timeouts would wait >= 3.0s + sync_cost
            assert elapsed < 2.2 + sync_cost, (
                "per-future timeouts multiplied the wedged stall"
            )
        finally:
            release.set()
            q.stop()


class TestShortLaunchResult:
    def test_result_length_mismatch_fails_batch_immediately(
        self, live_metrics, queue_guard
    ):
        """A launch/verifier returning fewer results than requests
        must fail every future at once (strict sync fallback), not
        leave the zip-truncated tail dangling until the wait times
        out."""
        q = vq.VerifyQueue(launch=lambda items: [True])  # always short
        q.start()
        vq.install_queue(q)
        try:
            items = _items(3, tag=b"short")
            t0 = time.monotonic()
            futs = q.submit_many(items)
            for f in futs:
                with pytest.raises(vq.QueueUnavailable):
                    f.result(30)
            assert time.monotonic() - t0 < 10, "futures hung"
            assert q.stats()["failed_batches"] == 1
            # the strict fallback still yields correct verdicts
            assert vq.verify_or_fallback(
                items, vq.PRIORITY_PREFETCH
            ) == [True, True, True]
        finally:
            q.stop()


class TestNegativeVerdictsNotCached:
    def test_invalid_signature_reverifies_every_time(
        self, live_metrics, queue_guard
    ):
        q = vq.VerifyQueue()
        q.start()
        vq.install_queue(q)
        try:
            pk, m, s = _items(1, tag=b"neg")[0]
            assert q.submit(pk, b"tampered", s).result(30) is False
            # the failure was NOT memoized: a consult misses and a
            # resubmit re-verifies (transient faults heal on retry)
            assert vq.cached_result(pk.bytes(), b"tampered", s) is None
            launched = q.stats()["launched_sigs"]
            assert q.submit(pk, b"tampered", s).result(30) is False
            _wait(
                lambda: q.stats()["launched_sigs"] == launched + 1,
                msg="negative verdict re-verified",
            )
            cache = vq.SpeculativeCache(capacity=2048)
            cache.store(b"k", False)
            assert len(cache) == 0  # never stored
        finally:
            q.stop()


class TestQueueDrain:
    def test_stop_drains_pending_work(self, live_metrics, queue_guard):
        def slow_launch(items):
            time.sleep(0.02)
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=slow_launch, max_batch=4)
        q.start()
        futs = q.submit_many(_items(16))
        futs += q.submit_many(_items(8, tag=b"pf"), vq.PRIORITY_PREFETCH)
        q.stop()  # drain: everything already submitted must resolve
        assert all(f.done() for f in futs)
        assert all(f.result(0) for f in futs)
        assert not q.accepting()
        assert q.stats()["draining"]

    def test_node_stop_uninstalls_queue(self, live_metrics,
                                        queue_guard):
        q = vq.VerifyQueue()
        q.start()
        vq.install_queue(q)
        assert vq.speculation_active()
        q.stop()  # on_stop uninstalls the process-wide slot
        assert vq._installed() is None
        assert not vq.speculation_active()


class TestVoteSetSpeculation:
    def test_vote_and_extension_verify_in_one_submission(
        self, live_metrics, queue_guard
    ):
        batches: list[int] = []

        def launch(items):
            batches.append(len(items))
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(launch=launch)
        q.start()
        vq.install_queue(q)
        vals, keys = make_val_set(4)
        bid = make_block_id()
        vs = VoteSet(
            CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals,
            extensions_enabled=True,
        )
        v = Vote(
            type=PRECOMMIT_TYPE, height=1, round=0, block_id=bid,
            timestamp_ns=1_700_000_000_000_000_000,
            validator_address=keys[0].pub_key().address(),
            validator_index=0, extension=b"payload",
        )
        v = replace(
            v,
            signature=keys[0].sign(v.sign_bytes(CHAIN_ID)),
            extension_signature=keys[0].sign(
                v.extension_sign_bytes(CHAIN_ID)
            ),
        )
        assert vs.add_vote(v)
        # satellite: signature + extension rode ONE batched submission
        assert 2 in batches
        # tampered extension signature still rejected through the queue
        v2 = replace(v, extension_signature=b"\x01" * 64)
        vs2 = VoteSet(
            CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals,
            extensions_enabled=True,
        )
        with pytest.raises(VoteSetError, match="extension signature"):
            vs2.add_vote(v2)
        # tampered vote signature rejected too
        v3 = replace(v, signature=b"\x02" * 64)
        vs3 = VoteSet(
            CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals,
            extensions_enabled=True,
        )
        with pytest.raises(VoteSetError, match="invalid vote signature"):
            vs3.add_vote(v3)
        q.stop()

    def test_add_vote_without_queue_unchanged(self, live_metrics,
                                              queue_guard):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        assert vs.add_vote(signed_vote(keys[0], 0, make_block_id()))
        bad = signed_vote(keys[1], 1, make_block_id())
        bad = replace(bad, signature=b"\x01" * 64)
        with pytest.raises(VoteSetError, match="invalid vote signature"):
            vs.add_vote(bad)


class TestSpeculativeCommitEquivalence:
    """Speculated verify_commit is bit-equivalent to synchronous, and
    a fully speculated vote set performs ZERO new device launches."""

    def _fixture(self):
        vals, keys = make_val_set(6)
        bid = make_block_id(b"spec")
        commit = make_commit(vals, keys, bid)
        return vals, keys, bid, commit

    def _tampered(self, commit):
        sigs = list(commit.signatures)
        sigs[2] = replace(sigs[2], signature=b"\x01" * 64)
        return replace(commit, signatures=tuple(sigs))

    def _with_absent(self, commit):
        sigs = list(commit.signatures)
        sigs[1] = CommitSig(block_id_flag=BLOCK_ID_FLAG_ABSENT)
        return replace(commit, signatures=tuple(sigs))

    def _outcome(self, vals, bid, commit):
        try:
            validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
            return "ok"
        except validation.CommitError as exc:
            return type(exc).__name__

    def test_equivalence_and_zero_launch_fully_speculated(
        self, live_metrics, queue_guard, monkeypatch
    ):
        from cometbft_tpu.crypto import batch as crypto_batch
        from cometbft_tpu.crypto import dispatch as _dispatch
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

        # order-robustness: a suite that demoted the generic tier
        # within its cool-down (test_health's watchdog drives) would
        # otherwise rob the "control pays a device launch" assertion
        # of its device route
        _dispatch.reset_for_tests()
        cm, _ = live_metrics
        vals, keys, bid, commit = self._fixture()
        tampered = self._tampered(commit)
        absent = self._with_absent(commit)
        # baseline: NO queue installed — today's synchronous behavior
        base = {
            "valid": self._outcome(vals, bid, commit),
            "tampered": self._outcome(vals, bid, tampered),
            "absent": self._outcome(vals, bid, absent),
        }
        assert base["valid"] == "ok"
        assert base["tampered"] == "InvalidCommitSignatures"
        assert base["absent"] == "ok"

        # control fixture built BEFORE the queue exists: make_commit
        # drives add_vote, which would otherwise speculate it too
        vals_c, keys_c = make_val_set(6)
        bid_c = make_block_id(b"control")
        commit_c = make_commit(vals_c, keys_c, bid_c)

        # force the device route (generic kernel on the virtual CPU
        # mesh's default device) so batch_verify_launches moves
        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        monkeypatch.setitem(
            crypto_batch.REGISTRY, ed.KEY_TYPE,
            lambda: TpuBatchVerifier(device_min_batch=1),
        )
        q = vq.VerifyQueue()
        q.start()
        vq.install_queue(q)
        # speculate: every precommit enters through add_vote (the live
        # consensus path) and the queue fills the result cache
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i, k in enumerate(keys):
            assert vs.add_vote(signed_vote(k, i, bid))
        # every vote's verdict landed in the cache — via the queue, or
        # via the inline busy-bypass (both feed it)
        _wait(
            lambda: len(q.cache) >= 6,
            timeout=120, msg="speculated verdicts cached",
        )

        def launches():
            return sum(
                c.get()
                for c in cm.batch_verify_launches.children().values()
            )

        # instrumentation control: the UN-speculated commit pays a
        # real device launch through this route
        before_control = launches()
        assert self._outcome(vals_c, bid_c, commit_c) == "ok"
        assert launches() > before_control, (
            "control commit must pay a device launch"
        )
        spec = {
            "valid": self._outcome(vals, bid, commit),
            "tampered": self._outcome(vals, bid, tampered),
            "absent": self._outcome(vals, bid, absent),
        }
        assert spec["valid"] == base["valid"]
        assert spec["tampered"] == base["tampered"]
        assert spec["absent"] == base["absent"]
        # acceptance: the fully speculated commit re-verified with
        # ZERO new device launches — only cache hits.  (The tampered
        # variant legitimately missed and re-verified, so assert the
        # delta for the valid commit alone.)
        before_valid = launches()
        assert self._outcome(vals, bid, commit) == "ok"
        assert launches() == before_valid
        hits = {
            k[0]: c.get()
            for k, c in cm.verify_queue_spec_cache.children().items()
        }
        assert hits.get("hit", 0) >= 6
        q.stop()


class TestJitguardSteadyState:
    def test_zero_steady_state_retraces_sealed(
        self, live_metrics, queue_guard, monkeypatch
    ):
        """Warm the queue's device path on the forced-8-device CPU
        mesh, seal the jitguard, keep submitting same-shape batches:
        zero retraces."""
        from cometbft_tpu.ops import jitguard
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        # generic mesh tier: constant (pow2) batch shape, no table
        # builds, so the steady state is one compiled program
        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        monkeypatch.setattr(jitguard, "_ENABLED", True)
        jitguard.reset()
        # cache OFF so the sealed rounds really LAUNCH (identical
        # triples would otherwise resolve speculatively and prove
        # nothing about retraces)
        q = vq.VerifyQueue(
            verifier_factory=lambda pk: ShardedTpuBatchVerifier(
                device_min_batch=1
            ),
            use_cache=False,
        )
        q.start()
        try:
            # 72 lanes pow2-pads to 128 — the SAME (batch=128,
            # bucket=128) generic program test_parallel's uneven-batch
            # test compiles, so tier-1 pays this shape once
            items = _items(72, tag=b"jg")
            assert all(f.result(420) for f in q.submit_many(items))
            before = dict(jitguard.compile_counts())
            jitguard.seal()
            for _ in range(2):
                futs = q.submit_many(items)
                assert all(f.result(420) for f in futs)
            assert jitguard.compile_counts() == before
            st = q.stats()
            assert st["failed_batches"] == 0
        finally:
            q.stop()
            jitguard.reset()


class TestBatchedLaneRelease:
    """What the light client's verify-ahead leans on (ISSUE 28): a
    batched lane that releases by size launches exactly its target,
    the lane's own counts, the target a caller cuts its submissions
    to, and the one helper for the triples a commit's check reads."""

    def test_a_release_by_size_takes_exactly_the_target(self, queue_guard):
        """What is over the target waits for the next buffer instead
        of padding this launch to the next power-of-two bucket."""
        sizes = []

        def launch(items):
            sizes.append(len(items))
            return [True] * len(items)

        q = vq.VerifyQueue(
            launch=launch, light_batch=4, light_wait_ms=60_000,
            use_cache=False,
        )
        q.start()
        vq.install_queue(q)
        futs = q.submit_many(_items(10, tag=b"cut"), vq.PRIORITY_LIGHT)
        assert all(f.result(30) for f in futs[:8])
        _wait(lambda: q.stats()["launched_batches"] == 2, msg="2 launches")
        stats = q.stats()
        assert sizes == [4, 4]
        assert stats["pending"]["light_client"] == 2  # parked, own stamps
        assert stats["launched_batches_by_lane"] == {
            "consensus": 0, "prefetch": 0, "light_client": 2, "ingest": 0,
        }
        assert stats["launched_sigs_by_lane"]["light_client"] == 8
        assert stats["launched_sigs"] == 8
        q.stop()  # the drain releases the two that were parked
        assert all(f.result(30) for f in futs) and sizes == [4, 4, 2]

    @pytest.mark.parametrize(
        "pending,taken", [(3, 3), (4, 4), (5, 4), (4096, 4)],
    )
    def test_the_gate_cuts_a_release_at_its_target(self, pending, taken):
        assert vq._LaneBatcher(4, 10).cut(pending) == taken

    def test_the_lanes_launches_reach_the_metrics(
        self, live_metrics, queue_guard
    ):
        cm, _ = live_metrics
        q = vq.VerifyQueue(
            launch=lambda items: [True] * len(items), use_cache=False,
        )
        q.start()
        futs = q.submit_many(_items(5, tag=b"lm"), vq.PRIORITY_PREFETCH)
        assert all(f.result(30) for f in futs)
        _wait(lambda: q.stats()["launched_batches"] == 1, msg="launch")
        lane = {"priority": "prefetch"}
        assert cm.verify_queue_launched.labels(**lane).get() == 1
        assert cm.verify_queue_launched_sigs.labels(**lane).get() == 5
        q.stop()

    def test_lane_batch_target_is_the_running_queues(self, queue_guard):
        vq.install_queue(None)
        assert vq.lane_batch_target(vq.PRIORITY_LIGHT) is None
        q = vq.VerifyQueue(light_batch=48, checktx_batch=7)
        q.start()
        vq.install_queue(q)
        assert vq.lane_batch_target(vq.PRIORITY_LIGHT) == 48
        assert vq.lane_batch_target(vq.PRIORITY_INGEST) == 7
        assert vq.lane_batch_target(vq.PRIORITY_PREFETCH) is None
        q.stop()
        assert vq.lane_batch_target(vq.PRIORITY_LIGHT) is None

    def test_submit_speculative_keeps_the_futures(self, queue_guard):
        vq.install_queue(None)
        assert vq.submit_speculative(_items(2), vq.PRIORITY_LIGHT) is None
        q = vq.VerifyQueue(light_batch=2, light_wait_ms=60_000)
        q.start()
        vq.install_queue(q)
        futs = vq.submit_speculative(_items(2), vq.PRIORITY_LIGHT)
        assert [f.result(30) for f in futs] == [True, True]
        q.stop()
        assert vq.submit_speculative(_items(2), vq.PRIORITY_LIGHT) is None

    @pytest.mark.parametrize(
        "needed,count",
        [(None, 6), (6 * 10 * 2 // 3, 5), (6 * 10 // 3, 3), (0, 1)],
    )
    def test_commit_check_triples_stop_past_the_power(self, needed, count):
        """The triples are the leading COMMIT-flag votes, to the first
        that takes the tally past ``needed``: what the check's own
        early break collects."""
        from cometbft_tpu.types.validation import commit_check_triples
        from cometbft_tpu.types.validator import Validator, ValidatorSet
        from tests.test_light_serve import CHAIN, make_chain

        vals, blocks = make_chain(2)  # 6 validators of power 10
        lb = blocks[1]
        got = commit_check_triples(CHAIN, vals, lb.commit, needed)
        assert len(got) == count
        assert got == [
            (vals.get_by_index(i).pub_key,
             lb.commit.vote_sign_bytes(CHAIN, i),
             lb.commit.signatures[i].signature)
            for i in range(count)
        ]
        # a set the commit does not line up with: never guessed at
        rotated = ValidatorSet([
            Validator(k.pub_key(), 10)
            for k in (ed.priv_key_from_secret(b"rot-%d" % i)
                      for i in range(6))
        ])
        assert commit_check_triples(CHAIN, rotated, lb.commit) is None
        assert commit_check_triples(
            CHAIN, ValidatorSet(vals.validators[:5]), lb.commit
        ) is None


class TestEnvValidation:
    def test_prefetch_depth_default_and_validation(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_VERIFY_PREFETCH", raising=False)
        assert vq.prefetch_depth_from_env() == 8
        monkeypatch.setenv("CMT_TPU_VERIFY_PREFETCH", "0")
        assert vq.prefetch_depth_from_env() == 0
        monkeypatch.setenv("CMT_TPU_VERIFY_PREFETCH", "abc")
        with pytest.raises(ValueError, match="CMT_TPU_VERIFY_PREFETCH"):
            vq.prefetch_depth_from_env()
        monkeypatch.setenv("CMT_TPU_VERIFY_PREFETCH", "-1")
        with pytest.raises(ValueError, match="CMT_TPU_VERIFY_PREFETCH"):
            vq.prefetch_depth_from_env()

    def test_spec_cache_validation(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_SPEC_CACHE", raising=False)
        assert vq.spec_cache_capacity_from_env() == 65536
        monkeypatch.setenv("CMT_TPU_SPEC_CACHE", "10")
        with pytest.raises(ValueError, match="CMT_TPU_SPEC_CACHE"):
            vq.spec_cache_capacity_from_env()
        monkeypatch.setenv("CMT_TPU_SPEC_CACHE", "2048")
        assert vq.spec_cache_capacity_from_env() == 2048

    def test_cache_is_bounded(self):
        cache = vq.SpeculativeCache(capacity=4)
        for i in range(8):
            cache.store(b"k%d" % i, True)
        assert len(cache) == 4
        assert cache.lookup(b"k0") is None  # evicted
        assert cache.lookup(b"k7") is True


class TestBlocksyncPrefetch:
    def test_prefetch_submits_each_height_once(self, live_metrics,
                                               queue_guard):
        from cometbft_tpu.blocksync.reactor import BlocksyncReactor

        q = vq.VerifyQueue()
        q.start()
        vq.install_queue(q)
        vals, keys = make_val_set(4)
        # chain: block at height h carries height h-1's commit
        bids = {h: make_block_id(b"blk%d" % h) for h in range(1, 7)}
        commits = {
            h: make_commit(vals, keys, bids[h], height=h)
            for h in range(1, 6)
        }
        blocks = {
            h: SimpleNamespace(
                header=SimpleNamespace(height=h),
                last_commit=commits.get(h - 1),
            )
            for h in range(2, 7)
        }

        pool = SimpleNamespace(
            height=2,
            peek_blocks_from=lambda start, count: [
                blocks.get(h) for h in range(start, start + count)
            ],
        )
        stub = SimpleNamespace(
            _prefetch_depth=3,
            _prefetched_height=0,
            pool=pool,
            state=SimpleNamespace(validators=vals, chain_id=CHAIN_ID),
        )
        BlocksyncReactor._prefetch_commit_verifies(stub)
        # heights 3..5 prefetched (pool.height+1 .. +depth)
        assert stub._prefetched_height == 5
        st = q.stats()
        assert st["submitted"]["prefetch"] == 3 * len(keys)
        # results land in the speculative cache
        commit = commits[3]
        _wait(
            lambda: vq.cached_result(
                vals.get_by_index(0).pub_key.bytes(),
                commit.vote_sign_bytes(CHAIN_ID, 0),
                commit.signatures[0].signature,
            ) is True,
            msg="prefetched result cached",
        )
        # idempotent: the watermark stops resubmission
        BlocksyncReactor._prefetch_commit_verifies(stub)
        assert q.stats()["submitted"]["prefetch"] == 3 * len(keys)
        q.stop()

    def test_watermark_not_advanced_when_queue_unavailable(
        self, live_metrics, queue_guard
    ):
        """A queue hiccup must RETRY these heights next step, not
        skip them forever."""
        from cometbft_tpu.blocksync.reactor import BlocksyncReactor

        class _FlakyQueue:
            """accepting() says yes, submit hits the drain race —
            the narrow window _prefetch_commit_verifies must survive
            without burning its watermark."""

            cache = None

            def accepting(self):
                return True

            def busy(self):
                return False

            def submit_many(self, items, priority):
                raise vq.QueueUnavailable("draining")

            def is_running(self):
                return False

        vq.install_queue(_FlakyQueue())
        vals, keys = make_val_set(4)
        bid = make_block_id(b"wm")
        commit = make_commit(vals, keys, bid, height=3)
        blocks = {
            3: SimpleNamespace(
                header=SimpleNamespace(height=3), last_commit=None
            ),
            4: SimpleNamespace(
                header=SimpleNamespace(height=4), last_commit=commit
            ),
        }
        stub = SimpleNamespace(
            _prefetch_depth=1,
            _prefetched_height=0,
            pool=SimpleNamespace(
                height=2,
                peek_blocks_from=lambda start, count: [
                    blocks.get(h) for h in range(start, start + count)
                ],
            ),
            state=SimpleNamespace(validators=vals, chain_id=CHAIN_ID),
        )
        BlocksyncReactor._prefetch_commit_verifies(stub)
        assert stub._prefetched_height == 0  # nothing silently skipped
        # queue recovers: the same heights retry and the watermark
        # advances only now
        real = vq.VerifyQueue()
        real.start()
        vq.install_queue(real)
        BlocksyncReactor._prefetch_commit_verifies(stub)
        assert stub._prefetched_height == 3
        real.stop()


class TestPipelinedBench:
    def test_pipelined_bench_round_trip(self, tmp_path, monkeypatch,
                                        queue_guard):
        """bench.py --pipelined on the host tier: a measured sync and
        pipelined row land in the perf ledger with the overlap ratio
        recorded."""
        import json

        import bench

        ledger = tmp_path / "ledger.json"
        monkeypatch.setenv("CMT_TPU_PERF_LEDGER", str(ledger))
        monkeypatch.setenv("CMT_BENCH_N", "48")
        monkeypatch.setenv("CMT_BENCH_NCHUNKS", "4")
        result = bench.pipelined_main()
        assert result["pipelined_sigs_per_sec"] > 0
        assert result["sync_sigs_per_sec"] > 0
        assert result["overlap_ratio"] is not None
        assert result["dispatch_tier"]
        doc = json.loads(ledger.read_text())
        configs = {e["config"] for e in doc["entries"]}
        assert {"verify_queue_sync", "verify_queue_pipelined"} <= configs

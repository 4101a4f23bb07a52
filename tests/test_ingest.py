"""Device-batched CheckTx ingest plane tests (ISSUE 10).

Covers: the signed-tx admission envelope (mempool/ingest.py), the
VerifyQueue ``ingest`` lane's micro-batch accumulation (size target +
deadline release) and its strict preemption by consensus buffers, the
sync-fallback equivalence when the queue is stopped, sharded-TxCache
equivalence vs the unsharded baseline (plus the concurrent hammer the
race mode checks), the zero-regression recheck/update semantics for
signed txs, the fail-loudly env validation, and the ``ingest-smoke``
node drive: a single-validator node keeps committing
strictly-increasing heights while the closed-loop sustained-load
harness saturates admission — the system sheds (MempoolFullError /
cache rejections, nonzero drop counters) instead of stalling
consensus.  ``make ingest-smoke`` runs the IngestSmoke subset
standalone.
"""

from __future__ import annotations

import threading
import time
import urllib.request

import pytest

from cometbft_tpu.abci.types import CheckTxResponse
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import verify_queue as vq
from cometbft_tpu.loadtime import SustainedLoader, parse_ramp
from cometbft_tpu.mempool import (
    CListMempool,
    MempoolFullError,
    TxCache,
    TxInCacheError,
    TxSignatureError,
    ingest,
    txcache_shards_from_env,
)
from cometbft_tpu.metrics import (
    CryptoMetrics,
    HealthMetrics,
    MempoolMetrics,
    install_crypto_metrics,
    install_health_metrics,
)
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.metrics import Registry


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
    yield
    q = vq._installed()
    if q is not None and q.is_running():
        q.stop()
    vq.install_queue(None)


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return
        time.sleep(0.005)
    raise AssertionError(f"timed out waiting for {msg}")


_PRIVS = [ed.priv_key_from_secret(b"ingest-%d" % i) for i in range(4)]


def _signed(n: int, tag: bytes = b"it"):
    return [
        ingest.make_signed_tx(
            _PRIVS[i % len(_PRIVS)], b"%s-%d=v" % (tag, i)
        )
        for i in range(n)
    ]


class _NullProxy:
    """Accept-everything app; ``reject`` lists payloads to fail at
    (re)check so recheck-eviction paths are drivable."""

    def __init__(self):
        self.reject: set[bytes] = set()
        self.calls = 0

    def check_tx(self, req):
        self.calls += 1
        if bytes(req.tx) in self.reject:
            return CheckTxResponse(code=1, log="rejected")
        return CheckTxResponse(gas_wanted=1)


def _mempool(size=5000, cache_size=10000, **kw):
    return CListMempool(
        _NullProxy(), size=size, cache_size=cache_size,
        metrics=MempoolMetrics(Registry()), **kw
    )


def _counter(metric, **labels) -> float:
    return metric.labels(**labels).get()


# -- the signed-tx envelope ----------------------------------------------


class TestSignedTxEnvelope:
    def test_round_trip(self):
        priv = _PRIVS[0]
        tx = ingest.make_signed_tx(priv, b"k=v")
        pub, sig, payload = ingest.parse_signed_tx(tx)
        assert pub == priv.pub_key().bytes()
        assert payload == b"k=v"
        assert priv.pub_key().verify_signature(
            ingest.sign_bytes(payload), sig
        )
        assert ingest.signed_tx_payload(tx) == b"k=v"

    def test_plain_tx_passes_through(self):
        assert ingest.parse_signed_tx(b"k=v") is None
        assert ingest.signed_tx_payload(b"k=v") == b"k=v"

    def test_malformed_envelope_raises(self):
        with pytest.raises(ingest.MalformedSignedTx):
            ingest.parse_signed_tx(b"stx:tooshort")
        # non-hex where the keys belong
        bad = b"stx:" + b"z" * (64 + 128) + b":k=v"
        with pytest.raises(ingest.MalformedSignedTx):
            ingest.parse_signed_tx(bad)

    def test_domain_separation(self):
        """An admission signature binds the stx| domain — the raw
        payload signature must NOT verify."""
        priv = _PRIVS[0]
        tx = ingest.make_signed_tx(priv, b"k=v")
        _, sig, payload = ingest.parse_signed_tx(tx)
        assert not priv.pub_key().verify_signature(payload, sig)

    def test_kvstore_executes_payload_not_envelope(self):
        """A committed enveloped tx executes as its PAYLOAD: the
        envelope is admission metadata, never application state — the
        same key signed by two senders is one key."""
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.abci.types import FinalizeBlockRequest

        app = KVStoreApp()
        tx = ingest.make_signed_tx(_PRIVS[0], b"ikey=ival")
        res = app.finalize_block(
            FinalizeBlockRequest(height=1, txs=(tx,))
        )
        assert res.tx_results[0].code == 0
        assert app.get("ikey") == "ival"
        assert app.get("stx:" + tx[4:68].decode()) is None
        # a different sender writing the same key overwrites it
        tx2 = ingest.make_signed_tx(_PRIVS[1], b"ikey=other")
        app.finalize_block(FinalizeBlockRequest(height=2, txs=(tx2,)))
        assert app.get("ikey") == "other"

    def test_forged_envelope_rejected_at_execution(self):
        """The admission guarantee survives block inclusion: a
        byzantine proposer putting a forged envelope straight into a
        block (bypassing its mempool) is rejected at the app seam —
        process_proposal refuses the block and a finalized forged tx
        executes as an error, never as state."""
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.abci.types import (
            FinalizeBlockRequest,
            ProcessProposalRequest,
            ProposalStatus,
        )

        app = KVStoreApp()
        tx = ingest.make_signed_tx(_PRIVS[0], b"fk=fv")
        forged = tx[:-1] + bytes([tx[-1] ^ 1])  # payload != signature
        assert app.process_proposal(
            ProcessProposalRequest(txs=(forged,))
        ).status == ProposalStatus.REJECT
        res = app.finalize_block(
            FinalizeBlockRequest(height=1, txs=(forged,))
        )
        assert res.tx_results[0].code != 0
        assert app.get("fk") is None


# -- sharded TxCache -----------------------------------------------------


class TestTxCacheSharding:
    def test_shard_equivalence_vs_unsharded_baseline(self):
        """Every push/remove/has/reset outcome must match shards=1
        (the pre-ISSUE-10 single-mutex cache) on a capacity no
        sequence overflows."""
        base = TxCache(256, shards=1)
        sharded = TxCache(256, shards=8)
        txs = [b"tx-%d" % i for i in range(64)]
        for t in txs:
            assert base.push(t) == sharded.push(t)
        for t in txs:  # duplicates refresh, return False, identically
            assert base.push(t) == sharded.push(t) is False
        for t in txs[::3]:
            base.remove(t)
            sharded.remove(t)
        for t in txs:
            assert base.has(t) == sharded.has(t)
        base.reset()
        sharded.reset()
        assert not any(base.has(t) or sharded.has(t) for t in txs)

    def test_total_capacity_at_least_size(self):
        """Per-shard eviction must never remember LESS than the
        unsharded cache promised: capacity rounds UP."""
        c = TxCache(100, shards=8)
        assert sum(s._size for s in c._shards) >= 100
        # and a size smaller than the shard count collapses shards
        # rather than evicting everything
        tiny = TxCache(2, shards=8)
        assert len(tiny._shards) <= 2
        assert sum(s._size for s in tiny._shards) >= 2
        tiny.push(b"a")
        tiny.push(b"b")
        # per-shard LRU: both survive unless they collide on one
        # size-1 shard, and even then the newest is remembered
        assert tiny.has(b"a") or tiny.has(b"b")

    def test_lru_evicts_within_shard(self):
        c = TxCache(4, shards=1)
        for t in (b"a", b"b", b"c", b"d"):
            c.push(t)
        c.push(b"a")  # refresh
        c.push(b"e")  # evicts b (LRU)
        assert c.has(b"a") and not c.has(b"b")

    def test_concurrent_hammer_clean(self):
        """The race-mode contract (CMT_TPU_RACE=1 activates the
        guarded-by checker inside _TxCacheShard): concurrent
        push/has/remove through the locked API must never trip it or
        corrupt the maps."""
        cache = TxCache(512, shards=8)
        errs: list = []

        def worker(seed: int):
            try:
                for i in range(200):
                    t = b"%d-%d" % (seed, i % 50)
                    cache.push(t)
                    cache.has(t)
                    if i % 7 == 0:
                        cache.remove(t)
            except Exception as e:  # noqa: BLE001 — incl. RaceError
                errs.append(e)

        threads = [
            threading.Thread(target=worker, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=30)
        assert not errs, errs

    def test_shards_env_validation(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_TXCACHE_SHARDS", raising=False)
        assert txcache_shards_from_env() == 8
        monkeypatch.setenv("CMT_TPU_TXCACHE_SHARDS", "4")
        assert txcache_shards_from_env() == 4
        monkeypatch.setenv("CMT_TPU_TXCACHE_SHARDS", "zero")
        with pytest.raises(ValueError):
            txcache_shards_from_env()
        monkeypatch.setenv("CMT_TPU_TXCACHE_SHARDS", "0")
        with pytest.raises(ValueError):
            txcache_shards_from_env()


# -- the ingest lane's micro-batcher -------------------------------------


class TestIngestAccumulation:
    def test_accumulates_to_batch_size(self, live_metrics, queue_guard):
        launches: list[int] = []

        def launch(items):
            launches.append(len(items))
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(
            launch=launch, checktx_batch=4, checktx_wait_ms=60_000
        )
        q.start()
        priv = _PRIVS[0]
        items = []
        for i in range(3):
            m = b"acc-%d" % i
            items.append((priv.pub_key(), m, priv.sign(m)))
        futs = q.submit_many(items, vq.PRIORITY_INGEST)
        time.sleep(0.3)
        # below the size target, far from the deadline: still parked
        assert launches == []
        assert q.stats()["pending"]["ingest"] == 3
        m = b"acc-3"
        futs += [q.submit(
            priv.pub_key(), m, priv.sign(m), vq.PRIORITY_INGEST
        )]
        assert all(f.result(30) for f in futs)
        assert launches == [4]  # ONE coalesced launch
        q.stop()

    def test_deadline_releases_partial_batch(
        self, live_metrics, queue_guard
    ):
        q = vq.VerifyQueue(checktx_batch=10_000, checktx_wait_ms=25)
        q.start()
        priv = _PRIVS[1]
        m = b"deadline"
        t0 = time.monotonic()
        fut = q.submit(
            priv.pub_key(), m, priv.sign(m), vq.PRIORITY_INGEST
        )
        assert fut.result(30) is True
        # released by the deadline, not a 10k batch that never fills
        assert time.monotonic() - t0 < 10
        q.stop()

    def test_consensus_preempts_parked_ingest_buffer(
        self, live_metrics, queue_guard
    ):
        """ISSUE 10 satellite: a prepared consensus buffer launches
        before a parked ingest buffer, whatever the arrival order."""
        order: list[bytes] = []
        release = threading.Event()
        started = threading.Event()

        def gated_launch(items):
            order.append(items[0][1])
            started.set()
            assert release.wait(30)
            return [pk.verify_signature(m, s) for pk, m, s in items]

        q = vq.VerifyQueue(
            launch=gated_launch, checktx_batch=2, checktx_wait_ms=0
        )
        q.start()
        priv = _PRIVS[2]

        def items(tag, n=2):
            out = []
            for i in range(n):
                m = b"%s-%d" % (tag, i)
                out.append((priv.pub_key(), m, priv.sign(m)))
            return out

        ia = items(b"ingestA")
        futs = list(q.submit_many(ia, vq.PRIORITY_INGEST))
        assert started.wait(10)  # ingest A launch gated in flight
        ib = items(b"ingestB")
        futs += q.submit_many(ib, vq.PRIORITY_INGEST)
        _wait(
            lambda: q.stats()["prepared"]["ingest"] == 1,
            msg="ingest buffer parked",
        )
        cons = items(b"cons")
        futs += q.submit_many(cons, vq.PRIORITY_CONSENSUS)
        _wait(
            lambda: q.stats()["prepared"]["consensus"] == 1,
            msg="consensus buffer parked",
        )
        release.set()
        assert all(f.result(30) for f in futs)
        assert order == [ia[0][1], cons[0][1], ib[0][1]]
        q.stop()

    def test_busy_excludes_accumulating_ingest(
        self, live_metrics, queue_guard
    ):
        """Pending ingest work must NOT push live consensus votes onto
        the inline path — that is exactly the work consensus
        preempts."""
        q = vq.VerifyQueue(checktx_batch=10_000, checktx_wait_ms=60_000)
        q.start()
        vq.install_queue(q)
        priv = _PRIVS[3]
        m = b"parked"
        q.submit(priv.pub_key(), m, priv.sign(m), vq.PRIORITY_INGEST)
        assert q.stats()["pending"]["ingest"] == 1
        assert q.busy() is False
        q.stop()

    def test_env_validation(self, monkeypatch):
        monkeypatch.delenv("CMT_TPU_CHECKTX_BATCH", raising=False)
        monkeypatch.delenv("CMT_TPU_CHECKTX_WAIT_MS", raising=False)
        assert vq.checktx_batch_from_env() == vq.DEFAULT_CHECKTX_BATCH
        assert (
            vq.checktx_wait_ms_from_env() == vq.DEFAULT_CHECKTX_WAIT_MS
        )
        monkeypatch.setenv("CMT_TPU_CHECKTX_BATCH", "0")
        with pytest.raises(ValueError):
            vq.checktx_batch_from_env()
        monkeypatch.setenv("CMT_TPU_CHECKTX_WAIT_MS", "-1")
        with pytest.raises(ValueError):
            vq.checktx_wait_ms_from_env()
        monkeypatch.setenv("CMT_TPU_CHECKTX_WAIT_MS", "5ms")
        with pytest.raises(ValueError):
            vq.checktx_wait_ms_from_env()


# -- mempool admission through the lane ----------------------------------


class TestMempoolSignedAdmission:
    def test_admits_valid_rejects_tampered_via_queue(
        self, live_metrics, queue_guard
    ):
        q = vq.VerifyQueue(checktx_batch=2, checktx_wait_ms=5)
        q.start()
        vq.install_queue(q)
        mp = _mempool()
        good = _signed(4, tag=b"adm")
        for tx in good:
            mp.check_tx(tx)
        assert mp.size() == 4
        bad = good[0][:-1] + bytes([good[0][-1] ^ 1])
        with pytest.raises(TxSignatureError):
            mp.check_tx(bad)
        assert not mp.cache.has(bad)  # rejectable again, not cached
        assert mp.size() == 4
        assert _counter(mp.metrics.checktx_batched) >= 4
        assert _counter(
            mp.metrics.checktx_total, result="accepted"
        ) == 4
        assert _counter(mp.metrics.checktx_total, result="sig") == 1
        assert q.stats()["submitted"]["ingest"] >= 4
        q.stop()

    def test_sync_fallback_equivalence_when_queue_stopped(
        self, live_metrics, queue_guard
    ):
        """Queue stopped == queue never installed == queue live: the
        same txs admit and the same tampered txs reject."""
        outcomes = []
        for mode in ("none", "stopped", "live"):
            mp = _mempool()
            q = None
            if mode != "none":
                q = vq.VerifyQueue(checktx_batch=2, checktx_wait_ms=5)
                q.start()
                vq.install_queue(q)
                if mode == "stopped":
                    q.stop()
            txs = _signed(3, tag=b"eq")
            bad = txs[1][:-1] + bytes([txs[1][-1] ^ 1])
            row = []
            for tx in (txs[0], bad, txs[2]):
                try:
                    mp.check_tx(tx)
                    row.append("ok")
                except TxSignatureError:
                    row.append("sig")
            row.append(mp.size())
            outcomes.append(row)
            if mode == "live":
                assert _counter(mp.metrics.checktx_batched) >= 2
            else:
                assert _counter(mp.metrics.checktx_inline) >= 2
            if q is not None and q.is_running():
                q.stop()
            vq.install_queue(None)
        assert outcomes[0] == outcomes[1] == outcomes[2] == [
            "ok", "sig", "ok", 2,
        ]

    def test_plain_txs_untouched(self, live_metrics, queue_guard):
        """No envelope, no signature work — the pre-ISSUE-10 path."""
        mp = _mempool()
        mp.check_tx(b"plain=v")
        assert mp.size() == 1
        assert _counter(mp.metrics.checktx_batched) == 0
        assert _counter(mp.metrics.checktx_inline) == 0

    def test_duplicate_and_full_shed_accounting(self):
        mp = _mempool(size=2)
        mp.check_tx(b"a=1")
        with pytest.raises(TxInCacheError):
            mp.check_tx(b"a=1")
        mp.check_tx(b"b=1")
        with pytest.raises(MempoolFullError):
            mp.check_tx(b"c=1")
        assert _counter(
            mp.metrics.checktx_total, result="duplicate"
        ) == 1
        assert _counter(mp.metrics.checktx_total, result="full") == 1
        assert _counter(
            mp.metrics.checktx_total, result="accepted"
        ) == 2

    def test_in_pool_resubmission_counts_duplicate(self):
        """Cache hash evicted while the tx still sits in the pool: the
        resubmission re-runs the app but lands in the `duplicate`
        bucket — every admission outcome in exactly one bucket."""
        mp = _mempool(cache_size=1)  # 1-entry cache: evicts instantly
        mp.check_tx(b"a=1")
        mp.check_tx(b"b=1")  # evicts a's hash from the cache
        mp.check_tx(b"a=1")  # still in _txs: duplicate, not accepted
        assert mp.size() == 2
        assert _counter(
            mp.metrics.checktx_total, result="accepted"
        ) == 2
        assert _counter(
            mp.metrics.checktx_total, result="duplicate"
        ) == 1

    def test_recheck_update_semantics_unchanged(self):
        """Zero-regression satellite: committed signed txs leave the
        pool (and stay in the cache), recheck evicts newly-invalid
        ones, gauges track shrinkage — identical under the sharded
        cache."""
        mp = _mempool()
        txs = _signed(6, tag=b"upd")
        for tx in txs:
            mp.check_tx(tx)
        assert mp.size() == 6
        # commit txs[0:2]; app now rejects txs[2] at recheck
        mp._proxy.reject.add(txs[2])
        from cometbft_tpu.abci.types import ExecTxResult

        mp.lock()
        try:
            mp.update(
                1, txs[:2], [ExecTxResult(code=0), ExecTxResult(code=0)]
            )
        finally:
            mp.unlock()
        assert mp.size() == 3  # 6 - 2 committed - 1 recheck-evicted
        assert mp.cache.has(txs[0])  # committed stay cached
        assert not mp.contains(txs[2])
        assert _counter(mp.metrics.evicted_txs) == 1
        assert _counter(mp.metrics.recheck_times) == 1
        # a committed tx re-submitted is a duplicate, as before
        with pytest.raises(TxInCacheError):
            mp.check_tx(txs[0])


# -- sustained-load harness plumbing -------------------------------------


class TestSustainedHarness:
    def test_parse_ramp(self):
        assert parse_ramp("0:2") == [(0, 2.0)]
        assert parse_ramp("100:5, 500:5, 0:10") == [
            (100, 5.0), (500, 5.0), (0, 10.0),
        ]
        for bad in ("", "100", "100:0", "-1:5", "x:5"):
            with pytest.raises(ValueError):
                parse_ramp(bad)

    def test_closed_loop_counts_shed_not_error(self):
        """MempoolFullError / TxInCacheError are load shed — the
        harness must report them separately from real failures."""
        mp = _mempool(size=3)
        loader = SustainedLoader(
            submit=mp.check_tx, workers=2, signed=False
        )
        rep = loader.run(parse_ramp("0:0.4"))
        assert rep["errors"] == 0
        assert rep["accepted"] == 3  # cap
        assert rep["shed"] > 0  # everything past the cap shed
        assert rep["latency_p95_s"] > 0

    def test_open_loop_paces_rate(self):
        mp = _mempool()
        loader = SustainedLoader(
            submit=mp.check_tx, workers=2, signed=False
        )
        rep = loader.run([(40, 0.5)])
        # paced: roughly the requested rate, not saturation
        assert rep["steps"][0]["offered_per_sec"] <= 80


# -- the ingest-smoke node drive (make ingest-smoke) ---------------------


class TestIngestSmoke:
    def test_node_sheds_load_without_stalling(
        self, tmp_path, live_metrics, queue_guard
    ):
        """ISSUE 10 acceptance: a single-validator node under
        closed-loop admission saturation (signed txs, small mempool)
        commits strictly-increasing heights while admission SHEDS
        (nonzero MempoolFullError / duplicate counters) — degradation
        by load shed, never by consensus stall."""
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.config import test_config
        from cometbft_tpu.node import Node
        from cometbft_tpu.privval import FilePV
        from cometbft_tpu.types.genesis import (
            GenesisDoc,
            GenesisValidator,
        )

        pv = FilePV(ed.priv_key_from_secret(b"ingest-smoke-val"))
        gen = GenesisDoc(
            chain_id="ingest-smoke",
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=(GenesisValidator(pv.pub_key, 10),),
        )
        cfg = test_config(str(tmp_path))
        # cap far below what one commit interval of closed-loop
        # admission offers: saturation MUST overrun it and shed
        cfg.mempool.size = 8
        cfg.instrumentation.prometheus = True
        cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
        cfg.ensure_dirs()
        node = Node(cfg, app=KVStoreApp(), genesis=gen,
                    priv_validator=pv)
        node.start()
        try:
            h0 = node.height()
            loader = SustainedLoader(
                submit=lambda tx: node.mempool.check_tx(tx),
                workers=8, tx_size=128, signed=True,
            )
            result: dict = {}

            def drive():
                result.update(loader.run(parse_ramp("0:6")))

            t = threading.Thread(target=drive, daemon=True)
            t.start()
            heights = [h0]
            deadline = time.time() + 120
            while time.time() < deadline:
                h = node.height()
                if h > heights[-1]:
                    heights.append(h)
                if not t.is_alive() and h >= h0 + 3:
                    break
                time.sleep(0.05)
            t.join(timeout=60)
            assert result, "loader did not finish"
            # liveness: consensus kept committing under saturation
            assert heights[-1] >= h0 + 3, (
                f"heights stalled at {heights[-1]} under load "
                f"(loader: {result})"
            )
            assert all(b > a for a, b in zip(heights, heights[1:]))
            # the generator actually saturated admission...
            assert result["accepted"] > 0
            assert result["errors"] == 0, result
            # ...and the node degraded by SHEDDING: drop counters
            assert result["shed"] > 0, (
                f"no load shed at saturation: {result}"
            )
            # admission rode the device lane, visible on /metrics
            body = urllib.request.urlopen(
                f"http://127.0.0.1:{node.metrics_server.port}/metrics",
                timeout=5,
            ).read().decode()
            full = dup = accepted = batched = 0.0
            for line in body.splitlines():
                if line.startswith("cometbft_mempool_checktx_total{"):
                    val = float(line.rsplit(" ", 1)[1])
                    if 'result="full"' in line:
                        full = val
                    elif 'result="duplicate"' in line:
                        dup = val
                    elif 'result="accepted"' in line:
                        accepted = val
                elif line.startswith(
                    "cometbft_mempool_checktx_batched"
                ):
                    batched = float(line.rsplit(" ", 1)[1])
            assert accepted > 0
            assert full + dup > 0, "shed not visible in checktx_total"
            assert batched > 0, (
                "signed admission never used the ingest lane"
            )
        finally:
            node.stop()

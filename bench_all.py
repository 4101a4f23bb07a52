"""North-star benchmark matrix (BASELINE.md "North-star targets").

Measures the five driver-specified configurations through the REAL
verification paths (types/validation.verify_commit* -> crypto.batch ->
TpuBatchVerifier), not raw kernel calls:

  1. 64-sig BatchVerifier micro-bench
  2. VerifyCommit on a 150-validator commit (e2e latency)
  3. VerifyCommit on a 10k-validator commit (e2e latency; <2ms target
     is device-compute; the e2e number includes host sign-bytes
     encoding and link transfer)
  4. light-header sync: 150-validator commits verified at scale with
     pipelined launches (10k headers modeled; n_run actually measured)
  5. blocksync replay: 1k-validator commits, pipelined (1k blocks
     modeled; mixed ed25519+bls variant lands with the BLS backend)

Prints one JSON line per config and writes BENCH_ALL.json.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time

import numpy as np


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


CHAIN_ID = "bench-chain"


def make_commit_fixture(nvals: int):
    """Real valset + commit: every validator signs its canonical
    precommit bytes (the exact messages verify_commit reconstructs)."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    keys = [ed.priv_key_from_secret(b"bench%d" % i) for i in range(nvals)]
    vals = ValidatorSet(
        [Validator(k.pub_key(), 10) for k in keys]
    )
    by_addr = {k.pub_key().address(): k for k in keys}
    ordered = [by_addr[v.address] for v in vals.validators]
    h = bytes(range(32))
    bid = BlockID(
        hash=h, part_set_header=PartSetHeader(total=1, hash=h[::-1])
    )
    sigs = []
    for i, k in enumerate(ordered):
        ts = 1_700_000_000_000_000_000 + i
        msg = canonical.vote_sign_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, 1, 0, bid, ts
        )
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=k.pub_key().address(),
                timestamp_ns=ts,
                signature=k.sign(msg),
            )
        )
    commit = Commit(height=1, round=0, block_id=bid, signatures=tuple(sigs))
    return vals, commit, bid


def make_bls_aggregate_fixture(nvals: int):
    """A commit carrying ONE BLS aggregate signature over its
    BLOCK_ID_FLAG_COMMIT precommits (types/block.py Commit docstring):
    every validator signs the shared canonical aggregate message, the
    per-validator signature fields stay EMPTY, and verification is one
    pairing-product check — the arXiv:2302.00418 committee shape the
    ``bls_aggregate_150val`` row measures against ``verify_commit_150``."""
    from cometbft_tpu.crypto import bls12381 as bls
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    keys = [
        bls.priv_key_from_secret(b"agg%d" % i) for i in range(nvals)
    ]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    ordered = [by_addr[v.address] for v in vals.validators]
    h = bytes(range(32))
    bid = BlockID(
        hash=h, part_set_header=PartSetHeader(total=1, hash=h[::-1])
    )
    msg = Commit(height=1, round=0, block_id=bid).aggregate_sign_bytes(
        CHAIN_ID
    )
    agg = bls.aggregate_signatures([k.sign(msg) for k in ordered])
    sigs = tuple(
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=k.pub_key().address(),
            timestamp_ns=0,
            signature=b"",
        )
        for k in ordered
    )
    commit = Commit(
        height=1, round=0, block_id=bid, signatures=sigs,
        agg_signature=agg,
    )
    return vals, commit, bid


def make_mixed_commit_fixture(n_ed: int, n_bls: int):
    """A commit signed by n_ed ed25519 + n_bls bls12_381 validators
    (BASELINE config 5's mega-commit shape)."""
    from cometbft_tpu.crypto import bls12381 as bls
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    keys = [
        ed.priv_key_from_secret(b"med%d" % i) for i in range(n_ed)
    ] + [
        bls.priv_key_from_secret(b"mbls%d" % i) for i in range(n_bls)
    ]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    ordered = [by_addr[v.address] for v in vals.validators]
    h = bytes(range(32))
    bid = BlockID(
        hash=h, part_set_header=PartSetHeader(total=1, hash=h[::-1])
    )
    sigs = []
    for i, k in enumerate(ordered):
        ts = 1_700_000_000_000_000_000 + i
        msg = canonical.vote_sign_bytes(
            CHAIN_ID, canonical.PRECOMMIT_TYPE, 1, 0, bid, ts
        )
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=k.pub_key().address(),
                timestamp_ns=ts,
                signature=k.sign(msg),
            )
        )
    commit = Commit(height=1, round=0, block_id=bid, signatures=tuple(sigs))
    return vals, commit, bid


def merge_results(
    path: str, results: list[dict], replace_if=None, **doc_fields
) -> None:
    """Merge ``results`` into a BENCH_ALL-shaped JSON file atomically.

    Existing entries are kept unless ``replace_if(existing_row)`` says
    this write owns them (default: same config name). ONE
    implementation for every bench tool — bench_all, loadtime, and the
    host-baseline tool all write the same file."""
    if replace_if is None:
        ours = {r["config"] for r in results}

        def replace_if(row):  # noqa: F811 — default policy
            return row.get("config") in ours

    try:
        with open(path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"results": []}
    doc["results"] = [
        r for r in doc.get("results", []) if not replace_if(r)
    ] + results
    doc.update(doc_fields)
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1)
    os.replace(tmp, path)


def timed(fn, warmups: int = 1, iters: int = 3) -> float:
    for _ in range(warmups):
        fn()
    best = float("inf")
    for _ in range(iters):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def main() -> None:
    import jax

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.metrics import CryptoMetrics, install_crypto_metrics
    from cometbft_tpu.ops.ed25519_verify import (
        TpuBatchVerifier,
        verify_stream,
    )
    from cometbft_tpu.types import validation
    from cometbft_tpu.utils.metrics import Registry

    # live crypto metrics for the run: every row's provenance records
    # the dispatch tier(s) the config ACTUALLY hit (keyed_mesh / keyed
    # / generic / host) — BENCH_ALL previously couldn't tell a keyed
    # measurement from a generic one, which is how the perf trajectory
    # kept quoting the generic kernel by accident
    from cometbft_tpu.ops import jitguard as _jg

    cm = CryptoMetrics(Registry())
    install_crypto_metrics(cm)
    tier_seen: dict[str, float] = {}
    compiles_seen: dict[str, int] = {}

    def compiles_delta() -> dict[str, int]:
        # per-seam jit compiles since the last record: a nonzero delta
        # on a row measured AFTER its warmup means the "steady state"
        # recompiled mid-measurement (docs/device_contracts.md)
        now = _jg.compile_counts()
        delta = {
            s: int(c - compiles_seen.get(s, 0))
            for s, c in now.items()
            if c > compiles_seen.get(s, 0)
        }
        compiles_seen.clear()
        compiles_seen.update(now)
        return delta

    def tier_delta() -> dict[str, int]:
        now = {
            k[0]: c.get() for k, c in cm.dispatch_tier.children().items()
        }
        delta = {
            t: int(v - tier_seen.get(t, 0))
            for t, v in now.items()
            if v > tier_seen.get(t, 0)
        }
        tier_seen.clear()
        tier_seen.update(now)
        return delta

    dev = jax.devices()[0]
    on_cpu = dev.platform == "cpu"
    log(f"device: {dev}")
    results = []
    path = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "BENCH_ALL.json"
    )

    def checkpoint():
        # merge-write after every config: a mid-run death (r3 lost the
        # mixed-megacommit entry this way) keeps what was measured.
        # Entries other tools own are preserved: loadtime_* by config
        # name, and the host-dispatch rows (host_path) even when they
        # share a config name with a device measurement
        ours = {r["config"] for r in results}
        merge_results(
            path, results,
            replace_if=lambda r: (
                r.get("config") in ours and not r.get("host_path")
            ),
            device=str(dev),
        )

    # attribution plane: sample the whole matrix once, window each
    # row's hotspots to the interval since the previous record — the
    # row's provenance says what the host CPU ran while it measured
    prof = None
    try:
        from cometbft_tpu.utils.profiler import SamplingProfiler

        prof = SamplingProfiler(hz=97, capacity=8192)
        prof.start()
    except Exception as exc:  # noqa: BLE001 — provenance only
        log(f"profiler unavailable (continuing without): {exc}")
    last_record = [time.time()]

    def record(config: str, value: float, unit: str, **extra):
        row = {"config": config, "value": round(value, 2), "unit": unit}
        row.update(extra)
        # winning tier = the most-hit tier since the last record; the
        # stream configs dispatch outside the verifier seam and pass an
        # explicit dispatch_tier instead
        tiers = tier_delta()
        if tiers and "dispatch_tier" not in row:
            row["dispatch_tier"] = max(tiers, key=tiers.get)
            row["dispatch_tiers"] = tiers
        compiles = compiles_delta()
        if compiles:
            row["jit_compiles"] = compiles
        if prof is not None:
            try:
                window = max(time.time() - last_record[0], 0.0)
                hot = prof.top_functions(5, seconds=window)
                if hot:
                    row["hotspots"] = hot
            except Exception:  # noqa: BLE001 — provenance only
                pass
        last_record[0] = time.time()
        row["measured"] = time.strftime("round 6, %Y-%m-%d")
        results.append(row)
        print(json.dumps(row), flush=True)
        checkpoint()
        # every measured row lands in the perf ledger with its
        # provenance (tier, compiles, hotspots) — the regression
        # gate's input
        from tools import perfledger

        perfledger.append_rows([row], source="bench_all")

    # ---- config 1: 64-sig micro-bench --------------------------------
    # PRODUCTION dispatch: the runtime threshold routes a 64-sig batch
    # wherever a real caller's batch would go (on a high-RTT link
    # that's the host batch verifier — measuring the forced-device
    # path here would record a path no caller takes; r4 verdict #3)
    rng = np.random.RandomState(7)
    priv = ed.gen_priv_key()
    msgs64 = [rng.bytes(120) for _ in range(64)]
    sigs64 = [priv.sign(m) for m in msgs64]
    pub = priv.pub_key()

    def micro():
        bv = TpuBatchVerifier()
        for m, s in zip(msgs64, sigs64):
            bv.add(pub, m, s)
        ok, bits = bv.verify()
        assert ok, "micro-bench sigs must verify"

    from cometbft_tpu.ops.ed25519_verify import runtime_device_min_batch

    threshold = runtime_device_min_batch()
    dt = timed(micro)
    record(
        "micro_64sig", 64 / dt, "sigs/sec", latency_ms=round(dt * 1e3, 2),
        dispatch=(
            "host batch verifier" if 64 < threshold else "device kernel"
        ),
        device_min_batch=threshold if threshold < (1 << 30) else "inf",
    )

    # forced-device variant: kernel+link progress stays visible even
    # when the production threshold keeps this size on the CPU
    def micro_device():
        bv = TpuBatchVerifier(device_min_batch=1)
        for m, s in zip(msgs64, sigs64):
            bv.add(pub, m, s)
        ok, _ = bv.verify()
        assert ok

    dt = timed(micro_device)
    record(
        "micro_64sig_device", 64 / dt, "sigs/sec",
        latency_ms=round(dt * 1e3, 2),
    )

    # ---- config 2: VerifyCommit @ 150 validators ---------------------
    t0 = time.time()
    vals150, commit150, bid150 = make_commit_fixture(150)
    log(f"150-val fixture in {time.time() - t0:.1f}s")

    def vc150():
        validation.verify_commit(CHAIN_ID, vals150, bid150, 1, commit150)

    # production routing: the runtime dispatch threshold decides (on a
    # high-RTT link a single 150-sig commit stays on the CPU batch
    # path — types/validation.go:15 shouldBatchVerify semantics)
    dt = timed(vc150)
    record(
        "verify_commit_150", dt * 1e3, "ms",
        sigs_per_sec=round(150 / dt, 1),
    )
    # device-forced variant (same rationale as micro_64sig_device)
    prior = os.environ.get("CMT_TPU_DEVICE_MIN_BATCH")
    os.environ["CMT_TPU_DEVICE_MIN_BATCH"] = "1"
    try:
        dt = timed(vc150)
        record(
            "verify_commit_150_device", dt * 1e3, "ms",
            sigs_per_sec=round(150 / dt, 1),
        )
    finally:
        if prior is None:
            del os.environ["CMT_TPU_DEVICE_MIN_BATCH"]
        else:
            os.environ["CMT_TPU_DEVICE_MIN_BATCH"] = prior

    # warm-table variant: the device-forced run above built the
    # 150-val set's comb tables, so PRODUCTION routing now takes the
    # keyed tier even below the generic batch threshold (the
    # keyed-by-default promotion; reason=keyed_warm) — on a no-device
    # box the row honestly records tier=host instead
    dt = timed(vc150)
    record(
        "verify_commit_150_warm", dt * 1e3, "ms",
        sigs_per_sec=round(150 / dt, 1),
    )

    # ---- config 2b: BLS aggregate commit @ 150 validators ------------
    # The side-by-side the ISSUE 13 acceptance pins: the SAME 150-vote
    # commit shape, carried as one BLS aggregate signature instead of
    # 150 ed25519 signatures — one pairing-product check
    # (crypto/bls_dispatch.py, e(agg_pk, H(m)) == e(g1, agg_sig))
    # against verify_commit_150's batch.  timed()'s warmup builds the
    # native lib and warms the aggregate-pubkey LRU, so the measured
    # steady state is the serving-plane shape: repeated commits from a
    # stable validator set, each paying exactly one pairing.
    t0 = time.time()
    vals_agg, commit_agg, bid_agg = make_bls_aggregate_fixture(150)
    log(f"150-val BLS aggregate fixture in {time.time() - t0:.1f}s")

    def vc_agg():
        validation.verify_commit(CHAIN_ID, vals_agg, bid_agg, 1, commit_agg)

    dt = timed(vc_agg)
    record(
        "bls_aggregate_150val", dt * 1e3, "ms",
        sigs_per_sec=round(150 / dt, 1),
        pairing_checks=1,
        baseline="verify_commit_150",
    )

    # ---- config 3: VerifyCommit @ 10k validators ---------------------
    nbig = 1000 if on_cpu else 10_000
    t0 = time.time()
    vals10k, commit10k, bid10k = make_commit_fixture(nbig)
    log(f"{nbig}-val fixture in {time.time() - t0:.1f}s")

    def vc10k():
        validation.verify_commit(CHAIN_ID, vals10k, bid10k, 1, commit10k)

    dt = timed(vc10k)
    record(
        f"verify_commit_{nbig}", dt * 1e3, "ms",
        sigs_per_sec=round(nbig / dt, 1), target_ms=2.0,
    )

    # ---- configs 4+5: pipelined multi-commit throughput --------------
    # The replay planes (light sync, blocksync) verify many independent
    # commits; the node drives them through verify_stream so launches
    # overlap.  Jobs are grouped to fill device batches.
    def stream_config(name, vals, commit, n_commits, modeled):
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.ops.ed25519_verify import (
            verify_arrays_keyed_async,
        )

        nsig = commit.size()
        pub_bytes = [
            vals.get_by_index(i).pub_key.bytes() for i in range(nsig)
        ]
        pubs = np.stack(
            [np.frombuffer(p, dtype=np.uint8) for p in pub_bytes]
        )
        sigs = np.stack(
            [
                np.frombuffer(cs.signature, dtype=np.uint8)
                for cs in commit.signatures
            ]
        )
        msgs = [
            commit.vote_sign_bytes(CHAIN_ID, i) for i in range(nsig)
        ]
        group = max(1, 4096 // nsig)  # commits per launch

        # stream through the per-validator precomputed tables — the
        # same hot path a replaying node gets via the batch seam; the
        # one-time table build happens before the clock starts.
        dispatch = None
        entry = PR.TABLE_CACHE.lookup_or_build(pub_bytes)
        if entry is not None:
            key_ids1 = entry.key_ids(pub_bytes)

            def dispatch(pub, sig, ms, _e=entry, _k=key_ids1):
                k = len(ms) // nsig
                return verify_arrays_keyed_async(
                    _e, np.concatenate([_k] * k), pub, sig, ms
                )

        def jobs():
            done = 0
            while done < n_commits:
                k = min(group, n_commits - done)
                yield (
                    np.concatenate([pubs] * k),
                    np.concatenate([sigs] * k),
                    msgs * k,
                )
                done += k

        t0 = time.perf_counter()
        total = 0
        for res in verify_stream(jobs(), max_in_flight=8,
                                 dispatch=dispatch):
            assert bool(res.all())
            total += len(res)
        dt = time.perf_counter() - t0
        extra = dict(
            commits_per_sec=round(n_commits / dt, 1),
            n_commits_run=n_commits,
            path="keyed" if dispatch is not None else "generic",
            # the stream path dispatches below the verifier seam, so
            # its tier is declared rather than metric-derived
            dispatch_tier="keyed" if dispatch is not None else "generic",
        )
        if modeled != n_commits:
            # only a CPU smoke run extrapolates; a device run measures
            # the full count and carries no modeling caveat
            extra["n_commits_modeled"] = modeled
        record(name, total / dt, "sigs/sec", **extra)

    # full modeled counts on the accelerator — nothing extrapolated
    n4 = 64 if on_cpu else 10_000
    stream_config("light_sync_150val", vals150, commit150, n4, 10_000)
    vals1k, commit1k, bid1k = make_commit_fixture(
        128 if on_cpu else 1000
    )
    n5 = 16 if on_cpu else 1000
    stream_config("blocksync_replay_1kval", vals1k, commit1k, n5, 1000)

    # ---- configs 4b+5b: the same replay workloads through the verify
    # queue (crypto/verify_queue.py) — commits submitted as batched
    # requests, the collector's host prep (prehash + plan/pack)
    # overlapping the launcher's in-flight batch.  The sync stream rows
    # above are the baselines tools/perfdiff.py gates these against;
    # the tier is metric-derived (the queue dispatches through the
    # production verifier seam) and the overlap ratio rides along.
    from cometbft_tpu.crypto import verify_queue as vqmod

    def queue_config(name, vals, commit, n_commits):
        nsig = commit.size()
        pks = [vals.get_by_index(i).pub_key for i in range(nsig)]
        msgs = [
            commit.vote_sign_bytes(CHAIN_ID, i) for i in range(nsig)
        ]
        items = [
            (pk, m, cs.signature)
            for pk, m, cs in zip(pks, msgs, commit.signatures)
        ]
        # cache OFF: every submitted commit re-verifies honestly;
        # max_batch = one commit per buffer so the measured shape IS
        # the double-buffered pipeline
        q = vqmod.VerifyQueue(use_cache=False, max_batch=nsig)
        q.start()
        try:
            t0 = time.perf_counter()
            futs = []
            for _ in range(n_commits):
                futs.extend(q.submit_many(items))
            assert all(f.result(600) for f in futs), (
                "queue bench sigs must verify"
            )
            dt = time.perf_counter() - t0
            overlap = q.stats()["overlap_ratio"]
        finally:
            q.stop()
        record(
            name, nsig * n_commits / dt, "sigs/sec",
            commits_per_sec=round(n_commits / dt, 1),
            n_commits_run=n_commits,
            overlap_ratio=overlap,
        )

    queue_config("light_sync_150val_pipelined", vals150, commit150, n4)
    queue_config(
        "blocksync_replay_1kval_pipelined", vals1k, commit1k, n5
    )

    # ---- configs 6a-c: device-batched CheckTx admission (ISSUE 10) ---
    # The ingest plane end to end: signed-envelope txs through
    # CListMempool.check_tx, once with the VerifyQueue OFF (the inline
    # host baseline — one pubkey.verify_signature per tx) and once
    # with the queue's ingest micro-batcher coalescing concurrent
    # admissions into DispatchLadder launches.  perfdiff gates
    # checktx_batched against checktx_host from the ledger; the
    # sustained row records what the closed-loop harness achieves at
    # saturation with admission latency percentiles.
    from cometbft_tpu.abci.types import CheckTxResponse as _CTResp
    from cometbft_tpu.loadtime import SustainedLoader
    from cometbft_tpu.mempool import CListMempool
    from cometbft_tpu.mempool import ingest as mingest

    class _NullProxy:
        """Admission-only app: the rows measure the mempool's own
        plane (cache, signature, bookkeeping), not kvstore parsing."""

        def check_tx(self, req):
            return _CTResp(gas_wanted=1)

    ct_privs = [
        ed.priv_key_from_secret(b"bench-checktx-%d" % i)
        for i in range(16)
    ]

    def signed_txs(n, tag):
        return [
            mingest.make_signed_tx(
                ct_privs[i % len(ct_privs)], b"%s-%d=v" % (tag, i)
            )
            for i in range(n)
        ]

    def fresh_mempool(capacity):
        return CListMempool(
            _NullProxy(), size=capacity + 16,
            cache_size=2 * capacity + 32,
        )

    # 6a: inline host baseline (queue not installed)
    n_host = 64 if on_cpu else 4096
    host_txs = signed_txs(n_host, b"host")
    mp = fresh_mempool(n_host)
    t0 = time.perf_counter()
    for txb in host_txs:
        mp.check_tx(txb)
    dt = time.perf_counter() - t0
    record(
        "checktx_host", n_host / dt, "tx/sec",
        n_txs=n_host, latency_ms=round(dt / n_host * 1e3, 3),
        dispatch="inline pubkey.verify_signature per tx",
    )

    # 6b: the ingest lane — concurrent submitters, coalesced launches.
    # Each submitter blocks on its own CheckTx (the RPC thread shape),
    # so the achievable coalesce width IS the submitter count; a 25 ms
    # accumulation window lets batches fill to where the per-launch
    # seam cost amortizes (the production 5 ms default favors latency;
    # the row records the knob it measured)
    n_batched = 1024 if on_cpu else 16384
    ct_wait_ms = 25
    batched_txs = signed_txs(n_batched, b"batched")
    mp = fresh_mempool(n_batched)
    q = vqmod.VerifyQueue(checktx_wait_ms=ct_wait_ms)
    q.start()
    vqmod.install_queue(q)
    try:
        import queue as _queue

        work: _queue.SimpleQueue = _queue.SimpleQueue()
        for txb in batched_txs:
            work.put(txb)
        errors: list = []

        def drain():
            while True:
                try:
                    txb = work.get_nowait()
                except _queue.Empty:
                    return
                try:
                    mp.check_tx(txb)
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

        nworkers = 128
        t0 = time.perf_counter()
        threads = [
            threading.Thread(target=drain, daemon=True)
            for _ in range(nworkers)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        dt = time.perf_counter() - t0
        assert not errors, f"checktx_batched rejected txs: {errors[:3]}"
        assert mp.size() == n_batched
        qstats = q.stats()
    finally:
        q.stop()
    record(
        "checktx_batched", n_batched / dt, "tx/sec",
        n_txs=n_batched, workers=nworkers,
        checktx_wait_ms=ct_wait_ms,
        ingest_batches=qstats["launched_batches"],
        avg_ingest_batch=round(
            qstats["launched_sigs"]
            / max(1, qstats["launched_batches"]), 1,
        ),
    )

    # 6c: the closed-loop sustained harness at saturation
    mp = fresh_mempool(1 << 20)
    q = vqmod.VerifyQueue()
    q.start()
    vqmod.install_queue(q)
    try:
        loader = SustainedLoader(
            submit=mp.check_tx, workers=8, signed=True,
        )
        rep = loader.run([(0, 2.0 if on_cpu else 10.0)])
    finally:
        q.stop()
    record(
        "checktx_sustained", rep["accepted_per_sec"], "tx/sec",
        shed=rep["shed"], errors=rep["errors"],
        latency_p50_ms=round(rep["latency_p50_s"] * 1e3, 2),
        latency_p95_ms=round(rep["latency_p95_s"] * 1e3, 2),
    )

    # ---- config 7: the light-client serving plane at 10k clients -----
    # The ISSUE 13 heavy-traffic scenario end to end: a header chain
    # served through light/serve.LightHeaderServer with the verify
    # queue's light_client lane underneath (micro-batched cross-client
    # coalescing) and the trust-period-aware header cache in front,
    # driven by loadtime.LightSyncLoader simulating 10k client
    # sessions.  The first pass verifies every header (launches); the
    # sustained phase measures the serving shape — repeat syncs riding
    # the header cache — with p50/p95 per request and headers/s.
    from cometbft_tpu.light.provider import Provider as _Provider
    from cometbft_tpu.light.serve import LightHeaderServer
    from cometbft_tpu.loadtime import LightSyncLoader
    from cometbft_tpu.metrics import LightMetrics, install_light_metrics
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT as _FLAG_COMMIT,
        BlockID as _BlockID,
        Commit as _Commit,
        CommitSig as _CommitSig,
        Header as _Header,
        PartSetHeader as _PSH,
    )
    from cometbft_tpu.types.light_block import (
        LightBlock as _LightBlock,
        SignedHeader as _SignedHeader,
    )
    from cometbft_tpu.types import canonical as _canonical
    from cometbft_tpu.types.validator import (
        Validator as _Validator,
        ValidatorSet as _ValidatorSet,
    )

    lm = LightMetrics(Registry())
    install_light_metrics(lm)
    n_heights = 6 if on_cpu else 32
    n_lvals = 20 if on_cpu else 150
    t0 = time.time()
    lkeys = [
        ed.priv_key_from_secret(b"light%d" % i) for i in range(n_lvals)
    ]
    lvals = _ValidatorSet([_Validator(k.pub_key(), 10) for k in lkeys])
    l_by_addr = {k.pub_key().address(): k for k in lkeys}
    l_ordered = [l_by_addr[v.address] for v in lvals.validators]
    lvh = lvals.hash()
    now_ns_ = time.time_ns()
    lblocks = {}
    for hh in range(1, n_heights + 1):
        hdr = _Header(
            chain_id=CHAIN_ID, height=hh,
            time_ns=now_ns_ - (n_heights - hh) * 1_000_000_000,
            validators_hash=lvh, next_validators_hash=lvh,
            proposer_address=l_ordered[0].pub_key().address(),
        )
        hhash = hdr.hash()
        lbid = _BlockID(
            hash=hhash, part_set_header=_PSH(total=1, hash=hhash[:32])
        )
        lsigs = []
        for i, k in enumerate(l_ordered):
            ts = now_ns_ + i
            m = _canonical.vote_sign_bytes(
                CHAIN_ID, _canonical.PRECOMMIT_TYPE, hh, 0, lbid, ts
            )
            lsigs.append(
                _CommitSig(
                    block_id_flag=_FLAG_COMMIT,
                    validator_address=k.pub_key().address(),
                    timestamp_ns=ts, signature=k.sign(m),
                )
            )
        lblocks[hh] = _LightBlock(
            signed_header=_SignedHeader(
                header=hdr,
                commit=_Commit(
                    height=hh, round=0, block_id=lbid,
                    signatures=tuple(lsigs),
                ),
            ),
            validator_set=lvals,
        )
    log(
        f"light chain fixture ({n_heights}h x {n_lvals}v) "
        f"in {time.time() - t0:.1f}s"
    )

    class _FixtureProvider(_Provider):
        def chain_id(self):
            return CHAIN_ID

        def light_block(self, height):
            return lblocks[height]

    q = vqmod.VerifyQueue(light_wait_ms=3)
    q.start()
    vqmod.install_queue(q)
    try:
        server = LightHeaderServer(CHAIN_ID, _FixtureProvider())
        loader = LightSyncLoader(
            sync=server.sync_range, clients=10_000, workers=16,
            span=4, chain_from=1, chain_to=n_heights,
        )
        rep = loader.run(3.0 if on_cpu else 10.0)
        qstats = q.stats()
    finally:
        q.stop()
    assert rep["errors"] == 0, (
        f"light_serve_sustained loader errors: {rep['errors']}"
    )
    record(
        "light_serve_sustained", rep["headers_per_sec"], "headers/sec",
        clients=rep["clients"], workers=rep["workers"],
        requests=rep["requests"], errors=rep["errors"],
        latency_p50_ms=round(rep["latency_p50_s"] * 1e3, 3),
        latency_p95_ms=round(rep["latency_p95_s"] * 1e3, 3),
        cache_hit_rate=rep["cache_hit_rate"],
        light_lane_submitted=qstats["submitted"]["light_client"],
        n_heights=n_heights, n_validators=n_lvals,
    )

    # ---- config 5: mixed ed25519 + bls12381 mega-commit --------------
    # One commit whose validators mix both key types; verify_commit's
    # per-key-type grouping sends ed25519 votes to the batch kernel and
    # BLS votes through the RLC multi-pairing (one shared Miller loop).
    # The BLS plane is host-side Python (tower pairing,
    # crypto/bls12381.py), so this measures the real deliverable — no
    # extrapolation: ONE full verification is timed.
    total_mixed = 100 if on_cpu else 10_000
    n_bls = min(
        total_mixed,
        int(os.environ.get("CMT_BENCH_BLS_N", "16" if on_cpu else "1000")),
    )
    n_ed = total_mixed - n_bls
    t0 = time.time()
    vals_mixed, commit_mixed, bid_mixed = make_mixed_commit_fixture(
        n_ed, n_bls
    )
    log(
        f"mixed fixture ({n_ed} ed25519 + {n_bls} bls) "
        f"in {time.time() - t0:.1f}s"
    )
    t0 = time.perf_counter()
    validation.verify_commit(
        CHAIN_ID, vals_mixed, bid_mixed, 1, commit_mixed
    )
    dt = time.perf_counter() - t0
    record(
        "mixed_megacommit", dt * 1e3, "ms",
        n_ed25519=n_ed, n_bls=n_bls,
        sigs_per_sec=round((n_ed + n_bls) / dt, 1),
    )

    checkpoint()
    if prof is not None:
        prof.stop()
    log("wrote BENCH_ALL.json")


if __name__ == "__main__":
    main()

"""Headline benchmark: Ed25519 batch-verify throughput on one chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.
Baseline (BASELINE.json north star): 1M verifies/sec on one TPU v5e.

Measures steady-state *pipelined* throughput — several launches kept in
flight so host->device transfer overlaps device compute, the way the
node's replay paths (blocksync, light sync) drive the kernel.  Sync
single-launch latency is logged to stderr alongside.

One process, in-process JAX: the benchmark needs an accelerator and
exits non-zero without one — a number taken on the CPU backend is never
printed under the device metric's name.  The KEYED section (the
production commit-verify path, the headline) is measured first.  The
XLA compile cache is the one cometbft_tpu/ops/__init__.py configures.
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
BASELINE_SIGS_PER_SEC = 1_000_000
METRIC = "ed25519_batch_verify_throughput"

#: span-trace provenance for the run (utils/trace Chrome trace-event
#: JSON, openable in Perfetto) — every device launch the bench made,
#: with timings, next to the headline number
TRACE_PATH = os.environ.get(
    "CMT_BENCH_TRACE", os.path.join(REPO, "BENCH_TRACE.json")
)


def _dump_trace() -> None:
    """Best-effort: write the in-process span ring to TRACE_PATH."""
    try:
        from cometbft_tpu.utils.trace import TRACER

        TRACER.dump(TRACE_PATH)
        log(f"trace written to {TRACE_PATH}")
    except Exception as exc:  # noqa: BLE001 — provenance must not
        log(f"trace dump failed (ignored): {exc}")  # fail the bench


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


#: span-name prefixes that make up the height pipeline
#: (docs/observability.md "Reading a height pipeline trace")
_PIPELINE_PREFIXES = (
    "height/", "consensus/", "exec/", "abci/", "wal/", "store/",
    "indexer/",
)


def _height_pipeline_provenance(n_heights: int = 3) -> dict:
    """Boot a single-validator node stub, commit ``n_heights``, and
    aggregate the per-stage height-pipeline spans into
    ``{stage: {count, total_ms, mean_ms}}`` — the BENCH provenance
    answer to "where does a committed height spend its time" on this
    machine (set CMT_BENCH_PIPELINE=0 to skip).  Best-effort: any
    failure is reported in the dict, never raised."""
    import tempfile

    try:
        from cometbft_tpu.abci.kvstore import KVStoreApp
        from cometbft_tpu.config import test_config
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.node import Node
        from cometbft_tpu.privval import FilePV
        from cometbft_tpu.types.genesis import (
            GenesisDoc,
            GenesisValidator,
        )
        from cometbft_tpu.utils.time import now_ns
        from cometbft_tpu.utils.trace import TRACER

        with tempfile.TemporaryDirectory(prefix="bench-pipeline-") as home:
            pv = FilePV(ed.priv_key_from_secret(b"bench-pipeline"))
            gen = GenesisDoc(
                chain_id="bench-pipeline",
                genesis_time_ns=now_ns(),
                validators=(GenesisValidator(pv.pub_key, 10),),
            )
            cfg = test_config(home)
            cfg.base.db_backend = "sqlite"  # real WAL -> wal/* spans
            cfg.ensure_dirs()
            # time cutoff, not a length offset: the bounded ring may
            # already be full of the bench's own crypto spans, and a
            # length mark misaligns as soon as it wraps
            cutoff_us = (time.perf_counter() - TRACER.epoch) * 1e6
            node = Node(cfg, app=KVStoreApp(), genesis=gen,
                        priv_validator=pv)
            node.start()
            try:
                deadline = time.time() + 60
                while time.time() < deadline and node.height() < n_heights:
                    time.sleep(0.05)
                reached = node.height()
            finally:
                node.stop()
            stages: dict[str, dict] = {}
            for ev in TRACER.events():
                if ev.get("ts", 0.0) < cutoff_us:
                    continue
                name = ev.get("name", "")
                if not name.startswith(_PIPELINE_PREFIXES):
                    continue
                st = stages.setdefault(
                    name, {"count": 0, "total_ms": 0.0}
                )
                st["count"] += 1
                st["total_ms"] += ev.get("dur", 0.0) / 1e3
            for st in stages.values():
                st["total_ms"] = round(st["total_ms"], 3)
                st["mean_ms"] = round(st["total_ms"] / st["count"], 3)
            return {"heights": reached, "stages": stages}
    except Exception as exc:  # noqa: BLE001 — provenance must not
        return {"error": f"{type(exc).__name__}: {exc}"}  # fail the bench


def _start_profiler():
    """Best-effort: a dedicated sampling profiler for this bench
    process (utils/profiler.py) so every measured row carries its
    top-k leaf hotspots as ledger provenance — what the number was
    spending its host CPU on.  97 Hz (prime) is cheap against a
    multi-second bench and fine-grained enough to rank hotspots."""
    try:
        from cometbft_tpu.utils.profiler import SamplingProfiler

        p = SamplingProfiler(hz=97, capacity=8192)
        p.start()
        return p
    except Exception as exc:  # noqa: BLE001 — provenance only
        log(f"bench profiler unavailable (ignored): {exc}")
        return None


def _attach_hotspots(p, *rows: dict, k: int = 5) -> None:
    """Stop ``p`` and record its top-k hotspots on each row (the
    ``hotspots`` provenance key tools/perfledger.py carries)."""
    if p is None:
        return
    try:
        p.stop()
        hot = p.top_functions(k)
        if hot:
            for r in rows:
                r.setdefault("hotspots", hot)
    except Exception as exc:  # noqa: BLE001 — provenance only
        log(f"hotspot attach failed (ignored): {exc}")


def _base_result(value: float, platform: str) -> dict:
    """The headline JSON shape — ONE definition for every path."""
    return {
        "metric": METRIC,
        "value": round(value, 1),
        "unit": "sigs/sec",
        "vs_baseline": round(value / BASELINE_SIGS_PER_SEC, 4),
        "platform": platform,
    }


def main() -> dict:
    _bench_prof = _start_profiler()
    import jax

    from cometbft_tpu.utils.trace import TRACER as _tr
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import jitguard as _jg
    from cometbft_tpu.ops.ed25519_verify import (
        _finish,
        verify_arrays,
        verify_arrays_async,
        verify_stream,
    )

    import numpy as np

    from contextlib import contextmanager

    # provenance: per-seam compile counts during warmup, and any
    # compile observed DURING a measured steady-state section — the
    # number future perf PRs assert to be zero (a steady-state retrace
    # is a silent multi-second stall; docs/device_contracts.md)
    steady_retraces: dict[str, int] = {}

    @contextmanager
    def _measured(section: str):
        before = sum(_jg.compile_counts().values())
        yield
        delta = sum(_jg.compile_counts().values()) - before
        steady_retraces[section] = steady_retraces.get(section, 0) + delta
        if delta:
            log(f"WARNING: {delta} recompile(s) during measured "
                f"section '{section}' — steady state is not steady")

    dev = jax.devices()[0]
    log(f"device: {dev}")
    if dev.platform == "cpu":
        raise SystemExit(
            "bench.py needs an accelerator: jax.devices()[0].platform is "
            "'cpu', and a CPU number is never reported as " + METRIC
        )

    n = int(os.environ.get("CMT_BENCH_N", "4096"))
    nchunks = int(os.environ.get("CMT_BENCH_NCHUNKS", "8"))
    msglen = 120
    rng = np.random.RandomState(0)
    priv = ed.gen_priv_key()
    pub_b = np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8)
    msgs = [
        rng.randint(0, 256, size=msglen, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]
    t0 = time.time()
    sigs = np.stack(
        [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
    )
    pubs = np.tile(pub_b, (n, 1))
    log(f"signed {n} msgs in {time.time() - t0:.2f}s (host)")

    def make_result(generic: float, keyed: float, note: str | None) -> dict:
        result = _base_result(max(generic, keyed), dev.platform)
        result["generic_sigs_per_sec"] = round(generic, 1)
        result["keyed_sigs_per_sec"] = round(keyed, 1)
        if keyed > generic:
            result["path"] = (
                "steady-state keyed (per-validator device-resident comb "
                "tables, 150-validator set round-robin)"
            )
        if note:
            result["note"] = note
        return result

    # Steady-state KEYED throughput — measured FIRST because it is the
    # headline: the production path for commit verification.
    # Per-validator comb tables live on
    # device in the LRU (ops/precompute.py; reference analog: the
    # expanded-pubkey cache, crypto/ed25519/ed25519.go:43,62-68), so
    # block after block the kernel does only SHA-512 + R decompress +
    # comb adds against hot tables.  Shape mirrors BASELINE: a
    # 150-validator set signing round-robin, streamed the way
    # blocksync/light-sync replay does.
    generic_best = 0.0
    keyed_best = 0.0
    keyed_cfg = None
    note = None
    try:
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.ops.ed25519_verify import (
            verify_arrays_keyed_async,
        )

        nval = int(os.environ.get("CMT_BENCH_NVAL", "150"))
        privs = [ed.gen_priv_key() for _ in range(nval)]
        pubs_b = [p.pub_key().bytes() for p in privs]
        t0 = time.time()
        entry = PR.TABLE_CACHE.lookup_or_build(pubs_b)
        np.asarray(jax.device_get(entry.table[0, 0, :4]))
        log(
            f"keyed tables: {nval} keys, {entry.window_bits}-bit, "
            f"{entry.set_nbytes / 1e6:.0f} MB this set "
            f"({entry.nbytes / 1e6:.0f} MB pool), built in "
            f"{time.time() - t0:.1f}s"
        )
        sel = [pubs_b[i % nval] for i in range(n)]
        kmsgs = [
            rng.randint(0, 256, size=msglen, dtype=np.uint8).tobytes()
            for _ in range(n)
        ]
        ksigs = np.stack(
            [
                np.frombuffer(privs[i % nval].sign(m), dtype=np.uint8)
                for i, m in enumerate(kmsgs)
            ]
        )
        kpubs = np.stack(
            [np.frombuffer(p, dtype=np.uint8) for p in sel]
        )
        key_ids = entry.key_ids(sel)

        def keyed_dispatch(pub, sig, msgs):
            return verify_arrays_keyed_async(
                entry, key_ids, pub, sig, msgs
            )

        def measure_keyed(label: str) -> float:
            t0 = time.time()
            out = _finish(keyed_dispatch(kpubs, ksigs, kmsgs))
            log(f"first keyed launch [{label}] {time.time() - t0:.1f}s")
            assert bool(out.all()), (
                "keyed benchmark signatures must verify"
            )
            best = 0.0
            for trial in range(3):
                t0 = time.time()
                total = 0
                with _measured(f"keyed_{label}"):
                    for res in verify_stream(
                        ((kpubs, ksigs, kmsgs) for _ in range(nchunks)),
                        max_in_flight=nchunks,
                        dispatch=keyed_dispatch,
                    ):
                        assert bool(res.all())
                        total += len(res)
                dt = time.time() - t0
                rate = total / dt
                log(
                    f"keyed [{label}] trial {trial}: {total} sigs in "
                    f"{dt * 1e3:.1f} ms = {rate:,.0f} sigs/s"
                )
                best = max(best, rate)
            return best

        from cometbft_tpu.ops import ed25519_verify as EV
        from cometbft_tpu.ops import field as F

        # the baseline core is whatever the env configured (stack by
        # default, CMT_TPU_COLS_IMPL otherwise) — label and report the
        # config actually measured
        keyed_cfg = F.COLS_IMPL
        with _tr.span("bench/keyed", cat="bench", cols_impl=keyed_cfg):
            keyed_best = measure_keyed(keyed_cfg)
        # A/B the int16 column stack (docs/device_kernel_perf.md §3.0):
        # the benchmark's job is the best honest number
        prior_cols, prior_sq = F.COLS_IMPL, F.SQUARE_IMPL
        if prior_cols != "stack16":
            try:
                F.COLS_IMPL = "stack16"
                F.SQUARE_IMPL = "mul"
                EV._keyed_cache.clear()  # force a retrace, new core
                rate16 = measure_keyed("stack16")
                if rate16 > keyed_best:
                    keyed_best, keyed_cfg = rate16, "stack16"
            except Exception as exc:  # noqa: BLE001 — variant optional
                log(f"stack16 variant failed "
                    f"({type(exc).__name__}: {exc}); keeping "
                    f"the {keyed_cfg} number")
            finally:
                if keyed_cfg != "stack16":
                    # leave module state matching the reported config
                    F.COLS_IMPL, F.SQUARE_IMPL = prior_cols, prior_sq
                    EV._keyed_cache.clear()
    except Exception as exc:  # noqa: BLE001 — keyed path must not
        # take down the headline; report the generic number instead
        # (and discard any keyed trials: a path that just failed —
        # possibly by mis-verifying — must not headline)
        keyed_best = 0.0
        keyed_cfg = None
        log(f"keyed path failed ({type(exc).__name__}: {exc}); "
            "headline falls back to the generic kernel")
        note = f"keyed path failed: {type(exc).__name__}: {exc}"

    # GENERIC kernel section (cold-key path: full pubkey decompress +
    # double-scalar ladder, no precomputed tables) — diagnostic depth
    # behind the headline.
    t0 = time.time()
    out = verify_arrays(pubs, sigs, msgs)
    log(f"first generic launch (compile or cache load) "
        f"{time.time() - t0:.1f}s")
    assert bool(out.all()), "benchmark signatures must verify"

    # sync latency (one launch, transfers + compute + result fetch)
    lat = float("inf")
    for _ in range(3):
        t0 = time.time()
        out = verify_arrays(pubs, sigs, msgs)
        lat = min(lat, time.time() - t0)
    assert bool(out.all())
    log(f"sync latency: {lat * 1e3:.1f} ms/launch ({n} sigs)")

    # device-vs-link split: time K back-to-back dispatches that all
    # synchronize through ONE combined fetch, vs a single dispatch+
    # fetch; the difference isolates marginal device compute from
    # the fixed host<->device round trip.
    k = 6
    t0 = time.time()
    parts = []
    for _ in range(k):
        parts.extend(verify_arrays_async(pubs, sigs, msgs))
    _finish(parts)
    t_k = time.time() - t0
    t0 = time.time()
    _finish(verify_arrays_async(pubs, sigs, msgs))
    t_1 = time.time() - t0
    dev_per_launch = max(t_k - t_1, 0.0) / (k - 1)
    log(
        f"marginal device+transfer: {dev_per_launch * 1e3:.1f} "
        f"ms/launch "
        f"({n / dev_per_launch if dev_per_launch else 0:,.0f} sigs/s "
        f"device-side); fixed link overhead ≈ "
        f"{max(t_1 - dev_per_launch, 0) * 1e3:.1f} ms"
    )

    # steady-state pipelined throughput over nchunks in-flight launches
    for trial in range(3):
        t0 = time.time()
        total = 0
        with _tr.span("bench/generic_pipelined", cat="bench", trial=trial):
            with _measured("generic_pipelined"):
                for res in verify_stream(
                    ((pubs, sigs, msgs) for _ in range(nchunks)),
                    max_in_flight=nchunks,
                ):
                    assert bool(res.all())
                    total += len(res)
        dt = time.time() - t0
        rate = total / dt
        log(
            f"pipelined trial {trial}: {total} sigs in {dt * 1e3:.1f} ms "
            f"= {rate:,.0f} sigs/s"
        )
        generic_best = max(generic_best, rate)

    result = make_result(generic_best, keyed_best, note)
    if keyed_cfg is not None and keyed_best > generic_best:
        result["keyed_cols_impl"] = keyed_cfg
    # warmup-phase compile counts per seam + recompiles seen inside
    # measured sections (assertable steady-state provenance)
    result["jit_compiles"] = _jg.compile_counts()
    result["steady_retraces"] = steady_retraces
    _attach_hotspots(_bench_prof, result)
    if os.environ.get("CMT_BENCH_PIPELINE", "1") != "0":
        # per-stage height-pipeline breakdown on this machine (the
        # replication-plane analog of the per-seam compile counts)
        result["height_pipeline"] = _height_pipeline_provenance()
    return result


def keyed_mesh_main() -> dict:
    """``bench.py --keyed-mesh``: steady-state sharded-keyed throughput
    through the production ShardedTpuBatchVerifier seam — per-chip and
    aggregate sigs/s, per-seam jit compile counts, steady-state retrace
    counts, and the crypto_dispatch_tier actually used, merged into
    MULTICHIP_KEYED.json (the multi-chip provenance for the keyed
    tier)."""
    _bench_prof = _start_profiler()
    import jax

    import numpy as np

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.metrics import CryptoMetrics, install_crypto_metrics
    from cometbft_tpu.ops import jitguard as _jg
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier
    from cometbft_tpu.utils.metrics import Registry

    cm = CryptoMetrics(Registry())
    install_crypto_metrics(cm)
    devs = jax.devices()
    ndev = len(devs)
    on_cpu = devs[0].platform == "cpu"
    log(f"devices: {ndev} x {devs[0].platform}")
    nval = int(os.environ.get("CMT_BENCH_NVAL", "16" if on_cpu else "150"))
    n = int(os.environ.get("CMT_BENCH_N", "256" if on_cpu else "4096"))
    privs = [ed.priv_key_from_secret(b"mesh%d" % i) for i in range(nval)]
    rng = np.random.RandomState(11)
    msgs = [rng.bytes(120) for _ in range(n)]
    sigs = [privs[i % nval].sign(m) for i, m in enumerate(msgs)]

    # warm the key-set table BEFORE the clock starts (the steady state
    # a replaying node lives in)
    t0 = time.time()
    entry = PR.TABLE_CACHE.lookup_or_build(
        [p.pub_key().bytes() for p in privs]
    )
    assert entry is not None, "key set outside table policy"
    log(f"keyed tables built in {time.time() - t0:.1f}s "
        f"({entry.window_bits}-bit, {entry.set_nbytes / 1e6:.0f} MB)")

    def run_once() -> float:
        bv = ShardedTpuBatchVerifier(device_min_batch=0)
        for i, m in enumerate(msgs):
            bv.add(privs[i % nval].pub_key(), m, sigs[i])
        t0 = time.perf_counter()
        ok, bits = bv.verify()
        dt = time.perf_counter() - t0
        assert ok and all(bits), "keyed-mesh bench sigs must verify"
        return dt

    t0 = time.time()
    first = run_once()
    log(f"first sharded-keyed verify (incl compile) {first:.1f}s "
        f"(total {time.time() - t0:.1f}s)")
    warm_compiles = _jg.compile_counts()
    best = float("inf")
    iters = int(os.environ.get("CMT_BENCH_ITERS", "3"))
    for trial in range(iters):
        dt = run_once()
        log(f"trial {trial}: {n} sigs in {dt * 1e3:.1f} ms = "
            f"{n / dt:,.0f} sigs/s aggregate")
        best = min(best, dt)
    steady_retraces = {
        seam: _jg.compile_counts().get(seam, 0) - c
        for seam, c in warm_compiles.items()
        if _jg.compile_counts().get(seam, 0) != c
    }
    agg = n / best
    tiers = {
        k[0]: int(c.get()) for k, c in cm.dispatch_tier.children().items()
    }
    tier = max(tiers, key=tiers.get) if tiers else "unknown"
    result = {
        "config": f"keyed_mesh_{ndev}dev",
        "metric": "keyed_mesh_batch_verify_throughput",
        "value": round(agg, 1),
        "unit": "sigs/sec",
        "ndev": ndev,
        "platform": devs[0].platform,
        "per_chip_sigs_per_sec": round(agg / ndev, 1),
        "nval": nval,
        "batch": n,
        "dispatch_tier": tier,
        "dispatch_tiers": tiers,
        "jit_compiles": _jg.compile_counts(),
        "steady_retraces": steady_retraces,
        "measured": time.strftime("%Y-%m-%d %H:%M"),
    }
    from bench_all import merge_results

    merge_results(
        os.path.join(REPO, "MULTICHIP_KEYED.json"), [result],
        device=str(devs[0]),
    )
    log("wrote MULTICHIP_KEYED.json")
    from tools import perfledger

    _attach_hotspots(_bench_prof, result)
    perfledger.append_rows([result], source="bench --keyed-mesh")
    install_crypto_metrics(None)
    return result


def pipelined_main() -> dict:
    """``bench.py --pipelined``: sync vs verify-queue throughput
    through the PRODUCTION verifier seam on whatever tier this box
    dispatches to (host on a no-device box — the tier is recorded).

    Sync measures plan()+execute() run back-to-back on one thread;
    pipelined drives the same batches through the VerifyQueue, whose
    collector overlaps buffer N+1's host prep with buffer N's launch.
    Both rows land in the perf ledger (configs ``verify_queue_sync`` /
    ``verify_queue_pipelined``) so tools/perfdiff.py gates
    sync-vs-pipelined regressions, and the measured
    crypto_host_device_overlap_ratio ships in the pipelined row."""
    _bench_prof = _start_profiler()
    import numpy as np  # noqa: F401 — keep jax import order stable

    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import verify_queue as vqmod
    from cometbft_tpu.metrics import (
        CryptoMetrics,
        HealthMetrics,
        install_crypto_metrics,
        install_health_metrics,
    )
    from cometbft_tpu.ops import jitguard as _jg
    from cometbft_tpu.utils.metrics import Registry

    cm = CryptoMetrics(Registry())
    hm = HealthMetrics(Registry())
    install_crypto_metrics(cm)
    install_health_metrics(hm)
    n = int(os.environ.get("CMT_BENCH_N", "512"))
    nbatches = int(os.environ.get("CMT_BENCH_NCHUNKS", "8"))
    priv = ed.priv_key_from_secret(b"bench-pipelined")
    pub = priv.pub_key()
    # distinct messages per batch so nothing aliases; the queue runs
    # with the speculative cache OFF so every trial re-verifies
    batches = []
    for b in range(nbatches):
        msgs = [b"pipelined-%d-%d" % (b, i) for i in range(n)]
        batches.append([(pub, m, priv.sign(m)) for m in msgs])
    total = n * nbatches

    def tier_delta(seen: dict) -> dict:
        now = {
            k[0]: c.get() for k, c in cm.dispatch_tier.children().items()
        }
        delta = {
            t: int(v - seen.get(t, 0))
            for t, v in now.items()
            if v > seen.get(t, 0)
        }
        seen.clear()
        seen.update(now)
        return delta

    seen: dict = {}

    def run_sync() -> float:
        t0 = time.perf_counter()
        for items in batches:
            bv = crypto_batch.create_batch_verifier(pub)
            for pk, m, s in items:
                bv.add(pk, m, s)
            ok, _bits = bv.verify()
            assert ok, "pipelined bench sigs must verify"
        return total / (time.perf_counter() - t0)

    def run_pipelined(q) -> tuple[float, float | None]:
        t0 = time.perf_counter()
        futs = []
        for items in batches:
            futs.extend(q.submit_many(items))
        assert all(f.result(600) for f in futs), (
            "pipelined bench sigs must verify"
        )
        rate = total / (time.perf_counter() - t0)
        return rate, q.stats()["overlap_ratio"]

    # warmup (compiles on a device tier; native lib load on host)
    run_sync()
    sync_best = max(run_sync() for _ in range(3))
    tier_delta(seen)  # reset the tier window to the measured sections
    pipe_best, overlap = 0.0, None
    # max_batch = n: one buffer per submitted batch, so the measured
    # shape IS the double-buffered pipeline (unbounded coalescing
    # would fold the whole run into one launch with nothing to
    # overlap)
    q = vqmod.VerifyQueue(use_cache=False, max_batch=n)
    q.start()
    try:
        run_pipelined(q)  # same warmup treatment as the sync path
        for _ in range(3):
            rate, ov = run_pipelined(q)
            if rate > pipe_best:
                pipe_best, overlap = rate, ov
    finally:
        q.stop()
    tiers = tier_delta(seen)
    tier = max(tiers, key=tiers.get) if tiers else "host"
    log(
        f"sync {sync_best:,.0f} sigs/s vs pipelined "
        f"{pipe_best:,.0f} sigs/s on tier={tier} "
        f"(overlap_ratio={overlap})"
    )
    measured = time.strftime("%Y-%m-%d %H:%M")
    result = {
        "metric": "verify_queue_throughput",
        "value": round(pipe_best, 1),
        "unit": "sigs/sec",
        "sync_sigs_per_sec": round(sync_best, 1),
        "pipelined_sigs_per_sec": round(pipe_best, 1),
        "speedup": round(pipe_best / sync_best, 3) if sync_best else 0,
        "overlap_ratio": overlap,
        "dispatch_tier": tier,
        "batch": n,
        "nbatches": nbatches,
        "jit_compiles": _jg.compile_counts(),
        "measured": measured,
    }
    from tools import perfledger

    rows = [
        {
            "config": "verify_queue_sync",
            "value": round(sync_best, 1),
            "unit": "sigs/sec",
            "dispatch_tier": tier,
            "batch": n,
            "measured": measured,
        },
        {
            "config": "verify_queue_pipelined",
            "value": round(pipe_best, 1),
            "unit": "sigs/sec",
            "dispatch_tier": tier,
            "overlap_ratio": overlap,
            "batch": n,
            "measured": measured,
        },
    ]
    _attach_hotspots(_bench_prof, result, *rows)
    perfledger.append_rows(rows, source="bench --pipelined")
    install_crypto_metrics(None)
    install_health_metrics(None)
    return result


def host_phase_profile_main(out: str | None = None) -> dict:
    """``bench.py --host-phase-profile``: drive the crypto HOST phase
    (the ROADMAP item-3 bottleneck: SHA-512 cache-key prehash, input
    packing, Merkle root) under the sampling profiler, span-tagged,
    and write the attributed evidence to
    docs/data/host_phase_profile.json — the committed artifact behind
    the prehash/pack/Merkle dominance claim in
    docs/device_kernel_perf.md.  Stdlib + numpy only (no device):
    the host phase is host work by definition, so the artifact is
    reproducible on any box."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import merkle
    from cometbft_tpu.crypto.verify_queue import cache_key
    from cometbft_tpu.utils.profiler import SamplingProfiler
    from cometbft_tpu.utils.trace import TRACER

    n = int(os.environ.get("CMT_BENCH_N", "4096"))
    rounds = int(os.environ.get("CMT_BENCH_ITERS", "6"))
    priv = ed.priv_key_from_secret(b"host-phase-profile")
    pub = priv.pub_key().bytes()
    rng = np.random.RandomState(3)
    msgs = [rng.bytes(120) for _ in range(n)]
    sigs = [priv.sign(m) for m in msgs]
    txs = [rng.bytes(250) for _ in range(n)]

    # 331 Hz (prime): the phases below run ~hundreds of ms each, so
    # the default 19 Hz would rank them on a handful of samples
    p = SamplingProfiler(hz=331, capacity=8192, tracer=TRACER)
    p.start()
    timings: dict[str, float] = {}
    try:
        for _ in range(rounds):
            t0 = time.perf_counter()
            with TRACER.span("host_phase/prehash", cat="crypto"):
                for m, s in zip(msgs, sigs):
                    cache_key(pub, m, s)
            t1 = time.perf_counter()
            with TRACER.span("host_phase/pack", cat="crypto"):
                np.frombuffer(b"".join(sigs), dtype=np.uint8).reshape(
                    n, 64
                )
                np.frombuffer(
                    b"".join(pub for _ in range(n)), dtype=np.uint8
                ).reshape(n, 32)
                lens = np.asarray([len(m) for m in msgs], np.int32)
                pad = int(lens.max())
                np.frombuffer(
                    b"".join(m.ljust(pad, b"\0") for m in msgs),
                    dtype=np.uint8,
                ).reshape(n, pad)
            t2 = time.perf_counter()
            with TRACER.span("host_phase/merkle", cat="crypto"):
                merkle.hash_from_byte_slices(txs)
            t3 = time.perf_counter()
            timings["prehash"] = timings.get("prehash", 0.0) + (t1 - t0)
            timings["pack"] = timings.get("pack", 0.0) + (t2 - t1)
            timings["merkle"] = timings.get("merkle", 0.0) + (t3 - t2)
    finally:
        p.stop()
    total = sum(timings.values())
    spans = p.span_seconds()
    phase_samples = {
        k[len("host_phase/"):]: v
        for k, v in spans.items()
        if k.startswith("host_phase/")
    }
    result = {
        "config": "crypto/host_phase",
        "n": n,
        "rounds": rounds,
        "wall_s": round(total, 3),
        "phase_seconds": {k: round(v, 4) for k, v in timings.items()},
        "phase_share": {
            k: round(v / total, 4) for k, v in timings.items()
        },
        "phase_samples": phase_samples,
        "sigs_per_sec_prehash": (
            round(n * rounds / timings["prehash"], 1)
            if timings.get("prehash") else None
        ),
        "hz": p.hz,
        "samples": p.payload()["samples"],
        "hotspots": p.top_functions(10),
        "measured": time.strftime("%Y-%m-%d %H:%M"),
        "note": (
            "host phase driven standalone (no device): SHA-512 "
            "cache-key prehash + input packing + Merkle root — the "
            "ROADMAP item-3 dominance evidence"
        ),
    }
    out = out or os.path.join(
        REPO, "docs", "data", "host_phase_profile.json"
    )
    os.makedirs(os.path.dirname(out), exist_ok=True)
    tmp = out + ".tmp"
    with open(tmp, "w") as f:
        json.dump(result, f, indent=1)
    os.replace(tmp, out)
    log(f"wrote {out}")
    from tools import perfledger

    perfledger.append_rows(
        [
            {
                "config": "host_phase_prehash",
                "value": result["sigs_per_sec_prehash"],
                "unit": "sigs/sec",
                "hotspots": result["hotspots"][:5],
                "measured": result["measured"],
            }
        ],
        source="bench --host-phase-profile",
    )
    return result


def run() -> None:
    result = main()
    _dump_trace()
    from tools import perfledger

    if perfledger.configured():
        # the headline lands in the operator's perf ledger with its
        # provenance (tier, per-seam compiles, steady retraces) —
        # perfdiff's gate input; best-effort, the result prints anyway
        try:
            entry = perfledger.headline_entry(result)
            if not entry.get("measured"):
                entry["measured"] = time.strftime("%Y-%m-%d %H:%M")
            perfledger.append([entry])
        except Exception as exc:  # noqa: BLE001 — provenance only
            log(f"perf ledger append failed (ignored): {exc}")
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    if "--keyed-mesh" in sys.argv[1:]:
        print(json.dumps(keyed_mesh_main()), flush=True)
    elif "--pipelined" in sys.argv[1:]:
        print(json.dumps(pipelined_main()), flush=True)
    elif "--host-phase-profile" in sys.argv[1:]:
        print(json.dumps(host_phase_profile_main()), flush=True)
    else:
        run()

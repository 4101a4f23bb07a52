"""BASELINE configs measured through the PRODUCTION dispatch on the
host path — full counts, no modeling, no device.

The production no-device dispatch runs the native RLC batch verifier;
these shapes are measured through types/validation.verify_commit — the
path a real no-accelerator deployment takes. Entries are merged into
BENCH_ALL.json with explicit host provenance.

    python tools/bench_host_baseline.py
"""

from __future__ import annotations

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

# host rows only: the batch-verifier seam hands out the host verifier
# and nothing here touches a device
os.environ["CMT_TPU_DISABLE_DEVICE_VERIFY"] = "1"

from bench_all import (  # noqa: E402
    CHAIN_ID,
    log,
    make_commit_fixture,
    merge_results,
    timed,
)


def main() -> int:
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--label", default="",
        help="measurement label prefix (e.g. 'round 5'); stamped "
        "alongside the date so reruns never carry a stale round tag",
    )
    args = ap.parse_args()
    label = (args.label + ", " if args.label else "") + time.strftime(
        "%Y-%m-%d"
    )

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto.batch import create_batch_verifier
    from cometbft_tpu.types import validation

    import numpy as np

    results = []

    def record(config: str, value: float, unit: str, **extra):
        row = {"config": config, "value": round(value, 2), "unit": unit}
        row.update(extra)
        row["measured"] = label
        row["host_path"] = True  # merge key: host rows replace only
        # host rows, never device-measured entries
        row["provenance"] = (
            "PRODUCTION no-device dispatch (native RLC host batch "
            "verifier, native/crypto/ed25519_batch.cpp); full counts, "
            "nothing modeled. Device keyed-path numbers are recorded "
            "separately when a device window allows."
        )
        results.append(row)
        print(json.dumps(row), flush=True)

    # ---- config 1: 64-sig micro-bench, production dispatch -----------
    # through the REAL seam (crypto/batch.py create_batch_verifier),
    # which honors CMT_TPU_DISABLE_DEVICE_VERIFY and selects the host
    # verifier here — so the recorded path matches the label even if a
    # device happens to be visible
    rng = np.random.RandomState(7)
    priv = ed.gen_priv_key()
    msgs64 = [rng.bytes(120) for _ in range(64)]
    sigs64 = [priv.sign(m) for m in msgs64]
    pub = priv.pub_key()

    def micro():
        bv = create_batch_verifier(pub)
        for m, s in zip(msgs64, sigs64):
            bv.add(pub, m, s)
        ok, _ = bv.verify()
        assert ok

    dt = timed(micro)
    record(
        "micro_64sig", 64 / dt, "sigs/sec",
        latency_ms=round(dt * 1e3, 2), dispatch="host RLC batch",
    )

    # ---- config 2: VerifyCommit @ 150 validators ---------------------
    t0 = time.time()
    vals150, commit150, bid150 = make_commit_fixture(150)
    log(f"150-val fixture in {time.time() - t0:.1f}s")

    def vc150():
        validation.verify_commit(CHAIN_ID, vals150, bid150, 1, commit150)

    dt = timed(vc150)
    record(
        "verify_commit_150", dt * 1e3, "ms",
        sigs_per_sec=round(150 / dt, 1),
    )

    # ---- config 3: VerifyCommit @ 10k validators (FULL) --------------
    t0 = time.time()
    vals10k, commit10k, bid10k = make_commit_fixture(10_000)
    log(f"10k-val fixture in {time.time() - t0:.1f}s")

    def vc10k():
        validation.verify_commit(CHAIN_ID, vals10k, bid10k, 1, commit10k)

    dt = timed(vc10k)
    record(
        "verify_commit_10000", dt * 1e3, "ms",
        sigs_per_sec=round(10_000 / dt, 1), target_ms=2.0,
    )

    # ---- config 4: light sync, 10k headers x 150-val commits (FULL) --
    n4 = 10_000
    t0 = time.time()
    done = 0
    while done < n4:
        vc150()
        done += 1
    dt = time.time() - t0
    record(
        "light_sync_150val", n4 * 150 / dt, "sigs/sec",
        commits_per_sec=round(n4 / dt, 1), n_commits_run=n4,
    )

    # ---- config 5: blocksync replay, 1k blocks x 1k-val (FULL) -------
    t0 = time.time()
    vals1k, commit1k, bid1k = make_commit_fixture(1000)
    log(f"1k-val fixture in {time.time() - t0:.1f}s")
    n5 = 1000
    t0 = time.time()
    for _ in range(n5):
        validation.verify_commit(CHAIN_ID, vals1k, bid1k, 1, commit1k)
    dt = time.time() - t0
    record(
        "blocksync_replay_1kval", n5 * 1000 / dt, "sigs/sec",
        commits_per_sec=round(n5 / dt, 1), n_commits_run=n5,
    )

    # merge into BENCH_ALL.json: host rows replace only PRIOR host
    # rows for the same config — device-measured entries (and the
    # top-level device field) are never clobbered by a host refresh
    path = os.path.join(REPO, "BENCH_ALL.json")
    ours = {r["config"] for r in results}
    merge_results(
        path, results,
        replace_if=lambda r: (
            r.get("config") in ours and r.get("host_path")
        ),
    )
    log(f"merged {len(results)} host entries into BENCH_ALL.json")
    return 0


if __name__ == "__main__":
    sys.exit(main())

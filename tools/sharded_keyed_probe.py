"""Sharded keyed verification ON HARDWARE with HBM accounting.

VERDICT r4 #7: the mesh path and keyed path compose in CPU tests, but
per-shard device placement of the keyed tables had never been exercised
on a real chip. This probe runs the composition on whatever devices are
visible (a single-device mesh still exercises the real sharded code
path and table replication), at the BASELINE config-2/5 shapes:

  - 150-validator commit (8-bit comb pages)
  - 10k-validator mega-commit (4-bit pages, 5 GiB pool)

and records, per shape: table pool bytes, device memory stats before /
after the table build (live_bytes from device.memory_stats when the
backend reports them), first-launch latency (compile), and steady
launch latency through ShardedTpuBatchVerifier.verify().

    python tools/sharded_keyed_probe.py [--nvals 150,10000]

Appends to docs/data/sharded_keyed_r05.json.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
OUT = os.path.join(REPO, "docs", "data", "sharded_keyed_r05.json")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def mem_stats(dev) -> dict:
    try:
        s = dev.memory_stats() or {}
        return {
            k: s[k]
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in s
        }
    except Exception:
        return {}


def probe_shape(nval: int, nsig: int) -> dict:
    import numpy as np

    import jax

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

    dev = jax.devices()[0]
    entry: dict = {
        "nval": nval,
        "nsig": nsig,
        "ndev": len(jax.devices()),
        "platform": dev.platform,
        "mem_before": mem_stats(dev),
    }
    # one shared key-table pool build at this shape
    privs = [ed.priv_key_from_secret(b"shard%d" % i) for i in range(nval)]
    pubs_b = [p.pub_key().bytes() for p in privs]
    t0 = time.time()
    tbl = PR.TABLE_CACHE.lookup_or_build(pubs_b)
    if tbl is None:
        raise SystemExit(
            f"{nval} unique keys is outside table policy "
            f"(CMT_TPU_TABLE_MAX_KEYS={PR.TABLE_MAX_KEYS})"
        )
    np.asarray(jax.device_get(tbl.table[0, 0, :4]))  # force build
    entry["table_build_s"] = round(time.time() - t0, 1)
    entry["window_bits"] = tbl.window_bits
    entry["set_table_bytes"] = tbl.set_nbytes
    entry["pool_bytes"] = tbl.nbytes
    entry["mem_after_tables"] = mem_stats(dev)
    log(
        f"nval={nval}: {tbl.window_bits}-bit tables, "
        f"{tbl.set_nbytes/1e9:.2f} GB set / {tbl.nbytes/1e9:.2f} GB pool, "
        f"built in {entry['table_build_s']}s"
    )

    # the commit-shaped batch: nsig votes round-robin over the set
    rng = np.random.RandomState(3)
    msgs = [rng.bytes(110) for _ in range(nsig)]

    def run_once() -> float:
        bv = ShardedTpuBatchVerifier(device_min_batch=0)
        for i, m in enumerate(msgs):
            p = privs[i % nval]
            bv.add(p.pub_key(), m, p.sign(m))
        t0 = time.time()
        ok, bits = bv.verify()
        dt = time.time() - t0
        assert ok and all(bits), "sharded keyed verification failed"
        return dt

    t0 = time.time()
    first = run_once()
    entry["first_verify_s"] = round(first, 2)
    log(f"nval={nval}: first sharded verify (incl compile) {first:.1f}s")
    best = min(run_once() for _ in range(3))
    entry["steady_verify_s"] = round(best, 4)
    entry["steady_sigs_per_sec"] = round(nsig / best, 1)
    entry["mem_after_verify"] = mem_stats(dev)
    log(
        f"nval={nval}: steady {best*1e3:.1f} ms / {nsig} sigs "
        f"({nsig/best:,.0f} sigs/s) through the sharded seam"
    )
    return entry


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--nvals", default="150,10000")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()
    try:
        with open(args.out) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {"results": []}
    for nval in [int(v) for v in args.nvals.split(",") if v]:
        # BASELINE: config 2 is one 150-val commit; config 5 is a 10k
        # mega-commit — nsig equals the validator count in both
        entry = probe_shape(nval, nsig=nval)
        entry["measured"] = time.strftime("round 5, %Y-%m-%d %H:%M")
        doc["results"] = [
            r for r in doc["results"] if r["nval"] != nval
        ] + [entry]
        os.makedirs(os.path.dirname(args.out), exist_ok=True)
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, args.out)
    print(json.dumps(doc, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

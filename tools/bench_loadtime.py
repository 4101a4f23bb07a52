"""Macro throughput baseline: loadtime vs a 4-validator localnet.

Reference comparison point: the QA report's saturation at 400 tx/s of
1 KB txs with c=1 on a 200-node DigitalOcean testnet
(docs/references/qa/CometBFT-QA-v1.md:137).  This harness runs the
same shape scaled to one machine: `testnet` CLI homes, four real node
subprocesses over TCP, the loadtime Loader at a fixed rate, then the
loadtime reporter over node0's block store for latency percentiles and
block-interval stats.

    python tools/bench_loadtime.py [--rate 200] [--duration 60]

Merges a "loadtime_localnet" entry into BENCH_ALL.json.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

BASE_PORT = 28100
N_NODES = 4


def _rpc_port(i: int) -> int:
    return BASE_PORT + 2 * i + 1


def _height(port: int) -> int:
    with urllib.request.urlopen(
        f"http://127.0.0.1:{port}/status", timeout=3
    ) as resp:
        return int(
            json.load(resp)["result"]["sync_info"]["latest_block_height"]
        )


def _node_env() -> dict:
    env = dict(
        os.environ,
        PYTHONPATH=REPO,
        JAX_PLATFORMS="cpu",
        CMT_TPU_DISABLE_DEVICE_VERIFY="1",
    )
    return env


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rate", type=int, default=200, help="tx/s target")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--size", type=int, default=1024, help="tx bytes")
    ap.add_argument("--connections", type=int, default=1)
    ap.add_argument("--out", default=os.path.join(REPO, "BENCH_ALL.json"))
    ap.add_argument(
        "--config-name", default="loadtime_localnet",
        help="BENCH_ALL.json entry to write (e.g. "
        "loadtime_localnet_saturation for the knee probe)",
    )
    args = ap.parse_args()

    env = _node_env()
    root = tempfile.mkdtemp(prefix="cmt-loadnet-")
    subprocess.run(
        [
            sys.executable, "-m", "cometbft_tpu", "testnet",
            "--v", str(N_NODES), "--o", root,
            "--chain-id", "load-chain",
            "--starting-port", str(BASE_PORT),
        ],
        env=env, check=True, capture_output=True, cwd=REPO,
    )
    procs = []
    for i in range(N_NODES):
        log = open(os.path.join(root, f"node{i}.log"), "ab", buffering=0)
        procs.append(
            subprocess.Popen(
                [
                    sys.executable, "-m", "cometbft_tpu",
                    "--home", os.path.join(root, f"node{i}"), "start",
                ],
                env=env, stdout=subprocess.DEVNULL, stderr=log, cwd=REPO,
            )
        )
    try:
        deadline = time.monotonic() + 120
        while True:
            try:
                if all(_height(_rpc_port(i)) >= 3 for i in range(N_NODES)):
                    break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("localnet failed to reach height 3")
            time.sleep(1.0)
        print("localnet up; loading...", file=sys.stderr)

        from cometbft_tpu.loadtime import Loader, block_interval_stats

        loader = Loader(
            endpoints=[
                f"http://127.0.0.1:{_rpc_port(i)}" for i in range(N_NODES)
            ],
            rate=args.rate,
            size=args.size,
            connections=args.connections,
        )
        t0 = time.time()
        summary = loader.run(args.duration)
        load_wall = time.time() - t0
        print(f"load summary: {summary}", file=sys.stderr)
        time.sleep(5)  # let the tail commit
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()

    from cometbft_tpu.config import Config
    from cometbft_tpu.loadtime import report_from_home
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.utils.db import open_db

    home0 = os.path.join(root, "node0")
    reports = report_from_home(home0)
    cfg = Config.load(home0)
    db = open_db("blockstore", cfg.base.db_backend, cfg.db_dir)
    try:
        stats = block_interval_stats(BlockStore(db), last_n=200)
    finally:
        db.close()
    rep = reports[0].as_dict() if reports else {}
    committed = rep.get("count", 0)
    entry = {
        "config": args.config_name,
        "value": round(committed / load_wall, 1),
        "unit": "tx/sec committed",
        "offered_rate": args.rate,
        "tx_bytes": args.size,
        "connections": args.connections,
        "duration_s": round(load_wall, 1),
        "nodes": N_NODES,
        "latency_s": {
            k: round(rep[k], 3)
            for k in ("min_s", "avg_s", "p50_s", "p95_s", "max_s")
            if k in rep
        },
        "blocks_per_min": stats.get("blocks_per_min"),
        "mean_block_interval_s": stats.get("mean_interval_s"),
        "reference_baseline": (
            "400 tx/s saturation, <=4 s latency, 20-40 blocks/min "
            "(200-node DO testnet, CometBFT-QA-v1.md:137)"
        ),
        "hardware": "single host, 1 CPU core, 4 subprocess validators",
    }
    print(json.dumps(entry, indent=1))
    from bench_all import merge_results

    merge_results(args.out, [entry])
    print(f"merged into {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

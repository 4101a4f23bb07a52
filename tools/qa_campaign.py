"""QA macro campaign: saturation sweep + latency CDF + resource
envelope + per-component CPU profile, as a reproducible artifact.

The reference's performance claims are a written methodology with
published numbers (docs/references/qa/CometBFT-QA-v1.md:137 — the
200-node saturation point at 400 tx/s of 1 KB txs, latency CDFs, and
resource envelopes).  This driver produces the same artifact shape for
this framework at localnet scale:

    python tools/qa_campaign.py                      # full sweep
    python tools/qa_campaign.py --rates 100,200      # subset
    python tools/qa_campaign.py --profile --rates 400  # + cProfile

Per offered rate it runs a FRESH 4-validator localnet, drives the
loadtime Loader for --duration seconds, and records committed tx/s,
latency percentiles (from tx-embedded timestamps via the loadtime
reporter), block cadence, and the per-node RSS envelope sampled during
load.  With --profile, node0 runs under cProfile and the dump is
aggregated into a per-component CPU breakdown (consensus / abci+codec /
p2p+frames / store / rpc / crypto).

Writes docs/qa/data/qa_localnet_r05.json incrementally (one entry per
rate, so a killed sweep keeps what it measured).
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import threading
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

OUT = os.path.join(REPO, "docs", "qa", "data", "qa_localnet_r05.json")
BASE_PORT = 28300
N_NODES = 4

#: repo-module prefixes -> report component (profile aggregation)
COMPONENTS = {
    "cometbft_tpu/consensus": "consensus",
    "cometbft_tpu/abci": "abci_codec",
    "cometbft_tpu/proxy": "abci_codec",
    "cometbft_tpu/p2p": "p2p_frames",
    "cometbft_tpu/store": "storage",
    "cometbft_tpu/state": "storage",
    "cometbft_tpu/wal": "storage",
    "cometbft_tpu/utils/db": "storage",
    "cometbft_tpu/rpc": "rpc",
    "cometbft_tpu/crypto": "crypto",
    "cometbft_tpu/ops": "crypto",
    "cometbft_tpu/mempool": "mempool",
    "cometbft_tpu/types": "types_hashing",
}


def log(msg: str) -> None:
    print(f"[qa] {msg}", file=sys.stderr, flush=True)


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


class ResourceSampler(threading.Thread):
    """Samples VmRSS and cumulative CPU (utime+stime) of the node pids
    every couple of seconds — the CPU series turns "the localnet is
    slower than the reference's 200-node testnet" into a measurable
    statement about how much of the single core each node got."""

    CLK = os.sysconf("SC_CLK_TCK")

    def __init__(self, pids: list[int], period: float = 2.0):
        super().__init__(daemon=True)
        self.pids = pids
        self.period = period
        self.samples: dict[int, list[int]] = {p: [] for p in pids}
        self.cpu0: dict[int, float] = {}
        self.cpu1: dict[int, float] = {}
        self.t0 = time.monotonic()
        # NB: must not be named _stop — that shadows Thread._stop,
        # which join() calls internally
        self._halt = threading.Event()

    def _cpu_s(self, pid: int) -> float | None:
        try:
            with open(f"/proc/{pid}/stat") as f:
                parts = f.read().rsplit(")", 1)[1].split()
            # fields 14/15 (1-based utime/stime) land at 11/12 here
            return (int(parts[11]) + int(parts[12])) / self.CLK
        except (OSError, IndexError, ValueError):
            return None

    def run(self) -> None:
        for pid in self.pids:
            c = self._cpu_s(pid)
            if c is not None:
                self.cpu0[pid] = c
        while not self._halt.wait(self.period):
            for pid in self.pids:
                try:
                    with open(f"/proc/{pid}/status") as f:
                        for line in f:
                            if line.startswith("VmRSS:"):
                                kb = int(line.split()[1])
                                self.samples[pid].append(kb)
                                break
                except OSError:
                    pass
                c = self._cpu_s(pid)
                if c is not None:
                    self.cpu1[pid] = c

    def stop(self) -> dict:
        self._halt.set()
        self.join(timeout=5)
        wall = max(time.monotonic() - self.t0, 1e-9)
        flat = [s for per in self.samples.values() for s in per]
        per_node_peak = [max(s) if s else 0 for s in self.samples.values()]
        cpu_per_node = [
            round(
                (self.cpu1.get(p, self.cpu0.get(p, 0.0))
                 - self.cpu0.get(p, 0.0)) / wall,
                3,
            )
            for p in self.pids
        ]
        return {
            "rss_peak_mb": round(max(flat) / 1024, 1) if flat else None,
            "rss_mean_mb": round(
                sum(flat) / len(flat) / 1024, 1
            ) if flat else None,
            "rss_peak_per_node_mb": [
                round(p / 1024, 1) for p in per_node_peak
            ],
            "cpu_cores_per_node": cpu_per_node,
            "cpu_cores_total": round(sum(cpu_per_node), 3),
        }


def aggregate_profile(pstats_path: str) -> dict:
    """cProfile dump -> per-component tottime shares."""
    import pstats

    st = pstats.Stats(pstats_path)
    total = 0.0
    by_comp: dict[str, float] = {}
    for (fname, _lineno, _fn), (
        _cc, _nc, tottime, _cum, _callers
    ) in st.stats.items():
        total += tottime
        comp = "other"
        norm = fname.replace("\\", "/")
        for prefix, name in COMPONENTS.items():
            if prefix in norm:
                comp = name
                break
        else:
            if "/python3" in norm or norm.startswith("<"):
                comp = "stdlib_interp"
        by_comp[comp] = by_comp.get(comp, 0.0) + tottime
    shares = {
        k: round(v / total, 4)
        for k, v in sorted(by_comp.items(), key=lambda kv: -kv[1])
    }
    return {"total_cpu_s": round(total, 1), "tottime_share": shares}


def run_rate(
    rate: int, duration: float, size: int, connections: int,
    profile: bool,
) -> dict:
    env = _node_env()
    root = tempfile.mkdtemp(prefix=f"cmt-qa-{rate}-")
    subprocess.run(
        [
            sys.executable, "-m", "cometbft_tpu", "testnet",
            "--v", str(N_NODES), "--o", root,
            "--chain-id", "qa-chain",
            "--starting-port", str(BASE_PORT),
        ],
        env=env, check=True, capture_output=True, cwd=REPO,
    )
    procs = []
    prof_path = os.path.join(root, "node0.pstats")
    for i in range(N_NODES):
        argv = [sys.executable]
        if profile and i == 0:
            argv += ["-m", "cProfile", "-o", prof_path]
            # cProfile -o + -m cometbft_tpu: profile the module run
            argv += [
                os.path.join(REPO, "cometbft_tpu", "__main__.py"),
            ]
        else:
            argv += ["-m", "cometbft_tpu"]
        argv += ["--home", os.path.join(root, f"node{i}"), "start"]
        logf = open(os.path.join(root, f"node{i}.log"), "ab", buffering=0)
        procs.append(
            subprocess.Popen(
                argv, env=env, stdout=subprocess.DEVNULL, stderr=logf,
                cwd=REPO,
            )
        )
    entry: dict = {
        "offered_rate": rate,
        "tx_bytes": size,
        "connections": connections,
        "nodes": N_NODES,
    }
    try:
        deadline = time.monotonic() + 150
        while True:
            try:
                if all(
                    _height(_rpc_port(i)) >= 3 for i in range(N_NODES)
                ):
                    break
            except Exception:
                pass
            if time.monotonic() > deadline:
                raise RuntimeError("localnet failed to reach height 3")
            time.sleep(1.0)
        log(f"rate {rate}: localnet up, loading {duration:.0f}s")
        from cometbft_tpu.loadtime import Loader

        sampler = ResourceSampler([p.pid for p in procs])
        sampler.start()
        loader = Loader(
            endpoints=[
                f"http://127.0.0.1:{_rpc_port(i)}" for i in range(N_NODES)
            ],
            rate=rate,
            size=size,
            connections=connections,
        )
        t0 = time.time()
        summary = loader.run(duration)
        load_wall = time.time() - t0
        time.sleep(5)  # tail commit
        entry.update(sampler.stop())
        entry["duration_s"] = round(load_wall, 1)
        # offered vs actually-sent vs committed: distinguishes a
        # client-side send shortfall / RPC rejections from consensus
        # throughput when reading the saturation knee
        entry["sent"] = summary.get("sent")
        entry["send_errors"] = summary.get("errors")
    finally:
        for p in procs:
            p.terminate()
        for p in procs:
            try:
                p.wait(timeout=20)
            except subprocess.TimeoutExpired:
                p.kill()

    from cometbft_tpu.config import Config
    from cometbft_tpu.loadtime import (
        block_interval_stats,
        report_from_home,
    )
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.utils.db import open_db

    home0 = os.path.join(root, "node0")
    reports = report_from_home(home0)
    rep = reports[0].as_dict() if reports else {}
    cfg = Config.load(home0)
    db = open_db("blockstore", cfg.base.db_backend, cfg.db_dir)
    try:
        stats = block_interval_stats(BlockStore(db), last_n=500)
    finally:
        db.close()
    committed = rep.get("count", 0)
    entry.update(
        committed_tx_per_s=round(committed / entry["duration_s"], 1),
        committed_total=committed,
        latency_s={
            k: round(rep[k], 3)
            for k in ("min_s", "avg_s", "p50_s", "p95_s", "max_s")
            if k in rep
        },
        blocks_per_min=stats.get("blocks_per_min"),
        mean_block_interval_s=stats.get("mean_interval_s"),
    )
    if profile and os.path.exists(prof_path):
        entry["profile"] = aggregate_profile(prof_path)
        entry["profile_dump"] = prof_path
    return entry


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rates", default="50,100,200,300,400")
    ap.add_argument("--duration", type=float, default=60.0)
    ap.add_argument("--size", type=int, default=1024)
    ap.add_argument("--connections", type=int, default=1)
    ap.add_argument("--profile", action="store_true",
                    help="run node0 under cProfile")
    ap.add_argument("--out", default=OUT)
    args = ap.parse_args()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    try:
        with open(args.out) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        doc = {
            "methodology": (
                "fresh 4-validator localnet per offered rate; loadtime "
                "Loader with tx-embedded timestamps; latency from the "
                "reporter over node0's block store; RSS sampled from "
                "/proc every 2 s during load; single host, 1 CPU core "
                "(all validators + load clients share it)"
            ),
            "reference_baseline": (
                "400 tx/s saturation, <=4 s latency "
                "(200-node DO testnet, CometBFT-QA-v1.md:137)"
            ),
            "results": [],
        }
    for rate in [int(r) for r in args.rates.split(",") if r]:
        entry = run_rate(
            rate, args.duration, args.size, args.connections, args.profile
        )
        entry["measured"] = time.strftime("round 5, %Y-%m-%d %H:%M")
        doc["results"] = [
            r
            for r in doc["results"]
            if (
                r["offered_rate"],
                r.get("connections"),
                bool(r.get("profile")),
            )
            != (rate, args.connections, args.profile)
        ] + [entry]
        doc["results"].sort(key=lambda r: r["offered_rate"])
        tmp = args.out + ".tmp"
        with open(tmp, "w") as f:
            json.dump(doc, f, indent=1)
        os.replace(tmp, args.out)
        log(
            f"rate {rate}: committed {entry['committed_tx_per_s']} tx/s, "
            f"p95 {entry['latency_s'].get('p95_s')}s, "
            f"rss peak {entry.get('rss_peak_mb')} MB"
        )
    print(json.dumps(doc["results"], indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

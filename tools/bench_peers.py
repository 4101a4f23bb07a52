"""Python networking-ceiling measurement (VERDICT r3 weak #6 / next #9).

Two curves back (or refute) the README's scaling stance that the
Python transport plane is fine for tens of peers:

A. **Per-peer transport cost**: a Switch server in a subprocess
   self-reports thread count, RSS, and process CPU while N synthetic
   peers (full SecretConnection + MConnection handshakes, echo
   traffic) hold connections — N stepped 8/16/32/64.  Echo round-trip
   latency is sampled at each step so degradation is visible, not
   just resource counts.

B. **tx/s vs peer count**: tools/bench_loadtime.py at different
   localnet sizes (full nodes, full-mesh peering).

Writes the curve to docs/data/peer_scaling.json and prints it.

    python tools/bench_peers.py [--steps 8,16,32,64]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SERVER_SNIPPET = r"""
import json, resource, sys, threading, time
sys.path.insert(0, {repo!r})
from cometbft_tpu.p2p.base_reactor import Reactor
from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
from cometbft_tpu.p2p.test_util import make_switch

CH = 0x77

class Echo(Reactor):
    def __init__(self):
        super().__init__(name="echo")
    def get_channels(self):
        return [ChannelDescriptor(id=CH, priority=1)]
    def receive(self, env):
        env.src.send(CH, env.message)

sw = make_switch(network="peer-bench", moniker="srv",
                 reactors={{"echo": Echo()}})
sw.start()
la = sw.transport.listen_addr
print(json.dumps({{"host": la.host, "port": la.port,
                   "id": sw.node_info().node_id}}), flush=True)
while True:
    time.sleep(2.0)
    print(json.dumps({{
        "peers": len(sw.peers.copy()),
        "threads": threading.active_count(),
        "rss_kb": resource.getrusage(resource.RUSAGE_SELF).ru_maxrss,
        "cpu_s": round(time.process_time(), 3),
    }}), flush=True)
"""


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", default="8,16,32,64")
    ap.add_argument("--window", type=float, default=10.0,
                    help="seconds of echo churn per step")
    ap.add_argument(
        "--out",
        default=os.path.join(REPO, "docs", "data", "peer_scaling.json"),
    )
    args = ap.parse_args()
    steps = [int(s) for s in args.steps.split(",")]

    env = dict(os.environ, PYTHONPATH=REPO, JAX_PLATFORMS="cpu")
    server = subprocess.Popen(
        [sys.executable, "-c", SERVER_SNIPPET.format(repo=REPO)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
        text=True, cwd=REPO,
    )
    hello = json.loads(server.stdout.readline())
    print(f"server: {hello}", file=sys.stderr)

    stats_lock = threading.Lock()
    latest: dict = {}

    def reader():
        for line in server.stdout:
            try:
                with stats_lock:
                    latest.update(json.loads(line))
            except ValueError:
                pass

    threading.Thread(target=reader, daemon=True).start()

    from cometbft_tpu.p2p.base_reactor import Reactor
    from cometbft_tpu.p2p.conn.connection import ChannelDescriptor
    from cometbft_tpu.p2p.netaddr import NetAddress
    from cometbft_tpu.p2p.test_util import make_switch

    CH = 0x77
    srv_addr = NetAddress(
        id=hello["id"], host=hello["host"], port=hello["port"]
    )

    class Client(Reactor):
        def __init__(self):
            super().__init__(name="echo")
            self.event = threading.Event()

        def get_channels(self):
            return [ChannelDescriptor(id=CH, priority=1)]

        def receive(self, env):
            self.event.set()

    clients = []
    reactors = []
    curve = []
    try:
        for target in steps:
            while len(clients) < target:
                r = Client()
                sw = make_switch(
                    network="peer-bench",
                    moniker=f"c{len(clients)}",
                    reactors={"echo": r},
                )
                sw.start()
                sw.dial_peer_with_address(srv_addr)
                clients.append(sw)
                reactors.append(r)
            deadline = time.monotonic() + 90
            while time.monotonic() < deadline:
                with stats_lock:
                    if latest.get("peers", 0) >= target:
                        break
                time.sleep(0.5)
            with stats_lock:
                cpu_a = latest.get("cpu_s", 0.0)
            lat = []
            t_end = time.monotonic() + args.window
            while time.monotonic() < t_end:
                for r, sw in zip(reactors, clients):
                    peers = sw.peers.copy()
                    if not peers:
                        continue
                    r.event.clear()
                    t0 = time.perf_counter()
                    if not peers[0].send(CH, b"ping"):
                        continue
                    if r.event.wait(timeout=5):
                        lat.append(time.perf_counter() - t0)
                time.sleep(0.1)
            time.sleep(2.5)  # one more stats beat
            with stats_lock:
                snap = dict(latest)
            cpu_rate = (snap.get("cpu_s", 0.0) - cpu_a) / (
                args.window + 2.5
            )
            lat.sort()
            row = {
                "peers": snap.get("peers"),
                "server_threads": snap.get("threads"),
                "server_rss_kb": snap.get("rss_kb"),
                "server_cpu_cores": round(cpu_rate, 3),
                "echo_p50_ms": round(lat[len(lat) // 2] * 1e3, 2)
                if lat else None,
                "echo_p95_ms": round(lat[int(len(lat) * 0.95)] * 1e3, 2)
                if lat else None,
                "echo_samples": len(lat),
            }
            curve.append(row)
            print(json.dumps(row), flush=True)
    finally:
        for sw in clients:
            try:
                sw.stop()
            except Exception:
                pass
        server.kill()

    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(
            {
                "measured": time.strftime("%Y-%m-%d"),
                "hardware": "single host, 1 CPU core (container); "
                            "clients share the core with the server",
                "transport_curve": curve,
                "promotion_criterion": (
                    "promote the secret-connection frame pump + accept "
                    "loop to native components when server CPU exceeds "
                    "~0.5 cores or echo p95 exceeds 50 ms at the "
                    "deployment's target peer count (reference default "
                    "caps: 40 inbound + 10 outbound peers)"
                ),
            },
            f,
            indent=1,
        )
    print(f"wrote {args.out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

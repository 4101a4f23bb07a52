"""CLI (reference: cmd/cometbft/, commands at cmd/cometbft/commands/).

``python -m cometbft_tpu <command>`` mirrors the reference's cobra
commands: init, start, testnet, unsafe-reset-all, reset-state,
rollback, gen-validator, gen-node-key, show-node-id, show-validator,
version.
"""

from __future__ import annotations

import argparse
import base64
import json
import sys
import os
import shutil
import signal

from cometbft_tpu.config import Config, default_config
from cometbft_tpu.version import __version__


def _load_config(home: str) -> Config:
    if os.path.exists(os.path.join(home, "config", "config.toml")):
        return Config.load(home)
    cfg = default_config(home)
    return cfg


def cmd_init(args) -> int:
    """(commands/init.go)"""
    from cometbft_tpu.node import init_files
    from cometbft_tpu.p2p.key import NodeKey

    cfg = _load_config(args.home)
    gen = init_files(cfg, chain_id=args.chain_id or "")
    NodeKey.load_or_generate(cfg.node_key_path)
    print(f"Initialized node in {args.home} (chain {gen.chain_id})")
    return 0


def node_from_args(args):
    """The Node ``start`` runs: the home's config with the command
    line's overrides applied (not yet started)."""
    from cometbft_tpu.node import Node

    cfg = _load_config(args.home)
    if args.proxy_app:
        cfg.base.proxy_app = args.proxy_app
    if args.p2p_laddr:
        cfg.p2p.laddr = args.p2p_laddr
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    if args.persistent_peers:
        cfg.p2p.persistent_peers = args.persistent_peers
    if args.block_sync is not None:
        cfg.base.block_sync = args.block_sync
    return Node(cfg)


def cmd_start(args) -> int:
    """(commands/run_node.go:97 NewRunNodeCmd)"""
    node = node_from_args(args)
    node.start()
    stop = {"done": False}

    def handle(signum, frame):
        stop["done"] = True

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)
    while not stop["done"]:
        if node.wait(0.5):
            break  # the node stopped on its own
    if node.is_running():
        node.stop()
    return 0


def cmd_reset_all(args) -> int:
    """(commands/reset.go UnsafeResetAllCmd) — wipe data, keep keys."""
    cfg = _load_config(args.home)
    data_dir = cfg.db_dir
    if os.path.isdir(data_dir):
        shutil.rmtree(data_dir)
    os.makedirs(data_dir, exist_ok=True)
    pv_state = cfg.priv_validator_state_path
    os.makedirs(os.path.dirname(pv_state), exist_ok=True)
    with open(pv_state, "w", encoding="utf-8") as f:
        json.dump({"height": "0", "round": 0, "step": 0}, f)
    print(f"Reset data in {data_dir}")
    return 0


def cmd_reset_state(args) -> int:
    """(commands/reset.go ResetStateCmd) — wipe chain stores AND the
    consensus WAL, but keep keys and the privval last-sign state (the
    safe validator-rotation path: CheckHRS keeps refusing re-signs of
    old heights)."""
    cfg = _load_config(args.home)
    for name in ("blockstore", "state", "evidence", "tx_index"):
        for suffix in (".db", ".sqlite", ""):
            path = os.path.join(cfg.db_dir, name + suffix)
            if os.path.isdir(path):
                shutil.rmtree(path)
            elif os.path.exists(path):
                os.remove(path)
    # remove the WAL itself; only rmtree the parent when it is the
    # WAL's dedicated directory (a custom flat wal_file must not take
    # its siblings — e.g. priv_validator_state.json — with it)
    if os.path.exists(cfg.wal_path):
        os.remove(cfg.wal_path)
    wal_dir = os.path.dirname(cfg.wal_path)
    if os.path.basename(wal_dir) == "cs.wal" and os.path.isdir(wal_dir):
        shutil.rmtree(wal_dir)
    print("Reset chain state")
    return 0


def cmd_rollback(args) -> int:
    """(commands/rollback.go)"""
    from cometbft_tpu.state import Store as StateStore
    from cometbft_tpu.state.rollback import rollback_state
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.utils.db import open_db

    cfg = _load_config(args.home)
    block_db = open_db("blockstore", cfg.base.db_backend, cfg.db_dir)
    state_db = open_db("state", cfg.base.db_backend, cfg.db_dir)
    try:
        height, app_hash = rollback_state(
            StateStore(state_db), BlockStore(block_db),
            remove_block=args.hard,
        )
        print(
            f"Rolled back state to height {height} "
            f"and app hash {app_hash.hex().upper()}"
        )
    finally:
        block_db.close()
        state_db.close()
    return 0


def cmd_gen_validator(args) -> int:
    """(commands/gen_validator.go) — emits the FULL key document, the
    same shape FilePV persists, so it can be piped into
    priv_validator_key.json."""
    from cometbft_tpu.privval import FilePV

    pv = FilePV.generate()
    print(
        json.dumps(
            {
                "address": pv.pub_key.address().hex().upper(),
                "pub_key": {
                    "type": "tendermint/PubKeyEd25519",
                    "value": base64.b64encode(pv.pub_key.bytes()).decode(),
                },
                "priv_key": {
                    "type": "tendermint/PrivKeyEd25519",
                    "value": base64.b64encode(
                        pv._priv_key.bytes()
                    ).decode(),
                },
            },
            indent=2,
        )
    )
    return 0


def cmd_gen_node_key(args) -> int:
    """Persists the key at node_key_path so the printed ID is the one
    the node will actually use (gen_node_key.go LoadOrGenNodeKey)."""
    from cometbft_tpu.p2p.key import NodeKey

    cfg = _load_config(args.home)
    nk = NodeKey.load_or_generate(cfg.node_key_path)
    print(nk.id())
    return 0


def cmd_show_node_id(args) -> int:
    from cometbft_tpu.p2p.key import NodeKey

    cfg = _load_config(args.home)
    print(NodeKey.load(cfg.node_key_path).id())
    return 0


def cmd_show_validator(args) -> int:
    from cometbft_tpu.privval import FilePV

    cfg = _load_config(args.home)
    pv = FilePV.load(
        cfg.priv_validator_key_path, cfg.priv_validator_state_path
    )
    print(
        json.dumps(
            {
                "type": "tendermint/PubKeyEd25519",
                "value": base64.b64encode(pv.pub_key.bytes()).decode(),
            }
        )
    )
    return 0


def cmd_inspect(args) -> int:
    """(internal/inspect/inspect.go) read-only RPC over a stopped
    node's stores."""
    from cometbft_tpu.inspect import Inspector

    cfg = _load_config(args.home)
    if args.rpc_laddr:
        cfg.rpc.laddr = args.rpc_laddr
    insp = Inspector(cfg)
    insp.start()
    stop = {"done": False}

    def handle(signum, frame):
        stop["done"] = True

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)
    import time as _time

    while not stop["done"]:
        _time.sleep(0.2)
    insp.stop()
    return 0


def cmd_light(args) -> int:
    """(light/cmd: cometbft light) — run a proof-verifying proxy.

    Verifies everything it serves against the subjective root of trust
    (--trusted-height/--trusted-hash) via the light client, with
    witness cross-checking when --witness addresses are given."""
    from cometbft_tpu.light.client import (
        SEQUENTIAL,
        SKIPPING,
        Client,
        TrustOptions,
    )
    from cometbft_tpu.light.proxy import Proxy
    from cometbft_tpu.light.provider import HTTPProvider
    from cometbft_tpu.light.rpc import VerifyingClient
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.rpc.client import HTTPClient
    from cometbft_tpu.utils.db import SQLiteDB

    home = os.path.join(args.home, "light")
    os.makedirs(home, exist_ok=True)
    primary = HTTPProvider(args.chain_id, args.primary)
    witnesses = [
        HTTPProvider(args.chain_id, w)
        for w in args.witness.split(",")
        if w.strip()
    ]
    trust_options = None
    if args.trusted_height or args.trusted_hash:
        if not (args.trusted_height and args.trusted_hash):
            print(
                "supply both --trusted-height and --trusted-hash "
                "(or neither to resume from the trusted store)",
                file=sys.stderr,
            )
            return 1
        trust_options = TrustOptions(
            period_ns=int(args.trust_period * 1e9),
            height=args.trusted_height,
            hash=bytes.fromhex(args.trusted_hash),
        )
    light = Client(
        chain_id=args.chain_id,
        trust_options=trust_options,
        trust_period_ns=int(args.trust_period * 1e9),
        primary=primary,
        witnesses=witnesses,
        trusted_store=LightStore(
            SQLiteDB(os.path.join(home, "trust.db"))
        ),
        verification_mode=SEQUENTIAL if args.sequential else SKIPPING,
    )
    base = args.primary if "://" in args.primary else f"http://{args.primary}"
    node = HTTPClient(base)
    host_port = args.laddr.split("://")[-1]
    host, _, port = host_port.rpartition(":")
    if not host:  # no port given: "tcp://0.0.0.0" or bare host
        host, port = host_port, ""
    try:
        port_no = int(port) if port else 8888
    except ValueError:
        print(f"invalid --laddr port: {port!r}", file=sys.stderr)
        return 1
    proxy = Proxy(
        VerifyingClient(node, light),
        host=host or "127.0.0.1",
        port=port_no,
    )
    proxy.start()
    print(f"light proxy listening on {proxy.port}")
    stop = {"done": False}

    def handle(signum, frame):
        stop["done"] = True

    signal.signal(signal.SIGINT, handle)
    signal.signal(signal.SIGTERM, handle)
    import time as _time

    while not stop["done"]:
        _time.sleep(0.2)
    proxy.stop()
    return 0


def cmd_load(args) -> int:
    """(test/loadtime/cmd/load) — generate timestamped tx load, or
    with ``--sustained`` the closed-loop ramp harness (ISSUE 10)."""
    from cometbft_tpu.loadtime import Loader, SustainedLoader, parse_ramp

    if args.sustained:
        loader = SustainedLoader(
            endpoints=[
                e for e in args.endpoints.split(",") if e.strip()
            ],
            workers=args.workers,
            tx_size=args.size,
            signed=args.signed,
            broadcast=args.broadcast_method,
        )
        report = loader.run(parse_ramp(args.sustained))
        print(json.dumps(report))
        return 0 if report["errors"] == 0 else 1
    loader = Loader(
        endpoints=[e for e in args.endpoints.split(",") if e.strip()],
        rate=args.rate,
        size=args.size,
        connections=args.connections,
        broadcast=args.broadcast_method,
    )
    summary = loader.run(args.duration)
    print(json.dumps(summary))
    return 0 if summary["errors"] == 0 else 1


def cmd_load_report(args) -> int:
    """(test/loadtime/cmd/report) — latency stats from the block
    store's timestamps."""
    from cometbft_tpu.loadtime import report_from_home

    reports = report_from_home(args.home)
    if not reports:
        print("no loadtime transactions found")
        return 1
    for rep in reports:
        print(json.dumps(rep.as_dict()))
    return 0


def cmd_compact_db(args) -> int:
    """(commands/compact.go) — reclaim storage in every chain store."""
    from cometbft_tpu.utils.db import open_db

    cfg = _load_config(args.home)
    if cfg.base.db_backend == "memdb":
        print("memdb backend: nothing to compact")
        return 0
    for name in ("blockstore", "state", "evidence", "tx_index"):
        path = os.path.join(cfg.db_dir, f"{name}.db")
        if not os.path.exists(path):
            continue
        before = os.path.getsize(path)
        db = open_db(name, cfg.base.db_backend, cfg.db_dir)
        try:
            db.compact()
        finally:
            db.close()
        after = os.path.getsize(path)
        print(f"{name}: {before} -> {after} bytes")
    return 0


def cmd_reindex_event(args) -> int:
    """(commands/reindex_event.go) — replay stored blocks + ABCI
    results through the configured indexers for [start, end]."""
    from cometbft_tpu.state import Store as StateStore
    from cometbft_tpu.store import BlockStore
    from cometbft_tpu.utils.db import open_db

    cfg = _load_config(args.home)
    if cfg.tx_index.indexer == "null":
        print("indexer = \"null\": nothing to reindex")
        return 1
    backend = cfg.base.db_backend
    block_db = open_db("blockstore", backend, cfg.db_dir)
    state_db = open_db("state", backend, cfg.db_dir)
    from cometbft_tpu.state.txindex import build_indexers
    from cometbft_tpu.types.genesis import GenesisDoc

    gen = GenesisDoc.from_file(cfg.genesis_path)
    tx_indexer, block_indexer, closer = build_indexers(cfg, gen.chain_id)
    try:
        block_store = BlockStore(block_db)
        state_store = StateStore(state_db)
        base, head = block_store.base(), block_store.height()
        start = args.start_height or base
        end = args.end_height or head
        if start < base or end > head or start > end:
            print(
                f"height range [{start}, {end}] outside stored "
                f"[{base}, {head}]",
                file=sys.stderr,
            )
            return 1
        n_txs = 0
        for height in range(start, end + 1):
            block = block_store.load_block(height)
            resp = state_store.load_finalize_block_response(height)
            if block is None or resp is None:
                print(f"missing block/results at {height}", file=sys.stderr)
                return 1
            block_indexer.index(height, resp.events)
            for i, tx in enumerate(block.data.txs):
                result = resp.tx_results[i]
                tx_indexer.index(height, i, bytes(tx), result)
                n_txs += 1
        print(f"reindexed heights [{start}, {end}]: {n_txs} txs")
        return 0
    finally:
        block_db.close()
        state_db.close()
        closer()


def cmd_confix(args) -> int:
    """(internal/confix migrations.go:1, upgrade.go:29) — migrate
    config.toml across versions and normalize to the current schema:
    keys renamed between versions carry the operator's value
    (fast_sync -> block_sync, timeout_prevote -> timeout_vote),
    missing keys are added at current defaults, unknown keys dropped.
    --from pins the source version (default: fingerprint detection);
    --dry-run prints the plan + result instead of writing; a .bak of
    the original is kept otherwise."""
    from cometbft_tpu import confix

    path = os.path.join(args.home, "config", "config.toml")
    if not os.path.exists(path):
        print(f"no config at {path}", file=sys.stderr)
        return 1
    with open(path, encoding="utf-8") as f:
        old = f.read()
    try:
        # migrate() owns the write: .bak of the original + tmp-file +
        # os.replace, so a crash mid-write can't truncate the config
        steps, new_toml = confix.migrate(
            args.home,
            from_version=args.from_version,
            dry_run=args.dry_run,
            skip_validate=args.skip_validate,
        )
    except Exception as exc:  # noqa: BLE001 — CLI boundary
        print(f"confix failed: {exc}", file=sys.stderr)
        return 1
    for step in steps:
        print(f"  {step}")
    if args.dry_run:
        print(new_toml)
    elif old == new_toml:
        print("config already at current schema")
    else:
        print(f"rewrote {path} (backup at {path}.bak)")
    return 0


def cmd_debug_kill(args) -> int:
    """(commands/debug/kill.go) — collect a diagnostic archive from a
    running node, trigger its SIGUSR1 stack dump, then SIGKILL it."""
    import tarfile
    import tempfile
    import time as _time
    import urllib.request

    cfg = _load_config(args.home)
    pid = args.pid
    tmp = tempfile.mkdtemp(prefix="cmt-debug-")

    def save(name: str, data: bytes) -> None:
        with open(os.path.join(tmp, name), "wb") as f:
            f.write(data)

    # 1. live RPC state if reachable (status/net_info/consensus)
    if args.rpc_laddr:
        base = args.rpc_laddr.split("://")[-1]
        for route in ("status", "net_info", "dump_consensus_state"):
            try:
                with urllib.request.urlopen(
                    f"http://{base}/{route}", timeout=3
                ) as resp:
                    save(f"{route}.json", resp.read())
            except Exception as exc:  # noqa: BLE001
                save(f"{route}.err", repr(exc).encode())
    # 2. stack dump via SIGUSR1 (diagnostics.install_stack_dump_signal)
    dump_path = os.path.join(cfg.db_dir, "stacks.dump")
    try:
        os.kill(pid, signal.SIGUSR1)
        _time.sleep(1.0)
        if os.path.exists(dump_path):
            with open(dump_path, "rb") as f:
                save("stacks.dump", f.read())
    except ProcessLookupError:
        save("kill.err", b"process not running")
    # 3. config + genesis
    for name in ("config.toml", "genesis.json"):
        p = os.path.join(args.home, "config", name)
        if os.path.exists(p):
            with open(p, "rb") as f:
                save(name, f.read())
    out = args.output or f"cometbft-debug-{pid}.tar.gz"
    with tarfile.open(out, "w:gz") as tar:
        tar.add(tmp, arcname="debug")
    shutil.rmtree(tmp, ignore_errors=True)
    # 4. kill
    try:
        os.kill(pid, signal.SIGKILL)
    except ProcessLookupError:
        pass
    print(f"wrote {out}")
    return 0


def cmd_debug_dump(args) -> int:
    """(commands/debug/dump.go) — periodically collect debug archives
    from a running node: RPC state (status/net_info/
    dump_consensus_state), the diagnostics plane's stack dump, GC
    stats, and a CPU profile (the goroutine/heap/profile analogs),
    plus config — one timestamped .tar.gz per interval in
    ``output_dir``."""
    import tarfile
    import tempfile
    import time as _time
    import urllib.request

    os.makedirs(args.output_dir, exist_ok=True)
    base = args.rpc_laddr.split("://")[-1]
    diag = args.diag_laddr.split("://")[-1] if args.diag_laddr else None
    rounds = 0
    while True:
        # round counter in the name: sub-second --frequency must not
        # overwrite the previous archive
        stamp = f"{_time.strftime('%Y%m%d-%H%M%S')}-{rounds:04d}"
        tmp = tempfile.mkdtemp(prefix="cmt-dump-")

        def save(name: str, data: bytes) -> None:
            with open(os.path.join(tmp, name), "wb") as f:
                f.write(data)

        for route in ("status", "net_info", "dump_consensus_state"):
            try:
                with urllib.request.urlopen(
                    f"http://{base}/{route}", timeout=5
                ) as resp:
                    save(f"{route}.json", resp.read())
            except Exception as exc:  # noqa: BLE001 — collect best-effort
                save(f"{route}.err", repr(exc).encode())
        if diag:
            probes = [
                ("stacks.txt", "/debug/stacks", 5),
                ("gc.txt", "/debug/gc", 5),
                (
                    "profile.txt",
                    f"/debug/profile?seconds={args.profile_seconds}",
                    args.profile_seconds + 10,
                ),
            ]
            for name, route, timeout in probes:
                try:
                    with urllib.request.urlopen(
                        f"http://{diag}{route}", timeout=timeout
                    ) as resp:
                        save(name, resp.read())
                except Exception as exc:  # noqa: BLE001
                    save(name + ".err", repr(exc).encode())
        p = os.path.join(args.home, "config", "config.toml")
        if os.path.exists(p):
            with open(p, "rb") as f:
                save("config.toml", f.read())
        out = os.path.join(args.output_dir, f"{stamp}.tar.gz")
        with tarfile.open(out, "w:gz") as tar:
            tar.add(tmp, arcname="debug")
        shutil.rmtree(tmp, ignore_errors=True)
        print(f"wrote {out}")
        rounds += 1
        if args.count and rounds >= args.count:
            return 0
        _time.sleep(args.frequency)


def cmd_version(args) -> int:
    print(__version__)
    return 0


def cmd_testnet(args) -> int:
    """(commands/testnet.go) — N validator homes + shared genesis +
    full-mesh persistent peers."""
    from cometbft_tpu.p2p.key import NodeKey
    from cometbft_tpu.privval import FilePV
    from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
    from cometbft_tpu.utils.time import now_ns

    n = args.v
    chain_id = args.chain_id or f"chain-{os.urandom(3).hex()}"
    pvs, configs = [], []
    for i in range(n):
        home = os.path.join(args.o, f"node{i}")
        cfg = default_config(home)
        cfg.ensure_dirs()
        pv = FilePV.generate(
            cfg.priv_validator_key_path, cfg.priv_validator_state_path
        )
        pv.save()
        NodeKey.load_or_generate(cfg.node_key_path)
        pvs.append(pv)
        configs.append(cfg)
    from dataclasses import replace as _replace

    from cometbft_tpu.types.params import ConsensusParams

    base_params = ConsensusParams()
    gen = GenesisDoc(
        chain_id=chain_id,
        genesis_time_ns=now_ns(),
        validators=tuple(GenesisValidator(pv.pub_key, 1) for pv in pvs),
        # PBTS from height 1, matching node.init_files (see its note)
        consensus_params=_replace(
            base_params,
            feature=_replace(base_params.feature, pbts_enable_height=1),
        ),
    )
    ids = [NodeKey.load(cfg.node_key_path).id() for cfg in configs]

    def node_addr(j: int) -> tuple[str, int, int]:
        """(host, p2p_port, rpc_port) for node j. With
        --starting-ip-address each node gets its OWN address
        (testnet.go:91 startingIPAddress, the docker-e2e convention)
        and the standard ports; otherwise sequential ports on
        localhost."""
        if args.starting_ip:
            base = args.starting_ip.rsplit(".", 1)
            host = f"{base[0]}.{int(base[1]) + j}"
            return host, args.starting_port, args.starting_port + 1
        return "127.0.0.1", (
            args.starting_port + 2 * j
        ), args.starting_port + 2 * j + 1

    for i, cfg in enumerate(configs):
        host, p2p_port, rpc_port = node_addr(i)
        # bind all interfaces: inside a netns/container the node's IP
        # lives on its veth, not on loopback
        bind = "0.0.0.0" if args.starting_ip else host
        cfg.p2p.laddr = f"tcp://{bind}:{p2p_port}"
        cfg.rpc.laddr = f"tcp://{bind}:{rpc_port}"
        cfg.p2p.persistent_peers = ",".join(
            "{}@{}:{}".format(ids[j], *node_addr(j)[:2])
            for j in range(n)
            if j != i
        )
        gen.save_as(cfg.genesis_path)
        cfg.save()
    print(f"Successfully initialized {n} node directories in {args.o}")
    return 0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(
        prog="cometbft_tpu",
        description="BFT state machine replication (TPU-native build)",
    )
    parser.add_argument(
        "--home",
        default=os.environ.get(
            "CMTHOME", os.path.expanduser("~/.cometbft_tpu")
        ),
    )
    sub = parser.add_subparsers(dest="command")

    p = sub.add_parser("init", help="initialize a node home")
    p.add_argument("--chain-id", default="")
    p.set_defaults(fn=cmd_init)

    p = sub.add_parser("start", help="run the node")
    p.add_argument("--proxy_app", default="")
    p.add_argument("--p2p.laddr", dest="p2p_laddr", default="")
    p.add_argument("--rpc.laddr", dest="rpc_laddr", default="")
    p.add_argument("--p2p.persistent_peers", dest="persistent_peers",
                   default="")
    p.add_argument(
        "--block_sync",
        action=argparse.BooleanOptionalAction,
        default=None,
        help="force block sync on/off (--no-block_sync for "
        "consensus-only startup)",
    )
    p.set_defaults(fn=cmd_start)

    p = sub.add_parser("unsafe-reset-all", help="wipe data, keep keys")
    p.set_defaults(fn=cmd_reset_all)
    p = sub.add_parser("reset-state", help="wipe chain stores")
    p.set_defaults(fn=cmd_reset_state)

    p = sub.add_parser(
        "inspect",
        help="read-only RPC server over the stores of a stopped node",
    )
    p.add_argument("--rpc.laddr", dest="rpc_laddr", default="")
    p.set_defaults(fn=cmd_inspect)

    p = sub.add_parser("rollback", help="roll state back one height")
    p.add_argument("--hard", action="store_true",
                   help="also remove the block")
    p.set_defaults(fn=cmd_rollback)

    for name, fn in (
        ("gen-validator", cmd_gen_validator),
        ("gen-node-key", cmd_gen_node_key),
        ("show-node-id", cmd_show_node_id),
        ("show-validator", cmd_show_validator),
        ("version", cmd_version),
    ):
        p = sub.add_parser(name)
        p.set_defaults(fn=fn)

    p = sub.add_parser(
        "light",
        help="run a proof-verifying light proxy against a full node",
    )
    p.add_argument("chain_id")
    p.add_argument("--primary", required=True,
                   help="primary full-node RPC address")
    p.add_argument("--witness", default="",
                   help="comma-separated witness RPC addresses")
    p.add_argument("--trusted-height", type=int, default=0,
                   help="trust-root height (required on first run; "
                   "omit with --trusted-hash to resume from the "
                   "existing trusted store, light.go:189)")
    p.add_argument("--trusted-hash", default="",
                   help="hex header hash at the trusted height")
    p.add_argument("--trust-period", type=float, default=168 * 3600,
                   help="trusting period in seconds")
    p.add_argument("--laddr", default="tcp://127.0.0.1:8888")
    p.add_argument("--sequential", action="store_true",
                   help="sequential verification instead of skipping")
    p.set_defaults(fn=cmd_light)

    p = sub.add_parser("compact-db", help="reclaim storage in the stores")
    p.set_defaults(fn=cmd_compact_db)

    p = sub.add_parser(
        "reindex-event",
        help="re-index stored blocks' events over a height range",
    )
    p.add_argument("--start-height", type=int, default=0)
    p.add_argument("--end-height", type=int, default=0)
    p.set_defaults(fn=cmd_reindex_event)

    p = sub.add_parser(
        "confix", help="migrate/normalize config.toml to the current schema"
    )
    p.add_argument("--dry-run", action="store_true")
    p.add_argument(
        "--from", dest="from_version", default=None,
        help="source config version (v0.34/v0.37/v0.38/v1.0); "
             "default: auto-detect",
    )
    p.add_argument("--skip-validate", action="store_true")
    p.set_defaults(fn=cmd_confix)

    p = sub.add_parser(
        "debug",
        help="debugging tools (kill: archive diagnostics then SIGKILL)",
    )
    dsub = p.add_subparsers(dest="debug_command")
    dk = dsub.add_parser("kill")
    dk.add_argument("pid", type=int)
    dk.add_argument("--output", default="")
    dk.add_argument("--rpc-laddr", default="",
                    help="node RPC to snapshot (host:port)")
    dk.set_defaults(fn=cmd_debug_kill)
    dd = dsub.add_parser(
        "dump", help="periodic debug archives (dump.go analog)"
    )
    dd.add_argument("output_dir")
    dd.add_argument("--frequency", type=float, default=30.0,
                    help="seconds between collections")
    dd.add_argument("--count", type=int, default=0,
                    help="stop after N archives (0 = run until killed)")
    dd.add_argument("--rpc-laddr", default="127.0.0.1:26657",
                    help="node RPC address (host:port)")
    dd.add_argument("--diag-laddr", default="",
                    help="diagnostics plane address (host:port)")
    dd.add_argument("--profile-seconds", type=int, default=5)
    dd.set_defaults(fn=cmd_debug_dump)

    p = sub.add_parser("load", help="generate timestamped tx load")
    p.add_argument("--endpoints", required=True,
                   help="comma-separated RPC addresses")
    p.add_argument("--rate", type=int, default=100, help="txs per second")
    p.add_argument("--size", type=int, default=1024, help="tx bytes")
    p.add_argument("--connections", type=int, default=1)
    p.add_argument("--duration", type=float, default=60.0, help="seconds")
    p.add_argument("--broadcast-method", default="broadcast_tx_sync")
    p.add_argument(
        "--sustained", default="",
        help="closed-loop ramp schedule 'rate:seconds,...' (rate 0 = "
        "saturate); measures admission latency percentiles and "
        "shed/accept accounting instead of the fixed-rate loader",
    )
    p.add_argument("--workers", type=int, default=8,
                   help="concurrent submitters (sustained mode)")
    p.add_argument("--signed", action="store_true",
                   help="wrap payloads in the signed admission "
                   "envelope (mempool/ingest.py) — exercises the "
                   "device-batched CheckTx plane")
    p.set_defaults(fn=cmd_load)

    p = sub.add_parser(
        "load-report",
        help="latency report from a node home's block store",
    )
    p.set_defaults(fn=cmd_load_report)

    p = sub.add_parser("testnet", help="generate a localnet")
    p.add_argument("--v", type=int, default=4)
    p.add_argument("--o", default="./mytestnet")
    p.add_argument("--chain-id", default="")
    p.add_argument("--starting-port", type=int, default=26656)
    p.add_argument("--starting-ip-address", dest="starting_ip", default="",
                   help="give node i the address base+i with standard "
                   "ports (one node per network namespace/container) "
                   "instead of sequential ports on localhost")
    p.set_defaults(fn=cmd_testnet)

    args = parser.parse_args(argv)
    if not getattr(args, "fn", None):
        parser.print_help()
        return 1
    return args.fn(args)


__all__ = ["main"]

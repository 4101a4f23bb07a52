"""Node — the composition root (reference: node/node.go:280-645).

Wires DBs → state → proxy app → event bus → privval → handshake/replay
→ mempool → block executor → WAL → consensus, in the reference's
startup order.  The p2p switch, sync reactors, and RPC server attach
here as those planes land (node/node.go:320-569).
"""

from __future__ import annotations

import os

from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.abci.types import Application
from cometbft_tpu.config import Config
from cometbft_tpu.consensus import ConsensusState, Handshaker
from cometbft_tpu.blocksync import BlocksyncReactor
from cometbft_tpu.consensus.reactor import ConsensusReactor
from cometbft_tpu.rpc import Environment, JSONRPCServer
from cometbft_tpu.state.txindex import (
    IndexerService,
)
from cometbft_tpu.statesync import StatesyncReactor
from cometbft_tpu.evidence import EvidenceReactor, Pool as EvidencePool
from cometbft_tpu.mempool.reactor import MempoolReactor
from cometbft_tpu.p2p import (
    MConnConfig,
    MultiplexTransport,
    NetAddress,
    NodeInfo,
    NodeKey,
    Switch,
    parse_peer_list,
)
from cometbft_tpu.mempool import (
    CListMempool,
    NopMempool,
    post_check_max_gas,
    pre_check_max_bytes,
)
from cometbft_tpu.privval import FilePV
from cometbft_tpu.proxy import (
    AppConns,
    default_client_creator,
    local_client_creator,
)
from cometbft_tpu.state import (
    Store as StateStore,
    determinism,
    load_state_from_db_or_genesis,
)
from cometbft_tpu.state.execution import BlockExecutor
from cometbft_tpu.store import BlockStore
from cometbft_tpu.types.event_bus import EventBus
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.utils.db import open_db
from cometbft_tpu.utils.env import flag_from_env
from cometbft_tpu.utils.log import Logger, default_logger
from cometbft_tpu.utils.service import BaseService
from cometbft_tpu.utils.time import now_ns
from cometbft_tpu.wal import WAL, NopWAL


class NodeError(Exception):
    pass


def init_files(config: Config, chain_id: str = "") -> GenesisDoc:
    """``cometbft init`` — write privval key/state and a
    single-validator genesis (cmd/cometbft/commands/init.go)."""
    config.ensure_dirs()
    pv = FilePV.load_or_generate(
        config.priv_validator_key_path, config.priv_validator_state_path
    )
    pv.save()
    gen_path = config.genesis_path
    if os.path.exists(gen_path):
        return GenesisDoc.from_file(gen_path)
    from dataclasses import replace as _replace

    from cometbft_tpu.types.params import ConsensusParams

    base_params = ConsensusParams()
    gen = GenesisDoc(
        chain_id=chain_id or f"test-chain-{os.urandom(3).hex()}",
        genesis_time_ns=now_ns(),
        validators=(GenesisValidator(pv.pub_key, 10),),
        # Proposer-based timestamps from height 1: block time is the
        # proposer's clock (bounded by synchrony params) instead of
        # the previous round's vote median, so block timestamps track
        # real time tightly — which also makes load-report latencies
        # meaningful.  (The reference leaves PBTS opt-in,
        # FeatureParams.PbtsEnableHeight; new chains here get the
        # modern behavior by default.)
        consensus_params=_replace(
            base_params,
            feature=_replace(base_params.feature, pbts_enable_height=1),
        ),
    )
    gen.save_as(gen_path)
    config.save()
    return gen


def default_app(config: Config) -> Application:
    """Resolve config.base.proxy_app to a builtin app (node/setup.go
    DefaultNewNode's kvstore shortcut); builtin_app_snapshot_interval
    makes the kvstore serve statesync snapshots."""
    name = config.base.proxy_app
    if name == "kvstore":
        return KVStoreApp(
            snapshot_interval=config.base.builtin_app_snapshot_interval
        )
    if name == "noop":
        return Application()
    raise NodeError(f"unknown builtin app {name!r}")


class Node(BaseService):
    """(node/node.go Node)"""

    def __init__(
        self,
        config: Config,
        app: Application | None = None,
        genesis: GenesisDoc | None = None,
        priv_validator: FilePV | None = None,
        state_providers: list | None = None,  # light providers for statesync
        logger: Logger | None = None,
    ):
        super().__init__(
            name="node",
            logger=logger or default_logger().with_fields(module="node"),
        )
        config.validate_basic()
        self.config = config

        # 0. metrics plane (node/node.go:334 metricsProvider)
        from cometbft_tpu.metrics import (
            NodeMetrics,
            install_attribution_metrics,
            install_crypto_metrics,
            install_fleet_metrics,
            install_health_metrics,
            install_light_metrics,
            install_netem_metrics,
            install_p2p_metrics,
        )
        from cometbft_tpu.utils.metrics import MetricsServer, Registry

        if config.instrumentation.prometheus:
            registry = Registry(config.instrumentation.namespace)
            self.metrics = NodeMetrics(registry)
            self.metrics_server = MetricsServer(
                registry,
                config.instrumentation.prometheus_listen_addr,
                logger=self.logger.with_fields(module="metrics"),
            )
            # the crypto/device hot paths (batch verifier, table cache)
            # are module-level singletons: point the process-wide sink
            # at this node's struct (last installed wins; updates to a
            # stopped node's registry are harmless).  SecretConnection
            # (handshake/frame accounting under the transport) uses the
            # analogous p2p sink.
            install_crypto_metrics(self.metrics.crypto)
            install_p2p_metrics(self.metrics.p2p)
            # the WAN-emulation plane (p2p/conn/netem.py stages are
            # constructed per peer with no node handle) — same sink
            install_netem_metrics(self.metrics.netem)
            # the device-health plane (watchdog, prober —
            # crypto/health.py) shares the singleton-sink pattern
            install_health_metrics(self.metrics.health)
            # the light serving plane (header cache + request surface,
            # light/serve.py) — consulted from RPC handler threads
            install_light_metrics(self.metrics.light)
            # the fleet plane (/debug/fleet + tools/fleet_scrape.py)
            # scrapes with no node handle — same sink pattern
            install_fleet_metrics(self.metrics.fleet)
            # the attribution plane (utils/critpath.py observe_height
            # runs from the consensus commit path) — same sink pattern
            install_attribution_metrics(self.metrics.attribution)
        else:
            self.metrics = NodeMetrics(None)
            self.metrics_server = None
        # the trust-boundary guard (utils/trustguard.py) trips from
        # sinks in types/ with no node handle — same sink pattern
        # (the no-op NodeMetrics branch installs a _NOP counter)
        from cometbft_tpu.utils import trustguard

        trustguard.install_metrics(self.metrics.consensus)
        #: background tier prober (started with the metrics server;
        #: CMT_TPU_HEALTH_INTERVAL=0 disables)
        self.health_prober = None
        #: pipelined verify-ahead queue (crypto/verify_queue.py;
        #: CMT_TPU_VERIFY_QUEUE=0 disables): consensus votes and
        #: blocksync prefetch coalesce into double-buffered batches
        #: through the dispatch ladder, and verify_commit consults the
        #: speculative-result cache.  Started in _start_services,
        #: drained in on_stop.
        self.verify_queue = None

        # 1. stores (node/node.go:320 initDBs)
        backend = config.base.db_backend
        db_dir = config.db_dir
        self.block_store_db = open_db("blockstore", backend, db_dir)
        self.state_db = open_db("state", backend, db_dir)
        self.block_store = BlockStore(
            self.block_store_db, metrics=self.metrics.store
        )
        self.state_store = StateStore(self.state_db)

        # 2. genesis + state (node.go:329)
        if genesis is None:
            genesis = GenesisDoc.from_file(config.genesis_path)
        self.genesis = genesis
        state = load_state_from_db_or_genesis(self.state_store, genesis)

        # 3. proxy app (setup.go:172) — external process for tcp://,
        # unix:// (socket protocol) and grpc:// addresses, builtin
        # in-process otherwise
        proxy_addr = config.base.proxy_app
        if app is None and proxy_addr.startswith(
            ("tcp://", "unix://", "grpc://")
        ):
            self.app = None
            self.proxy_app = AppConns(
                default_client_creator(proxy_addr),
                metrics=self.metrics.abci,
            )
        else:
            self.app = app if app is not None else default_app(config)
            self.proxy_app = AppConns(
                local_client_creator(self.app), metrics=self.metrics.abci
            )
        # fail-stop on the first fatal app/client error (multiAppConn
        # killChan semantics): an app whose state is unknown takes the
        # node down instead of leaving a poisoned proxy that answers
        # RPC as a zombie.  In-process apps report synchronously;
        # external (socket/grpc) apps via the AppConns error watcher.
        self.proxy_app.set_on_error(self._stop_for_app_error)

        # 4. event bus + indexer (setup.go:181,190)
        self.event_bus = EventBus(metrics=self.metrics.event_bus)
        from cometbft_tpu.state.txindex import build_indexers

        (
            self.tx_indexer,
            self.block_indexer,
            self._indexer_closer,
        ) = build_indexers(config, self.genesis.chain_id)
        self.indexer_service = IndexerService(
            self.tx_indexer,
            self.block_indexer,
            self.event_bus,
            logger=self.logger.with_fields(module="indexer"),
        )

        # 5. privval (setup.go:698) — a priv_validator_laddr means the
        # key lives in an external signer process that dials us
        self.privval_listener = None
        if priv_validator is None and config.base.priv_validator_laddr:
            from cometbft_tpu.privval.signer import (
                SignerClient,
                SignerListenerEndpoint,
            )

            self.privval_listener = SignerListenerEndpoint(
                config.base.priv_validator_laddr,
                genesis.chain_id,
                logger=self.logger.with_fields(module="privval"),
            )
            priv_validator = SignerClient(self.privval_listener)
        elif priv_validator is None and os.path.exists(
            config.priv_validator_key_path
        ):
            priv_validator = FilePV.load(
                config.priv_validator_key_path,
                config.priv_validator_state_path,
            )
        self.priv_validator = priv_validator

        # 6. handshake happens at start (doHandshake, setup.go:222)
        self._pre_handshake_state = state
        self.state = state

        # 7. mempool (setup.go:277)
        if config.mempool.type == "nop":
            self.mempool = NopMempool()
        else:
            self.mempool = CListMempool(
                self.proxy_app.mempool,
                height=state.last_block_height,
                size=config.mempool.size,
                max_tx_bytes=config.mempool.max_tx_bytes,
                max_txs_bytes=config.mempool.max_txs_bytes,
                cache_size=config.mempool.cache_size,
                keep_invalid_txs_in_cache=config.mempool.keep_invalid_txs_in_cache,
                recheck=config.mempool.recheck,
                metrics=self.metrics.mempool,
            )

        # 8. evidence pool (setup.go:329 createEvidenceReactor)
        self.evidence_db = open_db("evidence", backend, db_dir)
        self.evidence_pool = EvidencePool(
            self.evidence_db,
            self.state_store,
            self.block_store,
            logger=self.logger.with_fields(module="evidence"),
            metrics=self.metrics.evidence,
        )

        # 9. block executor (node.go:447)
        self.block_exec = BlockExecutor(
            self.state_store,
            self.proxy_app.consensus,
            self.mempool,
            block_store=self.block_store,
            event_bus=self.event_bus,
            evidence_pool=self.evidence_pool,
            metrics=self.metrics.state,
            logger=self.logger.with_fields(module="executor"),
        )

        # 9b. background pruner (node.go:1067 createPruner): consumes the
        # retain heights the app (and optionally a data companion)
        # persists, and deletes blocks/state/ABCI results behind them.
        from cometbft_tpu.state.pruner import Pruner

        self.pruner = Pruner(
            self.state_store,
            self.block_store,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            interval_s=config.storage.pruning_interval_ns / 1e9,
            companion_enabled=config.storage.companion_pruning,
            metrics=self.metrics.state,
            logger=self.logger.with_fields(module="pruner"),
        )
        self.block_exec.pruner = self.pruner

        # 9c. gRPC data + privileged services (rpc/grpc/server): opt-in
        # via [grpc] laddr / privileged_laddr.
        self.grpc_server = None
        self.grpc_privileged = None
        if config.grpc.laddr:
            from cometbft_tpu.rpc.grpc_services import GrpcDataServer

            self.grpc_server = GrpcDataServer(
                config.grpc.laddr,
                self.block_store,
                self.state_store,
                version_enabled=config.grpc.version_service_enabled,
                block_enabled=config.grpc.block_service_enabled,
                block_results_enabled=(
                    config.grpc.block_results_service_enabled
                ),
                logger=self.logger.with_fields(module="grpc"),
            )
        if config.grpc.privileged_laddr and config.grpc.pruning_service_enabled:
            from cometbft_tpu.rpc.grpc_services import GrpcPrivilegedServer

            self.pruner.companion_enabled = True
            self.grpc_privileged = GrpcPrivilegedServer(
                config.grpc.privileged_laddr,
                self.pruner,
                logger=self.logger.with_fields(module="grpc-privileged"),
            )

        # 10. WAL + consensus (setup.go:369).  memdb nodes are ephemeral
        # (tests): give them a no-op WAL.
        if config.base.db_backend == "memdb":
            self.wal = NopWAL()
        else:
            self.wal = WAL(config.wal_path, metrics=self.metrics.wal)
        self.consensus = ConsensusState(
            config.consensus,
            state,
            self.block_exec,
            self.block_store,
            priv_validator=self.priv_validator,
            event_bus=self.event_bus,
            wal=self.wal,
            metrics=self.metrics.consensus,
            logger=self.logger.with_fields(module="consensus"),
        )

        # 11. p2p: reactors → transport → switch (setup.go:404-473)
        # Block sync is ON by default (the reference has no off switch
        # in v1): a restarted or wiped node must catch up from peers
        # BEFORE consensus signs anything.  The blocksync reactor
        # switches to consensus immediately when this node's own
        # voting power blocks the chain (node can't be behind a chain
        # that cannot progress without it — reactor.go
        # localNodeBlocksTheChain), which covers the sole-validator
        # case.  config.base.block_sync=False is the test/embedding
        # escape hatch for consensus-only startup.
        self.block_sync_enabled = config.base.block_sync
        self.consensus_reactor = ConsensusReactor(
            self.consensus,
            wait_sync=self.block_sync_enabled or config.statesync.enable,
            logger=self.logger.with_fields(module="consensus-reactor"),
        )
        self.blocksync_reactor = BlocksyncReactor(
            state,
            self.block_exec,
            self.block_store,
            # statesync owns the bootstrap when enabled; it hands off to
            # blocksync via start_sync on completion (node.go blockSync
            # && !stateSync)
            block_sync=self.block_sync_enabled
            and not config.statesync.enable,
            consensus_reactor=self.consensus_reactor,
            # lazily resolved: a remote signer's address is unknown
            # until the external process dials in after start, and
            # resolving too early would BLOCK the pool routine for the
            # whole accept timeout — probe the listener first
            local_addr=self._make_local_addr_resolver(priv_validator),
            logger=self.logger.with_fields(module="blocksync"),
            metrics=self.metrics.blocksync,
            statesync_metrics=self.metrics.statesync,
        )
        self.mempool_reactor = MempoolReactor(
            self.mempool,
            broadcast=config.mempool.broadcast
            and config.mempool.type != "nop",
            logger=self.logger.with_fields(module="mempool-reactor"),
        )
        self.evidence_reactor = EvidenceReactor(
            self.evidence_pool,
            logger=self.logger.with_fields(module="evidence-reactor"),
        )
        # statesync (node/setup.go:557 startStateSync)
        ss_enabled = config.statesync.enable
        state_provider = None
        if ss_enabled:
            state_provider = self._make_state_provider(
                config, genesis, state_providers or []
            )
        self.statesync_reactor = StatesyncReactor(
            self.proxy_app.snapshot,
            enabled=ss_enabled,
            state_provider=state_provider,
            on_complete=self._on_statesync_complete,
            discovery_time=config.statesync.discovery_time_ns / 1e9,
            logger=self.logger.with_fields(module="statesync"),
            metrics=self.metrics.statesync,
        )

        reactors = {
            "BLOCKSYNC": self.blocksync_reactor,
            "CONSENSUS": self.consensus_reactor,
            "MEMPOOL": self.mempool_reactor,
            "EVIDENCE": self.evidence_reactor,
            "STATESYNC": self.statesync_reactor,
        }

        # PEX + address book (node/setup.go createSwitch/createPEXReactor)
        self.addr_book = None
        self.pex_reactor = None
        if config.p2p.pex:
            from cometbft_tpu.p2p.pex import AddrBook, PexReactor

            book_path = config.addr_book_path
            self.addr_book = AddrBook(
                book_path,
                strict=config.p2p.addr_book_strict,
                logger=self.logger.with_fields(module="addrbook"),
            )
            seeds = parse_peer_list(config.p2p.seeds)
            if config.p2p.private_peer_ids:
                self.addr_book.add_private_ids(
                    [
                        s.strip()
                        for s in config.p2p.private_peer_ids.split(",")
                        if s.strip()
                    ]
                )
            self.pex_reactor = PexReactor(
                self.addr_book,
                seeds=seeds,
                seed_mode=config.p2p.seed_mode,
                ensure_interval=config.p2p.ensure_peers_interval_ns / 1e9,
                logger=self.logger.with_fields(module="pex"),
            )
            reactors["PEX"] = self.pex_reactor
        self.node_key = NodeKey.load_or_generate(config.node_key_path)
        channels = bytes(
            d.id for r in reactors.values() for d in r.get_channels()
        )
        self._p2p_laddr = NetAddress.parse(config.p2p.laddr)
        node_info = NodeInfo(
            node_id=self.node_key.id(),
            listen_addr=config.p2p.laddr,
            network=genesis.chain_id,
            channels=channels,
            moniker=config.base.moniker,
        )
        self.transport = MultiplexTransport(
            node_info,
            self.node_key,
            handshake_timeout=config.p2p.handshake_timeout_ns / 1e9,
            dial_timeout=config.p2p.dial_timeout_ns / 1e9,
            logger=self.logger.with_fields(module="transport"),
        )
        self.switch = Switch(
            self.transport,
            mconn_config=MConnConfig(
                send_rate=config.p2p.send_rate,
                recv_rate=config.p2p.recv_rate,
                max_packet_msg_payload_size=config.p2p.max_packet_msg_payload_size,
                flush_throttle=config.p2p.flush_throttle_timeout_ns / 1e9,
            ),
            max_inbound=config.p2p.max_num_inbound_peers,
            max_outbound=config.p2p.max_num_outbound_peers,
            metrics=self.metrics.p2p,
            logger=self.logger.with_fields(module="switch"),
        )
        for name, reactor in reactors.items():
            self.switch.add_reactor(name, reactor)
        if self.addr_book is not None:
            self.switch.addr_book = self.addr_book
            self.addr_book.add_our_address(
                NetAddress(
                    id=self.node_key.id(),
                    host="127.0.0.1",
                    port=0,
                )
            )

        # 12. RPC (node.go:598 startRPC)
        self.rpc_env = Environment(
            block_store=self.block_store,
            state_store=self.state_store,
            consensus=self.consensus,
            mempool=self.mempool,
            switch=self.switch,
            event_bus=self.event_bus,
            tx_indexer=self.tx_indexer,
            block_indexer=self.block_indexer,
            proxy_app=self.proxy_app,
            evidence_pool=self.evidence_pool,
            genesis=genesis,
            node_info=node_info,
            pub_key=(
                (lambda: priv_validator.pub_key)
                if priv_validator is not None
                else None
            ),
            blocksync_reactor=self.blocksync_reactor,
            statesync_reactor=self.statesync_reactor,
            unsafe=config.rpc.unsafe,
            metrics=self.metrics.rpc,
            metrics_registry=self.metrics.registry,
        )
        self.rpc_server: JSONRPCServer | None = None
        if config.rpc.laddr:
            rpc_addr = NetAddress.parse(config.rpc.laddr)
            self.rpc_server = JSONRPCServer(
                self.rpc_env.routes(),
                ws_routes=self.rpc_env.ws_routes(),
                host=rpc_addr.host,
                port=rpc_addr.port,
                on_ws_disconnect=self.rpc_env.drop_client,
                metrics=self.metrics.rpc,
                logger=self.logger.with_fields(module="rpc"),
            )

    def _make_local_addr_resolver(self, priv_validator):
        """bytes | zero-arg callable for the blocksync reactor's
        blocks-the-chain check; returns b"" while a remote signer has
        not dialed in yet (wait_for_signer(0) probe) so the pool
        routine never blocks on address resolution."""
        if priv_validator is None:
            return b""
        listener = self.privval_listener

        def resolve() -> bytes:
            if listener is not None and not listener.wait_for_signer(0):
                return b""
            return priv_validator.address

        return resolve

    def _make_state_provider(self, config, genesis, providers):
        """Light-client-verified state provider (stateprovider.go:39)."""
        from cometbft_tpu.light import Client as LightClient, LightStore
        from cometbft_tpu.statesync import LightClientStateProvider
        from cometbft_tpu.light.client import TrustOptions
        from cometbft_tpu.utils.db import MemDB

        if not providers and config.statesync.rpc_servers:
            from cometbft_tpu.light.provider import HTTPProvider

            providers = [
                HTTPProvider(genesis.chain_id, addr)
                for addr in config.statesync.rpc_servers
            ]
        if len(providers) < 2:
            # primary + at least one witness, or fork detection is a
            # no-op and a lone malicious provider owns the bootstrap
            # (mirrors the rpc_servers >= 2 config rule)
            raise NodeError(
                "statesync needs >= 2 light providers (primary + witness)"
            )
        trust = TrustOptions(
            period_ns=config.statesync.trust_period_ns,
            height=config.statesync.trust_height,
            hash=bytes.fromhex(config.statesync.trust_hash),
        )
        lc = LightClient(
            genesis.chain_id,
            trust,
            providers[0],
            providers[1:],
            LightStore(MemDB()),
            logger=self.logger.with_fields(module="light"),
        )
        # params are fetched from the primary but verified against the
        # light-verified header's consensus_hash in the state provider
        params_fn = getattr(providers[0], "consensus_params", None)
        return LightClientStateProvider(lc, consensus_params_fn=params_fn)

    def _on_statesync_complete(self, state, commit) -> None:
        """Bootstrap stores from the synced state, then blocksync the
        remaining gap (node.go startStateSync completion)."""
        self.state_store.bootstrap(state)
        self.block_store.save_seen_commit(state.last_block_height, commit)
        self.state = state
        with self.consensus._rs_mtx:  # guarded field (lockcheck)
            self.consensus.state = state
        self.mempool_reactor.enable_in_out_txs()
        self.logger.info(
            "state sync complete", height=state.last_block_height
        )
        if self.block_sync_enabled:
            self.blocksync_reactor.start_sync(state)
        else:
            # sole validator: nothing to sync from (node.go: blockSync
            # && !stateSync gate applies post-statesync too)
            self.consensus_reactor.switch_to_consensus(state)

    # -- lifecycle -------------------------------------------------------

    def on_start(self) -> None:
        """(node/node.go:580 OnStart) — on ANY startup failure (e.g.
        the double-signing-risk refusal) already-started services are
        unwound before re-raising, so an embedder is not left with
        bound sockets and orphan threads it cannot stop."""
        try:
            self._start_services()
        except BaseException:
            try:
                self.on_stop()
            except Exception as exc:  # noqa: BLE001 — best-effort unwind
                self.logger.error(
                    "error unwinding failed start", err=repr(exc)
                )
            raise

    def _start_services(self) -> None:
        # chaos drill (CMT_TPU_CHAOS=1): pin the fault-plan epoch to
        # service start and log the armed schedule — a node under
        # chaos must SAY so, loudly, before the first injected fault
        from cometbft_tpu.crypto import dispatch as _dispatch

        if _dispatch.chaos_enabled():
            _dispatch.CHAOS.start()
            self.logger.error(
                "CHAOS MODE ARMED — seeded faults will be injected "
                "at the crypto dispatch seam (CMT_TPU_CHAOS_PLAN)",
                plan=_dispatch.CHAOS.snapshot()["windows"],
            )
        # WAN emulation (CMT_TPU_NETEM): parse fail-loudly at assembly
        # and pin the window epoch — a node emulating a hostile link
        # must SAY so before the first injected hold
        from cometbft_tpu.p2p.conn import netem as _netem

        _netem.NETEM.reload()
        if _netem.NETEM.enabled():
            _netem.NETEM.start()
            self.logger.error(
                "NETEM ARMED — WAN conditions will be injected on "
                "every send frame (CMT_TPU_NETEM)",
                plan=_netem.NETEM.plan().describe(),
            )
        # byzantine adversary (CMT_TPU_BYZ): validated at assembly,
        # armed loudly — a node about to misbehave must confess first
        from cometbft_tpu.consensus import byz as _byzmod

        _byzmod.BYZ.reload()
        if _byzmod.BYZ.mode is not None:
            self.logger.error(
                "BYZANTINE MODE ARMED — this node will misbehave "
                "(CMT_TPU_BYZ)",
                mode=_byzmod.BYZ.mode,
            )
        # scenario label (CMT_TPU_SCENARIO): validated here so a bad
        # label fails the node, not the first /debug/fleet request
        from cometbft_tpu.utils.env import name_from_env as _name_env

        _scenario = _name_env("CMT_TPU_SCENARIO", None)
        if _scenario:
            self.logger.info(
                "scenario labeled — /debug/fleet will carry it",
                scenario=_scenario,
            )
        # device plane FIRST: the JAX backend is initialised in THIS
        # process, once, before anything can ask for a batch verifier
        # — platform and device kind are logged and served on
        # /debug/perf, and a backend that cannot come up fails the
        # node here instead of quietly verifying on the host
        if not flag_from_env("CMT_TPU_DISABLE_DEVICE_VERIFY"):
            from cometbft_tpu.crypto.batch import init_device_plane

            init_device_plane(
                logger=self.logger.with_fields(module="device")
            )
        # verify-ahead queue next: the reactors that feed it
        # (consensus add_vote, blocksync prefetch) start below, and
        # every caller degrades to the synchronous path if this fails
        # — the queue is an accelerator, never a liveness dependency
        if flag_from_env("CMT_TPU_VERIFY_QUEUE", default=True):
            from cometbft_tpu.crypto.verify_queue import (
                VerifyQueue,
                checktx_batch_from_env,
                checktx_wait_ms_from_env,
                install_queue,
                light_batch_from_env,
                light_wait_ms_from_env,
            )
            from cometbft_tpu.light.serve import (
                header_cache_capacity_from_env,
            )

            # micro-batcher + header-cache knobs validate OUTSIDE the
            # degrade-to-sync try below: a malformed
            # CMT_TPU_CHECKTX_BATCH / CMT_TPU_CHECKTX_WAIT_MS /
            # CMT_TPU_LIGHT_BATCH / CMT_TPU_LIGHT_WAIT_MS /
            # CMT_TPU_LIGHT_CACHE fails the node LOUDLY (the
            # documented fail-loudly env contract) instead of
            # silently running un-batched or un-cached
            checktx_batch_from_env()
            checktx_wait_ms_from_env()
            light_batch_from_env()
            light_wait_ms_from_env()
            header_cache_capacity_from_env()
            try:
                self.verify_queue = VerifyQueue(
                    logger=self.logger.with_fields(module="verify_queue")
                )
                self.verify_queue.start()
                install_queue(self.verify_queue)
            except Exception as exc:  # noqa: BLE001 — optional plane
                self.verify_queue = None
                self.logger.error(
                    "verify queue failed to start", err=repr(exc)
                )
        if self.metrics_server is not None:
            self.metrics_server.start()
            # device-health prober: periodic canary verifies per
            # dispatch tier, feeding crypto_tier_healthy{tier} and the
            # /debug/perf surface.  A malformed CMT_TPU_HEALTH_INTERVAL
            # raises HERE — the documented fail-loudly contract (same
            # as the ring-size vars): an operator who configured
            # probing must not silently get none.  Runtime start
            # failures beyond that are a diagnostics loss, never a
            # node-down (same stance as pprof below).
            from cometbft_tpu.crypto.health import (
                HealthProber,
                health_interval_from_env,
            )

            interval = health_interval_from_env()
            if interval > 0:
                try:
                    self.health_prober = HealthProber(
                        interval_s=interval,
                        logger=self.logger.with_fields(module="health"),
                    )
                    self.health_prober.start()
                except Exception as exc:  # noqa: BLE001 — optional
                    self.health_prober = None  # plane
                    self.logger.error(
                        "health prober failed to start", err=repr(exc)
                    )
        # always-on sampling profiler (utils/profiler.py): env knobs
        # validate fail-loudly HERE (a malformed CMT_TPU_PROFILE_HZ /
        # _DEPTH / _RING fails the node LOUDLY instead of silently
        # sampling at a rate the operator didn't choose); runtime
        # start failures beyond that are a diagnostics loss, never a
        # node-down.  Stopped (joined) in on_stop so the PR 3 thread
        # leak gate covers the sampler.
        self.profiler = None
        from cometbft_tpu.utils import profiler as _profiler

        _profiler.profile_hz_from_env()
        _profiler.profile_depth_from_env()
        _profiler.profile_ring_from_env()
        try:
            self.profiler = _profiler.start_from_env(
                logger=self.logger.with_fields(module="profiler")
            )
        except Exception as exc:  # noqa: BLE001 — optional plane
            self.profiler = None
            self.logger.error(
                "sampling profiler failed to start", err=repr(exc)
            )
        # pprof-analog diagnostics plane (node.go:589 startPprofServer);
        # failures here must never take the node down — it is an
        # optional debug feature.  The SIGUSR1 stack-dump handler is
        # registered UNCONDITIONALLY: `debug kill` depends on it, and
        # SIGUSR1's default disposition would otherwise terminate the
        # process mid-diagnosis.
        self.diagnostics_server = None
        try:
            from cometbft_tpu.utils.diagnostics import (
                install_stack_dump_signal,
            )

            install_stack_dump_signal(
                os.path.join(self.config.db_dir, "stacks.dump")
            )
        except Exception:  # noqa: BLE001 — non-main thread / RO home
            pass
        if self.config.rpc.is_pprof_enabled():
            try:
                from cometbft_tpu.utils.diagnostics import (
                    DiagnosticsServer,
                )

                self.diagnostics_server = DiagnosticsServer(
                    self.config.rpc.pprof_laddr,
                    logger=self.logger.with_fields(module="pprof"),
                )
                self.diagnostics_server.start()
            except Exception as exc:  # noqa: BLE001 — e.g. port in use
                self.diagnostics_server = None
                self.logger.error(
                    "diagnostics server failed to start", err=repr(exc)
                )
        if self.privval_listener is not None:
            # the external signer must be reachable before consensus
            # needs a signature (node.go waits for the remote signer)
            self.privval_listener.start()
            if not self.privval_listener.wait_for_signer():
                raise NodeError(
                    "no remote signer connected to "
                    f"{self.config.base.priv_validator_laddr} within "
                    "the accept deadline"
                )
        self.proxy_app.start()
        self.event_bus.start()

        if self.config.statesync.enable:
            # statesync path skips the handshake: the app will be
            # restored from a snapshot, not replayed (node.go:363)
            self._post_handshake_setup()
            return

        # crash recovery: three-way height reconciliation (setup.go:222)
        hs = Handshaker(
            self.state_store,
            self._pre_handshake_state,
            self.block_store,
            self.genesis,
            logger=self.logger.with_fields(module="handshake"),
            metrics=self.consensus.metrics,
        )
        self.state = hs.handshake(self.proxy_app)
        # round state is guarded; the ticker/receive threads aren't
        # running yet, but race mode judges by lock, not by luck
        with self.consensus._rs_mtx:
            self.consensus.state = self.state
            self.consensus._update_to_state(self.state)
        # blocksync validates against the post-handshake state (its
        # app_hash reflects InitChain / replayed blocks)
        self.blocksync_reactor.state = self.state
        self.blocksync_reactor.pool.height = max(
            self.blocksync_reactor.pool.height,
            self.state.last_block_height + 1,
        )

        self._post_handshake_setup()

    def _post_handshake_setup(self) -> None:
        self.indexer_service.start()
        # RPC before p2p "so we can receive txs for the first block"
        # (node.go:598)
        if self.rpc_server is not None:
            self.rpc_server.start()

        if isinstance(self.mempool, CListMempool):
            max_bytes = self.state.consensus_params.block.max_bytes
            # the RPC server above is already serving CheckTx: the
            # hook swap must hold the mempool lock like update() does
            with self.mempool._mtx:
                self.mempool.pre_check = pre_check_max_bytes(
                    max_bytes if max_bytes > 0 else 104857600
                )
                self.mempool.post_check = post_check_max_gas(
                    self.state.consensus_params.block.max_gas
                )

        if isinstance(self.wal, WAL):
            if determinism.enabled():
                # before the WAL starts moving: every committed-height
                # digest still in the log must reproduce from the
                # stores we are about to build on
                n = determinism.verify_wal_digests(
                    self.wal, self.block_store, self.state_store,
                    metrics=self.consensus.metrics,
                )
                if n:
                    self.logger.info(
                        "determinism guard: wal digests verified",
                        heights=n,
                    )
            self.wal.start()

        # p2p (node.go:613-626): listen, start switch (which starts the
        # reactors; the consensus reactor starts the consensus state),
        # then dial persistent peers.
        self.transport.listen(self._p2p_laddr)
        actual = self.transport.listen_addr
        self.transport.node_info = NodeInfo(
            node_id=self.transport.node_info.node_id,
            listen_addr=f"tcp://{actual.host}:{actual.port}",
            network=self.transport.node_info.network,
            channels=self.transport.node_info.channels,
            moniker=self.transport.node_info.moniker,
        )
        # the RPC env reports the ACTUAL bound address, not the
        # configured (possibly port-0) one
        self.rpc_env.node_info = self.transport.node_info
        self.switch.start()
        peers = parse_peer_list(self.config.p2p.persistent_peers)
        if peers:
            self.switch.dial_peers_async(peers, persistent=True)
        if self.grpc_server is not None:
            self.grpc_server.start()
        if self.grpc_privileged is not None:
            self.grpc_privileged.start()
        # pruner last (node.go:645)
        self.pruner.start()

    def _stop_for_app_error(self, exc: BaseException) -> None:
        """First app exception -> stop the whole node (proxy fail-stop
        callback; reference analog: a Go app panic crashes the node
        process, and multiAppConn's killChan stops it on client
        errors).  Runs on its own thread, outside the app lock."""
        self.logger.error(
            "ABCI application raised; stopping node", err=repr(exc)
        )
        try:
            if self.is_running():
                self.stop()
        except Exception as stop_exc:  # noqa: BLE001 — best-effort stop
            self.logger.error("fail-stop error", err=repr(stop_exc))

    def on_stop(self) -> None:
        services = (
            self.pruner,
            self.grpc_server,
            self.grpc_privileged,
            self.rpc_server,
            self.switch,
            self.consensus,
            self.indexer_service,
            self.event_bus,
            self.proxy_app,
            self.privval_listener,
            # after consensus/switch so no reactor submits into a
            # draining queue; drain resolves every in-flight future
            self.verify_queue,
            self.health_prober,
            # the sampler joins its thread in stop(), so the leak
            # gate (assert_no_thread_leaks, daemons_too) stays clean
            getattr(self, "profiler", None),
            self.metrics_server,
            getattr(self, "diagnostics_server", None),
        )
        for svc in services:
            if svc is None:
                continue
            try:
                if svc.is_running():
                    svc.stop()
            except Exception as exc:  # noqa: BLE001 — best-effort teardown
                self.logger.error("error stopping service", err=repr(exc))
        self.block_store_db.close()
        self.state_db.close()
        self.evidence_db.close()
        try:
            self._indexer_closer()
        except Exception as exc:  # noqa: BLE001 — best-effort teardown
            self.logger.error("error closing indexer", err=repr(exc))

    # -- convenience -----------------------------------------------------

    def height(self) -> int:
        return self.block_store.height()


__all__ = ["Node", "NodeError", "default_app", "init_files"]

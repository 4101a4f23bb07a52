"""RPC core — the route handlers over node internals (reference:
rpc/core/, routes at rpc/core/routes.go:15-63).

``Environment`` holds references to the node's components; each public
method is one JSON-RPC route.  WebSocket-only routes (subscribe/
unsubscribe) live in ``ws_routes``.
"""

from __future__ import annotations

import base64
import threading
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils import trustguard
from cometbft_tpu.utils.flight import FLIGHT as _FLIGHT

from cometbft_tpu.abci.types import CheckTxRequest, InfoRequest, QueryRequest
from cometbft_tpu.rpc.jsonrpc import QuotedStr, RPCError
from cometbft_tpu.rpc.serialize import (
    b64,
    block_id_json,
    block_json,
    block_meta_json,
    commit_json,
    exec_tx_result_json,
    hexb,
    time_rfc3339,
    validator_json,
)
from cometbft_tpu.types.block import tx_hash
from cometbft_tpu.types.event_bus import (
    EVENT_TX,
    EventDataTx,
    query_for_event,
)
from cometbft_tpu.utils.pubsub import Query
from cometbft_tpu.version import __version__

SUBSCRIPTION_BUFFER = 200


def _to_int(value, name: str) -> int:
    if value is None or value == "":
        return 0
    try:
        return int(value)
    except (TypeError, ValueError):
        raise RPCError(-32602, f"invalid {name}: {value!r}") from None


def _to_bytes(value, name: str) -> bytes:
    """Accept hex (with/without 0x) or base64; a QUOTED URI arg means
    the literal bytes of the unquoted string (the reference's URI-arg
    semantics for []byte params — `tx="name=ada"` sends b"name=ada",
    http_uri_handler.go)."""
    if isinstance(value, bytes):
        return value
    if not isinstance(value, str):
        raise RPCError(-32602, f"invalid {name}")
    if isinstance(value, QuotedStr):
        return value.encode()
    s = value[2:] if value.startswith("0x") else value
    try:
        return bytes.fromhex(s)
    except ValueError:
        try:
            return base64.b64decode(value, validate=True)
        except Exception:
            raise RPCError(-32602, f"invalid {name}: not hex/base64") from None


class _AsyncTxPool:
    """Bounded fire-and-forget CheckTx workers for broadcast_tx_async.

    ``workers`` daemon threads drain a queue capped at ``depth`` txs;
    ``offer`` never blocks — when the queue is full the tx is DROPPED
    and counted in ``dropped`` (load shed at the RPC edge: async
    broadcast promises no admission verdict, and clients that need one
    use broadcast_tx_sync/commit).  Daemon threads mean node stop and
    interpreter exit never wait behind a backlog."""

    def __init__(self, submit, metrics=None, workers: int = 8,
                 depth: int = 1024):
        import queue as _q

        self._submit = submit
        self._metrics = metrics
        self._q: "_q.Queue[bytes]" = _q.Queue(maxsize=depth)
        self._drop_mtx = cmtsync.Mutex()
        self.dropped = 0
        for i in range(workers):
            threading.Thread(
                target=self._loop, name=f"rpc-checktx-{i}", daemon=True
            ).start()

    def _loop(self) -> None:
        while True:
            self._submit(self._q.get())

    def offer(self, raw: bytes) -> bool:
        import queue as _q

        try:
            self._q.put_nowait(raw)
            return True
        except _q.Full:
            with self._drop_mtx:
                self.dropped += 1
            if self._metrics is not None:
                # visible shed: without this the RPC edge drops txs
                # the checktx_total counters never saw
                self._metrics.checktx_async_dropped.inc()
            return False


class Environment:
    """(rpc/core/env.go:72 Environment)"""

    def __init__(
        self,
        block_store=None,
        state_store=None,
        consensus=None,
        mempool=None,
        switch=None,
        event_bus=None,
        tx_indexer=None,
        block_indexer=None,
        proxy_app=None,
        evidence_pool=None,
        genesis=None,
        node_info=None,
        pub_key=None,
        blocksync_reactor=None,
        statesync_reactor=None,
        unsafe=False,
        metrics=None,
        metrics_registry=None,
    ):
        from cometbft_tpu.metrics import RPCMetrics

        self.block_store = block_store
        self.state_store = state_store
        self.consensus = consensus
        self.mempool = mempool
        self.switch = switch
        self.event_bus = event_bus
        self.tx_indexer = tx_indexer
        self.block_indexer = block_indexer
        self.proxy_app = proxy_app
        self.evidence_pool = evidence_pool
        self.genesis = genesis
        self.node_info = node_info
        # a PubKey, or a zero-arg callable resolving to one (remote
        # signers aren't connected until the node starts)
        self._pub_key = pub_key
        self.blocksync_reactor = blocksync_reactor
        self.statesync_reactor = statesync_reactor
        self.unsafe = unsafe
        self.metrics = metrics if metrics is not None else RPCMetrics()
        #: the node's metric Registry (fleet plane: /debug/fleet reads
        #: SELF's families in-process rather than over the wire)
        self.metrics_registry = metrics_registry
        self._gen_chunks: list[str] | None = None  # lazy (env.go InitGenesisChunks)
        self._subs: dict[str, dict[str, object]] = {}  # client -> query -> sub
        self._subs_mtx = cmtsync.Mutex()
        #: bounded ingest pool for broadcast_tx_async (lazy): the old
        #: thread-per-tx spawn was a thread bomb at sustained-load
        #: rates — thousands of concurrent CheckTx threads convoying
        #: on the admission path.  A few daemon workers drain a
        #: BOUNDED queue instead; overflow is DROPPED (counted on the
        #: pool) — async broadcast is fire-and-forget by contract, and
        #: an unbounded backlog of tx bytes is a memory bomb plus a
        #: drain-everything shutdown hang.
        self._async_pool: _AsyncTxPool | None = None
        self._async_pool_mtx = cmtsync.Mutex()
        #: lazy light-client serving plane (light/serve.py): built on
        #: the first /light_sync request from the node's own stores —
        #: a node that never serves light clients pays nothing
        self._light_server = None
        self._light_server_mtx = cmtsync.Mutex()

    # -- route tables (routes.go:15-63) ---------------------------------


    @property
    def pub_key(self):
        pk = self._pub_key
        return pk() if callable(pk) else pk

    def routes(self) -> dict:
        routes = {
            "health": self.health,
            "status": self.status,
            "net_info": self.net_info,
            "blockchain": self.blockchain,
            "genesis": self.genesis_route,
            "block": self.block,
            "block_by_hash": self.block_by_hash,
            "block_results": self.block_results,
            "commit": self.commit,
            "header": self.header,
            "header_by_hash": self.header_by_hash,
            "tx": self.tx,
            "tx_search": self.tx_search,
            "block_search": self.block_search,
            "validators": self.validators,
            "consensus_state": self.consensus_state,
            "dump_consensus_state": self.dump_consensus_state,
            "consensus_params": self.consensus_params,
            "unconfirmed_txs": self.unconfirmed_txs,
            "num_unconfirmed_txs": self.num_unconfirmed_txs,
            "broadcast_tx_sync": self.broadcast_tx_sync,
            "broadcast_tx_async": self.broadcast_tx_async,
            "broadcast_tx_commit": self.broadcast_tx_commit,
            "broadcast_evidence": self.broadcast_evidence,
            "abci_query": self.abci_query,
            "abci_info": self.abci_info,
            "genesis_chunked": self.genesis_chunked,
            "check_tx": self.check_tx,
            "wire": self.wire,
            # the light-client serving plane (light/serve.py): verified
            # header ranges, cross-client coalesced + header-cached
            "light_sync": self.light_sync,
            # GET /debug/flight (the path strips to this route name):
            # the always-on flight recorder's recent replication events
            "debug/flight": self.debug_flight,
            # GET /debug/perf: device-health + perf-ledger snapshot
            "debug/perf": self.debug_perf,
            # GET /debug/dispatch: failover-ladder state + chaos plan
            "debug/dispatch": self.debug_dispatch,
            # GET /debug/fleet: cross-node rollup + stitched heights
            "debug/fleet": self.debug_fleet,
            # GET /debug/profile: span-tagged sampling-profiler stacks
            "debug/profile": self.debug_profile,
        }
        if self.unsafe:
            # routes.go:55 AddUnsafeRoutes (config.RPC.Unsafe)
            # reference names (routes.go:61-63) + explicit aliases
            routes["dial_seeds"] = self.unsafe_dial_seeds
            routes["dial_peers"] = self.unsafe_dial_peers
            routes["unsafe_dial_seeds"] = self.unsafe_dial_seeds
            routes["unsafe_dial_peers"] = self.unsafe_dial_peers
            routes["unsafe_flush_mempool"] = self.unsafe_flush_mempool
        return routes

    def ws_routes(self) -> dict:
        return {
            "subscribe": self.subscribe,
            "unsubscribe": self.unsubscribe,
            "unsubscribe_all": self.unsubscribe_all,
        }

    # -- info ------------------------------------------------------------

    def health(self) -> dict:
        return {}

    def status(self) -> dict:
        """(rpc/core/status.go Status)"""
        earliest = self.block_store.base()
        latest = self.block_store.height()
        latest_meta = (
            self.block_store.load_block_meta(latest) if latest else None
        )
        earliest_meta = (
            self.block_store.load_block_meta(earliest) if earliest else None
        )
        syncing = False
        if self.blocksync_reactor is not None:
            syncing = self.blocksync_reactor.is_syncing()
        return {
            "node_info": {
                "id": self.node_info.node_id if self.node_info else "",
                "listen_addr": (
                    self.node_info.listen_addr if self.node_info else ""
                ),
                "network": self.node_info.network if self.node_info else "",
                "version": __version__,
                "moniker": self.node_info.moniker if self.node_info else "",
                "channels": (
                    hexb(self.node_info.channels) if self.node_info else ""
                ),
            },
            "sync_info": {
                "latest_block_hash": (
                    hexb(latest_meta.block_id.hash) if latest_meta else ""
                ),
                "latest_app_hash": (
                    hexb(latest_meta.header.app_hash) if latest_meta else ""
                ),
                "latest_block_height": str(latest),
                "latest_block_time": (
                    time_rfc3339(latest_meta.header.time_ns)
                    if latest_meta
                    else ""
                ),
                "earliest_block_height": str(earliest),
                "earliest_block_hash": (
                    hexb(earliest_meta.block_id.hash) if earliest_meta else ""
                ),
                "catching_up": syncing,
            },
            "validator_info": {
                "address": (
                    hexb(self.pub_key.address()) if self.pub_key else ""
                ),
                "pub_key": (
                    {
                        "type": "tendermint/PubKeyEd25519",
                        "value": b64(self.pub_key.bytes()),
                    }
                    if self.pub_key
                    else None
                ),
                "voting_power": self._own_voting_power(),
            },
        }

    def light_sync(self, from_height=None, to_height=None) -> dict:
        """Serve a VERIFIED header range to a light client (no
        reference analog; light/serve.py): every header's +2/3 commit
        is re-verified server-side — through the verify queue's
        ``light_client`` lane, so concurrent clients' signatures
        coalesce into single launches — unless the trust-period-aware
        header cache already vouches for it."""
        server = self._light_server
        if server is None:
            with self._light_server_mtx:
                server = self._light_server
                if server is None:
                    if self.block_store is None or self.state_store is None:
                        raise ValueError(
                            "light_sync requires block and state stores"
                        )
                    from cometbft_tpu.light.provider import NodeProvider
                    from cometbft_tpu.light.serve import LightHeaderServer

                    chain_id = (
                        self.genesis.chain_id
                        if self.genesis is not None
                        else (
                            self.node_info.network
                            if self.node_info is not None else ""
                        )
                    )
                    server = LightHeaderServer(
                        chain_id,
                        NodeProvider(
                            chain_id, self.block_store, self.state_store,
                            self.evidence_pool,
                        ),
                    )
                    self._light_server = server
        frm = _to_int(from_height, "from_height")
        to = (
            _to_int(to_height, "to_height")
            if to_height is not None else frm
        )
        out = server.sync_range(frm, to)
        out["cache"] = server.cache.stats()
        return out

    def _own_voting_power(self) -> str:
        if self.pub_key is None or self.state_store is None:
            return "0"
        state = self.state_store.load()
        if state is None or state.validators is None:
            return "0"
        _, val = state.validators.get_by_address(self.pub_key.address())
        return str(val.voting_power) if val else "0"

    def net_info(self) -> dict:
        """(rpc/core/net.go NetInfo) — each peer carries its live
        ``connection_status`` (MConnection.status(): flowrate monitors,
        ping RTT, per-channel queue state, last error)."""
        peers = []
        if self.switch is not None:
            for peer in self.switch.peers.copy():
                peers.append(
                    {
                        "node_info": {
                            "id": peer.node_info.node_id,
                            "listen_addr": peer.node_info.listen_addr,
                            "moniker": peer.node_info.moniker,
                            "network": peer.node_info.network,
                        },
                        "is_outbound": peer.is_outbound(),
                        "connection_status": peer.status(),
                        "remote_ip": (
                            peer.socket_addr.host if peer.socket_addr else ""
                        ),
                    }
                )
        return {
            "listening": self.switch is not None
            and self.switch.is_running(),
            "listeners": (
                [str(self.switch.transport.listen_addr)]
                if self.switch and self.switch.transport.listen_addr
                else []
            ),
            "n_peers": str(len(peers)),
            "peers": peers,
        }

    def wire(self) -> dict:
        """Live wire-plane snapshot (no reference analog): the peer
        table with per-channel queue depth/bytes/fill ratio, pending
        send bytes, ping RTT, flowrate throughput, and the last
        connection error — the /net_info subset an operator greps
        when a peer stalls (docs/observability.md runbook)."""
        peers = []
        if self.switch is not None:
            for peer in self.switch.peers.copy():
                peers.append(
                    {
                        "peer_id": peer.id,
                        "moniker": peer.node_info.moniker,
                        "is_outbound": peer.is_outbound(),
                        "is_persistent": peer.is_persistent(),
                        "connection_status": peer.status(),
                    }
                )
        return {"n_peers": str(len(peers)), "peers": peers}

    def debug_flight(self) -> dict:
        """The flight recorder's bounded ring of recent replication
        events (utils/flight.py) — step transitions, WAL writes, ABCI
        calls, blocksync requests, peer errors.  Served on a live node
        AND in inspect mode, so the last ~2k events before a wedge are
        one curl away (docs/observability.md)."""
        from cometbft_tpu.utils.flight import FLIGHT

        return FLIGHT.export()

    def debug_perf(self) -> dict:
        """Device-health/perf snapshot (crypto/health.py): per-tier
        canary health + last probe latencies, launch-watchdog state,
        and the perf-ledger tail (a launch's timing is the span ring's,
        ``trace``).  Served on a live node AND in inspect
        mode — a wedged accelerator is precisely when the node may not
        be running (docs/observability.md "Device-health plane")."""
        from cometbft_tpu.crypto.health import debug_perf_payload

        return debug_perf_payload()

    def debug_dispatch(self) -> dict:
        """Failover dispatch-ladder snapshot (crypto/dispatch.py):
        ladder order, per-tier demotion/cool-down/streak state, the
        recent transition trail, and the armed chaos plan.  Served on
        a live node AND in inspect mode — post-mortem of a device-lost
        node starts with the transition trail
        (docs/dispatch_ladder.md)."""
        from cometbft_tpu.crypto.dispatch import debug_dispatch_payload

        return debug_dispatch_payload()

    def debug_fleet(self) -> dict:
        """Fleet-plane rollup (utils/fleetobs.py): scrape the metrics
        servers named in CMT_TPU_FLEET_PEERS, merge SELF in-process,
        and return the per-node height/lag/tier/queue table plus the
        stitched cross-node height summary.  Served on a live node
        AND in inspect mode (docs/observability.md "Fleet plane")."""
        import os as _os

        from cometbft_tpu.utils import fleetobs

        scrapes = fleetobs.scrape_fleet(
            fleetobs.fleet_peer_targets(
                _os.environ.get("CMT_TPU_FLEET_PEERS")  # env ok: free-form peer list — fleet_peer_targets validates each address
            ),
            include_self=True,
            self_registry=self.metrics_registry,
        )
        return fleetobs.fleet_payload(scrapes)

    def debug_profile(self, seconds=None) -> dict:
        """Sampling-profiler payload (utils/profiler.py): span-tagged
        folded stacks, per-span sample rollup, and leaf-frame hotspots
        — ``?seconds=N`` limits to the trailing window.  Served on a
        live node AND in inspect mode; honest about being disabled
        (docs/observability.md "Attribution plane")."""
        from cometbft_tpu.utils.profiler import profile_payload

        return profile_payload(
            None if seconds is None else float(seconds)
        )

    def genesis_route(self) -> dict:
        import json as _json

        if len(self._genesis_chunks()) > 1:
            raise RPCError(
                -32603,
                "genesis response is too large, please use the "
                "genesis_chunked API instead",
            )
        return {"genesis": _json.loads(self.genesis.to_json())}

    _GENESIS_CHUNK_SIZE = 16 * 1024 * 1024  # net.go:16 genesisChunkSize

    def _genesis_chunks(self) -> list[str]:
        if self._gen_chunks is None:
            import base64 as _b64

            raw = self.genesis.to_json().encode()
            size = self._GENESIS_CHUNK_SIZE
            self._gen_chunks = [
                _b64.b64encode(raw[i : i + size]).decode()
                for i in range(0, max(len(raw), 1), size)
            ]
        return self._gen_chunks

    def genesis_chunked(self, chunk=0) -> dict:
        """(rpc/core/net.go:115 GenesisChunked)"""
        chunks = self._genesis_chunks()
        cid = _to_int(chunk, "chunk")
        if not 0 <= cid < len(chunks):
            raise RPCError(
                -32602,
                f"there are {len(chunks)} chunks, {cid} is invalid "
                f"(should be between 0 and {len(chunks) - 1})",
            )
        return {
            "chunk": str(cid),
            "total": str(len(chunks)),
            "data": chunks[cid],
        }

    # -- blocks -----------------------------------------------------------

    def _height_or_latest(self, height) -> int:
        h = _to_int(height, "height")
        if h == 0:
            h = self.block_store.height()
        if h < self.block_store.base() or h > self.block_store.height():
            raise RPCError(
                -32603,
                f"height {h} not available "
                f"(base {self.block_store.base()}, "
                f"height {self.block_store.height()})",
            )
        return h

    def blockchain(self, minHeight=None, maxHeight=None) -> dict:
        """(rpc/core/blocks.go BlockchainInfo) — metas, newest first,
        max 20."""
        base, height = self.block_store.base(), self.block_store.height()
        max_h = _to_int(maxHeight, "maxHeight") or height
        min_h = _to_int(minHeight, "minHeight") or base
        max_h = min(max_h, height)
        min_h = max(min_h, base, max_h - 19)
        metas = []
        for h in range(max_h, min_h - 1, -1):
            meta = self.block_store.load_block_meta(h)
            if meta is not None:
                metas.append(block_meta_json(meta))
        return {"last_height": str(height), "block_metas": metas}

    def block(self, height=None) -> dict:
        h = self._height_or_latest(height)
        blk = self.block_store.load_block(h)
        meta = self.block_store.load_block_meta(h)
        if blk is None or meta is None:
            raise RPCError(-32603, f"no block at height {h}")
        return {
            "block_id": block_id_json(meta.block_id),
            "block": block_json(blk),
        }

    def block_by_hash(self, hash=None) -> dict:
        blk = self.block_store.load_block_by_hash(_to_bytes(hash, "hash"))
        if blk is None:
            raise RPCError(-32603, "block not found")
        return self.block(height=blk.header.height)

    def header(self, height=None) -> dict:
        h = self._height_or_latest(height)
        meta = self.block_store.load_block_meta(h)
        from cometbft_tpu.rpc.serialize import header_json

        return {"header": header_json(meta.header)}

    def header_by_hash(self, hash=None) -> dict:
        blk = self.block_store.load_block_by_hash(_to_bytes(hash, "hash"))
        if blk is None:
            raise RPCError(-32603, "header not found")
        return self.header(height=blk.header.height)

    def commit(self, height=None) -> dict:
        h = self._height_or_latest(height)
        meta = self.block_store.load_block_meta(h)
        commit = self.block_store.load_block_commit(h)
        canonical = True
        if commit is None:
            commit = self.block_store.load_seen_commit(h)
            canonical = False
        if commit is None:
            raise RPCError(-32603, f"no commit for height {h}")
        return {
            "signed_header": {
                "header": block_meta_json(meta)["header"],
                "commit": commit_json(commit),
            },
            "canonical": canonical,
        }

    def block_results(self, height=None) -> dict:
        """(rpc/core/blocks.go BlockResults)"""
        h = self._height_or_latest(height)
        resp = self.state_store.load_finalize_block_response(h)
        if resp is None:
            raise RPCError(-32603, f"no results for height {h}")
        return {
            "height": str(h),
            "txs_results": [
                exec_tx_result_json(r) for r in resp.tx_results
            ],
            "finalize_block_events": [
                {
                    "type": e.type,
                    "attributes": [
                        {"key": a.key, "value": a.value, "index": a.index}
                        for a in e.attributes
                    ],
                }
                for e in resp.events
            ],
            "app_hash": hexb(resp.app_hash),
            "validator_updates": [
                {"pub_key_type": u.pub_key_type, "power": str(u.power)}
                for u in resp.validator_updates
            ],
        }

    def validators(self, height=None, page=None, per_page=None) -> dict:
        h = self._height_or_latest(height)
        vals = self.state_store.load_validators(h)
        per = min(_to_int(per_page, "per_page") or 30, 100)
        pg = max(_to_int(page, "page") or 1, 1)
        items = list(vals.validators)
        start = (pg - 1) * per
        return {
            "block_height": str(h),
            "validators": [
                validator_json(v) for v in items[start : start + per]
            ],
            "count": str(len(items[start : start + per])),
            "total": str(len(items)),
        }

    def consensus_params(self, height=None) -> dict:
        h = self._height_or_latest(height)
        params = self.state_store.load_consensus_params(h)
        return {
            "block_height": str(h),
            "consensus_params": params.to_json_dict(),
        }

    def consensus_state(self) -> dict:
        """(rpc/core/consensus.go GetConsensusState)"""
        rs = self.consensus.round_state()
        return {
            "round_state": {
                "height": str(rs["height"]),
                "round": rs["round"],
                "step": rs["step_name"],
                "start_time": time_rfc3339(rs["start_time_ns"]),
                "proposal_block_hash": (
                    hexb(rs["proposal_block"].hash())
                    if rs["proposal_block"]
                    else ""
                ),
                "locked_block_hash": (
                    hexb(rs["locked_block"].hash())
                    if rs["locked_block"]
                    else ""
                ),
                "valid_block_hash": (
                    hexb(rs["valid_block"].hash())
                    if rs["valid_block"]
                    else ""
                ),
            }
        }

    def dump_consensus_state(self) -> dict:
        rs = self.consensus.round_state()
        out = self.consensus_state()
        votes = rs["votes"]
        if votes is not None:
            prevotes = votes.prevotes(rs["round"])
            precommits = votes.precommits(rs["round"])
            out["round_state"]["height_vote_set"] = {
                "round": rs["round"],
                "prevotes_bit_array": (
                    repr(prevotes.bit_array()) if prevotes else ""
                ),
                "precommits_bit_array": (
                    repr(precommits.bit_array()) if precommits else ""
                ),
            }
        peers = []
        if self.switch is not None:
            from cometbft_tpu.consensus.reactor import PEER_STATE_KEY

            for peer in self.switch.peers.copy():
                ps = peer.get(PEER_STATE_KEY)
                if ps is None:
                    continue
                prs = ps.snapshot()
                peers.append(
                    {
                        "node_address": peer.id,
                        "peer_state": {
                            "height": str(prs.height),
                            "round": prs.round,
                            "step": prs.step,
                        },
                    }
                )
        out["peers"] = peers
        return out

    # -- txs --------------------------------------------------------------

    def tx(self, hash=None, prove=False) -> dict:
        """(rpc/core/tx.go Tx)"""
        if self.tx_indexer is None:
            raise RPCError(-32603, "tx indexing is disabled")
        entry = self.tx_indexer.get(_to_bytes(hash, "hash"))
        if entry is None:
            raise RPCError(-32603, "tx not found")
        return {
            "hash": hexb(tx_hash(entry["tx"])),
            "height": str(entry["height"]),
            "index": entry["index"],
            "tx_result": exec_tx_result_json(entry["result"]),
            "tx": b64(entry["tx"]),
        }

    def tx_search(self, query=None, page=None, per_page=None,
                  prove=False, order_by=None) -> dict:
        if self.tx_indexer is None:
            raise RPCError(-32603, "tx indexing is disabled")
        if not query:
            raise RPCError(-32602, "query cannot be empty")
        try:
            q = Query.parse(query)
        except Exception as exc:
            raise RPCError(-32602, f"bad query: {exc}") from None
        per = min(_to_int(per_page, "per_page") or 30, 100)
        pg = max(_to_int(page, "page") or 1, 1)
        entries = self.tx_indexer.search(q, limit=pg * per)
        window = entries[(pg - 1) * per : pg * per]
        return {
            "txs": [
                {
                    "hash": hexb(tx_hash(e["tx"])),
                    "height": str(e["height"]),
                    "index": e["index"],
                    "tx_result": exec_tx_result_json(e["result"]),
                    "tx": b64(e["tx"]),
                }
                for e in window
            ],
            "total_count": str(len(entries)),
        }

    def block_search(self, query=None, page=None, per_page=None,
                     order_by=None) -> dict:
        if self.block_indexer is None:
            raise RPCError(-32603, "block indexing is disabled")
        if not query:
            raise RPCError(-32602, "query cannot be empty")
        heights = self.block_indexer.search(Query.parse(query), limit=1000)
        per = min(_to_int(per_page, "per_page") or 30, 100)
        pg = max(_to_int(page, "page") or 1, 1)
        window = heights[(pg - 1) * per : pg * per]
        blocks = []
        for h in window:
            try:
                blocks.append(self.block(height=h))
            except RPCError:
                continue
        return {"blocks": blocks, "total_count": str(len(heights))}

    def unconfirmed_txs(self, limit=None) -> dict:
        lim = min(_to_int(limit, "limit") or 30, 100)
        txs = self.mempool.reap_max_txs(lim)
        return {
            "n_txs": str(len(txs)),
            "total": str(self.mempool.size()),
            "total_bytes": str(self.mempool.size_bytes()),
            "txs": [b64(tx) for tx in txs],
        }

    def unconfirmed_tx(self, hash=None) -> dict:
        """One mempool tx by hash (rpc/core/mempool.go UnconfirmedTx,
        routes.go:40)."""
        h = _to_bytes(hash, "hash")
        tx = self.mempool.get_tx_by_hash(h)
        if tx is None:
            raise RPCError(-32603, f"tx {h.hex()} not found in mempool")
        return {"tx": b64(tx)}

    def num_unconfirmed_txs(self) -> dict:
        return {
            "n_txs": str(self.mempool.size()),
            "total": str(self.mempool.size()),
            "total_bytes": str(self.mempool.size_bytes()),
        }

    # -- broadcast (rpc/core/mempool.go) ----------------------------------

    def _ingest_pool(self) -> "_AsyncTxPool":
        with self._async_pool_mtx:
            if self._async_pool is None:
                self._async_pool = _AsyncTxPool(
                    self._check_tx_quiet, metrics=self.metrics
                )
            return self._async_pool

    def broadcast_tx_async(self, tx=None) -> dict:
        raw = _to_bytes(tx, "tx")
        self._ingest_pool().offer(raw)
        return {"code": 0, "data": "", "log": "", "hash": hexb(tx_hash(raw))}

    def _check_tx_quiet(self, raw: bytes) -> None:
        try:
            with trustguard.wire_context("rpc_tx_async"):
                self.mempool.check_tx(raw)
        except Exception as exc:  # noqa: BLE001
            # async broadcast promises no admission verdict, but a
            # swallowed rejection on the RPC ingress path must leave a
            # breadcrumb (PR 9 convention)
            _FLIGHT.record(
                "rpc_async_checktx_rejected", err=type(exc).__name__
            )

    def check_tx(self, tx=None) -> dict:
        """Run CheckTx against the app WITHOUT adding to the mempool
        (rpc/core/mempool.go:211 CheckTx)."""
        from cometbft_tpu.abci.types import CHECK_TX_TYPE_CHECK, CheckTxRequest

        raw = _to_bytes(tx, "tx")
        res = self.proxy_app.mempool.check_tx(
            CheckTxRequest(tx=raw, type=CHECK_TX_TYPE_CHECK)
        )
        return {
            "code": res.code,
            "data": b64(res.data) if res.data else "",
            "log": res.log,
            "codespace": res.codespace,
            "gas_wanted": str(res.gas_wanted),
            "gas_used": str(res.gas_used),
        }

    def unsafe_flush_mempool(self) -> dict:
        """(mempool.go UnsafeFlushMempool) — drop every pending tx."""
        self.mempool.flush()
        return {}

    def unsafe_dial_seeds(self, seeds=None) -> dict:
        """(rpc/core/net.go:50 UnsafeDialSeeds)"""
        from cometbft_tpu.p2p.netaddr import parse_peer_list

        if not seeds:
            raise RPCError(-32602, "no seeds provided")
        spec = ",".join(seeds) if isinstance(seeds, list) else str(seeds)
        addrs = parse_peer_list(spec)
        self.switch.dial_peers_async(addrs, persistent=False)
        return {"log": "Dialing seeds in progress. See /net_info for details"}

    def unsafe_dial_peers(self, peers=None, persistent=False,
                          unconditional=False, private=False) -> dict:
        """(rpc/core/net.go:63 UnsafeDialPeers)"""
        from cometbft_tpu.p2p.netaddr import parse_peer_list

        if not peers:
            raise RPCError(-32602, "no peers provided")
        spec = ",".join(peers) if isinstance(peers, list) else str(peers)
        addrs = parse_peer_list(spec)
        self.switch.dial_peers_async(
            addrs, persistent=bool(persistent)
        )
        return {"log": "Dialing peers in progress. See /net_info for details"}

    @trustguard.guarded_seam("rpc_tx")
    def broadcast_tx_sync(self, tx=None) -> dict:
        raw = _to_bytes(tx, "tx")
        try:
            res = self.mempool.check_tx(raw)
        except Exception as exc:  # noqa: BLE001
            raise RPCError(-32603, f"tx rejected: {exc}") from None
        return {
            "code": res.code,
            "data": b64(res.data) if res.data else "",
            "log": res.log,
            "hash": hexb(tx_hash(raw)),
        }

    @trustguard.guarded_seam("rpc_tx")
    def broadcast_tx_commit(self, tx=None, timeout=10.0) -> dict:
        """(rpc/core/mempool.go:76 BroadcastTxCommit) — subscribe to the
        tx event BEFORE CheckTx so the commit can't be missed."""
        raw = _to_bytes(tx, "tx")
        h = tx_hash(raw)
        sub = self.event_bus.subscribe(
            f"txc-{h.hex()[:16]}",
            Query.parse(f"tm.event='{EVENT_TX}' AND tx.hash='{h.hex().upper()}'"),
            capacity=1,
        )
        try:
            check = self.mempool.check_tx(raw)
            if check.code != 0:
                return {
                    "check_tx": {"code": check.code, "log": check.log},
                    "tx_result": None,
                    "hash": hexb(h),
                    "height": "0",
                }
            try:
                msg = sub.next(timeout=float(timeout))
            except TimeoutError:
                raise RPCError(
                    -32603, "timed out waiting for tx to be committed"
                ) from None
            data: EventDataTx = msg.data
            return {
                "check_tx": {"code": check.code, "log": check.log},
                "tx_result": exec_tx_result_json(data.result),
                "hash": hexb(h),
                "height": str(data.height),
            }
        except RPCError:
            raise
        except Exception as exc:  # noqa: BLE001
            raise RPCError(-32603, f"tx rejected: {exc}") from None
        finally:
            try:
                self.event_bus.unsubscribe_all(f"txc-{h.hex()[:16]}")
            except Exception:  # noqa: BLE001
                pass

    @trustguard.guarded_seam("rpc_evidence")
    def broadcast_evidence(self, evidence=None) -> dict:
        from cometbft_tpu.types import codec

        ev = codec.decode_evidence(_to_bytes(evidence, "evidence"))
        self.evidence_pool.add_evidence(ev)
        return {"hash": hexb(ev.hash())}

    # -- abci -------------------------------------------------------------

    def abci_query(self, path=None, data=None, height=None,
                   prove=False) -> dict:
        resp = self.proxy_app.query.query(
            QueryRequest(
                path=path or "",
                data=_to_bytes(data, "data") if data else b"",
                height=_to_int(height, "height"),
                prove=bool(prove),
            )
        )
        out = {
            "code": resp.code,
            "log": resp.log,
            "key": b64(resp.key) if resp.key else None,
            "value": b64(resp.value) if resp.value else None,
            "height": str(resp.height),
        }
        if resp.proof_ops:
            out["proofOps"] = {
                "ops": [
                    {
                        "type": op.type,
                        "key": b64(op.key),
                        "data": b64(op.data),
                    }
                    for op in resp.proof_ops
                ]
            }
        return {"response": out}

    def abci_info(self) -> dict:
        resp = self.proxy_app.query.info(InfoRequest())
        return {
            "response": {
                "data": resp.data,
                "version": resp.version,
                "app_version": str(resp.app_version),
                "last_block_height": str(resp.last_block_height),
                "last_block_app_hash": b64(resp.last_block_app_hash),
            }
        }

    # -- subscriptions (WS only; rpc/core/events.go) ----------------------

    def subscribe(self, query=None, _ws_ctx=None) -> dict:
        if _ws_ctx is None:
            raise RPCError(-32603, "subscribe requires a websocket")
        if not query:
            raise RPCError(-32602, "query cannot be empty")
        q = Query.parse(query)
        sub = self.event_bus.subscribe(
            _ws_ctx.client_id, q, capacity=SUBSCRIPTION_BUFFER
        )
        with self._subs_mtx:
            self._subs.setdefault(_ws_ctx.client_id, {})[query] = sub
            self._set_ws_subscriptions_locked()
        threading.Thread(
            target=self._pump_subscription,
            args=(sub, q, _ws_ctx, query),
            daemon=True,
        ).start()
        return {}

    def _pump_subscription(self, sub, q, ws_ctx, query_str) -> None:
        try:
            self._pump_subscription_loop(sub, ws_ctx, query_str)
        finally:
            # a pubsub-canceled subscription (slow consumer) must come
            # off the books too, or ws_subscriptions keeps counting it
            # as live while subscriber_dropped_total says otherwise;
            # idempotent vs unsubscribe/drop_client (both pop first)
            with self._subs_mtx:
                qs = self._subs.get(ws_ctx.client_id)
                if qs is not None and qs.get(query_str) is sub:
                    del qs[query_str]
                    if not qs:
                        del self._subs[ws_ctx.client_id]
                    self._set_ws_subscriptions_locked()

    def _pump_subscription_loop(self, sub, ws_ctx, query_str) -> None:
        while ws_ctx.alive:
            try:
                msg = sub.next(timeout=0.2)
            except TimeoutError:
                continue
            except Exception:  # noqa: BLE001 — canceled
                return
            payload = {
                "jsonrpc": "2.0",
                "id": -1,
                "result": {
                    "query": query_str,
                    "data": {
                        "type": type(msg.data).__name__,
                        "value": _event_data_json(msg.data),
                    },
                    "events": msg.events,
                },
            }
            if not ws_ctx.send(payload):
                return

    def _set_ws_subscriptions_locked(self) -> None:
        self.metrics.ws_subscriptions.set(
            sum(len(qs) for qs in self._subs.values())
        )

    def unsubscribe(self, query=None, _ws_ctx=None) -> dict:
        if _ws_ctx is None:
            raise RPCError(-32603, "unsubscribe requires a websocket")
        with self._subs_mtx:
            self._subs.get(_ws_ctx.client_id, {}).pop(query, None)
            self._set_ws_subscriptions_locked()
        self.event_bus.unsubscribe(_ws_ctx.client_id, Query.parse(query))
        return {}

    def unsubscribe_all(self, _ws_ctx=None) -> dict:
        if _ws_ctx is None:
            raise RPCError(-32603, "unsubscribe_all requires a websocket")
        self.drop_client(_ws_ctx.client_id)
        return {}

    def drop_client(self, client_id: str) -> None:
        with self._subs_mtx:
            self._subs.pop(client_id, None)
            self._set_ws_subscriptions_locked()
        try:
            self.event_bus.unsubscribe_all(client_id)
        except Exception:  # noqa: BLE001
            pass


def _event_data_json(data) -> dict:
    """Best-effort JSON projection of event payloads."""
    from cometbft_tpu.types.event_bus import (
        EventDataNewBlock,
        EventDataNewBlockHeader,
        EventDataTx,
        EventDataVote,
    )
    from cometbft_tpu.rpc.serialize import header_json

    if isinstance(data, EventDataNewBlock):
        return {
            "block": block_json(data.block),
            "block_id": block_id_json(data.block_id),
        }
    if isinstance(data, EventDataNewBlockHeader):
        return {"header": header_json(data.header)}
    if isinstance(data, EventDataTx):
        return {
            "height": str(data.height),
            "index": data.index,
            "tx": b64(data.tx),
            "result": exec_tx_result_json(data.result),
        }
    if isinstance(data, EventDataVote):
        v = data.vote
        return {
            "type": v.type,
            "height": str(v.height),
            "round": v.round,
            "validator_address": hexb(v.validator_address),
        }
    if hasattr(data, "__dict__"):
        return {
            k: str(v) for k, v in vars(data).items() if not k.startswith("_")
        }
    return {"repr": repr(data)}


__all__ = ["Environment", "SUBSCRIPTION_BUFFER"]

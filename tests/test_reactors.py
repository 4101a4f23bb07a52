"""Reactor-plane tests: evidence pool, mempool gossip, and the
multi-validator localnet over real TCP p2p.

Mirrors the reference's in-process consensus reactor tests
(internal/consensus/reactor_test.go) and evidence pool tests
(internal/evidence/pool_test.go).
"""

from __future__ import annotations

import time

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.abci.types import QueryRequest
from cometbft_tpu.config import test_config as make_test_config
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.node import Node
from cometbft_tpu.p2p.netaddr import NetAddress
from cometbft_tpu.privval import FilePV
from cometbft_tpu.types.evidence import DuplicateVoteEvidence
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from tests.helpers import make_block_id, signed_vote

GENESIS_TIME = 1_700_000_000_000_000_000
CHAIN = "reactor-test-chain"


def make_localnet(tmp_path, n: int, app_factory=KVStoreApp, configure=None,
                  consensus_params=None):
    """n validator nodes sharing one genesis, each with its own home.
    ``configure(i, cfg)`` may mutate each node's config pre-construction;
    ``consensus_params`` overrides the genesis defaults (e.g. PBTS)."""
    privs = [
        FilePV(ed.priv_key_from_secret(b"net-val%d" % i)) for i in range(n)
    ]
    kwargs = (
        {"consensus_params": consensus_params}
        if consensus_params is not None
        else {}
    )
    gen = GenesisDoc(
        chain_id=CHAIN,
        genesis_time_ns=GENESIS_TIME,
        validators=tuple(GenesisValidator(pv.pub_key, 10) for pv in privs),
        **kwargs,
    )
    nodes = []
    for i, pv in enumerate(privs):
        cfg = make_test_config(str(tmp_path / f"node{i}"))
        if configure is not None:
            configure(i, cfg)
        cfg.ensure_dirs()
        pv._key_path = cfg.priv_validator_key_path
        pv._state_path = cfg.priv_validator_state_path
        pv.save()
        external = cfg.base.proxy_app.startswith(
            ("tcp://", "unix://", "grpc://")
        )
        node = Node(
            cfg,
            app=None if external else app_factory(),
            genesis=gen,
            priv_validator=pv,
        )
        nodes.append(node)
    return nodes, privs, gen


def connect_star(nodes, timeout=10.0):
    hub = nodes[0]
    for node in nodes[1:]:
        addr = hub.transport.listen_addr
        node.switch.dial_peer_with_address(
            NetAddress(id=addr.id, host=addr.host, port=addr.port),
            persistent=True,
        )
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if hub.switch.peers.size() == len(nodes) - 1 and all(
            n.switch.peers.size() >= 1 for n in nodes[1:]
        ):
            return
        time.sleep(0.02)
    raise TimeoutError("localnet failed to connect")


def _crypto_speed_factor() -> float:
    """Pure-Python signing is ~100x slower than `cryptography`; the
    localnet-lite tests that still run without it (conftest only skips
    the heavy suites) sit right against the default height-wait budget
    on a contended core (docs/known_failures.md).  Scale waits, don't
    skip: a pass at 45 s beats a flaky timeout at 30 s."""
    try:
        import cryptography  # noqa: F401

        return 1.0
    except ImportError:
        return 4.0


def wait_all_height(nodes, h, timeout=30.0):
    deadline = time.monotonic() + timeout * _crypto_speed_factor()
    while time.monotonic() < deadline:
        if all(n.height() >= h for n in nodes):
            return
        time.sleep(0.05)
    heights = [n.height() for n in nodes]
    raise TimeoutError(f"heights {heights}, wanted all >= {h}")


class TestLocalnet:
    def test_four_validators_progress_over_tcp(self, tmp_path):
        nodes, _, _ = make_localnet(tmp_path, 4)
        try:
            for n in nodes:
                n.start()
            connect_star(nodes)
            wait_all_height(nodes, 3)
            # every node converged on the same block hashes
            h2 = {n.block_store.load_block_meta(2).block_id.hash
                  for n in nodes}
            assert len(h2) == 1
            # commits carry +2/3 signatures
            commit = nodes[0].block_store.load_block_commit(2)
            present = sum(1 for cs in commit.signatures if cs.signature)
            assert present >= 3
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_tx_gossip_and_execution(self, tmp_path):
        nodes, _, _ = make_localnet(tmp_path, 4)
        try:
            for n in nodes:
                n.start()
            connect_star(nodes)
            wait_all_height(nodes, 1)
            # submit a tx to a NON-proposing node: it must flood to the
            # proposer via the mempool reactor and land in a block
            nodes[3].mempool.check_tx(b"gossip-key=gossip-val")
            deadline = time.monotonic() + 30
            found = False
            while time.monotonic() < deadline and not found:
                for n in nodes:
                    resp = n.app.query(QueryRequest(data=b"gossip-key"))
                    if resp.value == b"gossip-val":
                        found = True
                        break
                time.sleep(0.05)
            assert found, "gossiped tx never executed"
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_late_joiner_catches_up(self, tmp_path):
        """A 5th node (same genesis, validator set of 4) joins late and
        catches up via consensus-reactor catchup gossip."""
        nodes, privs, gen = make_localnet(tmp_path, 4)
        cfg = make_test_config(str(tmp_path / "late"))
        cfg.ensure_dirs()
        late = Node(cfg, app=KVStoreApp(), genesis=gen, priv_validator=None)
        try:
            for n in nodes:
                n.start()
            connect_star(nodes)
            wait_all_height(nodes, 3)
            late.start()
            addr = nodes[0].transport.listen_addr
            late.switch.dial_peer_with_address(
                NetAddress(id=addr.id, host=addr.host, port=addr.port),
                persistent=True,
            )
            wait_all_height([late], 3, timeout=30)
            assert (
                late.block_store.load_block_meta(2).block_id.hash
                == nodes[0].block_store.load_block_meta(2).block_id.hash
            )
        finally:
            for n in [*nodes, late]:
                try:
                    n.stop()
                except Exception:
                    pass


class TestEvidencePool:
    def _produced_node(self, tmp_path, halt: bool = False):
        nodes, privs, gen = make_localnet(tmp_path, 4)
        for n in nodes:
            n.start()
        try:
            connect_star(nodes)
            wait_all_height(nodes, 2)
        except BaseException:
            # the caller never gets the nodes to stop: four nodes left
            # running starve every later test on this worker
            for n in nodes:
                n.stop()
            raise
        if halt:
            # freeze the chain so the pool can be driven deterministically:
            # with consensus running, node0's own proposer scoops pending
            # evidence into the next block and empties the pool mid-test.
            for n in nodes:
                n.switch.stop()
                n.consensus.stop()
        return nodes, privs

    def test_duplicate_vote_evidence_lifecycle(self, tmp_path):
        nodes, privs = self._produced_node(tmp_path, halt=True)
        try:
            node = nodes[0]
            state = node.state_store.load()
            val_set = node.state_store.load_validators(1)
            # find the validator index for privs[1] in the canonical set
            addr = privs[1].pub_key.address()
            idx, val = val_set.get_by_address(addr)
            assert val is not None
            va = signed_vote(privs[1]._priv_key, idx, make_block_id(b"a"),
                             height=1, chain_id=CHAIN)
            vb = signed_vote(privs[1]._priv_key, idx, make_block_id(b"b"),
                             height=1, chain_id=CHAIN)
            # evidence time must equal our header time at the evidence
            # height (verify.go:31-34)
            ev_time = node.block_store.load_block_meta(1).header.time_ns
            ev = DuplicateVoteEvidence.from_votes(va, vb, ev_time, val_set)
            pool = node.evidence_pool
            pool.add_evidence(ev)
            pending, size = pool.pending_evidence(-1)
            assert len(pending) == 1 and size > 0
            assert pending[0].hash() == ev.hash()
            # check_evidence accepts it; after commit it is rejected
            pool.check_evidence([ev])
            pool.update(state, [ev])
            pending, _ = pool.pending_evidence(-1)
            assert pending == []
            with pytest.raises(Exception):
                pool.check_evidence([ev])
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_detection_and_commitment_counters(self, tmp_path):
        """ISSUE 20: the byzantine scenario proves detection AND
        commitment via counters — add_evidence bumps
        evidence_pool_detected_total{type}, update() bumps
        evidence_committed_total exactly once per item (replays and
        re-adds must not double count), and the consensus-buffer path
        (report_conflicting_votes) feeds the same detection counter."""
        from cometbft_tpu.metrics import EvidenceMetrics
        from cometbft_tpu.utils.metrics import Registry

        nodes, privs = self._produced_node(tmp_path, halt=True)
        try:
            node = nodes[0]
            reg = Registry("cometbft")
            pool = node.evidence_pool
            pool.metrics = EvidenceMetrics(reg)
            state = node.state_store.load()
            val_set = node.state_store.load_validators(1)
            ev_time = node.block_store.load_block_meta(1).header.time_ns

            def dup_ev(pv):
                idx, val = val_set.get_by_address(pv.pub_key.address())
                assert val is not None
                va = signed_vote(pv._priv_key, idx, make_block_id(b"a"),
                                 height=1, chain_id=CHAIN)
                vb = signed_vote(pv._priv_key, idx, make_block_id(b"b"),
                                 height=1, chain_id=CHAIN)
                return DuplicateVoteEvidence.from_votes(
                    va, vb, ev_time, val_set
                ), va, vb

            ev, _, _ = dup_ev(privs[1])
            pool.add_evidence(ev)
            text = reg.expose()
            assert (
                'cometbft_evidence_pool_detected_total'
                '{type="duplicate_vote"} 1' in text
            )
            assert "cometbft_evidence_committed_total 0" in text
            # re-adding pending evidence is a no-op: no double detection
            pool.add_evidence(ev)
            assert (
                'cometbft_evidence_pool_detected_total'
                '{type="duplicate_vote"} 1' in reg.expose()
            )

            pool.update(state, [ev])
            assert "cometbft_evidence_committed_total 1" in reg.expose()
            # replaying the committed list must not double count
            pool.update(state, [ev])
            assert "cometbft_evidence_committed_total 1" in reg.expose()

            # consensus-buffer path: the reactor reports raw conflicting
            # votes; the next update() materializes them as evidence and
            # the detection counter moves through the same {type} child
            _, va2, vb2 = dup_ev(privs[2])
            pool.report_conflicting_votes(va2, vb2)
            pool.update(state, [])
            assert (
                'cometbft_evidence_pool_detected_total'
                '{type="duplicate_vote"} 2' in reg.expose()
            )
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_invalid_evidence_rejected(self, tmp_path):
        nodes, privs = self._produced_node(tmp_path)
        try:
            node = nodes[0]
            state = node.state_store.load()
            val_set = node.state_store.load_validators(1)
            outsider = ed.priv_key_from_secret(b"outsider")
            va = signed_vote(outsider, 0, make_block_id(b"a"), height=1,
                             chain_id=CHAIN)
            vb = signed_vote(outsider, 0, make_block_id(b"b"), height=1,
                             chain_id=CHAIN)
            ev = DuplicateVoteEvidence(
                vote_a=min(va, vb, key=lambda v: v.block_id.key()),
                vote_b=max(va, vb, key=lambda v: v.block_id.key()),
                total_voting_power=val_set.total_voting_power(),
                validator_power=10,
                timestamp_ns=state.last_block_time_ns,
            )
            with pytest.raises(Exception):
                node.evidence_pool.add_evidence(ev)
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def _lunatic_evidence(self, node, privs, conflicting_height=2,
                          common_height=1):
        """Build verifiable lunatic-attack evidence against the real
        chain: the conflicting header differs from ours (bad app hash)
        but carries genuine +2/3 signatures from the validator set."""
        from dataclasses import replace as dreplace

        from cometbft_tpu.types import BlockID, PartSetHeader
        from cometbft_tpu.types.evidence import LightClientAttackEvidence
        from cometbft_tpu.types.light_block import LightBlock, SignedHeader
        from tests.helpers import make_commit

        val_set = node.state_store.load_validators(conflicting_height)
        by_addr = {pv.pub_key.address(): pv._priv_key for pv in privs}
        keys = [by_addr[v.address] for v in val_set.validators]
        real = node.block_store.load_block_meta(conflicting_height)
        header = dreplace(real.header, app_hash=b"\xaa" * 32)
        hh = header.hash()
        bid = BlockID(
            hash=hh, part_set_header=PartSetHeader(total=1, hash=hh[::-1])
        )
        commit = make_commit(
            val_set, keys, bid, height=conflicting_height, chain_id=CHAIN
        )
        cb = LightBlock(
            signed_header=SignedHeader(header=header, commit=commit),
            validator_set=val_set,
        )
        common_vals = node.state_store.load_validators(common_height)
        ev = LightClientAttackEvidence(
            conflicting_block=cb,
            common_height=common_height,
            total_voting_power=common_vals.total_voting_power(),
            timestamp_ns=node.block_store.load_block_meta(
                common_height
            ).header.time_ns,
        )
        trusted = SignedHeader(
            header=real.header,
            commit=node.block_store.load_block_commit(conflicting_height),
        )
        byz = ev.get_byzantine_validators(common_vals, trusted)
        return dreplace(
            ev, byzantine_validators=tuple(v.address for v in byz)
        )

    def test_light_client_attack_evidence_verified(self, tmp_path):
        """Real-signature lunatic evidence passes full verification and
        flows through the pending/committed lifecycle."""
        nodes, privs = self._produced_node(tmp_path, halt=True)
        try:
            node = nodes[0]
            ev = self._lunatic_evidence(node, privs)
            assert len(ev.byzantine_validators) == 4
            pool = node.evidence_pool
            pool.add_evidence(ev)
            pending, _ = pool.pending_evidence(-1)
            assert [e.hash() for e in pending] == [ev.hash()]
            pool.check_evidence([ev])
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_light_client_attack_frameup_rejected(self, tmp_path):
        """Evidence whose byzantine list or signatures don't hold up is
        rejected — honest validators can't be framed."""
        from dataclasses import replace as dreplace

        from cometbft_tpu.evidence.pool import EvidenceInvalidError

        nodes, privs = self._produced_node(tmp_path)
        try:
            node = nodes[0]
            ev = self._lunatic_evidence(node, privs)
            # (a) fabricated byzantine list (subset) != actual signers
            framed = dreplace(
                ev, byzantine_validators=ev.byzantine_validators[:1]
            )
            with pytest.raises(EvidenceInvalidError):
                node.evidence_pool.verify(framed)
            # (b) forged signatures: zero out every commit sig
            cb = ev.conflicting_block
            bad_sigs = tuple(
                dreplace(cs, signature=b"\x00" * 64)
                for cs in cb.commit.signatures
            )
            bad_commit = dreplace(cb.commit, signatures=bad_sigs)
            bad_cb = dreplace(
                cb,
                signed_header=dreplace(
                    cb.signed_header, commit=bad_commit
                ),
            )
            forged = dreplace(ev, conflicting_block=bad_cb)
            with pytest.raises(EvidenceInvalidError):
                node.evidence_pool.verify(forged)
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

    def test_evidence_gossip_between_nodes(self, tmp_path):
        nodes, privs = self._produced_node(tmp_path)
        try:
            node = nodes[0]
            state = node.state_store.load()
            val_set = node.state_store.load_validators(1)
            addr = privs[2].pub_key.address()
            idx, _ = val_set.get_by_address(addr)
            va = signed_vote(privs[2]._priv_key, idx, make_block_id(b"x"),
                             height=1, chain_id=CHAIN)
            vb = signed_vote(privs[2]._priv_key, idx, make_block_id(b"y"),
                             height=1, chain_id=CHAIN)
            ev_time = node.block_store.load_block_meta(1).header.time_ns
            ev = DuplicateVoteEvidence.from_votes(va, vb, ev_time, val_set)
            node.evidence_pool.add_evidence(ev)
            # the evidence reactor floods it to all peers
            deadline = time.monotonic() + 10
            spread = False
            while time.monotonic() < deadline and not spread:
                spread = all(
                    len(n.evidence_pool.pending_evidence(-1)[0]) >= 1
                    or n.evidence_pool._is_committed(ev)
                    for n in nodes[1:]
                )
                time.sleep(0.05)
            assert spread, "evidence did not reach all peers"
        finally:
            for n in nodes:
                try:
                    n.stop()
                except Exception:
                    pass

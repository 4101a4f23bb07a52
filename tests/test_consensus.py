"""Consensus state machine + node tests (reference analogs:
internal/consensus/state_test.go, common_test.go, replay_test.go)."""

import os
import time

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.abci.types import QueryRequest
from cometbft_tpu.config import test_config as make_test_config
from cometbft_tpu.consensus import (
    BlockPartMessage,
    ProposalMessage,
    TimeoutInfo,
    TimeoutTicker,
    VoteMessage,
)
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.node import Node, init_files
from cometbft_tpu.privval import FilePV
from cometbft_tpu.types import PRECOMMIT_TYPE, PREVOTE_TYPE
from cometbft_tpu.types.block import BlockID
from cometbft_tpu.types.event_bus import (
    EVENT_COMPLETE_PROPOSAL,
    EVENT_NEW_ROUND,
    EVENT_VOTE,
    query_for_event,
)
from cometbft_tpu.types.genesis import GenesisDoc, GenesisValidator
from cometbft_tpu.types.part_set import BLOCK_PART_SIZE_BYTES
from cometbft_tpu.types.vote import Proposal, Vote
from cometbft_tpu.utils.time import now_ns
from tests.helpers import signed_vote

GENESIS_TIME = 1_700_000_000_000_000_000


def make_node(tmp_path, n_stub_validators=0, backend="memdb", app=None):
    """Single real validator (v0) plus optional stub validators whose
    keys the test controls (common_test.go validatorStub pattern)."""
    cfg = make_test_config(str(tmp_path))
    cfg.base.db_backend = backend
    # stub validators have no real peers to blocksync from; start in
    # consensus directly (the embedding escape hatch)
    cfg.base.block_sync = False
    cfg.ensure_dirs()
    priv = FilePV(
        ed.priv_key_from_secret(b"v0"),
        cfg.priv_validator_key_path,
        cfg.priv_validator_state_path,
    )
    priv.save()
    stubs = [
        FilePV(ed.priv_key_from_secret(b"stub%d" % i))
        for i in range(n_stub_validators)
    ]
    gen = GenesisDoc(
        chain_id="cs-test-chain",
        genesis_time_ns=GENESIS_TIME,
        validators=tuple(
            GenesisValidator(pv.pub_key, 10) for pv in [priv, *stubs]
        ),
    )
    node = Node(
        cfg,
        app=app or KVStoreApp(),
        genesis=gen,
        priv_validator=priv,
    )
    return node, stubs


def wait_for_height(node, h, timeout=45.0):  # generous: nproc=1 box
    deadline = time.time() + timeout
    while node.height() < h:
        if time.time() > deadline:
            raise TimeoutError(
                f"node stuck at height {node.height()}, wanted {h}"
            )
        time.sleep(0.01)


class TestTimeoutTicker:
    def test_fires(self):
        fired = []
        t = TimeoutTicker(fired.append)
        t.start()
        t.schedule(TimeoutInfo(10 * 10**6, 1, 0, 3))
        deadline = time.time() + 2
        while not fired and time.time() < deadline:
            time.sleep(0.005)
        t.stop()
        assert fired and fired[0].height == 1

    def test_newer_replaces(self):
        fired = []
        t = TimeoutTicker(fired.append)
        t.start()
        t.schedule(TimeoutInfo(50 * 10**6, 1, 0, 3))
        t.schedule(TimeoutInfo(10 * 10**6, 1, 1, 3))  # newer round, sooner
        deadline = time.time() + 2
        while not fired and time.time() < deadline:
            time.sleep(0.005)
        t.stop()
        assert fired[0].round == 1

    def test_stale_schedule_ignored(self):
        fired = []
        t = TimeoutTicker(fired.append)
        t.start()
        t.schedule(TimeoutInfo(30 * 10**6, 5, 2, 3))
        t.schedule(TimeoutInfo(1 * 10**6, 4, 0, 3))  # older height: ignored
        time.sleep(0.02)
        t.stop()
        assert all(f.height == 5 for f in fired)


class TestSingleValidator:
    def test_produces_blocks_and_executes_txs(self, tmp_path):
        node, _ = make_node(tmp_path)
        node.start()
        try:
            app = node.app
            node.mempool.check_tx(b"name=alice")
            wait_for_height(node, 3)
            assert app.query(QueryRequest(data=b"name")).value == b"alice"
            # committed chain state follows the store by one beat
            deadline = time.time() + 30
            while node.consensus.state.last_block_height < 3:
                assert time.time() < deadline
                time.sleep(0.05)
        finally:
            node.stop()

    def test_block_chain_linkage(self, tmp_path):
        node, _ = make_node(tmp_path)
        node.start()
        try:
            wait_for_height(node, 3)
        finally:
            node.stop()
        b1 = node.block_store.load_block(1)
        b2 = node.block_store.load_block(2)
        assert b2.header.last_block_id.hash == b1.hash()
        assert b2.last_commit.height == 1
        # seen commit saved and verifiable
        sc = node.block_store.load_seen_commit(2)
        assert sc is not None and sc.height == 2

    def test_empty_blocks_have_genesis_apphash_chain(self, tmp_path):
        node, _ = make_node(tmp_path)
        node.start()
        try:
            wait_for_height(node, 2)
        finally:
            node.stop()
        meta = node.block_store.load_block_meta(1)
        assert meta.header.chain_id == "cs-test-chain"


class TestMultiValidator:
    """One real consensus state (v0) + 3 stub validators injected as if
    from peers (common_test.go:84 validatorStub)."""

    def _run_stub_driver(self, node, stubs, n_blocks, timeout=30.0):
        cs = node.consensus
        state = cs.state
        chain_id = state.chain_id
        bus = node.event_bus
        sub_nr = bus.subscribe("driver-nr", query_for_event(EVENT_NEW_ROUND))
        sub_cp = bus.subscribe(
            "driver-cp", query_for_event(EVENT_COMPLETE_PROPOSAL)
        )
        # map stub address -> (priv, index in val set)
        val_set = cs.state.validators
        stub_idx = {}
        for pv in stubs:
            idx, _ = val_set.get_by_address(pv.address)
            stub_idx[pv.address] = (pv, idx)

        voted: set[tuple[int, int]] = set()
        deadline = time.time() + timeout
        while node.height() < n_blocks and time.time() < deadline:
            # stub proposer duties: if the round's proposer is a stub,
            # build + sign a proposal on its behalf (decideProposal,
            # common_test.go:258)
            try:
                ev = sub_nr.next(timeout=0.05)
            except TimeoutError:
                ev = None
            if ev is not None:
                rs = cs.round_state()
                proposer = rs["validators"].get_proposer()
                if proposer.address in stub_idx and rs["proposal"] is None:
                    pv, _ = stub_idx[proposer.address]
                    last_commit = None
                    if rs["height"] > cs.state.initial_height:
                        last_commit = node.block_store.load_seen_commit(
                            rs["height"] - 1
                        )
                    block = node.block_exec.create_proposal_block(
                        rs["height"], cs.state, last_commit, proposer.address
                    )
                    parts = block.make_part_set(BLOCK_PART_SIZE_BYTES)
                    block_id = BlockID(block.hash(), parts.header)
                    prop = Proposal(
                        height=rs["height"],
                        round=rs["round"],
                        pol_round=-1,
                        block_id=block_id,
                        timestamp_ns=block.header.time_ns,
                    )
                    prop = pv.sign_proposal(chain_id, prop)
                    cs.send_peer_msg(ProposalMessage(prop), "stub-peer")
                    for i in range(parts.header.total):
                        cs.send_peer_msg(
                            BlockPartMessage(
                                rs["height"], rs["round"], parts.get_part(i)
                            ),
                            "stub-peer",
                        )
            # stub voting: once a proposal completes, prevote+precommit
            # it.  The event only paces the loop; the round state is
            # what is read, once per round — the node can complete its
            # first proposal before this driver has subscribed, and
            # without the stubs' votes it never leaves that round.
            try:
                sub_cp.next(timeout=0.05)
            except TimeoutError:
                pass
            rs = cs.round_state()
            h, r = rs["height"], rs["round"]
            if rs["proposal"] is None or (h, r) in voted:
                continue
            voted.add((h, r))
            block_id = rs["proposal"].block_id
            for pv, idx in stub_idx.values():
                for vt in (PREVOTE_TYPE, PRECOMMIT_TYPE):
                    vote = Vote(
                        type=vt,
                        height=h,
                        round=r,
                        block_id=block_id,
                        timestamp_ns=max(
                            now_ns(), cs.state.last_block_time_ns + 1
                        ),
                        validator_address=pv.address,
                        validator_index=idx,
                    )
                    vote = pv.sign_vote(chain_id, vote)
                    cs.send_peer_msg(VoteMessage(vote), "stub-peer")
        bus.unsubscribe_all("driver-nr")
        bus.unsubscribe_all("driver-cp")

    def test_four_validators_commit_blocks(self, tmp_path):
        node, stubs = make_node(tmp_path, n_stub_validators=3)
        node.start()
        try:
            self._run_stub_driver(node, stubs, n_blocks=3)
            assert node.height() >= 3
            # commits carry signatures from multiple validators
            commit = node.block_store.load_seen_commit(2)
            present = [
                cs for cs in commit.signatures if not cs.is_absent()
            ]
            assert len(present) >= 3  # +2/3 of 4
        finally:
            node.stop()


class TestCrashRecovery:
    def test_restart_continues_chain(self, tmp_path):
        node, _ = make_node(tmp_path, backend="sqlite")
        node.start()
        try:
            wait_for_height(node, 3)
        finally:
            node.stop()
        h1 = node.height()
        assert h1 >= 3

        # "restart": brand-new Node over the same home dir
        node2, _ = make_node(tmp_path, backend="sqlite")
        node2.start()
        try:
            wait_for_height(node2, h1 + 2)
            assert node2.height() >= h1 + 2
            # chain is linked across the restart
            b = node2.block_store.load_block(h1 + 1)
            prev = node2.block_store.load_block(h1)
            assert b.header.last_block_id.hash == prev.hash()
        finally:
            node2.stop()

    def test_app_restart_replays_to_app(self, tmp_path):
        """Fresh app instance (height 0) + existing chain → handshake
        replays every block into the app (replay.go ReplayBlocks)."""
        node, _ = make_node(tmp_path, backend="sqlite")
        node.start()
        try:
            node.mempool.check_tx(b"k=v")
            wait_for_height(node, 3)
        finally:
            node.stop()
        h1 = node.height()

        # new node, FRESH app state — simulates an app that lost its disk
        node2, _ = make_node(tmp_path, backend="sqlite", app=KVStoreApp())
        node2.start()
        try:
            # handshake replayed the chain: the tx state is back
            assert (
                node2.app.query(QueryRequest(data=b"k")).value == b"v"
            )
            wait_for_height(node2, h1 + 1)
        finally:
            node2.stop()


class TestCrashMatrix:
    """Crash at every fail point inside ApplyBlock's persistence
    sequence and assert full recovery (replay_test.go + internal/fail).

    apply_block fires 4 fail points per height; index (h-1)*4 + i is
    point i of height h:
      0: after FinalizeBlock, before saving the ABCI response
      1: after saving the response, before app Commit
      2: after app Commit, before saving state      ← app ahead of state
      3: after saving state, before firing events   ← all consistent
    """

    @pytest.mark.parametrize("fail_index", [4, 5, 6, 7])
    def test_crash_point_recovers(self, tmp_path, fail_index):
        import subprocess
        import sys

        home = str(tmp_path)
        env = dict(
            os.environ,
            JAX_PLATFORMS="cpu",
            PYTHONPATH="/root/repo",
            FAIL_TEST_INDEX=str(fail_index),
        )
        # run until the fail point hard-exits the process at height 2
        p = subprocess.run(
            [sys.executable, "-m", "tests.crash_child", home, "10"],
            env=env,
            capture_output=True,
            timeout=120,
            cwd="/root/repo",
        )
        assert p.returncode == 1, (
            f"expected fail-point exit, got {p.returncode}: "
            f"{p.stderr.decode()[-500:]}"
        )

        # restart WITHOUT the fail point: handshake must reconcile
        env.pop("FAIL_TEST_INDEX")
        p = subprocess.run(
            [sys.executable, "-m", "tests.crash_child", home, "4"],
            env=env,
            capture_output=True,
            timeout=120,
            cwd="/root/repo",
        )
        assert p.returncode == 0, (
            f"recovery failed (rc={p.returncode}): "
            f"{p.stderr.decode()[-800:]}"
        )


class TestPrivvalIntegration:
    def test_no_double_sign_across_restart(self, tmp_path):
        node, _ = make_node(tmp_path, backend="sqlite")
        node.start()
        try:
            wait_for_height(node, 2)
        finally:
            node.stop()
        pv = FilePV.load(
            node.config.priv_validator_key_path,
            node.config.priv_validator_state_path,
        )
        assert pv.height >= 2  # last-sign-state persisted


def test_double_sign_risk_check_refuses_after_state_reset(tmp_path):
    """(state.go:2643 checkDoubleSigningRisk) with
    double_sign_check_height set, a validator whose sign-state was
    wiped refuses to join consensus while its own signature is visible
    in recent seen commits."""
    import json

    from cometbft_tpu.consensus.state import ConsensusError

    node, stubs = make_node(
        tmp_path, n_stub_validators=0, backend="sqlite"
    )
    node.config.consensus.double_sign_check_height = 10
    node.start()
    try:
        deadline = time.monotonic() + 60
        while node.height() < 3:
            assert time.monotonic() < deadline
            time.sleep(0.05)
    finally:
        node.stop()

    # wipe the privval sign-state (the unsafe-reset-all hazard)
    with open(node.config.priv_validator_state_path, "w") as f:
        json.dump({"height": "0", "round": 0, "step": 0}, f)
    from cometbft_tpu.node import Node
    from cometbft_tpu.privval import FilePV

    pv = FilePV.load(
        node.config.priv_validator_key_path,
        node.config.priv_validator_state_path,
    )
    node2 = Node(
        node.config, genesis=node.genesis, priv_validator=pv
    )
    with pytest.raises(ConsensusError, match="double-signing risk"):
        node2.start()
    # the guard is opt-in: knob off, the node starts fine
    node.config.consensus.double_sign_check_height = 0
    node3 = Node(node.config, genesis=node.genesis, priv_validator=pv)
    node3.start()
    node3.stop()


class TestLockSafety:
    """Tendermint locking rules (reference state_test.go
    TestStateLock_*): once a validator precommits (locks) a block, it
    must not prevote a different block in a later round unless the
    proposal carries a valid POL round."""

    def _wait_vote(self, bus, addr, height, round_, vtype, timeout=20):
        sub = bus.subscribe("lock-watch", query_for_event(EVENT_VOTE))
        try:
            deadline = time.time() + timeout
            while time.time() < deadline:
                try:
                    ev = sub.next(timeout=0.5)
                except TimeoutError:
                    continue
                v = ev.data.vote
                if (
                    v.validator_address == addr
                    and v.height == height
                    and v.round == round_
                    and v.type == vtype
                ):
                    return v
            raise AssertionError(
                f"no vote h={height} r={round_} t={vtype} from us"
            )
        finally:
            bus.unsubscribe_all("lock-watch")

    def test_stays_locked_without_pol(self, tmp_path):
        node, stubs = make_node(tmp_path, n_stub_validators=3)
        node.start()
        try:
            cs = node.consensus
            bus = node.event_bus
            chain_id = cs.state.chain_id
            our_addr = cs.priv_validator.address
            val_set = cs.state.validators
            stub_by_addr = {pv.address: pv for pv in stubs}

            def stub_indices():
                out = {}
                for pv in stubs:
                    idx, _ = val_set.get_by_address(pv.address)
                    out[pv.address] = (pv, idx)
                return out

            sidx = stub_indices()

            def send_stub_votes(vt, h, r, block_id):
                for pv, idx in sidx.values():
                    vote = Vote(
                        type=vt, height=h, round=r, block_id=block_id,
                        timestamp_ns=max(
                            now_ns(), cs.state.last_block_time_ns + 1
                        ),
                        validator_address=pv.address,
                        validator_index=idx,
                    )
                    cs.send_peer_msg(
                        VoteMessage(pv.sign_vote(chain_id, vote)),
                        "stub-peer",
                    )

            def propose_as(pv, h, r, block, parts, pol_round=-1):
                block_id = BlockID(block.hash(), parts.header)
                prop = Proposal(
                    height=h, round=r, pol_round=pol_round,
                    block_id=block_id,
                    timestamp_ns=block.header.time_ns,
                )
                prop = pv.sign_proposal(chain_id, prop)
                cs.send_peer_msg(ProposalMessage(prop), "stub-peer")
                for i in range(parts.header.total):
                    cs.send_peer_msg(
                        BlockPartMessage(h, r, parts.get_part(i)),
                        "stub-peer",
                    )
                return block_id

            # --- round 0: get a proposal B in front of the node ------
            deadline = time.time() + 20
            while cs.round_state()["height"] != 1:
                assert time.time() < deadline
                time.sleep(0.05)
            rs = cs.round_state()
            proposer0 = rs["validators"].get_proposer().address
            if proposer0 == our_addr:
                # node proposes on its own; wait for it
                deadline = time.time() + 20
                while cs.round_state()["proposal"] is None:
                    assert time.time() < deadline
                    time.sleep(0.05)
                b_id = cs.round_state()["proposal"].block_id
            else:
                pv = stub_by_addr[proposer0]
                block = node.block_exec.create_proposal_block(
                    1, cs.state, None, proposer0
                )
                parts = block.make_part_set(BLOCK_PART_SIZE_BYTES)
                b_id = propose_as(pv, 1, 0, block, parts)

            # stubs prevote B -> node locks B and precommits it
            send_stub_votes(PREVOTE_TYPE, 1, 0, b_id)
            our_pc = self._wait_vote(
                bus, our_addr, 1, 0, PRECOMMIT_TYPE
            )
            assert our_pc.block_id.hash == b_id.hash, "did not lock B"
            rs = cs.round_state()
            assert rs["locked_round"] == 0
            assert rs["locked_block"].hash() == b_id.hash

            # stubs precommit NIL -> no decision -> round 1
            send_stub_votes(PRECOMMIT_TYPE, 1, 0, BlockID())
            deadline = time.time() + 30
            while cs.round_state()["round"] < 1:
                assert time.time() < deadline, "never reached round 1"
                time.sleep(0.05)

            # --- round 1: different proposal, NO POL -----------------
            rs = cs.round_state()
            proposer1 = rs["validators"].get_proposer().address
            if proposer1 == our_addr:
                # a locked proposer must re-propose its LOCKED block
                deadline = time.time() + 20
                while True:
                    prop = cs.round_state()["proposal"]
                    if prop is not None:
                        break
                    assert time.time() < deadline
                    time.sleep(0.05)
                assert prop.block_id.hash == b_id.hash, (
                    "locked proposer proposed a different block"
                )
            else:
                pv = stub_by_addr[proposer1]
                # a DIFFERENT block: different proposer address changes
                # the header, hence the hash
                block2 = node.block_exec.create_proposal_block(
                    1, cs.state, None, proposer1
                )
                parts2 = block2.make_part_set(BLOCK_PART_SIZE_BYTES)
                b2_id = propose_as(pv, 1, 1, block2, parts2, pol_round=-1)
                assert b2_id.hash != b_id.hash
                our_pv = self._wait_vote(
                    bus, our_addr, 1, 1, PREVOTE_TYPE
                )
                assert our_pv.block_id.is_nil(), (
                    "prevoted a conflicting block while locked and "
                    "the proposal carried no POL"
                )
                rs = cs.round_state()
                assert rs["locked_round"] == 0
                assert rs["locked_block"].hash() == b_id.hash
        finally:
            node.stop()

    def test_relocks_with_valid_pol(self, tmp_path):
        """A proposal carrying a valid POL round (+2/3 prevotes for
        the new block at pol_round >= locked_round) DOES override the
        lock (state_test.go TestStateLock_POLRelock)."""
        node, stubs = make_node(tmp_path, n_stub_validators=3)
        node.start()
        try:
            cs = node.consensus
            bus = node.event_bus
            chain_id = cs.state.chain_id
            our_addr = cs.priv_validator.address
            val_set = cs.state.validators
            stub_by_addr = {pv.address: pv for pv in stubs}
            sidx = {}
            for pv in stubs:
                idx, _ = val_set.get_by_address(pv.address)
                sidx[pv.address] = (pv, idx)

            def send_stub_votes(vt, h, r, block_id):
                for pv, idx in sidx.values():
                    vote = Vote(
                        type=vt, height=h, round=r, block_id=block_id,
                        timestamp_ns=max(
                            now_ns(), cs.state.last_block_time_ns + 1
                        ),
                        validator_address=pv.address,
                        validator_index=idx,
                    )
                    cs.send_peer_msg(
                        VoteMessage(pv.sign_vote(chain_id, vote)),
                        "stub-peer",
                    )

            def propose_as(pv, h, r, block, parts, pol_round=-1):
                block_id = BlockID(block.hash(), parts.header)
                prop = Proposal(
                    height=h, round=r, pol_round=pol_round,
                    block_id=block_id,
                    timestamp_ns=block.header.time_ns,
                )
                prop = pv.sign_proposal(chain_id, prop)
                cs.send_peer_msg(ProposalMessage(prop), "stub-peer")
                for i in range(parts.header.total):
                    cs.send_peer_msg(
                        BlockPartMessage(h, r, parts.get_part(i)),
                        "stub-peer",
                    )
                return block_id

            deadline = time.time() + 20
            while cs.round_state()["height"] != 1:
                assert time.time() < deadline
                time.sleep(0.05)
            rs = cs.round_state()

            # round 0: lock on B (ours or a stub's, whoever proposes)
            proposer0 = rs["validators"].get_proposer().address
            if proposer0 == our_addr:
                deadline = time.time() + 20
                while cs.round_state()["proposal"] is None:
                    assert time.time() < deadline
                    time.sleep(0.05)
                b_id = cs.round_state()["proposal"].block_id
            else:
                block = node.block_exec.create_proposal_block(
                    1, cs.state, None, proposer0
                )
                parts = block.make_part_set(BLOCK_PART_SIZE_BYTES)
                b_id = propose_as(
                    stub_by_addr[proposer0], 1, 0, block, parts
                )
            send_stub_votes(PREVOTE_TYPE, 1, 0, b_id)
            pc = TestLockSafety._wait_vote(
                self, bus, our_addr, 1, 0, PRECOMMIT_TYPE
            )
            assert pc.block_id.hash == b_id.hash
            send_stub_votes(PRECOMMIT_TYPE, 1, 0, BlockID())
            deadline = time.time() + 30
            while cs.round_state()["round"] < 1:
                assert time.time() < deadline
                time.sleep(0.05)

            # advance past any round where WE propose (we would
            # re-propose our locked B); stop at a stub-proposed round
            while True:
                rs = cs.round_state()
                r = rs["round"]
                proposer = rs["validators"].get_proposer().address
                if proposer != our_addr:
                    break
                # nil the whole round to move on
                send_stub_votes(PREVOTE_TYPE, 1, r, BlockID())
                send_stub_votes(PRECOMMIT_TYPE, 1, r, BlockID())
                deadline = time.time() + 30
                while cs.round_state()["round"] <= r:
                    assert time.time() < deadline
                    time.sleep(0.05)

            # POL round: B2 proposed + stub POL prevotes for B2
            rs = cs.round_state()
            pol_r = rs["round"]
            proposer1 = rs["validators"].get_proposer().address
            block2 = node.block_exec.create_proposal_block(
                1, cs.state, None, proposer1
            )
            parts2 = block2.make_part_set(BLOCK_PART_SIZE_BYTES)
            b2_id = propose_as(
                stub_by_addr[proposer1], 1, pol_r, block2, parts2
            )
            assert b2_id.hash != b_id.hash
            send_stub_votes(PREVOTE_TYPE, 1, pol_r, b2_id)  # the POL
            send_stub_votes(PRECOMMIT_TYPE, 1, pol_r, BlockID())
            deadline = time.time() + 30
            while cs.round_state()["round"] <= pol_r:
                assert time.time() < deadline
                time.sleep(0.05)

            # next round: B2 re-proposed WITH pol_round -> relock
            rs = cs.round_state()
            next_r = rs["round"]
            proposer2 = rs["validators"].get_proposer().address
            if proposer2 == our_addr:
                pytest.skip("our node proposes the post-POL round")
            propose_as(
                stub_by_addr[proposer2], 1, next_r, block2, parts2,
                pol_round=pol_r,
            )
            our_pv = TestLockSafety._wait_vote(
                self, bus, our_addr, 1, next_r, PREVOTE_TYPE
            )
            assert our_pv.block_id.hash == b2_id.hash, (
                "did not follow a valid POL past the lock"
            )
        finally:
            node.stop()

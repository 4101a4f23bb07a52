"""Blocksync tests: pool scheduling and end-to-end fast sync
(reference: internal/blocksync/pool_test.go, reactor_test.go)."""

from __future__ import annotations

import time

import pytest

from cometbft_tpu.abci.kvstore import KVStoreApp
from cometbft_tpu.blocksync.pool import BlockPool
from cometbft_tpu.config import test_config as make_test_config
from cometbft_tpu.node import Node
from cometbft_tpu.p2p.netaddr import NetAddress
from tests.test_reactors import (
    connect_star,
    make_localnet,
    wait_all_height,
)


class TestBlockPool:
    def test_requests_fill_window_and_complete(self):
        sent = []
        pool = BlockPool(
            1,
            send_request=lambda p, h: sent.append((p, h)),
            send_error=lambda p, r: None,
        )
        pool.set_peer_range("peerA", 1, 5)
        pool.set_peer_range("peerB", 1, 5)
        pool.make_next_requests()
        assert sorted(h for _, h in sent) == [1, 2, 3, 4, 5]

    def test_add_block_requires_matching_peer(self):
        from tests.helpers import make_val_set

        pool = BlockPool(1, lambda p, h: None, lambda p, r: None)
        pool.set_peer_range("peerA", 1, 3)
        pool.make_next_requests()

        class FakeBlock:
            class header:
                height = 1

        assert not pool.add_block("stranger", FakeBlock(), 100)

    def test_timeout_reassigns(self, monkeypatch):
        import cometbft_tpu.blocksync.pool as pool_mod

        sent = []
        errors = []
        pool = BlockPool(
            1,
            send_request=lambda p, h: sent.append((p, h)),
            send_error=lambda p, r: errors.append(p),
        )
        monkeypatch.setattr(pool_mod, "REQUEST_TIMEOUT", 0.01)
        pool.set_peer_range("slow", 1, 2)
        pool.make_next_requests()
        assert sent and all(p == "slow" for p, _ in sent)
        time.sleep(0.05)
        pool.set_peer_range("fast", 1, 2)
        pool.make_next_requests()
        assert errors == ["slow"]
        assert any(p == "fast" for p, _ in sent)

    def test_caught_up(self):
        pool = BlockPool(5, lambda p, h: None, lambda p, r: None)
        assert not pool.is_caught_up()  # no peers
        pool.set_peer_range("a", 1, 4)
        assert pool.is_caught_up()  # we're past every peer
        pool.set_peer_range("b", 1, 9)
        assert not pool.is_caught_up()


class TestBlocksyncE2E:
    def test_fresh_node_fast_syncs(self, tmp_path):
        """Validators build a chain; a fresh observer in block_sync mode
        catches up via 0x40 and then switches to consensus."""
        nodes, privs, gen = make_localnet(tmp_path, 4)
        cfg = make_test_config(str(tmp_path / "syncer"))
        cfg.base.block_sync = True
        cfg.ensure_dirs()
        syncer = Node(cfg, app=KVStoreApp(), genesis=gen, priv_validator=None)
        try:
            for n in nodes:
                n.start()
            connect_star(nodes)
            wait_all_height(nodes, 5)
            syncer.start()
            addr = nodes[0].transport.listen_addr
            syncer.switch.dial_peer_with_address(
                NetAddress(id=addr.id, host=addr.host, port=addr.port),
                persistent=True,
            )
            wait_all_height([syncer], 5, timeout=30)
            # same chain
            assert (
                syncer.block_store.load_block_meta(4).block_id.hash
                == nodes[0].block_store.load_block_meta(4).block_id.hash
            )
            # eventually switches to consensus and keeps following live
            deadline = time.monotonic() + 20
            while (
                time.monotonic() < deadline
                and syncer.blocksync_reactor.is_syncing()
            ):
                time.sleep(0.05)
            assert not syncer.blocksync_reactor.is_syncing()
            target = nodes[0].height() + 2
            wait_all_height([syncer], target, timeout=30)
        finally:
            for n in [*nodes, syncer]:
                try:
                    n.stop()
                except Exception:
                    pass


# -- the sync step's repair (PR 36): validate in full before any write ----


def _sync_chain(bad: dict, n_vals: int = 4, blocks: int = 8):
    """A real chain from genesis (the benchmark's reference builds it):
    ``bad`` maps a commit height to the index of its one flipped
    signature (below ``n_vals * 2 // 3 + 1``: inside what the light
    check reads; above: the unchecked third)."""
    import os
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if repo not in sys.path:
        sys.path.insert(0, repo)
    from benchmark.drivers import blocksync_node as bn

    config = {"validators": n_vals, "blocks": blocks, "txs_per_block": 2,
              "tx_bytes": 64, "key_space": 4, "peers": 3}
    params = {"warm": 0, "tamper_every": blocks, "tamper_strata": [[0, 1]]}
    chain = bn.plan(config, params, seed=36)
    for item in chain.items:
        item.bad = (bad[item.height],) if item.height in bad else ()
    bn.build(chain, 1).finish()
    return chain, config


def _sync(chain, config, until: int, timeout: float = 60.0):
    """A node (the program's assembly) synced from scripted peers in
    memory until block ``until`` applied; its driver state."""
    from benchmark.drivers import node_peers

    st = node_peers.start(chain, config, {})
    node_peers._resume(st)
    deadline = time.monotonic() + timeout
    while st.max_applied() < until and time.monotonic() < deadline:
        if st.reactor.apply_error is not None:
            break
        time.sleep(0.02)
    node_peers._pause(st, 10.0)
    return st


class TestSyncRepair:
    @pytest.mark.parametrize("idx, kind", [(0, "light"), (3, "fork")])
    def test_a_tampered_last_commit_is_rejected_before_any_write(
        self, idx, kind
    ):
        """Commit 3's copy with signature ``idx`` flipped: inside the
        light check's two thirds the copy of block 4 is rejected at
        step 3; in the unchecked third (a pair that passes the light
        check) at step 4 by validate_block's full check — which, before
        PR 36, ran after save_block, so the node stored a block it never
        applied and retried the pair forever."""
        from benchmark.drivers import node_peers
        from cometbft_tpu.types import codec

        chain, config = _sync_chain({3: idx})
        assert chain.node.tampers[3][:2] == (idx, kind)
        st = _sync(chain, config, until=7)
        try:
            assert st.reactor.apply_error is None
            assert st.max_applied() >= 7, "the sync stalled"
            err = st.verdicts[3]
            step = 3 if kind == "light" else 4
            assert f"sent invalid block {step}:" in err and f"#{idx})" in err
            served = [p for p, got in st.peers.delivered.items() if got]
            assert served
            dropped = {p for p, reason in st.peers.drops
                       if f"invalid block {step}:" in reason}
            assert set(served) <= dropped
            store = st.node.block_store
            for h in range(1, 8):  # the honest chain, nothing else
                assert (codec.encode_block(store.load_block(h))
                        == chain.node.blocks[h - 1].block_bytes)
        finally:
            node_peers.shutdown(st)

    def test_the_full_check_runs_once_per_applied_block(self, monkeypatch):
        from benchmark.drivers import node_peers
        from cometbft_tpu.state import execution

        checked = []
        full = execution.verify_commit

        def counting(chain_id, vals, block_id, height, commit):
            full(chain_id, vals, block_id, height, commit)
            checked.append(height)

        monkeypatch.setattr(execution, "verify_commit", counting)
        chain, config = _sync_chain({})
        st = _sync(chain, config, until=7)
        try:
            applied = [a.height for a in st.applied]
            assert applied[:7] == list(range(1, 8))
            # block h's LastCommit (commit h - 1) once, for h >= 2
            assert checked == [h - 1 for h in applied if h > 1]
        finally:
            node_peers.shutdown(st)

    def test_apply_block_still_validates_for_its_other_callers(self):
        """Consensus and replay call apply_block: it validates (the
        full check of the LastCommit among it) and applies nothing on
        a failure; apply_verified_block is the same apply without it."""
        from benchmark.drivers import node_peers
        from cometbft_tpu.types import codec
        from cometbft_tpu.types.block import BlockID, PartSetHeader
        from cometbft_tpu.types.validation import InvalidCommitSignatures

        def ref(h):
            b = chain.node.blocks[h - 1]
            return BlockID(b.block_hash, PartSetHeader(
                b.parts_total, b.parts_hash)), codec.decode_block(
                    b.block_bytes)

        chain, config = _sync_chain({5: 3})
        st = node_peers.start(chain, config, {})  # peers hold: no sync
        try:
            exec_, state = st.node.block_exec, st.reactor.state
            calls = []
            real = exec_.validate_block

            def counting(s, b):
                calls.append(b.header.height)
                real(s, b)

            exec_.validate_block = counting
            for h in range(1, 6):
                state = exec_.apply_block(state, *ref(h))
            assert calls == [1, 2, 3, 4, 5]
            # block 6's copy whose LastCommit (commit 5) has #3 flipped
            bad = codec.decode_block(chain.node.tampers[5][2][6])
            with pytest.raises(InvalidCommitSignatures, match="#3"):
                exec_.apply_block(state, BlockID(), bad)
            assert calls == [1, 2, 3, 4, 5, 6]
            assert st.node.app.height == 5  # nothing of it applied
            state = exec_.apply_verified_block(state, *ref(6))
            assert calls == [1, 2, 3, 4, 5, 6]  # no validation
            assert state.last_block_height == 6
            assert state.app_hash == chain.node.blocks[5].app_hash
        finally:
            node_peers.shutdown(st)

    def test_a_validated_block_that_fails_to_apply_halts_the_sync(self):
        """No retry of the same pair forever: the pool routine stops
        and says why (upstream panics)."""
        from benchmark.drivers import node_peers
        from cometbft_tpu.blocksync.reactor import ApplyError

        chain, config = _sync_chain({})
        st = None
        try:
            st = node_peers.start(chain, config, {})
            real = st.node.block_exec.apply_verified_block

            def broken(state, block_id, block, **kw):
                if block.header.height == 3:
                    raise RuntimeError("disk full")
                return real(state, block_id, block, **kw)

            st.node.block_exec.apply_verified_block = broken
            node_peers._resume(st)
            deadline = time.monotonic() + 60
            while (st.reactor.apply_error is None
                   and time.monotonic() < deadline):
                time.sleep(0.02)
            err = st.reactor.apply_error
            assert isinstance(err, ApplyError) and err.height == 3
            assert "disk full" in repr(err.__cause__)
            time.sleep(0.3)
            assert not [t for t in node_peers._routines()]
            assert st.max_applied() == 2
        finally:
            if st is not None:
                node_peers.shutdown(st)


class TestPoolRoutineSpans:
    def test_blocksync_wait_recorded_when_no_pair_is_ready(self):
        """A pool routine with no peers has no block pair to apply: each
        pass that made no progress is a ``blocksync/wait`` span on its
        own thread — the node waiting for its peers — and no step."""
        import threading
        from types import SimpleNamespace

        from cometbft_tpu.blocksync import reactor as R
        from cometbft_tpu.utils.trace import TRACER

        r = R.BlocksyncReactor(
            SimpleNamespace(initial_height=1), None,
            SimpleNamespace(height=lambda: 0), block_sync=True,
        )
        r._maybe_switch_to_consensus = lambda: False
        TRACER.clear()
        routine = threading.Thread(target=r._pool_routine)
        routine.start()
        time.sleep(8 * R.POOL_TICK)
        r._quit.set()
        routine.join(5)
        assert not routine.is_alive()
        events = TRACER.events()
        waits = [e for e in events if e["name"] == "blocksync/wait"]
        assert len(waits) >= 3
        assert {e["tid"] for e in waits} == {routine.ident}
        # all but the last, cut short by the quit, wait out a tick
        assert all(e["dur"] >= 0.8 * R.POOL_TICK * 1e6 for e in waits[:-1])
        # read by span_ms alone: the wait reads no thread clock
        assert not [e for e in waits if "tdur" in e]
        assert not [e for e in events if e["name"] == "blocksync/step"]

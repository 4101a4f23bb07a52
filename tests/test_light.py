"""Light client tests (reference: light/client_test.go, verifier_test.go,
detector_test.go) — run against a real 2-validator chain."""

from __future__ import annotations

import pytest

from cometbft_tpu.light import (
    Client,
    ErrLightClientAttack,
    LightStore,
    NodeProvider,
    SEQUENTIAL,
    SKIPPING,
    TrustOptions,
    verify_adjacent,
    verify_non_adjacent,
)
from cometbft_tpu.light.verifier import (
    ErrInvalidHeader,
    ErrOldHeaderExpired,
)
from cometbft_tpu.types.light_block import LightBlock, SignedHeader
from cometbft_tpu.utils.db import MemDB
from cometbft_tpu.utils.time import now_ns
from tests.test_reactors import connect_star, make_localnet, wait_all_height

WEEK_NS = 100 * 365 * 24 * 3600 * 10**9  # ample: test genesis time is fixed in 2023


@pytest.fixture(scope="module")
def chain(tmp_path_factory):
    """A 2-validator chain grown to height >= 10, then stopped."""
    tmp = tmp_path_factory.mktemp("lightchain")
    nodes, privs, gen = make_localnet(tmp, 2)
    for n in nodes:
        n.start()
    connect_star(nodes)
    wait_all_height(nodes, 10)
    for n in nodes:
        n.consensus.stop()  # freeze the chain; stores stay open
    yield nodes
    for n in nodes:
        try:
            n.stop()
        except Exception:
            pass


def provider_for(node):
    return NodeProvider(
        "reactor-test-chain", node.block_store, node.state_store
    )


def trust_root(node, height=1):
    meta = node.block_store.load_block_meta(height)
    return TrustOptions(
        period_ns=WEEK_NS, height=height, hash=meta.block_id.hash
    )


class TestVerifier:
    def _lb(self, node, h):
        return provider_for(node).light_block(h)

    def test_verify_adjacent_ok(self, chain):
        lb1, lb2 = self._lb(chain[0], 1), self._lb(chain[0], 2)
        verify_adjacent(lb1, lb2, "reactor-test-chain", WEEK_NS)

    def test_verify_non_adjacent_ok(self, chain):
        lb1, lb8 = self._lb(chain[0], 1), self._lb(chain[0], 8)
        verify_non_adjacent(lb1, lb8, "reactor-test-chain", WEEK_NS)

    def test_expired_trusted_header_rejected(self, chain):
        lb1, lb2 = self._lb(chain[0], 1), self._lb(chain[0], 2)
        with pytest.raises(ErrOldHeaderExpired):
            verify_adjacent(
                lb1, lb2, "reactor-test-chain",
                trusting_period_ns=1,  # expired immediately
                now=now_ns(),
            )

    def test_tampered_header_rejected(self, chain):
        from dataclasses import replace

        lb1, lb2 = self._lb(chain[0], 1), self._lb(chain[0], 2)
        tampered_header = replace(lb2.header, app_hash=b"\xde\xad" * 16)
        tampered = LightBlock(
            signed_header=SignedHeader(
                header=tampered_header, commit=lb2.signed_header.commit
            ),
            validator_set=lb2.validator_set,
        )
        with pytest.raises(Exception):
            verify_adjacent(lb1, tampered, "reactor-test-chain", WEEK_NS)

    def test_future_header_rejected(self, chain):
        lb1, lb2 = self._lb(chain[0], 1), self._lb(chain[0], 2)
        with pytest.raises(ErrInvalidHeader):
            verify_adjacent(
                lb1, lb2, "reactor-test-chain", WEEK_NS,
                now=lb1.time_ns,  # "now" is before header 2's time
                max_clock_drift_ns=0,
            )


class TestLightClient:
    def test_skipping_verification(self, chain):
        client = Client(
            "reactor-test-chain",
            trust_root(chain[0]),
            provider_for(chain[0]),
            [provider_for(chain[1])],
            LightStore(MemDB()),
        )
        lb = client.verify_light_block_at_height(9)
        assert lb.height == 9
        assert client.trusted_light_block(9) is not None

    def test_sequential_verification(self, chain):
        client = Client(
            "reactor-test-chain",
            trust_root(chain[0]),
            provider_for(chain[0]),
            [provider_for(chain[1])],
            LightStore(MemDB()),
            verification_mode=SEQUENTIAL,
        )
        lb = client.verify_light_block_at_height(6)
        assert lb.height == 6
        # sequential stores every intermediate header
        for h in range(1, 7):
            assert client.trusted_light_block(h) is not None

    def test_backwards_verification(self, chain):
        client = Client(
            "reactor-test-chain",
            trust_root(chain[0], height=8),
            provider_for(chain[0]),
            [provider_for(chain[1])],
            LightStore(MemDB()),
        )
        lb = client.verify_light_block_at_height(3)
        assert lb.height == 3

    def test_update_follows_head(self, chain):
        client = Client(
            "reactor-test-chain",
            trust_root(chain[0]),
            provider_for(chain[0]),
            [provider_for(chain[1])],
            LightStore(MemDB()),
        )
        latest = client.update()
        assert latest is not None
        assert latest.height >= 10

    def test_divergent_witness_detected(self, chain):
        from dataclasses import replace

        class EvilProvider(NodeProvider):
            """Serves a header with a forged app hash at every height."""

            def __init__(self, inner):
                super().__init__(
                    "reactor-test-chain",
                    inner.block_store,
                    inner.state_store,
                )
                self.reported = []

            def light_block(self, height):
                lb = super().light_block(height)
                forged = replace(lb.header, app_hash=b"\x66" * 32)
                return LightBlock(
                    signed_header=SignedHeader(
                        header=forged, commit=lb.signed_header.commit
                    ),
                    validator_set=lb.validator_set,
                )

            def report_evidence(self, ev):
                self.reported.append(ev)

        evil = EvilProvider(provider_for(chain[1]))
        client = Client(
            "reactor-test-chain",
            trust_root(chain[0]),
            provider_for(chain[0]),
            [],  # no witnesses at init...
            LightStore(MemDB()),
        )
        client.witnesses = [evil]  # ...so init passes; then divergence
        with pytest.raises(ErrLightClientAttack):
            client.verify_light_block_at_height(5)
        assert evil.reported, "evidence was not reported"


# -- the trusted store: a write-through newest block, a counted size ------


@pytest.fixture(scope="module")
def store_blocks() -> list[LightBlock]:
    """Heights 1..5 of one 3-validator set: the store reads no more
    of a block than its height and its bytes."""
    from tests.helpers import make_light_block, make_val_set

    vals, keys = make_val_set(3)
    return [make_light_block(vals, keys, height=h) for h in range(1, 6)]


@pytest.fixture(params=["memdb", "sqlite"])
def any_db(request, tmp_path):
    from cometbft_tpu.utils.db import SQLiteDB

    if request.param == "memdb":
        yield MemDB()
        return
    db = SQLiteDB(str(tmp_path / "trust.db"))
    yield db
    db.close()


def _walked(db) -> list[int]:
    """The heights under the store's prefix, by a walk of the DB."""
    return [
        int.from_bytes(k[3:], "big") for k, _ in db.prefix_iterator(b"lb/")
    ]


class TestLightStore:
    @pytest.mark.parametrize(
        "op",
        [
            pytest.param(lambda s, b: None, id="first-saves"),
            pytest.param(lambda s, b: s.save(b[1]), id="overwrite"),
            pytest.param(lambda s, b: s.delete(2), id="delete-present"),
            pytest.param(lambda s, b: s.delete(9), id="delete-missing"),
            pytest.param(lambda s, b: s.prune(2), id="prune-excess"),
            pytest.param(lambda s, b: s.prune(7), id="prune-no-excess"),
        ],
    )
    def test_size_is_a_walk_of_the_prefix(self, store_blocks, op):
        db = MemDB()
        db.set(b"la/other", b"x")  # neighbours of the prefix: not counted
        db.set(b"lc/other", b"x")
        store = LightStore(db)
        store.save(store_blocks[0])
        assert store.size() == len(_walked(db)) == 1
        for lb in store_blocks[1:4]:
            store.save(lb)
            assert store.size() == len(_walked(db))
        op(store, store_blocks)
        assert store.size() == len(_walked(db))
        # counted once, by the first size(); every later answer is the
        # count kept beside the writes
        assert store.stats()["size_walks"] == 1
        # a second store over the same DB counts what the first left
        assert LightStore(db).size() == store.size()

    def test_prune_by_count_touches_no_key_without_excess(self, store_blocks):
        class Counting(MemDB):
            walks = 0

            def iterator(self, start=None, end=None):
                self.walks += 1
                return super().iterator(start, end)

        db = Counting()
        store = LightStore(db)
        for lb in store_blocks:
            store.save(lb)
        assert store.prune(5) == 0 and store.prune(9) == 0
        assert db.walks == 1  # the count; no walk after it
        assert store.size() == 5 and db.walks == 1

    def test_prune_keeps_the_newest_and_forgets_a_dropped_newest(
        self, store_blocks, any_db
    ):
        store = LightStore(any_db)
        for lb in store_blocks:
            store.save(lb)
        assert store.latest().height == 5  # loaded once; kept from here
        assert store.prune(2) == 3
        assert _walked(any_db) == [4, 5] and store.size() == 2
        store.save(store_blocks[4])
        assert store.latest() is store_blocks[4]  # still the kept object
        assert store.prune(0) == 2
        assert _walked(any_db) == [] and store.size() == 0
        assert store.latest() is None and store.light_block_before(9) is None
        store.save(store_blocks[2])
        assert store.latest() is store_blocks[2]

    def test_delete_of_the_newest_forgets_it(self, store_blocks):
        store = LightStore(MemDB())
        assert store.latest() is None
        for lb in store_blocks[:3]:
            store.save(lb)
        assert store.latest() is store_blocks[2]
        store.delete(1)  # not the newest: nothing forgotten
        assert store.latest() is store_blocks[2]
        store.delete(3)
        decoded = store.stats()["anchor_decoded"]
        newest = store.latest()
        assert newest.height == 2 and newest is not store_blocks[1]
        assert newest.encode() == store_blocks[1].encode()
        assert store.latest() is newest  # loaded once
        assert store.stats()["anchor_decoded"] == decoded + 1
        # a save below the newest is stored and does not become it
        store.save(store_blocks[0])
        assert store.latest() is newest and store.size() == 2

    def test_light_block_before_below_the_head_reads_the_db(
        self, store_blocks
    ):
        store = LightStore(MemDB())
        assert store.latest() is None
        for lb in (store_blocks[0], store_blocks[2], store_blocks[4]):
            store.save(lb)
        assert store.light_block_before(6) is store_blocks[4]
        assert store.light_block_before(5).height == 3
        assert store.light_block_before(3).height == 1
        assert store.light_block_before(1) is None
        assert store.stats() == {
            "anchor_memory": 1, "anchor_decoded": 2, "size_walks": 0,
        }

    def test_save_writes_before_it_returns_and_a_reopened_store_agrees(
        self, store_blocks, any_db
    ):
        first = LightStore(any_db)
        for lb in store_blocks:
            first.save(lb)
            # in the DB when save returns: not deferred, not batched
            assert bytes(
                any_db.get(b"lb/" + lb.height.to_bytes(8, "big"))
            ) == lb.encode()
        reopened = LightStore(any_db)
        for lb in store_blocks:
            assert reopened.get(lb.height) == lb
        for store in (first, reopened):
            assert store.latest() == store_blocks[-1]
            assert store.latest().encode() == store_blocks[-1].encode()
            assert store.light_block_before(4) == store_blocks[2]
            assert store.first() == store_blocks[0]
            assert store.size() == 5
        assert reopened.stats()["anchor_decoded"] == 2  # newest; before 4


class _TamperedAt(NodeProvider):
    """The chain, with one commit signature altered at ``bad``: the
    block is well-formed and the verifier rejects it."""

    def __init__(self, node, bad: int | None):
        super().__init__(
            "reactor-test-chain", node.block_store, node.state_store
        )
        self.bad = bad

    def light_block(self, height):
        from dataclasses import replace

        lb = super().light_block(height)
        if height != self.bad:
            return lb
        commit = lb.signed_header.commit
        sigs = list(commit.signatures)
        raw = bytearray(sigs[0].signature)
        raw[3] ^= 0x10
        sigs[0] = replace(sigs[0], signature=bytes(raw))
        return LightBlock(
            signed_header=SignedHeader(
                header=lb.header,
                commit=replace(commit, signatures=tuple(sigs)),
            ),
            validator_set=lb.validator_set,
        )


WALKS = [
    pytest.param(SKIPPING, [4, 9], None, id="skipping"),
    pytest.param(SEQUENTIAL, [3, 6], None, id="sequential"),
    pytest.param(SKIPPING, [3, 5, 7, 9], 5, id="list-with-a-rejected-target"),
]


def _walk(chain, mode, heights, bad, forget: bool):
    """-> (verdicts, DB contents, the store) of one catch-up;
    ``forget``: the store's kept block is dropped before every step,
    so every anchor is read back from the DB and decoded."""
    db = MemDB()
    store = LightStore(db)
    client = Client(
        "reactor-test-chain", trust_root(chain[0]),
        _TamperedAt(chain[0], bad), [provider_for(chain[1])], store,
        verification_mode=mode,
    )
    verdicts = []
    walk = client.verify_light_blocks_at_heights(heights)
    for _ in heights:
        if forget:
            store._forget_newest()
        height, lb, err = next(walk)
        verdicts.append(
            (height, lb is not None and lb.hash(), type(err).__name__)
        )
        assert client.latest_trusted().height == max(_walked(db))
    return verdicts, list(db.iterator()), store


class TestAnchorFromMemory:
    @pytest.mark.parametrize("mode,heights,bad", WALKS)
    def test_the_kept_block_changes_no_verdict_and_no_byte(
        self, chain, mode, heights, bad
    ):
        verdicts, contents, store = _walk(chain, mode, heights, bad, False)
        again, decoded, slow = _walk(chain, mode, heights, bad, True)
        assert verdicts == again
        assert contents == decoded  # the same keys, byte-equal values
        assert [h for h, ok, _ in verdicts if not ok] == (
            [bad] if bad else []
        )
        trusted = _walked(store.db)
        assert bad not in trusted and set(heights) - {bad} <= set(trusted)
        # as built no anchor is decoded; forgotten, every step's is
        assert store.stats()["anchor_decoded"] == 0
        assert slow.stats()["anchor_decoded"] >= len(heights)

    def test_a_rejected_target_does_not_move_the_anchor(self, chain):
        store = LightStore(MemDB())
        client = Client(
            "reactor-test-chain", trust_root(chain[0]),
            _TamperedAt(chain[0], 5), [provider_for(chain[1])], store,
        )
        walk = client.verify_light_blocks_at_heights([3, 5, 7])
        _, lb3, err = next(walk)
        assert err is None and client.latest_trusted() is lb3
        height, lb, err = next(walk)
        assert (height, lb) == (5, None) and err is not None
        assert client.latest_trusted() is lb3
        assert client.trusted_light_block(5) is None
        before = store.stats()
        _, lb7, err = next(walk)
        after = store.stats()
        assert err is None and client.latest_trusted() is lb7
        # the step after the rejection read its anchor (header 3) from
        # memory, once; nothing was decoded
        assert after["anchor_memory"] == before["anchor_memory"] + 1
        assert after["anchor_decoded"] == before["anchor_decoded"] == 0

    @pytest.mark.parametrize("resumed", [False, True], ids=["fresh", "resumed"])
    def test_a_walk_decodes_at_most_one_anchor_and_counts_once(
        self, chain, resumed
    ):
        from cometbft_tpu.utils.trace import TRACER

        db = MemDB()
        if resumed:  # a store another process left: the reopen path
            LightStore(db).save(provider_for(chain[0]).light_block(1))
        store = LightStore(db)
        client = Client(
            "reactor-test-chain",
            None if resumed else trust_root(chain[0]),
            provider_for(chain[0]), [provider_for(chain[1])], store,
            trust_period_ns=WEEK_NS,
        )
        heights = [2, 4, 5, 8, 9]
        TRACER.clear()
        for _, lb, err in client.verify_light_blocks_at_heights(heights):
            assert err is None and lb is not None
        assert store.stats() == {
            "anchor_memory": len(heights),
            "anchor_decoded": int(resumed),
            "size_walks": 1,
        }
        roots = [
            e["args"] for e in TRACER.events() if e["name"] == "light/verify"
        ]
        assert [a["anchor"] for a in roots] == ["memory"] * len(heights)
        assert [a["trusted_height"] for a in roots] == [1, 2, 4, 5, 8]

    def test_a_target_below_the_head_reads_the_store(self, chain):
        from cometbft_tpu.utils.trace import TRACER

        store = LightStore(MemDB())
        client = Client(
            "reactor-test-chain", trust_root(chain[0], height=2),
            provider_for(chain[0]), [provider_for(chain[1])], store,
        )
        client.verify_light_block_at_height(8)
        TRACER.clear()
        client.verify_light_block_at_height(5)  # between stored blocks
        client.verify_light_block_at_height(1)  # backwards
        roots = [
            e["args"] for e in TRACER.events() if e["name"] == "light/verify"
        ]
        assert [(a["anchor"], a["mode"]) for a in roots] == [
            ("store", "non_adjacent"), ("store", "backwards"),
        ]
        assert client.latest_trusted().height == 8
        assert _walked(store.db) == [1, 2, 5, 8]

"""Tests for the domain types layer."""

import random
import sys
import threading
from dataclasses import replace
from fractions import Fraction

import pytest

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.types import (
    BLOCK_ID_FLAG_ABSENT,
    BLOCK_ID_FLAG_COMMIT,
    BLOCK_ID_FLAG_NIL,
    Block,
    BlockID,
    Commit,
    CommitSig,
    ConflictingVoteError,
    Data,
    DuplicateVoteEvidence,
    GenesisDoc,
    GenesisValidator,
    Header,
    NIL_BLOCK_ID,
    PRECOMMIT_TYPE,
    PREVOTE_TYPE,
    PartSet,
    Validator,
    ValidatorSet,
    Vote,
    VoteSet,
)
from cometbft_tpu.types import canonical, codec, validation
from cometbft_tpu.types.part_set import BLOCK_PART_SIZE_BYTES, PartSetError

from tests.helpers import (
    CHAIN_ID,
    make_block_id,
    make_commit,
    make_val_set,
    signed_vote,
)


class TestCanonical:
    def test_vote_sign_bytes_deterministic_and_distinct(self):
        bid = make_block_id()
        a = canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 5, 0, bid, 1000)
        b = canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 5, 0, bid, 1000)
        assert a == b
        # any field change produces different bytes
        variants = [
            canonical.vote_sign_bytes(CHAIN_ID, PREVOTE_TYPE, 5, 0, bid, 1000),
            canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 6, 0, bid, 1000),
            canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 5, 1, bid, 1000),
            canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 5, 0, None, 1000),
            canonical.vote_sign_bytes(CHAIN_ID, PRECOMMIT_TYPE, 5, 0, bid, 1001),
            canonical.vote_sign_bytes("other", PRECOMMIT_TYPE, 5, 0, bid, 1000),
        ]
        assert len({a, *variants}) == len(variants) + 1

    def test_fixed_width_height_round(self):
        """Nonzero heights/rounds are sfixed64: sign bytes have constant
        size regardless of magnitude (zero fields are omitted, proto3)."""
        bid = make_block_id()
        sizes = {
            len(canonical.vote_sign_bytes(CHAIN_ID, 2, h, r, bid, 99))
            for h, r in [(1, 1), (2**40, 100), (2**62, 2**31)]
        }
        assert len(sizes) == 1


class TestHeaderAndBlock:
    def test_header_hash_requires_validators_hash(self):
        h = Header(chain_id=CHAIN_ID, height=1)
        assert h.hash() is None
        h2 = replace(h, validators_hash=b"\x01" * 32)
        assert isinstance(h2.hash(), bytes) and len(h2.hash()) == 32

    def test_header_hash_sensitivity(self):
        base = Header(
            chain_id=CHAIN_ID, height=3, validators_hash=b"\x01" * 32
        )
        assert base.hash() != replace(base, height=4).hash()
        assert base.hash() != replace(base, app_hash=b"x" * 32).hash()

    def test_block_roundtrip_through_codec(self):
        vals, keys = make_val_set(4)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        block = Block(
            header=Header(
                chain_id=CHAIN_ID,
                height=2,
                time_ns=123456789,
                validators_hash=vals.hash(),
                proposer_address=vals.get_proposer().address,
            ),
            data=Data(txs=(b"tx1", b"tx2")),
            last_commit=commit,
        ).with_hashes()
        rt = codec.decode_block(block.encode())
        assert rt.header == block.header
        assert rt.data.txs == block.data.txs
        assert rt.last_commit == block.last_commit
        assert rt.hash() == block.hash()

    def test_commit_vote_sign_bytes_match_votes(self):
        """Commit-reconstructed sign bytes must equal the original vote
        sign bytes — this is what makes batch verification sound."""
        vals, keys = make_val_set(4)
        bid = make_block_id()
        vote_set = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        votes = []
        for i, key in enumerate(keys):
            v = signed_vote(key, i, bid)
            vote_set.add_vote(v)
            votes.append(v)
        commit = vote_set.make_commit()
        for i, v in enumerate(votes):
            assert commit.vote_sign_bytes(CHAIN_ID, i) == v.sign_bytes(CHAIN_ID)


class TestValidatorSet:
    def test_canonical_ordering(self):
        vals, _ = make_val_set(4, powers=[5, 20, 10, 10])
        powers = [v.voting_power for v in vals.validators]
        assert powers == sorted(powers, reverse=True)
        # ties broken by address
        tied = [v for v in vals.validators if v.voting_power == 10]
        assert tied[0].address < tied[1].address

    def test_proposer_rotation_visits_all(self):
        vals, _ = make_val_set(4, powers=[1, 1, 1, 1])
        seen = set()
        vs = vals
        for _ in range(4):
            vs = vs.increment_proposer_priority(1)
            seen.add(vs.get_proposer().address)
        assert len(seen) == 4

    def test_proposer_frequency_weighted_by_power(self):
        vals, _ = make_val_set(3, powers=[1, 2, 3])
        counts: dict[bytes, int] = {}
        vs = vals
        for _ in range(600):
            vs = vs.increment_proposer_priority(1)
            a = vs.get_proposer().address
            counts[a] = counts.get(a, 0) + 1
        by_power = {
            v.address: v.voting_power for v in vals.validators
        }
        freq = sorted((counts[a], by_power[a]) for a in counts)
        assert freq == [(100, 1), (200, 2), (300, 3)]

    def test_hash_changes_with_membership(self):
        vals, _ = make_val_set(3)
        vals2, _ = make_val_set(4)
        assert vals.hash() != vals2.hash()

    def test_update_with_change_set(self):
        vals, keys = make_val_set(3, powers=[10, 10, 10])
        new_key = ed.priv_key_from_secret(b"newval")
        vs = vals.update_with_change_set([(new_key.pub_key(), 5)])
        assert len(vs) == 4
        # update power
        vs2 = vs.update_with_change_set([(new_key.pub_key(), 50)])
        _, v = vs2.get_by_address(new_key.pub_key().address())
        assert v.voting_power == 50
        # removal
        vs3 = vs2.update_with_change_set([(new_key.pub_key(), 0)])
        assert not vs3.has_address(new_key.pub_key().address())
        with pytest.raises(ValueError):
            vs3.update_with_change_set([(new_key.pub_key(), 0)])

    def test_new_validator_not_immediate_proposer(self):
        vals, _ = make_val_set(3, powers=[10, 10, 10])
        new_key = ed.priv_key_from_secret(b"sneaky")
        vs = vals.update_with_change_set([(new_key.pub_key(), 1000)])
        vs = vs.increment_proposer_priority(1)
        assert vs.get_proposer().address != new_key.pub_key().address()

    def test_get_by_address_indexes_the_current_members(self):
        """The address index is built once an instance: after a rotation
        it still gives each member's place and its updated priority."""
        vals, _ = make_val_set(5, powers=[1, 2, 3, 3, 9])
        assert vals.get_by_address(b"\x00" * 20) == (-1, None)
        vs = vals.increment_proposer_priority(1)
        vs.get_by_address(vs.validators[0].address)  # index it
        vs._increment_once()  # what increment_proposer_priority runs
        for i, v in enumerate(vs.validators):
            assert vs.get_by_address(v.address) == (i, v)
        assert vs.get_by_address(b"\x00" * 20) == (-1, None)


class TestVoteSet:
    def test_two_thirds_majority(self):
        vals, keys = make_val_set(4)  # power 10 each, need > 26
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        bid = make_block_id()
        for i in range(2):
            assert vs.add_vote(signed_vote(keys[i], i, bid))
        assert not vs.has_two_thirds_majority()
        assert vs.add_vote(signed_vote(keys[2], 2, bid))
        assert vs.has_two_thirds_majority()
        assert vs.two_thirds_majority() == bid

    def test_duplicate_vote_not_added(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        v = signed_vote(keys[0], 0, make_block_id())
        assert vs.add_vote(v)
        assert not vs.add_vote(v)

    def test_conflicting_vote_raises(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        assert vs.add_vote(signed_vote(keys[0], 0, make_block_id(b"a")))
        with pytest.raises(ConflictingVoteError) as ei:
            vs.add_vote(signed_vote(keys[0], 0, make_block_id(b"b")))
        assert ei.value.vote_a.block_id != ei.value.vote_b.block_id

    def test_bad_signature_rejected(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        v = signed_vote(keys[0], 0, make_block_id())
        bad = replace(v, signature=v.signature[:-1] + b"\x00")
        with pytest.raises(Exception, match="signature"):
            vs.add_vote(bad)

    def test_wrong_index_address_mismatch(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        v = signed_vote(keys[0], 1, make_block_id())  # wrong index
        with pytest.raises(Exception, match="mismatch"):
            vs.add_vote(v)

    def test_nil_votes_count_toward_any_but_not_block(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i in range(3):
            vs.add_vote(signed_vote(keys[i], i, NIL_BLOCK_ID))
        assert vs.has_two_thirds_any()
        assert not vs.has_two_thirds_majority() or vs.two_thirds_majority().is_nil()

    def test_make_commit_excludes_other_blocks(self):
        vals, keys = make_val_set(4)
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        bid = make_block_id(b"win")
        for i in range(3):
            vs.add_vote(signed_vote(keys[i], i, bid))
        vs.add_vote(signed_vote(keys[3], 3, make_block_id(b"lose")))
        commit = vs.make_commit()
        assert commit.block_id == bid
        flags = [cs.block_id_flag for cs in commit.signatures]
        assert flags.count(BLOCK_ID_FLAG_ABSENT) == 1


class TestVerifyCommit:
    def test_verify_commit_ok(self):
        vals, keys = make_val_set(7)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        validation.verify_commit_light(CHAIN_ID, vals, bid, 1, commit)
        validation.verify_commit_light_trusting(CHAIN_ID, vals, commit)

    def test_verify_commit_wrong_height_and_block(self):
        vals, keys = make_val_set(4)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        with pytest.raises(validation.InvalidCommitHeight):
            validation.verify_commit(CHAIN_ID, vals, bid, 2, commit)
        with pytest.raises(validation.InvalidCommitSignatures):
            validation.verify_commit(
                CHAIN_ID, vals, make_block_id(b"other"), 1, commit
            )

    def test_verify_commit_bad_signature(self):
        vals, keys = make_val_set(4)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        sigs = list(commit.signatures)
        sigs[2] = replace(sigs[2], signature=bytes(64))
        bad = replace(commit, signatures=tuple(sigs))
        with pytest.raises(validation.InvalidCommitSignatures, match="#2"):
            validation.verify_commit(CHAIN_ID, vals, bid, 1, bad)

    def test_verify_commit_insufficient_power(self):
        vals, keys = make_val_set(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i in range(3):
            vs.add_vote(signed_vote(keys[i], i, bid))
        commit = vs.make_commit()
        # drop one signature -> only 2 of 4 powers counted
        sigs = list(commit.signatures)
        sigs[2] = CommitSig(block_id_flag=BLOCK_ID_FLAG_ABSENT)
        commit = replace(commit, signatures=tuple(sigs))
        with pytest.raises(validation.NotEnoughVotingPower):
            validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)

    def test_verify_commit_cpu_fallback_matches(self, monkeypatch):
        monkeypatch.setenv("CMT_TPU_DISABLE_DEVICE_VERIFY", "1")
        vals, keys = make_val_set(5)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)

    def test_light_trusting_different_valset(self):
        """Trusting verification matches by address: a superset commit
        verifies against the old (trusted) set."""
        vals, keys = make_val_set(4)
        bid = make_block_id()
        commit = make_commit(vals, keys, bid)
        trusted_vals = ValidatorSet(list(vals.validators[:2]))
        validation.verify_commit_light_trusting(
            CHAIN_ID, trusted_vals, commit, Fraction(1, 3)
        )

    def test_light_trusting_insufficient(self):
        vals, keys = make_val_set(4)
        bid = make_block_id()
        vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
        for i in range(3):
            vs.add_vote(signed_vote(keys[i], i, bid))
        commit = vs.make_commit()
        # trusted set = only the validator that did NOT sign
        trusted = ValidatorSet(
            [
                v
                for v in vals.validators
                if v.address == keys[3].pub_key().address()
            ]
        )
        with pytest.raises(validation.NotEnoughVotingPower):
            validation.verify_commit_light_trusting(
                CHAIN_ID, trusted, commit, Fraction(1, 3)
            )


class TestPartSet:
    def test_split_and_assemble(self):
        data = bytes(range(256)) * 1000  # 256 KB
        ps = PartSet.from_bytes(data, 65536)
        assert ps.header.total == 4
        assert ps.is_complete()
        assert ps.assemble() == data

    def test_add_part_with_proof(self):
        data = b"z" * 100000
        src = PartSet.from_bytes(data, 65536)
        dst = PartSet(src.header)
        assert not dst.is_complete()
        for i in range(src.header.total):
            assert dst.add_part(src.get_part(i))
        assert dst.is_complete() and dst.assemble() == data
        assert not dst.add_part(src.get_part(0))  # duplicate

    def test_add_part_bad_proof_rejected(self):
        data = b"z" * 100000
        src = PartSet.from_bytes(data, 65536)
        other = PartSet.from_bytes(b"y" * 100000, 65536)
        dst = PartSet(src.header)
        with pytest.raises(PartSetError):
            dst.add_part(other.get_part(0))


class TestEvidence:
    def test_duplicate_vote_evidence_ordering(self):
        vals, keys = make_val_set(4)
        va = signed_vote(keys[0], 0, make_block_id(b"bbb"))
        vb = signed_vote(keys[0], 0, make_block_id(b"aaa"))
        ev = DuplicateVoteEvidence.from_votes(va, vb, 1000, vals)
        assert ev.vote_a.block_id.key() < ev.vote_b.block_id.key()
        ev.validate_basic()
        assert len(ev.hash()) == 32
        assert ev.validator_power == 10
        assert ev.total_voting_power == 40


class TestGenesis:
    def test_json_roundtrip(self):
        vals, keys = make_val_set(3)
        doc = GenesisDoc(
            chain_id=CHAIN_ID,
            genesis_time_ns=1_700_000_000_000_000_000,
            validators=tuple(
                GenesisValidator(pub_key=k.pub_key(), power=10, name=f"v{i}")
                for i, k in enumerate(keys)
            ),
        )
        rt = GenesisDoc.from_json(doc.to_json())
        assert rt.chain_id == doc.chain_id
        assert rt.validator_set().hash() == vals.hash()
        assert rt.hash() == doc.hash()

    def test_validation(self):
        with pytest.raises(Exception, match="chain_id"):
            GenesisDoc(chain_id="").validate_and_complete()
        with pytest.raises(Exception, match="initial_height"):
            GenesisDoc(chain_id="c", initial_height=0).validate_and_complete()


class TestRegressions:
    def test_block_id_key_no_collision(self):
        """Distinct part-set totals must not collide in vote tallies
        (total 1 vs 257 once truncated to a byte)."""
        from cometbft_tpu.types import PartSetHeader

        h = b"\x01" * 32
        a = BlockID(hash=h, part_set_header=PartSetHeader(total=1, hash=h))
        b = BlockID(hash=h, part_set_header=PartSetHeader(total=257, hash=h))
        assert a.key() != b.key()

    def test_proposal_pol_round_at_round_zero(self):
        from cometbft_tpu.types import Proposal

        _, keys = make_val_set(1)
        p = Proposal(
            height=1, round=0, pol_round=5, block_id=make_block_id(),
            signature=b"\x01" * 64,
        )
        with pytest.raises(ValueError, match="POL"):
            p.validate_basic()

    def test_light_client_attack_evidence_codec(self):
        from cometbft_tpu.types import LightClientAttackEvidence
        from tests.helpers import make_light_block

        vals, keys = make_val_set(4)
        lb = make_light_block(vals, keys, height=2)
        ev = LightClientAttackEvidence(
            conflicting_block=lb,
            common_height=1,
            byzantine_validators=(keys[0].pub_key().address(),),
            total_voting_power=40,
            timestamp_ns=123,
        )
        rt = codec.decode_evidence(codec.encode_evidence(ev))
        assert rt == ev
        blk = Block(
            header=Header(chain_id=CHAIN_ID, height=2, validators_hash=b"\x01" * 32),
            evidence=(ev,),
        ).with_hashes()
        assert codec.decode_block(blk.encode()).evidence == (ev,)


class TestVoteCodec:
    def test_vote_roundtrip(self):
        _, keys = make_val_set(1)
        v = signed_vote(keys[0], 0, make_block_id(), height=7, round_=2)
        assert Vote.decode(v.encode()) == v

    def test_nil_vote_roundtrip(self):
        _, keys = make_val_set(1)
        v = signed_vote(keys[0], 0, NIL_BLOCK_ID)
        rt = Vote.decode(v.encode())
        assert rt.is_nil() and rt == v


_T0 = 1_700_000_000_000_000_000


def _mixed_commit() -> Commit:
    """COMMIT, NIL, absent and a COMMIT vote beside the absent one,
    every vote with a timestamp of its own."""
    return Commit(
        height=3,
        round=1,
        block_id=make_block_id(b"memo"),
        signatures=(
            CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x01" * 20, _T0 + 1, b"a" * 64),
            CommitSig(BLOCK_ID_FLAG_NIL, b"\x02" * 20, _T0 + 2, b"b" * 64),
            CommitSig(BLOCK_ID_FLAG_ABSENT),
            CommitSig(BLOCK_ID_FLAG_COMMIT, b"\x04" * 20, _T0 + 4, b"d" * 64),
        ),
    )


def _fresh_sign_bytes(commit: Commit, chain_id: str, idx: int) -> bytes:
    cs = commit.signatures[idx]
    return canonical.vote_sign_bytes(
        chain_id, PRECOMMIT_TYPE, commit.height, commit.round,
        cs.block_id(commit.block_id), cs.timestamp_ns,
    )


def _timed_commit(n: int = 6):
    """A signed commit of ``n`` equal validators whose vote ``i`` has
    timestamp ``T0 + i``: an encoding names the vote it encoded."""
    vals, keys = make_val_set(n)
    bid = make_block_id(b"memo-signed")
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
    for i, key in enumerate(keys):
        vs.add_vote(signed_vote(key, i, bid, time_ns=_T0 + i))
    return vals, bid, vs.make_commit()


@pytest.fixture
def timed(monkeypatch):
    """``(vals, block_id, commit, encoded)``: a :func:`_timed_commit`
    and the vote indices a commit's encoder is asked to encode from
    here on, in order — ``canonical.VoteTemplate._encode``, called once
    for every vote ``Commit.vote_sign_bytes`` and
    ``vote_sign_bytes_many`` encode, fast path and fallback alike."""
    vals, bid, commit = _timed_commit()
    encoded: list[int] = []
    real = canonical.VoteTemplate._encode

    def counting(template, for_block, time_ns):
        encoded.append(time_ns - _T0)
        return real(template, for_block, time_ns)

    monkeypatch.setattr(canonical.VoteTemplate, "_encode", counting)
    return vals, bid, commit, encoded


def _template_commit(
    height: int = 3, round_: int = 1, part_total: int = 2,
    timestamps=(_T0 + 1, _T0 + 2, _T0 + 3),
) -> Commit:
    """Votes at ``timestamps`` whose flags cycle COMMIT, NIL, ABSENT."""
    bid = make_block_id(b"template")
    bid = BlockID(bid.hash, replace(bid.part_set_header, total=part_total))
    flags = (BLOCK_ID_FLAG_COMMIT, BLOCK_ID_FLAG_NIL, BLOCK_ID_FLAG_ABSENT)
    return Commit(
        height=height,
        round=round_,
        block_id=bid,
        signatures=tuple(
            CommitSig(flags[i % 3], bytes([i % 256]) * 20, ts, b"s" * 64)
            for i, ts in enumerate(timestamps)
        ),
    )


def _random_timestamps(n: int) -> tuple[int, ...]:
    rng = random.Random(39)
    return tuple(_T0 + rng.randrange(3_000_000_000) for _ in range(n))


class TestVoteTemplate:
    """A commit's sign-bytes from one prefix and one suffix
    (``canonical.VoteTemplate``) are ``canonical.vote_sign_bytes``'s,
    byte for byte, and a time outside the fast path takes the latter."""

    @pytest.mark.parametrize("commit_kw, chain_id", [
        ({"height": 0}, CHAIN_ID),
        ({"height": 2**63 - 1}, CHAIN_ID),
        ({"round_": 0}, CHAIN_ID),
        ({"round_": 7}, CHAIN_ID),
        ({"timestamps": (0, 0, 0)}, CHAIN_ID),
        ({"timestamps": (5 * 10**9, 10**9, 2**62 // 10**9 * 10**9)}, CHAIN_ID),
        ({"timestamps": (1, 127, 999_999_999)}, CHAIN_ID),
        ({"timestamps": (-1, -(10**9), -(10**9) - 7)}, CHAIN_ID),
        ({"timestamps": (2**63 - 1, _T0, -5)}, CHAIN_ID),
        ({}, ""),
        ({}, "c"),
        ({}, "x" * 127),
        ({}, "\u00e9" * 100),
        ({"part_total": 128}, CHAIN_ID),
        ({"part_total": 2**31}, CHAIN_ID),
        ({"height": 2**63 - 1, "round_": 2**31}, "chain-" + "y" * 60),
        # a body of 131 bytes at a full timestamp, 126 at whole seconds:
        # one commit's votes straddle the one-byte length prefix
        ({"timestamps": (_T0 + 123_456_789, _T0, _T0, _T0 + 10**9)},
         "b" * 22),
        ({"timestamps": _random_timestamps(10_000)}, CHAIN_ID),
    ], ids=[
        "height_0", "height_max", "round_0", "round_7", "time_0",
        "whole_seconds", "nanos_only", "negative", "int64_edge_and_negative",
        "chain_id_0_bytes", "chain_id_1_byte", "chain_id_127_bytes",
        "chain_id_200_utf8_bytes", "part_total_128", "part_total_2_31",
        "two_byte_length_prefix", "length_prefix_boundary",
        "random_10000_votes",
    ])
    def test_bytes_are_vote_sign_bytes(self, commit_kw, chain_id):
        commit = _template_commit(**commit_kw)
        n = commit.size()
        want = [_fresh_sign_bytes(commit, chain_id, i) for i in range(n)]
        sbs, encoded, generic = commit.vote_sign_bytes_many(
            chain_id, range(n)
        )
        assert sbs == want
        assert encoded == n
        # the fallback takes a negative time, and only that
        assert generic == sum(
            1 for cs in commit.signatures if cs.timestamp_ns < 0
        )
        # one at a time, on a copy that kept nothing, the same bytes
        copy = replace(commit)
        assert [copy.vote_sign_bytes(chain_id, i) for i in range(n)] == want

    def test_threads_sharing_a_commit_read_the_same_bytes(self):
        """Threads encoding one commit's votes at once (the groups of a
        mixed commit run concurrently) fill its slots and its template's
        seconds and heads without a lock: every read is the canonical
        bytes."""
        commit = _template_commit(timestamps=_random_timestamps(3_000))
        want = [_fresh_sign_bytes(commit, CHAIN_ID, i) for i in range(3_000)]
        reads = {
            k: (list(range(k % 3, 3_000, 1 + k % 2)), range(k, 3_000, 7))
            for k in range(16)
        }
        got: dict = {}

        def read(k):
            many, singles = reads[k]
            got[k] = commit.vote_sign_bytes_many(CHAIN_ID, many)[0] + [
                commit.vote_sign_bytes(CHAIN_ID, i) for i in singles
            ]

        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-6)
        try:
            threads = [
                threading.Thread(target=read, args=(k,)) for k in reads
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(60)
        finally:
            sys.setswitchinterval(interval)
        assert not any(t.is_alive() for t in threads)
        for k, (many, singles) in reads.items():
            assert got[k] == [want[i] for i in [*many, *singles]], k
        kept = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(3_000)]
        assert kept == want

    def test_the_fallback_is_vote_sign_bytes(self, monkeypatch):
        """Only a negative time calls the one definition; the others
        are the template's own."""
        commit = _template_commit(timestamps=(_T0, -3, 0, _T0 + 10**9))
        calls: list[int] = []
        real = canonical.vote_sign_bytes

        def spy(*args):
            calls.append(args[-1])
            return real(*args)

        monkeypatch.setattr(canonical, "vote_sign_bytes", spy)
        _, encoded, generic = commit.vote_sign_bytes_many(CHAIN_ID, range(4))
        assert (encoded, generic, calls) == (4, 1, [-3])


class TestCommitSignBytesMemo:
    """A commit keeps the sign-bytes it encodes (ISSUE 31): the bytes
    are canonical's, the memo is invisible, a copy starts without."""

    @pytest.mark.parametrize("chain_id", [CHAIN_ID, "other-chain"])
    @pytest.mark.parametrize(
        "idx", [0, 1, 3], ids=["commit", "nil", "absent_neighbour"]
    )
    def test_memoised_bytes_are_canonicals_and_kept(self, idx, chain_id):
        commit = _mixed_commit()
        first = commit.vote_sign_bytes(chain_id, idx)
        assert first == _fresh_sign_bytes(commit, chain_id, idx)
        assert commit.vote_sign_bytes(chain_id, idx) is first
        # one chain id's list at a time: another id encodes afresh,
        # and coming back encodes the first id's bytes again
        other = chain_id + "-b"
        assert commit.vote_sign_bytes(other, idx) == _fresh_sign_bytes(
            commit, other, idx
        )
        again = commit.vote_sign_bytes(chain_id, idx)
        assert again == first and again is not first
        # the neighbours were not encoded along the way
        assert commit.vote_sign_bytes_many(chain_id, range(4))[1] == 3

    @pytest.mark.parametrize("change", [
        lambda c: {"signatures": tuple(
            replace(cs, timestamp_ns=cs.timestamp_ns + 7)
            for cs in c.signatures
        )},
        lambda c: {"round": c.round + 1},
        lambda c: {"block_id": make_block_id(b"memo-other")},
    ], ids=["signatures", "round", "block_id"])
    def test_a_replaced_commit_carries_no_memo(self, change):
        commit = _mixed_commit()
        old = [commit.vote_sign_bytes(CHAIN_ID, i) for i in range(4)]
        copy = replace(commit, **change(commit))
        assert "_sign_bytes" not in vars(copy)
        new, encoded, _ = copy.vote_sign_bytes_many(CHAIN_ID, range(4))
        assert encoded == 4
        assert new == [_fresh_sign_bytes(copy, CHAIN_ID, i) for i in range(4)]
        assert new[0] != old[0] and new[3] != old[3]
        # and the original still answers with what it kept
        assert all(
            commit.vote_sign_bytes(CHAIN_ID, i) is old[i] for i in range(4)
        )

    @pytest.mark.parametrize("facet", [
        lambda c: c == _mixed_commit(),
        hash,
        repr,
        Commit.hash,
        lambda c: codec.encode_commit(c),
        lambda c: codec.decode_commit(codec.encode_commit(c)) == c,
        lambda c: sorted(k for k in vars(c) if not k.startswith("_")),
    ], ids=["eq", "hash", "repr", "commit_hash", "encode", "round_trip",
            "public_vars"])
    def test_the_memo_is_invisible(self, facet):
        commit = _mixed_commit()
        before = facet(commit)
        for i in range(4):
            commit.vote_sign_bytes(CHAIN_ID, i)
        assert "_sign_bytes" in vars(commit)
        assert facet(commit) == before
        assert before is not False  # the eq facets held to begin with

    def test_a_check_after_the_prefetch_encodes_nothing(self, timed):
        vals, bid, commit, encoded = timed
        triples = validation.commit_check_triples(CHAIN_ID, vals, commit)
        assert encoded == list(range(6))
        validation.verify_commit_light(CHAIN_ID, vals, bid, 1, commit)
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        assert encoded == list(range(6))
        assert all(
            commit.vote_sign_bytes(CHAIN_ID, i) is t[1]
            for i, t in enumerate(triples)
        )

    def test_a_check_encodes_only_beyond_the_prefetchs_cut(self, timed):
        vals, bid, commit, encoded = timed
        needed = vals.total_voting_power() * 2 // 3
        cut = validation.commit_check_triples(CHAIN_ID, vals, commit, needed)
        assert len(cut) == 5 and encoded == list(range(5))
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        assert encoded == list(range(6))

    def test_two_checks_of_a_commit_encode_each_index_once(self, timed):
        vals, bid, commit, encoded = timed
        validation.verify_commit_light_trusting(CHAIN_ID, vals, commit)
        assert sorted(encoded) == [0, 1, 2]
        validation.verify_commit_light(CHAIN_ID, vals, bid, 1, commit)
        assert sorted(encoded) == [0, 1, 2, 3, 4]

    def test_many_over_kept_indices_encodes_nothing(self, timed):
        vals, bid, commit, encoded = timed
        first, n, generic = commit.vote_sign_bytes_many(CHAIN_ID, [1, 3, 4])
        assert (n, generic, encoded) == (3, 0, [1, 3, 4])
        again, n, generic = commit.vote_sign_bytes_many(CHAIN_ID, [4, 1, 3])
        assert (n, generic, encoded) == (0, 0, [1, 3, 4])
        assert [id(b) for b in again] == [id(first[2]), id(first[0]),
                                          id(first[1])]
        assert commit.vote_sign_bytes(CHAIN_ID, 3) is first[1]

    @pytest.mark.parametrize("tamper", [
        lambda cs: replace(
            cs, signature=bytes([cs.signature[0] ^ 1]) + cs.signature[1:]
        ),
        lambda cs: replace(cs, timestamp_ns=cs.timestamp_ns + 100),
    ], ids=["signature", "timestamp"])
    def test_a_tampered_copy_encodes_its_own_bytes(self, timed, tamper):
        vals, bid, commit, encoded = timed
        validation.commit_check_triples(CHAIN_ID, vals, commit)
        del encoded[:]
        sigs = list(commit.signatures)
        sigs[2] = tamper(sigs[2])
        bad = replace(commit, signatures=tuple(sigs))
        with pytest.raises(
            validation.InvalidCommitSignatures,
            match=r"wrong signature \(#2\)",
        ):
            validation.verify_commit(CHAIN_ID, vals, bid, 1, bad)
        want = [0, 1, 2, 3, 4, 5]
        want[2] = sigs[2].timestamp_ns - _T0
        assert encoded == want
        # the prefetched original is untouched and still accepted
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        assert encoded == want

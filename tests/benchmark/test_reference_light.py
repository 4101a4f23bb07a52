"""The light client held to the plain reference
(``benchmark/reference_light.py``) on the CPU at a small size: 12
validators of seeded keys, real headers hashed by the reference, signed
with ``cryptography`` over the reference's sign-bytes.

- one step, client against reference: verdict and index equal for a
  flipped bit in each of the three ranges, a header field altered after
  signing, a set that rotated too far (the reference says "not enough
  trusted power", the client bisects and succeeds), an expired trusted
  header;
- the repaired error mapping of ``verify_non_adjacent``;
- the verify-ahead's answers equal the one-at-a-time client's on the
  same chain: with the queue running, stopped mid-sync, and with the
  look-ahead deeper than the list.
"""

from __future__ import annotations

import hashlib
import os
import sys
from dataclasses import replace

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import gen, reference  # noqa: E402
from benchmark import reference_light as RL  # noqa: E402
from benchmark.check import _named_index as named_index  # noqa: E402
from benchmark.drivers.light_sync import program_header  # noqa: E402
from cometbft_tpu.crypto import verify_queue as vq  # noqa: E402
from cometbft_tpu.light.client import (  # noqa: E402
    SKIPPING,
    Client,
    LightClientError,
    TrustOptions,
)
from cometbft_tpu.light.provider import (  # noqa: E402
    LightBlockNotFoundError,
    Provider,
)
from cometbft_tpu.light.store import LightStore  # noqa: E402
from cometbft_tpu.light.verifier import (  # noqa: E402
    ErrInvalidHeader,
    ErrNewValSetCantBeTrusted,
    ErrOldHeaderExpired,
    verify_non_adjacent,
)
from cometbft_tpu.types.light_block import LightBlockError  # noqa: E402
from cometbft_tpu.utils.db import MemDB  # noqa: E402

CHAIN_ID = "ref-light-chain"
N = 12  # trusting check reads 5 signatures, self-commit check 9
POWER = 10
SECOND = 1_000_000_000
PERIOD = 14 * 24 * 3600 * SECOND
T0 = 1_700_000_000 * SECOND
ROOT, TARGET = 1, 101


def _keys(count: int):
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    out = {}
    for i in range(count):
        key = Ed25519PrivateKey.from_private_bytes(
            hashlib.sha256(b"ref-light/%d" % i).digest()
        )
        out[key.public_key().public_bytes_raw()] = key
    return out


KEYS = _keys(2 * N)
POOL = list(KEYS)  # the order keys rotate in


def time_of(height: int) -> int:
    return T0 + height * SECOND


def static_set(_height: int) -> RL.ValidatorSet:
    return sliding_set(0)


def sliding_set(height: int) -> RL.ValidatorSet:
    """One validator replaced every ten heights; canonical order (equal
    powers: by address)."""
    first = height // 10
    pubs = sorted(POOL[first:first + N], key=RL.address)
    return RL.ValidatorSet(tuple(pubs), (POWER,) * N)


def light_block(height: int, set_at=static_set, flip: int | None = None,
                alter: bool = False) -> RL.LightBlock:
    """The chain's light block at ``height`` as plain data; ``flip``:
    one bit of that signature flipped; ``alter``: the app hash changed
    after signing."""
    vals = set_at(height)
    digest = hashlib.sha256(b"%d" % height).digest()
    header = RL.Header(
        chain_id=CHAIN_ID, height=height, time_ns=time_of(height),
        last_block_hash=digest, last_parts_total=1,
        last_parts_hash=digest[::-1], last_commit_hash=digest,
        data_hash=b"", validators_hash=RL.validator_set_hash(vals),
        next_validators_hash=RL.validator_set_hash(set_at(height + 1)),
        consensus_hash=digest, app_hash=digest[:8],
        last_results_hash=b"", evidence_hash=b"",
        proposer_address=RL.address(vals.pubs[0]),
    )
    block_hash, parts_hash = RL.header_hash(header), digest[::-1]
    sigs = []
    for i, pub in enumerate(vals.pubs):
        stamp = time_of(height) + i
        sig = KEYS[pub].sign(reference.vote_sign_bytes(
            CHAIN_ID, height, 0, block_hash, 1, parts_hash, stamp
        ))
        if i == flip:
            sig = gen.tamper(sig)
        sigs.append(RL.CommitSig(RL.FLAG_COMMIT, RL.address(pub), stamp, sig))
    if alter:
        header = replace(header, app_hash=b"\xde\xad" * 4)
    return RL.LightBlock(
        header, RL.Commit(height, 0, block_hash, 1, parts_hash, tuple(sigs)),
        vals,
    )


def to_program(lb: RL.LightBlock):
    """The same light block in the program's types."""
    from cometbft_tpu.crypto.ed25519 import Ed25519PubKey
    from cometbft_tpu.types.block import (
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )
    from cometbft_tpu.types.light_block import LightBlock, SignedHeader
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    c = lb.commit
    header = program_header(lb.header)
    commit = Commit(
        height=c.height, round=c.round,
        block_id=BlockID(c.block_hash,
                         PartSetHeader(c.parts_total, c.parts_hash)),
        signatures=tuple(
            CommitSig(s.flag, s.address, s.timestamp_ns, s.signature)
            for s in c.sigs
        ),
    )
    vals = ValidatorSet([
        Validator(Ed25519PubKey(p), w)
        for p, w in zip(lb.vals.pubs, lb.vals.powers)
    ])
    assert [v.pub_key.bytes() for v in vals.validators] == list(lb.vals.pubs)
    return LightBlock(SignedHeader(header, commit), vals)


class Served(Provider):
    """Serves the reference's chain in the program's types, and keeps
    the heights it was asked for."""

    def __init__(self, make, heights=None) -> None:
        self.make, self.heights, self.asked = make, heights, []

    def chain_id(self) -> str:
        return CHAIN_ID

    def light_block(self, height: int):
        self.asked.append(height)
        if self.heights is not None and height not in self.heights:
            raise LightBlockNotFoundError(f"no block at {height}")
        return to_program(self.make(height))

    def report_evidence(self, ev) -> None:
        raise AssertionError("no fork here")


def client_over(make, heights=None) -> tuple[Client, Served]:
    primary = Served(make, heights)
    root = to_program(make(ROOT))
    client = Client(
        CHAIN_ID, TrustOptions(PERIOD, ROOT, root.hash()), primary,
        [Served(make)], LightStore(MemDB()), verification_mode=SKIPPING,
    )
    primary.asked.clear()
    return client, primary


def reference_step(trusted: RL.LightBlock, new: RL.LightBlock,
                   now: int | None = None) -> RL.Verdict:
    return RL.verify_light(
        CHAIN_ID, trusted, new, PERIOD,
        new.header.time_ns + SECOND if now is None else now,
    )


# -- the hashes ------------------------------------------------------------


def test_the_program_hashes_headers_and_sets_as_published():
    lb = light_block(TARGET, sliding_set)
    mine = to_program(lb)
    assert mine.header.hash() == RL.header_hash(lb.header)
    assert mine.validator_set.hash() == RL.validator_set_hash(lb.vals)
    mine.validate_basic(CHAIN_ID)
    # golden: a one-validator set, worked by hand from the published
    # encoding — leaf 0x00 || SimpleValidator{1: PublicKey{1: key}, 2: 10}
    key = bytes(range(32))
    leaf = b"\x0a\x22\x0a\x20" + key + b"\x10\x0a"
    assert RL.validator_set_hash(RL.ValidatorSet((key,), (10,))) == (
        hashlib.sha256(b"\x00" + leaf).digest()
    )
    assert RL.merkle_root([]) == hashlib.sha256(b"").digest()


# -- one step, client against reference ------------------------------------


@pytest.mark.parametrize(
    "flip,rejected",
    [
        pytest.param(2, True, id="inside-the-trusting-third"),
        pytest.param(7, True, id="between-one-third-and-two-thirds"),
        pytest.param(10, False, id="beyond-what-either-check-reads"),
    ],
)
def test_a_flipped_bit_gets_the_references_verdict_and_index(flip, rejected):
    def make(height):
        return light_block(height, flip=flip if height == TARGET else None)

    ref = reference_step(make(ROOT), make(TARGET))
    client, primary = client_over(make, heights={ROOT, TARGET})
    ((height, lb, err),) = client.verify_light_blocks_at_heights(
        [TARGET], now=time_of(TARGET) + SECOND
    )
    assert height == TARGET
    if not rejected:
        assert ref == RL.Verdict(RL.ACCEPT) and err is None
        assert client.trusted_light_block(TARGET) is not None
        return
    assert ref == RL.Verdict(RL.INVALID, flip)
    assert isinstance(err, ErrInvalidHeader) and lb is None
    assert named_index(str(err)) == flip == ref.index
    # rejected, not bisected: no midpoint was asked for, nothing stored
    assert primary.asked == [TARGET]
    assert client.trusted_light_block(TARGET) is None
    assert client.latest_trusted().height == ROOT


def test_a_header_altered_after_signing_is_rejected():
    def make(height):
        return light_block(height, alter=height == TARGET)

    ref = reference_step(make(ROOT), make(TARGET))
    assert ref == RL.Verdict(RL.INVALID) and "different header" in ref.why
    client, primary = client_over(make)
    with pytest.raises(LightBlockError, match="different header"):
        client.verify_light_block_at_height(
            TARGET, now=time_of(TARGET) + SECOND
        )
    assert primary.asked == [TARGET]
    assert client.trusted_light_block(TARGET) is None


def test_a_set_rotated_past_one_third_bisects_and_succeeds():
    make = lambda h: light_block(h, sliding_set)  # noqa: E731
    root, mid, new = make(ROOT), make((ROOT + TARGET) // 2), make(TARGET)
    assert reference_step(root, new).verdict == RL.CANNOT_TRUST
    assert reference_step(root, mid) == RL.Verdict(RL.ACCEPT)
    assert reference_step(mid, new) == RL.Verdict(RL.ACCEPT)
    with pytest.raises(ErrNewValSetCantBeTrusted):
        verify_non_adjacent(
            to_program(root), to_program(new), CHAIN_ID, PERIOD,
            now=time_of(TARGET) + SECOND,
        )
    client, primary = client_over(make)
    lb = client.verify_light_block_at_height(
        TARGET, now=time_of(TARGET) + SECOND
    )
    assert lb.hash() == RL.header_hash(new.header)
    assert primary.asked == [TARGET, (ROOT + TARGET) // 2]
    assert client.trusted_light_block((ROOT + TARGET) // 2) is not None


def test_a_wrong_signature_in_the_trusting_check_is_not_bisected():
    """The repaired mapping: only too little trusted power asks for a
    midpoint; a bad signature among the first third is the header's
    fault and names its index."""
    root = to_program(light_block(ROOT))
    bad = to_program(light_block(TARGET, flip=1))
    with pytest.raises(ErrInvalidHeader, match=r"#1\b") as exc:
        verify_non_adjacent(root, bad, CHAIN_ID, PERIOD,
                            now=time_of(TARGET) + SECOND)
    assert not isinstance(exc.value, ErrNewValSetCantBeTrusted)


def test_an_expired_trusted_header_is_refused():
    make = light_block
    late = time_of(ROOT) + PERIOD + 1
    ref = reference_step(make(ROOT), make(TARGET), now=late)
    assert ref == RL.Verdict(RL.EXPIRED)
    client, _ = client_over(make)
    with pytest.raises(ErrOldHeaderExpired):
        client.verify_light_block_at_height(TARGET, now=late)
    assert client.trusted_light_block(TARGET) is None


def test_the_list_entry_point_refuses_a_list_it_cannot_walk():
    client, _ = client_over(light_block)
    with pytest.raises(LightClientError, match="ascending"):
        client.verify_light_blocks_at_heights([201, 101])
    with pytest.raises(LightClientError, match="positive"):
        client.verify_light_blocks_at_heights([0, 101])
    with pytest.raises(LightClientError, match="positive"):
        client.verify_light_block_at_height(0)


# -- the verify-ahead against the one-at-a-time client ---------------------

TARGETS = [ROOT + 100 * k for k in range(1, 13)]
FLIPS = {301: 2, 501: 7, 701: 10, 1101: 0}
MISSING = 901  # the primary does not have it


def walk_chain(height: int) -> RL.LightBlock:
    return light_block(height, flip=FLIPS.get(height))


def answers(results) -> list:
    return [
        (h, None if lb is None else lb.hash(),
         None if err is None else (type(err).__name__, str(err)))
        for h, lb, err in results
    ]


def one_at_a_time() -> list:
    client, _ = client_over(walk_chain, set(TARGETS) - {MISSING} | {ROOT})
    out = []
    for h in TARGETS:
        try:
            out.append((h, client.verify_light_block_at_height(
                h, now=time_of(h) + SECOND
            ), None))
        except Exception as exc:  # noqa: BLE001 — the verdict
            out.append((h, None, exc))
    return answers(out)


@pytest.fixture(scope="module")
def expected():
    got = one_at_a_time()
    rejected = {h for h, lb, _ in got if lb is None}
    assert rejected == {301, 501, 1101, MISSING}
    return got


@pytest.fixture
def queue():
    q = vq.VerifyQueue(light_batch=16, light_wait_ms=2)
    q.start()
    vq.install_queue(q)
    yield q
    if q.is_running():
        q.stop()
    vq.install_queue(None)


def ahead(stop_after: int | None = None, q=None) -> tuple[list, Served]:
    client, primary = client_over(
        walk_chain, set(TARGETS) - {MISSING} | {ROOT}
    )
    out = []
    walk = client.verify_light_blocks_at_heights(
        TARGETS, now=lambda lb: lb.time_ns + SECOND
    )
    for k, result in enumerate(walk):
        out.append(result)
        if k + 1 == stop_after:
            q.stop()
    return answers(out), primary


def test_verify_ahead_answers_as_one_at_a_time(expected, queue):
    got, primary = ahead()
    assert got == expected
    # each target fetched once, ahead of its turn, and no midpoint
    assert primary.asked == TARGETS
    lane = queue.stats()
    assert lane["launched_sigs_by_lane"][vq.PRIORITY_LIGHT] == (
        lane["launched_sigs"]
    ) > 0
    # 11 headers x 9 signatures in submissions of 16, the last short
    assert lane["submitted"][vq.PRIORITY_LIGHT] == 99
    assert lane["launched_batches_by_lane"][vq.PRIORITY_LIGHT] == 7
    assert lane["launched_batches_by_lane"][vq.PRIORITY_PREFETCH] == 0


def test_verify_ahead_with_the_queue_stopped_mid_sync(expected, queue):
    got, primary = ahead(stop_after=3, q=queue)
    assert got == expected
    assert primary.asked == TARGETS


def test_verify_ahead_deeper_than_the_list(expected):
    """The default lane target (1,024 signatures) against 99: all of
    the list is one short submission, released by the lane's deadline."""
    q = vq.VerifyQueue()
    q.start()
    vq.install_queue(q)
    try:
        got, _ = ahead()
        stats = q.stats()
    finally:
        q.stop()
        vq.install_queue(None)
    assert got == expected
    assert stats["launched_batches_by_lane"][vq.PRIORITY_LIGHT] == 1
    assert stats["launched_sigs_by_lane"][vq.PRIORITY_LIGHT] == 99


def test_one_target_takes_no_look_ahead(queue):
    client, _ = client_over(walk_chain)
    client.verify_light_block_at_height(TARGET, now=time_of(TARGET) + SECOND)
    assert queue.stats()["submitted"][vq.PRIORITY_LIGHT] == 0

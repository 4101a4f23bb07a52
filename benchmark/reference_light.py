"""The plain reference of a light client's one step, independent of the
code under test.

Pure Python over ``hashlib`` and ``reference.py`` (ZIP-215, the
canonical precommit) — this file imports nothing of ``cometbft_tpu``,
nor JAX, and takes nothing the program computed:

- ``header_hash``: CometBFT's block hash (types/block.go ``Header.Hash``):
  the RFC 6962 merkle root over the header's fourteen fields, each in
  its published encoding (``cdcEncode``: the protobuf wrapper message of
  a scalar, nothing at all for an empty one; Consensus version, Timestamp
  and BlockID as their own messages).
- ``validator_set_hash``: types/validator_set.go ``Hash``: the merkle
  root over every validator's ``SimpleValidator{pub_key, voting_power}``.
- ``verify_light``: light/verifier.go ``Verify`` in upstream's order —
  the trusted header's expiry, the new header's basic validity (its
  hash is what the commit signs, its validator hash is the set's),
  then for a non-adjacent step the trusting tally BY ADDRESS over the
  trusted set to past ``trust_level`` of its power
  (``VerifyCommitLightTrusting``) and the new set's own tally BY INDEX to
  past two thirds (``VerifyCommitLight``); for an adjacent step the
  next-validators link and the own tally alone.

A header, a commit and a validator set are plain data here (the three
dataclasses below); the drivers and the tests build the program's types
and these from the same numbers.
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass, field
from fractions import Fraction

from benchmark import reference

FLAG_ABSENT, FLAG_COMMIT, FLAG_NIL = 1, 2, 3

ACCEPT = "accept"
#: the header or its commit is invalid; with a wrong signature,
#: ``Verdict.index`` is its index in the commit's order
INVALID = "invalid"
#: light/verifier.go ErrNewValSetCantBeTrusted: too little of the
#: trusted set's power signed — the one verdict a client bisects on
CANNOT_TRUST = "not enough trusted power"
EXPIRED = "trusted header expired"


# -- protobuf, as far as a header needs it ---------------------------------


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _varint_field(number: int, value: int) -> bytes:
    """proto3: a zero scalar is not emitted; a negative int64 is its
    64-bit two's complement."""
    if not value:
        return b""
    return bytes([number << 3]) + _uvarint(value & 0xFFFFFFFFFFFFFFFF)


def _bytes_field(number: int, value: bytes) -> bytes:
    if not value:
        return b""
    return bytes([(number << 3) | 2]) + _uvarint(len(value)) + value


def _message_field(number: int, body: bytes) -> bytes:
    """An embedded message that gogoproto marks non-nullable is
    written even when empty."""
    return bytes([(number << 3) | 2]) + _uvarint(len(body)) + body


# -- RFC 6962 merkle tree (crypto/merkle/tree.go) --------------------------


def merkle_root(leaves: list[bytes]) -> bytes:
    if not leaves:
        return hashlib.sha256(b"").digest()
    if len(leaves) == 1:
        return hashlib.sha256(b"\x00" + leaves[0]).digest()
    split = 1
    while split * 2 < len(leaves):
        split *= 2
    return hashlib.sha256(
        b"\x01" + merkle_root(leaves[:split]) + merkle_root(leaves[split:])
    ).digest()


# -- the data ---------------------------------------------------------------


@dataclass(frozen=True)
class Header:
    """types/block.go Header, field for field; times in unix-epoch
    nanoseconds."""

    chain_id: str
    height: int
    time_ns: int
    last_block_hash: bytes
    last_parts_total: int
    last_parts_hash: bytes
    last_commit_hash: bytes
    data_hash: bytes
    validators_hash: bytes
    next_validators_hash: bytes
    consensus_hash: bytes
    app_hash: bytes
    last_results_hash: bytes
    evidence_hash: bytes
    proposer_address: bytes
    version_block: int = 11
    version_app: int = 0


@dataclass(frozen=True)
class CommitSig:
    flag: int
    address: bytes
    timestamp_ns: int
    signature: bytes


@dataclass(frozen=True)
class Commit:
    height: int
    round: int
    block_hash: bytes
    parts_total: int
    parts_hash: bytes
    sigs: tuple[CommitSig, ...]


@dataclass(frozen=True)
class ValidatorSet:
    """Ed25519 keys and voting powers, in the set's canonical order."""

    pubs: tuple[bytes, ...]
    powers: tuple[int, ...]

    @property
    def total_power(self) -> int:
        return sum(self.powers)


@dataclass(frozen=True)
class LightBlock:
    header: Header
    commit: Commit
    vals: ValidatorSet


@dataclass(frozen=True)
class Verdict:
    verdict: str
    #: the first bad signature's index in the commit, where one was found
    index: int | None = None
    why: str = field(default="", compare=False)


# -- hashes -----------------------------------------------------------------


def address(pub: bytes) -> bytes:
    """crypto/ed25519 Address: the first 20 bytes of SHA-256(key)."""
    return hashlib.sha256(pub).digest()[:20]


def _timestamp(ns: int) -> bytes:
    return _varint_field(1, ns // 1_000_000_000) + _varint_field(
        2, ns % 1_000_000_000
    )


def _block_id(block_hash: bytes, parts_total: int, parts_hash: bytes) -> bytes:
    parts = _varint_field(1, parts_total) + _bytes_field(2, parts_hash)
    return _bytes_field(1, block_hash) + _message_field(2, parts)


def header_hash(h: Header) -> bytes:
    """types/block.go Header.Hash."""
    version = _varint_field(1, h.version_block) + _varint_field(
        2, h.version_app
    )
    return merkle_root([
        version,
        _bytes_field(1, h.chain_id.encode("utf-8")),
        _varint_field(1, h.height),
        _timestamp(h.time_ns),
        _block_id(h.last_block_hash, h.last_parts_total, h.last_parts_hash),
        _bytes_field(1, h.last_commit_hash),
        _bytes_field(1, h.data_hash),
        _bytes_field(1, h.validators_hash),
        _bytes_field(1, h.next_validators_hash),
        _bytes_field(1, h.consensus_hash),
        _bytes_field(1, h.app_hash),
        _bytes_field(1, h.last_results_hash),
        _bytes_field(1, h.evidence_hash),
        _bytes_field(1, h.proposer_address),
    ])


def validator_set_hash(vals: ValidatorSet) -> bytes:
    """types/validator_set.go Hash over SimpleValidator{pub_key:
    PublicKey{ed25519 = 1}, voting_power = 2}."""
    return merkle_root([
        _message_field(1, _bytes_field(1, pub)) + _varint_field(2, power)
        for pub, power in zip(vals.pubs, vals.powers)
    ])


# -- the commit checks ------------------------------------------------------


def _sign_bytes(chain_id: str, commit: Commit, sig: CommitSig) -> bytes:
    commits = sig.flag == FLAG_COMMIT
    return reference.vote_sign_bytes(
        chain_id, commit.height, commit.round,
        commit.block_hash if commits else b"",
        commit.parts_total if commits else 0,
        commit.parts_hash if commits else b"",
        sig.timestamp_ns,
    )


def _tally(chain_id: str, commit: Commit, vals: ValidatorSet, needed: int,
           by_address: bool) -> Verdict | None:
    """The commit's COMMIT-flag votes in order, each verified and then
    tallied, to the first that takes the tally past ``needed``.
    -> None once past it, else why not.  By address, a vote by a
    validator not in ``vals`` is passed over; by index, the commit must
    line up with ``vals`` entry for entry."""
    if not by_address and len(commit.sigs) != len(vals.pubs):
        return Verdict(INVALID, why="commit size is not the set's")
    where = {address(p): i for i, p in enumerate(vals.pubs)}
    seen: set[int] = set()
    tallied = 0
    for idx, sig in enumerate(commit.sigs):
        if sig.flag != FLAG_COMMIT:
            continue
        if by_address:
            v = where.get(sig.address)
            if v is None:
                continue
            if v in seen:
                return Verdict(INVALID, why=f"double vote at #{idx}")
            seen.add(v)
        else:
            v = idx
            if address(vals.pubs[v]) != sig.address:
                return Verdict(INVALID, why=f"address mismatch at #{idx}")
        if not reference.verify_zip215(
            vals.pubs[v], _sign_bytes(chain_id, commit, sig), sig.signature
        ):
            return Verdict(INVALID, idx, f"wrong signature (#{idx})")
        tallied += vals.powers[v]
        if tallied > needed:
            return None
    return Verdict(CANNOT_TRUST, why=f"tallied {tallied} <= {needed}")


def _basic(chain_id: str, lb: LightBlock) -> str | None:
    """types/light.go LightBlock.ValidateBasic, as far as a verdict can
    turn on it."""
    h, c = lb.header, lb.commit
    if h.chain_id != chain_id:
        return "another chain's header"
    if h.height <= 0 or c.height != h.height:
        return "commit and header heights differ"
    if c.block_hash != header_hash(h):
        return "commit signs a different header"
    if not lb.vals.pubs or h.validators_hash != validator_set_hash(lb.vals):
        return "validator set is not the header's"
    return None


def verify_light(
    chain_id: str,
    trusted: LightBlock,
    untrusted: LightBlock,
    trusting_period_ns: int,
    now_ns: int,
    trust_level: Fraction = Fraction(1, 3),
    max_clock_drift_ns: int = 10 * 10**9,
) -> Verdict:
    """light/verifier.go Verify: one step from a trusted light block to
    a later one."""
    th, uh = trusted.header, untrusted.header
    if now_ns > th.time_ns + trusting_period_ns:
        return Verdict(EXPIRED)
    why = _basic(chain_id, untrusted)
    if why is None and uh.height <= th.height:
        why = "height not above the trusted header's"
    if why is None and uh.time_ns <= th.time_ns:
        why = "time not after the trusted header's"
    if why is None and uh.time_ns >= now_ns + max_clock_drift_ns:
        why = "header from the future"
    if why is not None:
        return Verdict(INVALID, why=why)
    if uh.height == th.height + 1:
        if uh.validators_hash != th.next_validators_hash:
            return Verdict(INVALID, why="not the trusted next validators")
    else:
        needed = (
            trusted.vals.total_power * trust_level.numerator
            // trust_level.denominator
        )
        bad = _tally(chain_id, untrusted.commit, trusted.vals, needed,
                     by_address=True)
        if bad is not None:
            return bad
    bad = _tally(chain_id, untrusted.commit, untrusted.vals,
                 untrusted.vals.total_power * 2 // 3, by_address=False)
    if bad is not None:
        # the own set signing too little is an invalid commit, not a
        # reason to bisect
        return Verdict(INVALID, bad.index, bad.why)
    return Verdict(ACCEPT)

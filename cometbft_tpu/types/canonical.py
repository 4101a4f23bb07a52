"""Canonical sign-bytes — the byte-deterministic encodings validators sign.

Mirrors the semantics of the reference's canonicalization
(types/canonical.go:57 CanonicalizeVote, types/vote.go:151
VoteSignBytes): length-delimited protobuf with fixed-width height/round
(sfixed64) so encodings are unambiguous and identically sized across
implementations. The signed payload deliberately excludes validator
address/index (signatures must be position-independent) and includes
chain_id for cross-chain replay protection.

These bytes are exactly what the TPU kernel hashes in-device, so this
module is consensus-critical: any nondeterminism here is a fork.
"""

from __future__ import annotations

from cometbft_tpu.utils.protoio import (
    ProtoWriter,
    encode_uvarint,
    length_prefixed,
)

# SignedMsgType (types/signed_msg_type.go)
PREVOTE_TYPE = 1
PRECOMMIT_TYPE = 2
PROPOSAL_TYPE = 32


def encode_timestamp(ns: int) -> bytes:
    """google.protobuf.Timestamp: seconds(1) + nanos(2), from unix-epoch
    nanoseconds."""
    w = ProtoWriter()
    w.varint(1, (ns // 1_000_000_000) & 0xFFFFFFFFFFFFFFFF)
    w.varint(2, ns % 1_000_000_000)
    return w.finish()


def encode_canonical_part_set_header(total: int, hash_: bytes) -> bytes:
    w = ProtoWriter()
    w.varint(1, total)
    w.bytes_(2, hash_)
    return w.finish()


def encode_canonical_block_id(block_id) -> bytes | None:
    """CanonicalBlockID; None for nil block ids (field omitted)."""
    if block_id is None or block_id.is_nil():
        return None
    w = ProtoWriter()
    w.bytes_(1, block_id.hash)
    w.message(
        2,
        encode_canonical_part_set_header(
            block_id.part_set_header.total, block_id.part_set_header.hash
        ),
    )
    return w.finish()


def vote_sign_bytes(
    chain_id: str,
    msg_type: int,
    height: int,
    round_: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    """CanonicalVote marshal, length-prefixed (types/vote.go:151)."""
    w = ProtoWriter()
    w.varint(1, msg_type)
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    w.message(4, encode_canonical_block_id(block_id))
    w.message(5, encode_timestamp(timestamp_ns))
    w.string(6, chain_id)
    return length_prefixed(w.finish())


#: :class:`VoteTemplate` encodes a timestamp itself where
#: ``0 <= timestamp_ns < _NS_END`` (a non-negative int64); any other (a
#: negative time) takes :func:`vote_sign_bytes`, the one definition.
_NS_END = 1 << 63


class VoteTemplate:
    """:func:`vote_sign_bytes` for votes that differ in their timestamp
    alone — a commit's precommits: one chain id, type, height and
    round, and a block id that is the commit's (a COMMIT-flag vote) or
    nil.  Fields 1-4 and 6 are encoded once, by the writer
    :func:`vote_sign_bytes` uses; a vote builds only its Timestamp (5)
    and picks the head that carries its outer length.  The bytes are
    :func:`vote_sign_bytes`'s (tests hold them equal).  Its two memos
    (seconds fields, heads) take no lock: threads that race on an
    entry write equal bytes."""

    __slots__ = ("_args", "_fields", "_heads", "_suffix", "_seconds")

    def __init__(self, chain_id: str, msg_type: int, height: int,
                 round_: int, block_id) -> None:
        self._args = (chain_id, msg_type, height, round_, block_id)
        tail = ProtoWriter()
        tail.string(6, chain_id)
        self._suffix = tail.finish()
        w = ProtoWriter()
        w.varint(1, msg_type)
        w.sfixed64(2, height)
        w.sfixed64(3, round_)
        nil = w.finish()
        w.message(4, encode_canonical_block_id(block_id))
        self._fields = (nil, w.finish())
        # _heads[for_block][n]: the outer length, fields 1-4 and the
        # key and length of a Timestamp body of n bytes, built at its
        # first use (a commit's votes use two or three lengths)
        self._heads: tuple[dict, dict] = ({}, {})
        # a seconds field a vote shares with most of its commit
        self._seconds: dict[int, bytes] = {}

    def _head(self, for_block: bool, n: int) -> bytes:
        fields = self._fields[for_block]
        head = self._heads[for_block][n] = (
            encode_uvarint(len(fields) + 2 + n + len(self._suffix))
            + fields + bytes((0x2A, n))
        )
        return head

    def sign_bytes(self, for_block: bool, timestamp_ns: int) -> bytes:
        """The sign-bytes of the vote at ``timestamp_ns`` for the
        template's block id (``for_block``) or for nil."""
        return self.sign_bytes_many(((for_block, timestamp_ns),))[0][0]

    def sign_bytes_many(self, votes) -> tuple[list[bytes], int]:
        """:meth:`sign_bytes` of each ``(for_block, timestamp_ns)`` of
        ``votes``, in order, and how many of them the fast path did not
        cover (took :func:`vote_sign_bytes`)."""
        out = []
        generic = 0
        for for_block, timestamp_ns in votes:
            sb = self._encode(for_block, timestamp_ns)
            if sb is None:
                generic += 1
                chain_id, msg_type, height, round_, block_id = self._args
                sb = vote_sign_bytes(
                    chain_id, msg_type, height, round_,
                    block_id if for_block else None, timestamp_ns,
                )
            out.append(sb)
        return out, generic

    def _encode(self, for_block: bool, timestamp_ns: int) -> bytes | None:
        """The fast path, called once for every vote either method
        above encodes: None where ``timestamp_ns`` is outside it."""
        if not 0 <= timestamp_ns < _NS_END:
            return None
        seconds, nanos = divmod(timestamp_ns, 1_000_000_000)
        ts = self._seconds.get(seconds)
        if ts is None:
            ts = self._seconds[seconds] = (
                b"\x08" + encode_uvarint(seconds) if seconds else b""
            )
        if nanos:
            ts += b"\x10" + encode_uvarint(nanos)
        n = len(ts)
        head = self._heads[for_block].get(n) or self._head(for_block, n)
        return b"".join((head, ts, self._suffix))


def proposal_sign_bytes(
    chain_id: str,
    height: int,
    round_: int,
    pol_round: int,
    block_id,
    timestamp_ns: int,
) -> bytes:
    """CanonicalProposal marshal, length-prefixed (types/proposal.go)."""
    w = ProtoWriter()
    w.varint(1, PROPOSAL_TYPE)
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    # pol_round is -1 when absent; encode via two's complement varint
    w.varint(4, pol_round & 0xFFFFFFFFFFFFFFFF)
    w.message(5, encode_canonical_block_id(block_id))
    w.message(6, encode_timestamp(timestamp_ns))
    w.string(7, chain_id)
    return length_prefixed(w.finish())


def vote_extension_sign_bytes(
    chain_id: str, height: int, round_: int, extension: bytes
) -> bytes:
    """CanonicalVoteExtension (types/vote.go VoteExtensionSignBytes)."""
    w = ProtoWriter()
    w.bytes_(1, extension)
    w.sfixed64(2, height)
    w.sfixed64(3, round_)
    w.string(4, chain_id)
    return length_prefixed(w.finish())

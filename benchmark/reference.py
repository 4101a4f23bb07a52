"""The plain reference: what a commit's signatures mean, independent of
the code under test.

Pure Python over ``hashlib`` alone — this file imports nothing of
``cometbft_tpu`` and takes nothing the program computed:

- ``verify_zip215``: Ed25519 verification with ZIP-215 semantics (the
  cofactored equation, non-canonical point encodings accepted, S < L
  required).  A copy of ``cometbft_tpu/crypto/edwards.py``'s oracle
  (PR 22 ran it on the chip's host), kept here so that no later PR to
  the program can move the yardstick.
- ``vote_sign_bytes``: the canonical precommit a validator signs
  (CometBFT types/canonical.go CanonicalVote, length-prefixed
  protobuf), written out field by field.  The generator signs THESE
  bytes; the program derives its own from the Commit it is handed, so a
  program whose canonical encoding drifts rejects valid commits and
  ``correct`` reads false.
- ``first_bad``: the verdict the reference gives a commit — the index
  of the first signature, among those the call is bound to look at,
  that does not verify, or None.
"""

from __future__ import annotations

import hashlib

# Field and group parameters (RFC 8032 §5.1).
P = 2**255 - 19
L = 2**252 + 27742317777372353535851937790883648493
D = (-121665 * pow(121666, P - 2, P)) % P
SQRT_M1 = pow(2, (P - 1) // 4, P)  # sqrt(-1) mod p

# Base point B (RFC 8032): y = 4/5, x recovered with even... positive sign.
_BY = (4 * pow(5, P - 2, P)) % P


def _recover_x(y: int, sign: int) -> int | None:
    """x with x^2 = (y^2-1)/(d*y^2+1), lsb matching ``sign``; None if the
    quotient is not a square. Accepts x=0 with sign=1 (ZIP-215 "-0")."""
    u = (y * y - 1) % P
    v = (D * y * y + 1) % P
    # candidate root of u/v: x = u*v^3 * (u*v^7)^((p-5)/8)
    x = (u * pow(v, 3, P) * pow(u * pow(v, 7, P), (P - 5) // 8, P)) % P
    vxx = (v * x * x) % P
    if vxx == u % P:
        pass
    elif vxx == (-u) % P:
        x = (x * SQRT_M1) % P
    else:
        return None
    if x & 1 != sign:
        x = (P - x) % P
    return x


_BX = _recover_x(_BY, 0)
assert _BX is not None

# Extended coordinates point: (X, Y, Z, T) with x=X/Z, y=Y/Z, T=XY/Z.
Point = tuple[int, int, int, int]

IDENTITY: Point = (0, 1, 1, 0)
B_POINT: Point = (_BX, _BY, 1, (_BX * _BY) % P)


def pt_add(p: Point, q: Point) -> Point:
    """Unified addition, add-2008-hwcd-3 (complete for a=-1, k=2d)."""
    x1, y1, z1, t1 = p
    x2, y2, z2, t2 = q
    a = ((y1 - x1) * (y2 - x2)) % P
    b = ((y1 + x1) * (y2 + x2)) % P
    c = (2 * t1 * D % P) * t2 % P
    dd = 2 * z1 * z2 % P
    e, f, g, h = (b - a) % P, (dd - c) % P, (dd + c) % P, (b + a) % P
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def pt_double(p: Point) -> Point:
    """Doubling, dbl-2008-hwcd."""
    x1, y1, z1, _ = p
    a = x1 * x1 % P
    b = y1 * y1 % P
    c = 2 * z1 * z1 % P
    h = (a + b) % P
    e = (h - (x1 + y1) * (x1 + y1)) % P
    g = (a - b) % P
    f = (c + g) % P
    return (e * f % P, g * h % P, f * g % P, e * h % P)


def pt_neg(p: Point) -> Point:
    x, y, z, t = p
    return ((P - x) % P, y, z, (P - t) % P)


def pt_mul(k: int, p: Point) -> Point:
    """Scalar multiplication (double-and-add, MSB first)."""
    q = IDENTITY
    for i in reversed(range(k.bit_length())):
        q = pt_double(q)
        if (k >> i) & 1:
            q = pt_add(q, p)
    return q


def pt_is_identity(p: Point) -> bool:
    x, y, z, _ = p
    return x % P == 0 and (y - z) % P == 0


def decode_point(s: bytes) -> Point | None:
    """ZIP-215 decoding: non-canonical y accepted (reduced mod p)."""
    if len(s) != 32:
        return None
    enc = int.from_bytes(s, "little")
    sign = enc >> 255
    y = (enc & ((1 << 255) - 1)) % P
    x = _recover_x(y, sign)
    if x is None:
        return None
    return (x, y, 1, (x * y) % P)


def verify_zip215(pub: bytes, msg: bytes, sig: bytes) -> bool:
    """The oracle verifier: ZIP-215 semantics, cofactored equation."""
    if len(sig) != 64 or len(pub) != 32:
        return False
    a_pt = decode_point(pub)
    r_pt = decode_point(sig[:32])
    if a_pt is None or r_pt is None:
        return False
    s = int.from_bytes(sig[32:], "little")
    if s >= L:
        return False
    k = int.from_bytes(hashlib.sha512(sig[:32] + pub + msg).digest(), "little") % L
    # [8]([S]B - R - [k]A) == identity
    q = pt_add(pt_mul(s, B_POINT), pt_neg(pt_add(r_pt, pt_mul(k, a_pt))))
    for _ in range(3):
        q = pt_double(q)
    return pt_is_identity(q)


# -- canonical sign-bytes --------------------------------------------------

PRECOMMIT_TYPE = 2


def _uvarint(n: int) -> bytes:
    out = bytearray()
    while n >= 0x80:
        out.append((n & 0x7F) | 0x80)
        n >>= 7
    out.append(n)
    return bytes(out)


def _field_bytes(field: int, value: bytes) -> bytes:
    return bytes([(field << 3) | 2]) + _uvarint(len(value)) + value


def _field_varint(field: int, value: int) -> bytes:
    # proto3: a zero scalar is not emitted
    return bytes([field << 3]) + _uvarint(value) if value else b""


def _field_sfixed64(field: int, value: int) -> bytes:
    if not value:
        return b""
    return bytes([(field << 3) | 1]) + value.to_bytes(8, "little", signed=True)


def vote_sign_bytes(
    chain_id: str, height: int, round_: int, block_hash: bytes,
    parts_total: int, parts_hash: bytes, timestamp_ns: int,
) -> bytes:
    """CanonicalVote{type=PRECOMMIT, height, round (sfixed64), block_id,
    timestamp, chain_id}, length-prefixed."""
    parts = _field_varint(1, parts_total) + _field_bytes(2, parts_hash)
    block_id = _field_bytes(1, block_hash) + _field_bytes(2, parts)
    stamp = _field_varint(1, timestamp_ns // 1_000_000_000) + _field_varint(
        2, timestamp_ns % 1_000_000_000
    )
    body = (
        _field_varint(1, PRECOMMIT_TYPE)
        + _field_sfixed64(2, height)
        + _field_sfixed64(3, round_)
        + _field_bytes(4, block_id)
        + _field_bytes(5, stamp)
        + _field_bytes(6, chain_id.encode("utf-8"))
    )
    return _uvarint(len(body)) + body


def first_bad(pubs, msgs, sigs, upto: int) -> int | None:
    """Index of the first of the leading ``upto`` signatures the
    reference rejects, or None when all of them verify."""
    for i in range(upto):
        if not verify_zip215(pubs[i], msgs[i], sigs[i]):
            return i
    return None

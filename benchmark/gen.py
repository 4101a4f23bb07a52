"""The one general generator: a chain of signed commits from ``--seed``.

A traffic file (``traffic/<cell>.json``) gives the parameters — how many
commits, the height stride, how often and where a signature is tampered;
a configuration file gives the validator count.  Everything else follows
from the seed: the validators' keys, the block hashes, the positions of
the tampered commits and the index of the flipped signature in each.
Every seed gets the same amount of work (same counts, same strata), in
another order.

Signing uses the ``cryptography`` package (OpenSSL) over the reference's
own sign-bytes, never the program's signer or encoder; ``sign_items`` is
the worker function of the signing pool and imports neither JAX nor
``cometbft_tpu``.  Only ``validator_set`` / ``commit_of`` touch the
program, to hand it its inputs in its own types.
"""

from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass, field

from benchmark import reference

CHAIN_ID = "bench-chain"
TS0 = 1_700_000_000_000_000_000
POWER = 10
#: a vote's timestamp: one millisecond a height, one nanosecond a validator
HEIGHT_NS = 1_000_000


@dataclass
class Item:
    """One commit of the chain, as plain data."""

    height: int
    block_hash: bytes
    parts_hash: bytes
    bad: tuple[int, ...] = ()
    sigs: list[bytes] = field(default_factory=list)


@dataclass
class Chain:
    """Validators (canonical order: by address, powers being equal) and
    the items to verify.  ``warm`` items are signed like the rest (the
    last of them tampered); the window never sees them."""

    seed: int
    key_seeds: list[bytes]
    pubs: list[bytes]
    warm: list[Item]
    items: list[Item]
    sign_bytes_total: int = 0

    @property
    def n_vals(self) -> int:
        return len(self.pubs)

    @property
    def sign_bytes_mean(self) -> float:
        """Mean length of one vote's sign-bytes, once signed."""
        return self.sign_bytes_total / (
            self.n_vals * (len(self.items) + len(self.warm))
        )


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def vote_time(height: int, index: int) -> int:
    return TS0 + height * HEIGHT_NS + index


def sign_bytes(item: Item, index: int) -> bytes:
    return reference.vote_sign_bytes(
        CHAIN_ID, item.height, 0, item.block_hash, 1, item.parts_hash,
        vote_time(item.height, index),
    )


def tamper(sig: bytes) -> bytes:
    return sig[:5] + bytes([sig[5] ^ 0x04]) + sig[6:]


def _keys(seed: int, n: int) -> tuple[list[bytes], list[bytes]]:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    pairs = []
    for i in range(n):
        ks = hashlib.sha256(b"benchmark/key/%d/%d/%d" % (seed, n, i)).digest()
        pub = Ed25519PrivateKey.from_private_bytes(ks).public_key()
        pairs.append((ks, pub.public_bytes_raw()))
    pairs.sort(key=lambda p: address(p[1]))
    return [p[0] for p in pairs], [p[1] for p in pairs]


def tamper_schedule(
    rng: random.Random, n_items: int, every: int, strata: list[list[int]],
    first_group: list[int] | None,
) -> dict[int, tuple[int, ...]]:
    """One tampered commit in each run of ``every`` items, at a seeded
    offset (the first within ``first_group`` so that every window sees
    one); the flipped signature's index is drawn from the strata in
    turn, so a window with a few tampered commits covers each range."""
    bad: dict[int, tuple[int, ...]] = {}
    for k, start in enumerate(range(0, n_items, every)):
        lo, hi = (first_group if k == 0 and first_group else (0, every))
        pos = start + rng.randrange(lo, min(hi, every))
        if pos >= n_items:
            continue
        s_lo, s_hi = strata[k % len(strata)]
        bad[pos] = (rng.randrange(s_lo, s_hi),)
    return bad


def plan(seed: int, n_vals: int, n_items: int, n_warm: int, stride: int,
         tamper_every: int, strata: list[list[int]],
         first_group: list[int] | None = None) -> Chain:
    """The chain before signing."""
    rng = random.Random(seed)
    key_seeds, pubs = _keys(seed, n_vals)
    bad = tamper_schedule(rng, n_items, tamper_every, strata, first_group)
    base = 1_000_000 + seed % 1_000_000

    def item(j: int, bad_at=()) -> Item:
        height = base + j * stride
        h = hashlib.sha256(b"%s/%d/%d" % (CHAIN_ID.encode(), seed, height))
        return Item(height, h.digest(), h.digest()[::-1], tuple(bad_at))

    # the last warm-up commit is tampered too, so that the rejection
    # path has run once before the window
    warm_bad = {n_warm - 1: (rng.randrange(*strata[0]),)} if n_warm > 1 else {}
    return Chain(
        seed=seed, key_seeds=key_seeds, pubs=pubs,
        warm=[item(j, warm_bad.get(j, ())) for j in range(n_warm)],
        items=[item(n_warm + j, bad.get(j, ())) for j in range(n_items)],
    )


def sign_items(job) -> tuple[list[list[bytes]], int]:
    """Pool worker: every validator's signature over each item's
    canonical precommit, the ``bad`` ones with one bit flipped.
    -> (signatures per item, total sign-bytes length)."""
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    key_seeds, items = job
    keys = [Ed25519PrivateKey.from_private_bytes(ks) for ks in key_seeds]
    out, total = [], 0
    for it in items:
        sigs = []
        for i, key in enumerate(keys):
            msg = sign_bytes(it, i)
            total += len(msg)
            sig = key.sign(msg)
            sigs.append(tamper(sig) if i in it.bad else sig)
        out.append(sigs)
    return out, total


def sign_jobs(chain: Chain, n_jobs: int) -> list:
    """The chain's items (warm first) cut into ``n_jobs`` pool jobs."""
    todo = chain.warm + chain.items
    size = -(-len(todo) // max(1, n_jobs))
    return [
        (chain.key_seeds, todo[k:k + size])
        for k in range(0, len(todo), size)
    ]


def attach(chain: Chain, results: list) -> None:
    """Put the pool's signatures (in job order) onto the items."""
    todo = chain.warm + chain.items
    k = 0
    for sigs_list, total in results:
        chain.sign_bytes_total += total
        for sigs in sigs_list:
            todo[k].sigs = sigs
            k += 1
    if k != len(todo):
        raise RuntimeError(f"signed {k} of {len(todo)} commits")


# -- the program's own types, for its inputs -------------------------------


def validator_set(chain: Chain):
    from cometbft_tpu.crypto.ed25519 import Ed25519PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    vals = ValidatorSet(
        [Validator(Ed25519PubKey(p), POWER) for p in chain.pubs]
    )
    got = [v.pub_key.bytes() for v in vals.validators]
    if got != chain.pubs:
        raise RuntimeError(
            "the program orders the validator set otherwise than by "
            "address; the generator's indices would not be the commit's"
        )
    return vals


def commit_of(chain: Chain, item: Item):
    """-> (BlockID, Commit) in the program's types."""
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )

    bid = BlockID(
        hash=item.block_hash,
        part_set_header=PartSetHeader(total=1, hash=item.parts_hash),
    )
    sigs = tuple(
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=address(pub),
            timestamp_ns=vote_time(item.height, i),
            signature=sig,
        )
        for i, (pub, sig) in enumerate(zip(chain.pubs, item.sigs))
    )
    return bid, Commit(height=item.height, round=0, block_id=bid,
                       signatures=sigs)

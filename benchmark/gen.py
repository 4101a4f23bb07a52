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

What an item may be (PR 35; each a default that leaves a chain of plain
commits byte for byte what it was): it carries the total of its block's
part set (``parts_total``: a real block of two parts or more is signed
over that id) and names the signer set of its height (``epoch``, an index
into ``Chain.epochs``).  Whoever needs an item's keys asks ``signers``.
A driver whose blocks depend on the signatures before them signs in
order itself (``run.py``: ``build``), with ``sign_item``.
"""

from __future__ import annotations

import hashlib
import random
from dataclasses import dataclass, field, replace

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
    #: parts of the block's part set, as its id and every vote name it
    parts_total: int = 1
    #: which of ``Chain.epochs`` signs this height
    epoch: int = 0


@dataclass
class Chain:
    """Validators (canonical order: by address, powers being equal) and
    the items to verify.  ``warm`` items are signed like the rest (the
    last of them tampered); the window never sees them.  ``key_seeds``
    and ``pubs`` are epoch 0's; a chain whose set changes lists every
    set, in ``epochs``, as ``(key_seeds, pubs)``."""

    seed: int
    key_seeds: list[bytes]
    pubs: list[bytes]
    warm: list[Item]
    items: list[Item]
    sign_bytes_total: int = 0
    epochs: list[tuple[list[bytes], list[bytes]]] = field(
        default_factory=list
    )

    def __post_init__(self) -> None:
        if not self.epochs:
            self.epochs = [(self.key_seeds, self.pubs)]

    @property
    def n_vals(self) -> int:
        return len(self.pubs)

    @property
    def sign_bytes_mean(self) -> float:
        """Mean length of one vote's sign-bytes, once signed."""
        return self.sign_bytes_total / sum(
            len(it.sigs) for it in self.warm + self.items
        )


def address(pub: bytes) -> bytes:
    return hashlib.sha256(pub).digest()[:20]


def vote_time(height: int, index: int) -> int:
    return TS0 + height * HEIGHT_NS + index


def signers(chain: Chain, item: Item) -> tuple[list[bytes], list[bytes]]:
    """-> (key seeds, public keys) of the set that signs ``item``, in
    the commit's order."""
    return chain.epochs[item.epoch]


def sign_bytes(item: Item, index: int) -> bytes:
    return reference.vote_sign_bytes(
        CHAIN_ID, item.height, 0, item.block_hash, item.parts_total,
        item.parts_hash, vote_time(item.height, index),
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


def private_keys(key_seeds: list[bytes]) -> list:
    from cryptography.hazmat.primitives.asymmetric.ed25519 import (
        Ed25519PrivateKey,
    )

    return [Ed25519PrivateKey.from_private_bytes(ks) for ks in key_seeds]


def sign_item(keys: list, item: Item) -> tuple[list[bytes], int]:
    """Every key's signature over ``item``'s canonical precommit, the
    ``bad`` ones with one bit flipped.  -> (signatures, total
    sign-bytes length)."""
    sigs, total = [], 0
    for i, key in enumerate(keys):
        msg = sign_bytes(item, i)
        total += len(msg)
        sig = key.sign(msg)
        sigs.append(tamper(sig) if i in item.bad else sig)
    return sigs, total


def sign_items(job) -> tuple[list[list[bytes]], int]:
    """Pool worker: ``sign_item`` over a job's items, each by the set
    of its epoch.  -> (signatures per item, total sign-bytes length)."""
    chain, items = job
    keys: dict[int, list] = {}
    out, total = [], 0
    for it in items:
        if it.epoch not in keys:
            keys[it.epoch] = private_keys(signers(chain, it)[0])
        sigs, n = sign_item(keys[it.epoch], it)
        out.append(sigs)
        total += n
    return out, total


def sign_jobs(chain: Chain, n_jobs: int) -> list:
    """The chain's items (warm first) cut into ``n_jobs`` pool jobs,
    each with the chain's sets and none of its other items."""
    todo = chain.warm + chain.items
    size = -(-len(todo) // max(1, n_jobs))
    sets = replace(chain, warm=[], items=[])
    return [(sets, todo[k:k + size]) for k in range(0, len(todo), size)]


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


def validator_set(chain: Chain, epoch: int = 0):
    from cometbft_tpu.crypto.ed25519 import Ed25519PubKey
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    pubs = chain.epochs[epoch][1]
    vals = ValidatorSet([Validator(Ed25519PubKey(p), POWER) for p in pubs])
    got = [v.pub_key.bytes() for v in vals.validators]
    if got != pubs:
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
        part_set_header=PartSetHeader(total=item.parts_total,
                                      hash=item.parts_hash),
    )
    sigs = tuple(
        CommitSig(
            block_id_flag=BLOCK_ID_FLAG_COMMIT,
            validator_address=address(pub),
            timestamp_ns=vote_time(item.height, i),
            signature=sig,
        )
        for i, (pub, sig) in enumerate(zip(signers(chain, item)[1],
                                           item.sigs))
    )
    return bid, Commit(height=item.height, round=0, block_id=bid,
                       signatures=sigs)

"""Trusted light-block store (reference: light/store/db/db.go).

Two answers come from memory, as upstream has them: the newest
trusted block (light/client.go keeps ``latestTrustedBlock`` and
verifies forward from it) and the number of blocks
(light/store/db/db.go keeps ``size`` as a field under its mutex).
Both are write-through: every ``save`` puts the encoded block in the
DB before it returns, and the kept block and the count change only
beside that write, a ``delete`` or a ``prune``.
"""

from __future__ import annotations

from itertools import islice

from cometbft_tpu.types.light_block import LightBlock
from cometbft_tpu.utils.db import DB
from cometbft_tpu.utils import sync as cmtsync

_PREFIX = b"lb/"
#: above every height a chain reaches: ``latest`` is "the block before it"
_TOP = 1 << 62


def _key(height: int) -> bytes:
    return _PREFIX + height.to_bytes(8, "big")


class LightStore:
    """(light/store/store.go Store iface, db implementation)

    One ``LightStore`` owns its DB's ``lb/`` prefix, as upstream's
    ``dbs`` does: what it keeps in memory is derived from the writes
    it saw itself, so a second writer to the same prefix would leave
    it stale.  Opened over a DB that already holds blocks, it loads
    the newest on the first read that wants it and counts the blocks
    on the first ``size`` or ``prune``: one decode and one walk a
    store's life (more only after the kept block was deleted).

    The block ``latest`` and ``light_block_before`` return may be the
    very object that was saved, shared with every other reader: it
    must not be mutated.  (``LightBlock``, ``SignedHeader``,
    ``Header``, ``Commit`` and ``Validator`` are frozen, and a
    ``ValidatorSet``'s updates return copies.)"""

    def __init__(self, db: DB):
        self.db = db
        self._mtx = cmtsync.Mutex()
        #: the block of the highest stored height, valid while
        #: ``_newest_known`` (None then: the store is empty)
        self._newest: LightBlock | None = None
        self._newest_known = False
        #: blocks under the prefix; None until the one walk
        self._size: int | None = None
        self._anchor_memory = 0
        self._anchor_decoded = 0
        self._size_walks = 0

    def save(self, lb: LightBlock) -> None:
        key = _key(lb.height)
        with self._mtx:
            if self._size is not None and not self.db.has(key):
                self._size += 1
            self.db.set(key, lb.encode())
            if self._newest_known and (
                self._newest is None or lb.height >= self._newest.height
            ):
                self._newest = lb

    def get(self, height: int) -> LightBlock | None:
        raw = self.db.get(_key(height))
        return LightBlock.decode(bytes(raw)) if raw is not None else None

    def latest(self) -> LightBlock | None:
        """(db.go LastLightBlockHeight; client.go latestTrustedBlock)"""
        with self._mtx:
            return self._anchor(_TOP)

    def first(self) -> LightBlock | None:
        with self._mtx:
            for _, raw in self.db.prefix_iterator(_PREFIX):
                return LightBlock.decode(bytes(raw))
        return None

    def light_block_before(self, height: int) -> LightBlock | None:
        """Largest stored height strictly below ``height`` (db.go
        LightBlockBefore): the kept block when ``height`` is above
        it, else one reverse-range step and a decode."""
        with self._mtx:
            return self._anchor(height)

    def _anchor(self, below: int) -> LightBlock | None:
        """The caller holds the lock."""
        loaded = not self._newest_known
        if loaded:
            self._newest = self._decode_before(_TOP)
            self._newest_known = True
        newest = self._newest
        if newest is not None and below <= newest.height:
            return self._decode_before(below)
        if newest is not None and not loaded:
            self._anchor_memory += 1
        return newest

    def _decode_before(self, height: int) -> LightBlock | None:
        for _, raw in self.db.reverse_iterator(_PREFIX, _key(height)):
            self._anchor_decoded += 1
            return LightBlock.decode(bytes(raw))
        return None

    def delete(self, height: int) -> None:
        key = _key(height)
        with self._mtx:
            if self._size is not None and self.db.has(key):
                self._size -= 1
            self.db.delete(key)
            if self._newest is not None and self._newest.height == height:
                self._forget_newest()

    def prune(self, keep: int) -> int:
        """Drop oldest blocks beyond ``keep`` (db.go Prune): the first
        ``size - keep`` keys of the prefix, and no key touched where
        nothing is in excess."""
        with self._mtx:
            excess = self._count() - keep
            if excess <= 0:
                return 0
            doomed = [
                k for k, _ in islice(
                    self.db.prefix_iterator(_PREFIX), excess
                )
            ]
            for k in doomed:
                self.db.delete(k)
            self._size -= len(doomed)
            if self._newest is not None and (
                _key(self._newest.height) <= doomed[-1]
            ):
                self._forget_newest()
            return len(doomed)

    def _forget_newest(self) -> None:
        """The kept block left the DB: the next read loads the newest
        that is there."""
        self._newest = None
        self._newest_known = False

    def size(self) -> int:
        with self._mtx:
            return self._count()

    def _count(self) -> int:
        """The caller holds the lock."""
        if self._size is None:
            self._size = sum(1 for _ in self.db.prefix_iterator(_PREFIX))
            self._size_walks += 1
        return self._size

    def stats(self) -> dict:
        """``anchor_memory`` / ``anchor_decoded``: reads of ``latest``
        and ``light_block_before`` answered by the kept block / by a
        decode; ``size_walks``: walks of the prefix to count it."""
        with self._mtx:
            return {
                "anchor_memory": self._anchor_memory,
                "anchor_decoded": self._anchor_decoded,
                "size_walks": self._size_walks,
            }


__all__ = ["LightStore"]

"""Device-resident per-validator precomputation for the verify kernel.

The reference caches expanded public keys in an LRU sized to the
validator set because the same keys verify every block
(crypto/ed25519/ed25519.go:43,62-68 — a 4k-entry ExpandedPublicKey
cache).  On TPU the analogous (and much larger) win is keeping whole
scalar-multiplication tables device-resident: steady-state commit
verification then does only SHA-512, the R decompression, and comb
table adds — no per-launch point decompression or window-table build.

Two table families:

- **Fixed base B** (shared, host-built once): an 8-bit comb
  ``B_COMB8[w][j] = j * 256^w * B`` in affine-Niels form — 32 mixed
  adds for [S]B instead of 64.

- **Per-validator-set tables** (device-built): for each key A, comb
  entries ``j * (2^wb)^w * (-A)`` in *projective* Niels form
  (Y+X, Y-X, 2Z, 2dT) — keeping Z projective skips the batched field
  inversion at build time for one extra field mul per add
  (curve.pt_add_pniels).  Window width adapts to the set size: 8-bit
  combs (32 adds/verify, 4 MiB/key) for sets up to KEY8_MAX keys,
  4-bit (64 adds, 512 KiB/key) above.

**Layout of the per-key tables** (the one fact ``slot_rows`` and
``lane_rows`` hold; everything else pads, writes, gathers or shards
whole pages on the leading axis): the pool is SLOT-MAJOR with ONE ROW
PER ENTRY, logical ``(cap, nwin * nent, ROW)`` int32.  A key's page is
one contiguous block; the row of (slot, window w, digit j) in the flat
``(cap * nwin * nent, ROW)`` view is ``slot * nwin * nent + w * nent +
j``; a row is the entry's (4, 26) limbs flattened to 104 and
zero-padded to ROW = 128.  The verify kernel gathers its lanes' rows
straight from that flat view, so a launch moves the bytes it reads
(32 x 256 x 512 B at Cosmos-Hub size) and not the table.  Why 128 and
not 104: the TPU tiles the two minor axes (8, 128) and chooses the
physical order itself — for a logical ``(..., m, 104)`` table it puts
the ENTRY axis minor again and re-lays the table before every gather,
or pads the whole table into a temporary every launch; a full
128-lane row is what it keeps minor, at 23% more bytes.  What
also does not work: slicing one window ``(nwin, m, ROW)[w]`` inside
the comb's scan (no relayout, but the slice is materialised every
step), and a window-major ``(nwin, 4, 26, cap * nent)`` table
gathered on its last axis (each scan step slices AND re-lays a
window's 27 MB, padded to 134 MB: most of a 150-validator launch).

Tables are cached PER KEY in a device pool (``_KeyPool``) bounded by
CMT_TPU_TABLE_CACHE_MB, matching the reference's per-key LRU
(crypto/ed25519/ed25519.go:43,62-68): a set lookup EC-builds pages only
for keys not already pooled, so rotating one validator out of 150 (or
10,000) costs one key's build (~10 verifies), not the whole set's.
``KeySetTables`` entries are immutable snapshots of the pool, memoized
per set-hash while the pool is unchanged.
"""

from __future__ import annotations

import hashlib
import os
import threading
from collections import OrderedDict
from dataclasses import dataclass, field

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from cometbft_tpu.crypto import edwards as _ref
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.ops import curve as C
from cometbft_tpu.ops import field as F
from cometbft_tpu.ops import jitguard
from cometbft_tpu.ops.ed25519_verify import _next_pow2
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.env import int_from_env
from cometbft_tpu.utils.trace import TRACER as _tracer

#: largest set that gets 8-bit per-key combs (4 MiB/key on device)
KEY8_MAX = int_from_env("CMT_TPU_KEY8_MAX", 256)
#: largest set we precompute tables for at all
TABLE_MAX_KEYS = int_from_env("CMT_TPU_TABLE_MAX_KEYS", 16384)
#: total device bytes across cached sets before LRU eviction
TABLE_CACHE_MB = int_from_env("CMT_TPU_TABLE_CACHE_MB", 6144)
#: most keys one build call computes pages for.  A build holds its
#: output and 2-3x that in temporaries (1,024 4-bit keys: 0.5 GiB out,
#: 1.3 GiB scratch on the v5e), so a 10,000-key set is built and placed
#: a chunk at a time; 1,024 is a shape the 1,000-validator sets compile
#: anyway.
BUILD_CHUNK = 1024


# -- fixed-base 8-bit comb (host-built, shared) ------------------------

_B8_LOCK = cmtsync.Mutex()
_B8: np.ndarray | None = None


def b_comb8() -> np.ndarray:
    """(32, 3, 26, 256) affine-Niels comb of B, gather-friendly layout
    (entry index on the minor axis). Built lazily: ~8k host EC ops."""
    global _B8
    with _B8_LOCK:
        if _B8 is None:
            table = np.zeros((32, 256, 3, F.NLIMBS), dtype=np.int32)
            base = _ref.B_POINT
            for w in range(32):
                acc = _ref.IDENTITY
                for j in range(256):
                    if j == 0:
                        table[w, j] = np.stack([F.ONE, F.ONE, F.ZERO])
                    else:
                        acc = _ref.pt_add(acc, base)
                        ax, ay = _ref.pt_to_affine(acc)
                        table[w, j] = C._niels_from_affine(ax, ay)
                for _ in range(8):
                    base = _ref.pt_double(base)
            _B8 = np.ascontiguousarray(table.transpose(0, 2, 3, 1))
        return _B8


def comb_mul_base8(s_bytes):
    """[S]B via the 8-bit Niels comb: s_bytes (32, *batch) uint8 (LE
    scalar encoding; the comb is exact for any 256-bit integer)."""
    table = jnp.asarray(b_comb8())
    idx = s_bytes.astype(jnp.int32)

    def body(acc, xs):
        tbl_w, byte = xs  # (3, 26, 256), (*batch,)
        e = jnp.take(tbl_w, byte, axis=-1)  # (3, 26, *batch)
        return C.pt_add_niels(acc, (e[0], e[1], e[2])), None

    acc, _ = lax.scan(body, C.identity(s_bytes.shape[1:]), (table, idx))
    return acc


# -- per-key projective-Niels comb builder (device) --------------------

_BX, _BY = _ref.pt_to_affine(_ref.B_POINT)
_B_AFFINE = (F.from_int(_BX), F.from_int(_BY))


#: int32 limbs of one comb entry: (Y+X, Y-X, 2Z, 2dT) x NLIMBS
ENTRY_LIMBS = 4 * F.NLIMBS
#: width of one table row.  An entry's 104 limbs are zero-padded to one
#: 128-lane TPU tile so the compiler keeps the ROW on the minor axis
#: and a gather fetches whole rows from the table where it lies.
ROW = 128


def slot_rows(window_bits: int) -> int:
    """Rows of one key's page: an entry for each (window, digit)."""
    return (256 // window_bits) << window_bits


def lane_rows(key_ids, windows, window_bits: int):
    """Row of the flat ``(cap * slot_rows, ROW)`` table that each lane
    reads at each window: key_ids (*batch,) pool slots, windows
    (nwin, *batch) digits -> (nwin, *batch) int32.  The largest pool
    (16,384 slots x 64 x 16 rows) stays inside int32."""
    nwin = 256 // window_bits
    win_base = jnp.arange(nwin, dtype=jnp.int32) << window_bits
    win_base = win_base.reshape((nwin,) + (1,) * key_ids.ndim)
    return key_ids * slot_rows(window_bits) + win_base + windows


def build_tables_kernel(pub, window_bits: int):
    """pub (32, n) uint8 -> (table, valid).

    table: (n, nwin * nent, ROW) int32 — one page per key, one row per
    projective Niels entry ``j * (2^wb)^w * (-A_key)`` at page row
    ``w * nent + j``; the row is the entry's (4, 26) limbs flattened to
    104 and zero-padded to ROW (see ``lane_rows``).
    valid: (n,) bool — ZIP-215 decompression validity per key; invalid
    keys get B's table (harmless) and must be masked by callers.
    """
    n = pub.shape[-1]
    nwin = 256 // window_bits
    nent = 1 << window_bits
    a_pt, valid = C.decompress(pub)
    # keep the formulas on-curve for invalid encodings: substitute B
    bx = F.cvec(_B_AFFINE[0], pub.ndim)
    by = F.cvec(_B_AFFINE[1], pub.ndim)
    one = F.cvec(F.ONE, pub.ndim)
    x = F.select(valid, a_pt[0], jnp.broadcast_to(bx, a_pt[0].shape))
    y = F.select(valid, a_pt[1], jnp.broadcast_to(by, a_pt[1].shape))
    z = jnp.broadcast_to(one, y.shape)
    base = C.pt_neg((x, y, z, F.mul(x, y)))

    def win_body(p, _):
        out = p
        for _ in range(window_bits):
            p = C.pt_double(p)
        return p, out

    _, bases = lax.scan(win_body, base, None, length=nwin)
    # (nwin, 26, n) per coord -> windows into the batch: (26, nwin*n)
    base_flat = tuple(
        jnp.moveaxis(c, 0, 1).reshape(F.NLIMBS, nwin * n) for c in bases
    )

    def ent_body(acc, _):
        return C.pt_add(acc, base_flat), acc  # collect j, carry j+1

    _, entries = lax.scan(
        ent_body, C.identity((nwin * n,)), None, length=nent
    )
    # scan stacked the entry axis in front: (nent, 26, nwin*n) per
    # coord; field ops want limbs first.
    ex, ey, ez, et = (jnp.moveaxis(c, 0, 1) for c in entries)
    t2d = F.mul(et, F.cvec(C.TWO_D_LIMBS, et.ndim))
    pn = jnp.stack([ey + ex, ey - ex, ez + ez, t2d])  # (4, 26, nent, nwin*n)
    pn = pn.reshape(ENTRY_LIMBS, nent, nwin, n)
    # -> (n, nwin, nent, 104): one row per entry, pages slot-major
    pn = jnp.transpose(pn, (3, 2, 1, 0))
    pn = jnp.pad(pn, [(0, 0), (0, 0), (0, 0), (0, ROW - ENTRY_LIMBS)])
    return pn.reshape(n, nwin * nent, ROW), valid


_build_cache: dict[tuple[int, int], object] = {}


def _compiled_build(n: int, window_bits: int):
    key = (n, window_bits, F.trace_config())
    fn = _build_cache.get(key)
    if fn is None:
        jitguard.note_compile("table_build", key)

        def run(pub):
            return build_tables_kernel(pub, window_bits)

        run.__name__ = f"table_build_w{window_bits}"
        fn = jax.jit(run)
        _build_cache[key] = fn
    return fn


def comb_mul_keyed(table, key_ids, windows, window_bits: int):
    """Per-key comb: table (cap, nwin * nent, ROW) of pages from
    build_tables_kernel, key_ids (*batch,) int32 pool slots, windows
    (nwin, *batch) int32 LE digit decomposition of k.
    Returns [k](-A_key) per lane as an extended point.

    The lanes' ROWS for every window are gathered straight from the
    whole table (a free reshape to 2-D) in ONE gather ahead of the scan
    — the v5e pays a gather's fixed cost however few rows it fetches,
    so one per window was most of the comb — and the scan only adds;
    nothing table-sized is sliced or re-laid, and the launch holds the
    lane-sized block of rows, ``nwin x lanes x ROW`` int32 (4 MB at 256
    lanes x 8 bits, 64 MB a 2,048-lane slice, 256 MB at the straight
    8,192 lanes x 4 bits; docs/device_kernel_perf.md §2.1)."""
    batch = key_ids.shape
    rows2d = table.reshape(-1, ROW)
    row_ids = lane_rows(key_ids, windows, window_bits)  # (nwin, *batch)
    block = jnp.take(rows2d, row_ids, axis=0, mode="clip")  # (.., ROW)

    def body(acc, e):  # e (*batch, ROW): one window's entries
        e = jnp.moveaxis(e[..., :ENTRY_LIMBS], -1, 0)
        e = e.reshape((4, F.NLIMBS) + batch)
        return C.pt_add_pniels(acc, (e[0], e[1], e[2], e[3])), None

    return lax.scan(body, C.identity(batch), block)[0]


# -- per-key incremental table cache ----------------------------------


@dataclass
class KeySetTables:
    """A validator set's view into the device-resident key-table pool.

    ``key_index`` maps each pubkey to its POOL SLOT — the index of its
    page on ``table``'s leading axis, and the key id the verify kernel
    turns into rows (``lane_rows``); ``table``/``valid`` are immutable
    snapshots of the pool arrays, so an entry stays self-consistent
    even after later rotations grow, compact, or evict the pool
    underneath it.
    """

    sethash: bytes
    window_bits: int
    key_index: dict[bytes, int]  # pubkey bytes -> pool slot
    table: object                # device array (cap, nwin*nent, ROW)
    valid: np.ndarray            # (cap,) bool
    nbytes: int                  # bytes of ``table`` (whole pool)
    set_nbytes: int = 0          # bytes attributable to this set's keys
    _valid_dev: object = None    # lazy device copy of ``valid``
    #: per-mesh device placements hung off this entry (sharded shards,
    #: replicated copies): placement key -> (value, device bytes).
    #: These are EXTRA device copies beyond the base pool array, so the
    #: cache's budget accounting sums them (``placement_bytes``) —
    #: before this, an 8-chip replicated placement held 8x the pool
    #: bytes in HBM that the TABLE_CACHE_MB budget never saw.
    #: Guarded by ``_mtx``: verify threads place concurrently with the
    #: cache's budget sweep reading the dict under the cache lock (the
    #: entry lock is always innermost, never taken around cache calls,
    #: so the cache-lock -> entry-lock order is acyclic).
    placements: dict = field(default_factory=dict)
    _mtx: object = field(default_factory=cmtsync.Mutex)

    def key_ids(self, pubs: list[bytes]) -> np.ndarray:
        return np.fromiter(
            (self.key_index[p] for p in pubs), dtype=np.int32, count=len(pubs)
        )

    def valid_device(self):
        """The validity mask as a device array, transferred EXPLICITLY
        once per entry — the keyed dispatch previously jnp.asarray'd it
        per launch, an implicit h2d transfer the CMT_TPU_JITGUARD
        window flags (and a wasted transfer per steady-state batch)."""
        if self._valid_dev is None:
            self._valid_dev = jax.device_put(self.valid)
        return self._valid_dev

    def placement_bytes(self) -> int:
        """Device bytes held by this entry's mesh placements (counted
        against TABLE_CACHE_MB alongside the base pools)."""
        with self._mtx:
            return sum(n for _, n in self.placements.values())

    def sharded_tables(self, mesh, table_sharding, valid_sharding,
                       ndev: int):
        """Per-chip shards of this set's table (and validity mask),
        device-resident under the given ``NamedSharding``s — built once
        per (entry, mesh) and cached on the entry.

        Slot ownership is STRIDED round-robin — device ``d`` owns slots
        ``{d, d+ndev, d+2*ndev, ...}`` — because live slots cluster in
        ``[0, n_live)`` after compaction: contiguous block ownership
        would leave the high-block devices with only dead slots (150
        live keys in a 256-slot pool on 8 chips would idle 3 of them
        every launch).  The pages are gathered into per-device
        contiguous order ONCE here (a device page gather per placement,
        same cost class as the pad), so on the leading (slot) axis
        device ``d``'s shard block holds its strided slots at LOCAL
        positions ``slot // ndev`` — the shard-local row gather with
        rebased ids touches only local HBM and the sharded keyed kernel
        runs with zero collectives.  Returns ``(table, valid, per_cap)``; the
        placement's device bytes are recorded for the cache's budget
        accounting.

        Locking follows the stage_growth pattern: the pool-sized
        device work (pad + gather + sharded device_put, seconds at 10k
        keys) runs OUTSIDE ``_mtx`` so the cache's
        budget sweep — which reads placement_bytes() under the global
        cache lock — never queues every lookup behind a placement
        build; ``_mtx`` guards only the dict swap.  Two threads racing
        a cold placement may both build; the loser's copy is dropped
        and freed (a transient, bounded duplicate — the same trade
        stage_growth makes)."""
        key = ("sharded", mesh)
        with self._mtx:
            placed = self.placements.get(key)
        if placed is None:
            cap = len(self.valid)
            per_cap = -(-cap // ndev)
            shard_cap = per_cap * ndev
            # A post-seal placement build (validator rotation) runs
            # inside the armed CMT_TPU_JITGUARD transfer window, whose
            # job is catching silent PER-LAUNCH transfers.  This is
            # deliberate ONE-TIME staging per (entry, mesh) — pad
            # constants, the gather-index upload, and the sharded
            # device_puts all move data on purpose — so it opens an
            # audited allow scope the same way warmup does.
            with jax.transfer_guard("allow"):
                table, valid = self.table, self.valid
                if shard_cap > cap:
                    table = _pad_slots(table, shard_cap - cap)
                    valid = np.pad(valid, (0, shard_cap - cap))
                # strided -> per-device-contiguous page permutation:
                # position (d*per_cap + j) <- slot (j*ndev + d)
                slot_perm = (
                    np.arange(shard_cap).reshape(per_cap, ndev).T.ravel()
                )
                table = table[jax.device_put(slot_perm)]
                valid = valid[slot_perm]
                table = jax.device_put(table, table_sharding)
                valid = jax.device_put(valid, valid_sharding)
            built = (
                (table, valid, per_cap),
                int(table.nbytes) + int(valid.nbytes),
            )
            with self._mtx:
                placed = self.placements.setdefault(key, built)
        return placed[0]


def _pad_slots(table, extra: int):
    """``table`` with ``extra`` zero pages appended on the slot axis."""
    return jnp.pad(table, [(0, extra), (0, 0), (0, 0)])


_B_ENC = np.frombuffer(_ref.encode_point(_ref.B_POINT), dtype=np.uint8)


def _pool_cap(nkeys: int) -> int:
    """Pool capacities come from a small fixed ladder (pow2 up to 4096,
    then 2048-slot steps) so the shape-specialized verify kernel only
    retraces a bounded number of times — while avoiding pow2's up-to-2x
    HBM waste at large validator counts (10k keys: 10240 slots =
    5 GiB at 4-bit, vs 16384 slots = 8 GiB; measured on one v5e, PR 33:
    5,368,709,120 B resident, 12,096,562,688 B at the chunked build's
    peak — docs/device_kernel_perf.md §2.1)."""
    if nkeys <= 4096:
        return _next_pow2(max(nkeys, 1))
    return -(-nkeys // 2048) * 2048


class _KeyPool:
    """One window width's device pool of per-key comb pages.

    The pool is SLOT-MAJOR: ``table[slot]`` is one key's whole page,
    ``slot_rows`` contiguous ROW-wide rows, so growth pads, page writes
    set and compaction gathers whole pages on the leading axis, and
    ``comb_mul_keyed`` reads rows with slot numbers as key ids
    (``lane_rows``).
    Capacity follows the ``_pool_cap`` ladder — powers of two up to
    4096 slots, then 2048-slot steps: the compiled keyed-verify kernel
    specializes on the table shape, so growth only retraces at ladder
    boundaries (a bounded count), while large pools avoid pow2's
    up-to-2x HBM waste.
    """

    def __init__(self, window_bits: int) -> None:
        self.window_bits = window_bits
        # what the device holds: ROW-wide rows, padding included
        self.key_bytes = slot_rows(window_bits) * ROW * 4
        self.cap = 0
        self.table = None  # device (cap, nwin*nent, ROW) int32
        self.valid = np.zeros(0, dtype=bool)
        self.slots: OrderedDict[bytes, int] = OrderedDict()  # LRU order
        self.free: list[int] = []
        self.version = 0  # bumped on any table-array change

    def nbytes(self) -> int:
        return self.cap * self.key_bytes

    def stage_growth(
        self, version: int, table, cap: int, nkeys: int
    ):
        """Build the grown table array from a (version, table, cap)
        snapshot WITHOUT the cache lock held — the jnp.pad is a device
        copy of the whole pool, and doing it under the lock stalls
        every concurrent cached-set lookup for the copy's duration
        (ADVICE round 5).  Returns (snapshot_version, new_cap,
        grown_table), or None when the snapshot needs no growth;
        ``ensure_capacity(..., staged=...)`` applies it only if the
        pool version is still the snapshot's."""
        if cap >= nkeys:
            return None
        new_cap = _pool_cap(nkeys)
        return (version, new_cap, self._grown(table, cap, new_cap))

    def _grown(self, table, cap: int, new_cap: int):
        """``table`` (``cap`` pages, or None) with zero pages appended
        up to ``new_cap``."""
        if table is None:
            shape = (new_cap, slot_rows(self.window_bits), ROW)
            return jnp.zeros(shape, dtype=jnp.int32)
        return _pad_slots(table, new_cap - cap)

    def ensure_capacity(self, nkeys: int, staged=None) -> None:
        """Grow to the ladder capacity for ``nkeys``.  Lock held.  A
        ``staged`` pre-grown array (from stage_growth) is swapped in
        when its snapshot version still matches and it is big enough;
        otherwise (concurrent build/compact moved the pool — rare) the
        pad runs here as before."""
        if self.cap >= nkeys:
            return
        new_cap = _pool_cap(nkeys)
        if (
            staged is not None
            and staged[0] == self.version
            and staged[1] >= new_cap
        ):
            new_cap = staged[1]
            self.table = staged[2]
        else:
            self.table = self._grown(self.table, self.cap, new_cap)
        self.valid = np.concatenate(
            [self.valid, np.zeros(new_cap - self.cap, dtype=bool)]
        )
        self.free.extend(range(self.cap, new_cap))
        self.cap = new_cap
        self.version += 1
        _crypto_metrics().key_pool_retraces.labels(
            window_bits=str(self.window_bits)
        ).inc()

    def compact(self) -> None:
        """Gather live pages into a fresh ladder-capacity array (device
        gather, no EC recompute) — run after eviction freed enough
        slots that the pool holds mostly dead pages."""
        n_live = len(self.slots)
        new_cap = _pool_cap(n_live)
        if new_cap >= self.cap:
            return
        order = list(self.slots.items())  # preserves LRU order
        live = np.array([s for _, s in order], dtype=np.int32)
        new_table = _pad_slots(
            self.table[jnp.asarray(live)], new_cap - n_live
        )
        new_valid = np.zeros(new_cap, dtype=bool)
        new_slots: OrderedDict[bytes, int] = OrderedDict()
        for i, (p, s) in enumerate(order):
            new_slots[p] = i
            new_valid[i] = self.valid[s]
        self.table = new_table
        self.valid = new_valid
        self.slots = new_slots
        self.free = list(range(n_live, new_cap))
        self.cap = new_cap
        self.version += 1
        _crypto_metrics().key_pool_retraces.labels(
            window_bits=str(self.window_bits)
        ).inc()


class KeyTableCache:
    """PER-KEY LRU of device-resident comb-table pages, bounded by
    device bytes across both window widths.

    The reference's expanded-pubkey cache is per-key
    (crypto/ed25519/ed25519.go:43,62-68) precisely so validator churn is
    incremental; this cache matches that: a set lookup builds tables
    ONLY for keys not already pooled, so rotating 1 of 150 (or 10,000)
    validators costs one key's build (~10 verifies), not the whole
    set's.
    """

    def __init__(self, cap_bytes: int = TABLE_CACHE_MB << 20) -> None:
        self._cap = cap_bytes
        self._lock = cmtsync.Mutex()
        self._pools = {8: _KeyPool(8), 4: _KeyPool(4)}
        # pubkey-level build latches: concurrent misses on overlapping
        # keys (consensus addVote + light client racing on a rotation)
        # build each key ONCE — losers wait on the winner's latch
        self._pending: dict[tuple[int, bytes], threading.Event] = {}
        # set-hash -> (pool version, entry) memo so repeat lookups of
        # an unchanged set return the SAME entry object (the mesh path
        # hangs replicated copies off it)
        self._entries: OrderedDict[bytes, tuple[int, KeySetTables]] = (
            OrderedDict()
        )
        self.stats = {"keys_built": 0, "keys_evicted": 0, "build_chunks": 0}

    def _set_key(self, pubs: list[bytes]):
        """The dispatch-policy prologue shared by peek and
        lookup_or_build: (unique keys, window pool, set hash), or None
        when the unique-key count is out of table policy.  ONE
        implementation so the size gate / window-width choice / hash
        can never drift between the warm probe and the build path —
        a divergence would make peek probe the wrong pool and silently
        demote warm batches off the keyed tier."""
        unique = sorted(set(pubs))
        n = len(unique)
        if n == 0 or n > TABLE_MAX_KEYS:
            return None
        pool = self._pools[8 if n <= KEY8_MAX else 4]
        return unique, pool, hashlib.sha256(b"".join(unique)).digest()

    def peek(self, pubs: list[bytes]) -> KeySetTables | None:
        """An entry iff EVERY key is already resident — no builds, no
        waiting on in-flight builds.  This is the keyed-by-default
        dispatch probe: a batch below the generic device threshold
        still takes the keyed tier when its tables are warm, and the
        probe must never stall a small batch behind an EC build."""
        sk = self._set_key(pubs)
        if sk is None:
            return None
        unique, pool, h = sk
        with self._lock:
            if any(p not in pool.slots for p in unique):
                return None
            return self._finish_lookup(h, pool, unique)

    def lookup_or_build(self, pubs: list[bytes]) -> KeySetTables | None:
        """An entry covering every key in ``pubs``, building pages only
        for keys not already pooled; None when the unique-key count is
        out of policy."""
        sk = self._set_key(pubs)
        if sk is None:
            return None
        unique, pool, h = sk
        window_bits = pool.window_bits
        while True:
            with self._lock:
                waits = [
                    self._pending[k]
                    for p in unique
                    if (k := (window_bits, p)) in self._pending
                ]
                if not waits:
                    missing = [p for p in unique if p not in pool.slots]
                    if not missing:
                        return self._finish_lookup(h, pool, unique)
                    for p in missing:
                        self._pending[(window_bits, p)] = threading.Event()
            if waits:
                for ev in waits:
                    ev.wait()
                continue
            try:
                chunks = [
                    missing[i : i + BUILD_CHUNK]
                    for i in range(0, len(missing), BUILD_CHUNK)
                ]
                for k, chunk in enumerate(chunks):
                    pages, page_valid = self._build_pages(
                        chunk, window_bits, k + 1, len(chunks)
                    )
                    self.stats["build_chunks"] += 1
                    # the pool grows ONCE, with the first chunk, to
                    # hold every missing key; between chunks the write
                    # is waited for, so that the next build's scratch
                    # is never allocated beside the pool being copied
                    self._place_pages(
                        pool, chunk, pages, page_valid,
                        room=len(missing) - k * BUILD_CHUNK,
                        wait=len(chunks) > 1,
                    )
                    del pages
                with self._lock:
                    self._evict_over_budget(keep=set(unique))
                    self._update_pool_gauges()
                    # a concurrent lookup's eviction may have dropped
                    # keys of ours that were present before our build
                    # released the lock — loop to rebuild them if so
                    if all(p in pool.slots for p in unique):
                        return self._finish_lookup(h, pool, unique)
            finally:
                with self._lock:
                    for p in missing:
                        self._pending.pop((window_bits, p)).set()

    def _finish_lookup(
        self, h: bytes, pool: _KeyPool, unique: list[bytes]
    ) -> KeySetTables:
        """Touch LRU order and return a (memoized) entry. Lock held."""
        for p in unique:
            pool.slots.move_to_end(p)
        memo = self._entries.get(h)
        if memo is not None and memo[0] == pool.version:
            self._entries.move_to_end(h)
            return memo[1]
        # each memoized entry pins ITS version's full pool array: sweep
        # stale-version entries so the memo never holds device arrays
        # beyond the two live pools (a 64-count bound alone would pin
        # ~64 pool-sized snapshots across rotations — an HBM leak)
        self._sweep_stale_entries()
        entry = KeySetTables(
            sethash=h,
            window_bits=pool.window_bits,
            key_index={p: pool.slots[p] for p in unique},
            table=pool.table,
            valid=pool.valid.copy(),
            nbytes=pool.nbytes(),
            set_nbytes=len(unique) * pool.key_bytes,
        )
        self._entries[h] = (pool.version, entry)
        while len(self._entries) > 64:
            self._entries.popitem(last=False)
        return entry

    def _build_pages(
        self, missing: list[bytes], window_bits: int, chunk: int, of: int
    ):
        """EC-compute comb pages for ``missing`` keys (device kernel,
        pow2-padded with B's encoding) — chunk ``chunk`` of ``of`` of
        one lookup's build. Runs OUTSIDE the cache lock so cached-set
        lookups aren't blocked behind a build."""
        n = len(missing)
        n_pad = _next_pow2(n)
        pub = np.zeros((32, n_pad), dtype=np.uint8)
        for i, p in enumerate(missing):
            pub[:, i] = np.frombuffer(p, dtype=np.uint8)
        if n_pad > n:
            pub[:, n:] = _B_ENC[:, None]
        fn = _compiled_build(n_pad, window_bits)
        with _tracer.span(
            "table_build", cat="device", keys=n, window_bits=window_bits,
            chunk=chunk, of=of,
        ):
            table, valid = fn(jax.device_put(pub))
            valid = jax.device_get(valid)[:n]  # host sync: per-build validity fetch (build path, not the verify hot loop)
        return table, valid

    def _place_pages(
        self, pool: _KeyPool, keys: list[bytes], pages, page_valid,
        room: int, wait: bool,
    ) -> None:
        """Write one build call's pages into free slots of the pool,
        grown first to hold ``room`` more keys (``keys`` and whatever
        of the same lookup is still to build).  The write is a COPY of
        the pool, never a donated in-place update: memoized entries,
        plans in flight and a concurrent ``stage_growth`` snapshot hold
        the old array, and a donated buffer would be deleted under
        their launch.  So for the length of a placement the device
        holds the pool twice, and one build's pages; ``wait`` blocks
        (lock released) until the old pool can go."""
        n = len(keys)
        with _tracer.span(
            "table_build/place", cat="device", keys=n,
        ) as sp:
            # stage any pool growth outside the lock: the pad is a
            # device copy of the whole table, and cached-set
            # lookups must not queue behind it
            with self._lock:
                snap = (pool.version, pool.table, pool.cap)
                need = len(pool.slots) + room
            staged = pool.stage_growth(*snap, need)
            with self._lock:
                pool.ensure_capacity(len(pool.slots) + room, staged=staged)
                # the slots ``pop()`` would hand out, taken off the
                # free list only once the write is dispatched
                slots = pool.free[: -n - 1 : -1]
                pool.table = pool.table.at[
                    jnp.asarray(slots, dtype=jnp.int32)
                ].set(pages if pages.shape[0] == n else pages[:n])
                del pool.free[-n:]
                pool.version += 1
                for i, (p, s) in enumerate(zip(keys, slots)):
                    pool.slots[p] = s
                    pool.valid[s] = page_valid[i]
                self.stats["keys_built"] += n
                _crypto_metrics().key_pool_builds.inc(n)
                sp.set(slots=len(pool.slots), cap=pool.cap)
                placed = pool.table
            if wait:
                placed.block_until_ready()  # host sync: between the chunks of one build the old pool must be released before the next build allocates (build path, not the verify hot loop)

    def _sweep_stale_entries(self) -> None:
        """Drop memoized entries whose pool version moved on.  Lock
        held.  Besides un-pinning stale pool-array snapshots, this also
        releases the entries' mesh PLACEMENTS (sharded shards /
        replicated copies) so their device bytes leave the budget."""
        for k in [
            k
            for k, (v, e) in self._entries.items()
            if v != self._pools[e.window_bits].version
        ]:
            del self._entries[k]

    def placement_bytes(self) -> int:
        """Device bytes held by live memoized entries' mesh placements
        — the per-device sharded/replicated table copies that exist in
        HBM beyond the base pool arrays.  Lock held."""
        return sum(e.placement_bytes() for _, e in self._entries.values())

    def _evict_over_budget(self, keep: set[bytes]) -> None:
        """Drop LRU keys (never ones in ``keep``) until compaction can
        bring the pools under budget, then compact. Lock held. A single
        set larger than the budget stays resident: the ACTIVE set must
        always fit. Eviction is minimal — LRU-first, stopping as soon
        as the post-compaction footprint fits.

        The OVER-BUDGET TRIGGER counts the base pool arrays PLUS live
        entries' mesh placements (placement_bytes): on an 8-chip mesh a
        replicated placement alone is 8x the pool bytes, so ignoring it
        (the pre-mesh accounting) let the real HBM footprint run ~9x
        past TABLE_CACHE_MB.  The eviction loop's STOP condition,
        however, compares only the post-compaction pool footprint:
        compaction bumps the pool versions, staling every memoized
        entry, and the sweep below releases the placements those
        entries pinned — so counting ``placed`` (a term key eviction
        can never reduce) in the stop condition would evict EVERY
        evictable key on each over-budget rotation instead of the
        minimal LRU set.  Steady-state placement overhead is bounded:
        the sharded placement is ~1x the active pool (vs ndev-x for
        the replaced replicated path), one per mesh per live entry."""

        def compacted_bytes(p: _KeyPool) -> int:
            return min(p.cap, _pool_cap(len(p.slots))) * p.key_bytes

        # release placements pinned by already-stale entries FIRST:
        # they are garbage awaiting the sweep, not working set, and
        # dropping them is often enough to get back under budget with
        # zero key evictions (a live entry's placement is the active
        # working set and — like the active key set — stays resident)
        self._sweep_stale_entries()
        placed = self.placement_bytes()
        if (
            sum(p.nbytes() for p in self._pools.values()) + placed
            <= self._cap
        ):
            return
        changed = False
        for pool in self._pools.values():
            evictable = [p for p in pool.slots if p not in keep]  # LRU order
            for p in evictable:
                if (
                    sum(compacted_bytes(q) for q in self._pools.values())
                    <= self._cap
                ):
                    break
                s = pool.slots.pop(p)
                pool.valid[s] = False
                pool.free.append(s)
                self.stats["keys_evicted"] += 1
                _crypto_metrics().key_pool_evictions.inc()
                changed = True
        if changed:
            for pool in self._pools.values():
                pool.compact()
            # compaction bumped versions: stale entries (and the
            # placement bytes they pinned) can go now
            self._sweep_stale_entries()

    def _update_pool_gauges(self) -> None:
        """Refresh the occupancy/capacity gauges for both window
        widths.  Lock held (reads pool.slots / pool.cap)."""
        cm = _crypto_metrics()
        for wb, pool in self._pools.items():
            lbl = str(wb)
            cm.key_pool_keys.labels(window_bits=lbl).set(len(pool.slots))
            cm.key_pool_capacity.labels(window_bits=lbl).set(pool.cap)

    def clear(self) -> None:
        with self._lock:
            self._pools = {8: _KeyPool(8), 4: _KeyPool(4)}
            self._entries.clear()
            self._update_pool_gauges()


TABLE_CACHE = KeyTableCache()


#: kernel shape/dtype contracts (grammar: ops/contracts.py; verified
#: statically by tools/jitcheck.py, swept devicelessly by
#: tests/test_jitcheck.py).  ``windows`` for comb_mul_keyed is the LE
#: digit decomposition of the scalar — one digit per comb window.
_CONTRACTS = {
    "build_tables_kernel": {
        "args": {"pub": ("u8", (32, "B"))},
        "static": ("window_bits",),
        "out": [
            ("i32", ("B", "nwin*nent", "ROW")),
            ("bool", ("B",)),
        ],
    },
    "comb_mul_base8": {
        "args": {"s_bytes": ("u8", (32, "B"))},
        "static": (),
        "out": [
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
        ],
    },
    "comb_mul_keyed": {
        "args": {
            "table": ("i32", ("cap", "nwin*nent", "ROW")),
            "key_ids": ("i32", ("B",)),
            "windows": ("i32", ("nwin", "B")),
        },
        "static": ("window_bits",),
        "out": [
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
            ("i32", ("NLIMBS", "B")),
        ],
    },
}

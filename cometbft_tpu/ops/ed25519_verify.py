"""The Ed25519 batch-verify kernel — the TPU execution backend.

This is the device seam the reference exposes as crypto.BatchVerifier
(crypto/crypto.go:44, crypto/ed25519/ed25519.go:190): callers enqueue
(pubkey, msg, sig) tuples and one launch returns per-signature validity
for the whole batch. Everything happens in-device: point decompression,
SHA-512 of R||A||M, digest reduction mod L, the comb/windowed double
scalar multiplication, and the cofactored ZIP-215 acceptance equation

    [8]([S]B + [k](-A) - R) == identity.

Per-signature results come back as a bool vector — no bisection search
for the first bad index is needed (cf. types/validation.go:310, which
has to re-verify on batch failure because the RLC trick only yields a
single bit; data-parallel verification gives the per-vote bits for
free).

Batch shaping (TPU-first):
- Device arrays are **feature-first**: the packed buffer is
  (100+bucket, batch) so the batch axis rides the 128-wide vector
  lanes (see ops/field.py design notes).
- Inputs are padded to (lanes, message-length bucket) so the jit cache
  stays small and shapes stay static for XLA.  ``launch_lanes`` is the
  one rule for the lanes: up to MAX_LAUNCH signatures pad to the next
  power of two and run as one straight program (the queue's coalesced
  batches vary in size, and each shape is a compile and a load).
- A batch of more than MAX_LAUNCH signatures is still ONE launch: it
  pads to whole slices of WIDE_SLICE lanes and one program runs the
  slices in turn (lax.map), so the working set is one slice's — one
  huge straight launch falls off a memory cliff (measured round 3) —
  and the empty lanes are at most a slice less one: 10,000 signatures
  ride 10,240 lanes, not the 16,384 of the next power of two.  Only a
  batch whose messages span length buckets goes out as several
  launches of at most MAX_LAUNCH signatures each.
- A and R decompress as ONE concatenated batch (32, 2B): the sqrt
  exponentiation chain is the deepest part of the graph, and fusing
  both halves halves the traced program.
"""

from __future__ import annotations

import contextlib
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import BatchVerifier, PubKey
from cometbft_tpu.crypto import dispatch as _failover
from cometbft_tpu.crypto import ed25519 as _ed
from cometbft_tpu.crypto import health as _health
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.ops import curve as C
from cometbft_tpu.ops import field as _F
from cometbft_tpu.utils.env import flag_from_env, int_from_env
from cometbft_tpu.ops import jitguard as _jitguard
from cometbft_tpu.utils.trace import TRACER as _tracer
from cometbft_tpu.ops import scalar as SC
from cometbft_tpu.ops import sha512 as SH

# Message-length buckets (bytes). Vote sign-bytes are ~120 bytes; the
# largest bucket covers arbitrary app-level uses.
_BUCKETS = (128, 256, 512, 1024, 4096)
_MIN_BATCH = 8

#: Widest straight program (lanes): a batch of more signatures runs in
#: slices (``launch_lanes``). Derived from round-3 measurement: 8192
#: sustains peak device rate; 65536 in one launch hits an XLA memory
#: cliff.
MAX_LAUNCH = int_from_env("CMT_TPU_MAX_LAUNCH", 8192, minimum=1)

#: Slice width (lanes) of a launch wider than MAX_LAUNCH.  It divides
#: 8,192, and every commit the protocol admits above MAX_LAUNCH
#: (8,193-10,000 signatures) lands on one shape, five slices.  Chosen
#: on the chip among 1,024 / 2,048 / 4,096 / 8,192 (PERF.md section 6,
#: PR 34).
WIDE_SLICE = 2048


def nblocks_for_bucket(bucket: int) -> int:
    """SHA-512 block count for a message bucket: 64 bytes of R||A
    prefix + the bucket + 17 bytes of minimal padding (0x80 marker +
    16-byte length), in 128-byte blocks.  The ONE definition shared by
    the compile seams and the contract sweep (ops/contracts.ladder_env)
    — a layout change must move both together.
    """
    return (64 + bucket + 17 + 127) // 128



def build_padded_input(r_enc, a_enc, msg, msglen, nblocks: int):
    """Assemble SHA-512 input R || A || M with FIPS 180-4 padding, fully
    vectorized (per-lane dynamic message length, static bucket width).

    Inputs are feature-first: r_enc/a_enc (32, B), msg (M, B),
    msglen (B,). SHA padding is minimal per message: each lane's 0x80
    marker and 16-byte big-endian bit length land at the end of *its
    own* final block, not the bucket's. Returns (buf (width, B) uint8,
    nblocks_lane (B,))."""
    width = nblocks * 128
    content = jnp.concatenate(
        [r_enc.astype(jnp.int64), a_enc.astype(jnp.int64), msg.astype(jnp.int64)],
        axis=0,
    )
    content = jnp.pad(
        content, [(0, width - content.shape[0])] + [(0, 0)] * (msg.ndim - 1)
    )
    total = (64 + msglen).astype(jnp.int64)[None]       # (1, B)
    nblocks_lane = (total + 17 + 127) // 128            # ceil((total+17)/128)
    lane_width = nblocks_lane * 128
    idx = jnp.arange(width, dtype=jnp.int64).reshape(
        (width,) + (1,) * (msg.ndim - 1)
    )
    buf = jnp.where(idx < total, content, 0)
    buf = jnp.where(idx == total, 0x80, buf)
    bitlen = total * 8
    pos_from_end = lane_width - 1 - idx
    lenbyte = (bitlen >> jnp.minimum(8 * pos_from_end, 56)) & 0xFF
    buf = jnp.where((pos_from_end >= 0) & (pos_from_end < 8), lenbyte, buf)
    return buf.astype(jnp.uint8), nblocks_lane[0]


def verify_kernel(pub, sig, msg, msglen, nblocks: int):
    """(32, B) u8, (64, B) u8, (M, B) u8, (B,) i32 -> (B,) bool.

    Semantics are bit-identical to crypto.edwards.verify_zip215 (the
    pure-Python oracle); differential fuzz in tests/test_ops_kernel.py.
    """
    n = pub.shape[-1]
    r_enc = sig[:32]
    s_bytes = sig[32:]
    # one decompression for A and R, concatenated on the trailing batch
    # axis: (32, ..., 2B)
    both, both_ok = C.decompress(jnp.concatenate([pub, r_enc], axis=-1))
    a_pt = tuple(c[..., :n] for c in both)
    r_pt = tuple(c[..., n:] for c in both)
    a_ok, r_ok = both_ok[..., :n], both_ok[..., n:]
    s_ok = SC.bytes_lt_l(s_bytes)

    buf, nblocks_lane = build_padded_input(r_enc, pub, msg, msglen, nblocks)
    digest = SH.sha512_padded(buf, nblocks, nblocks_lane)
    k_nib = SC.limbs_to_nibbles(SC.reduce_digest(digest))
    s_nib = C.nibbles_from_bytes_le(s_bytes)

    p1 = C.comb_mul_base(s_nib)                    # [S]B
    p2 = C.window_mul(k_nib, C.pt_neg(a_pt))       # [k](-A)
    q = C.pt_add(C.pt_add(p1, p2), C.pt_neg(r_pt))
    eq_ok = C.pt_is_identity(C.mul8(q))
    return eq_ok & a_ok & r_ok & s_ok


def verify_kernel_keyed(
    pub, sig, msg, msglen, key_ids, table, key_valid, nblocks: int,
    window_bits: int,
):
    """Keyed variant: A's decompression and window tables come from the
    device-resident per-validator-set precompute (ops/precompute.py) —
    steady-state commit verification does only SHA-512, R's
    decompression, and comb adds against hot tables.  Reference analog:
    the expanded-pubkey LRU (crypto/ed25519/ed25519.go:43).

    key_ids (B,) int32 are pool slots — pages of ``table``, entries of
    ``key_valid``; semantics otherwise identical to verify_kernel.
    """
    from cometbft_tpu.ops import precompute as PR

    r_enc = sig[:32]
    s_bytes = sig[32:]
    r_pt, r_ok = C.decompress(r_enc)
    s_ok = SC.bytes_lt_l(s_bytes)
    buf, nblocks_lane = build_padded_input(r_enc, pub, msg, msglen, nblocks)
    digest = SH.sha512_padded(buf, nblocks, nblocks_lane)
    k_limbs = SC.reduce_digest(digest)
    if window_bits == 8:
        k_win = SC.limbs_to_windows8(k_limbs)
    else:
        k_win = SC.limbs_to_nibbles(k_limbs)
    p1 = PR.comb_mul_base8(s_bytes)                       # [S]B
    p2 = PR.comb_mul_keyed(table, key_ids, k_win, window_bits)  # [k](-A)
    q = C.pt_add(C.pt_add(p1, p2), C.pt_neg(r_pt))
    eq_ok = C.pt_is_identity(C.mul8(q))
    return eq_ok & r_ok & s_ok & key_valid[key_ids]


def verify_kernel_keyed_packed(
    buf, table, key_valid, bucket: int, nblocks: int, window_bits: int
):
    """Packed keyed variant: (104+bucket, B) u8 rows
    pub[32] | sig[64] | msg[bucket] | msglen_le[4] | key_id_le[4]."""
    pub = buf[:32]
    sig = buf[32:96]
    msg = buf[96 : 96 + bucket]
    lnb = buf[96 + bucket : 100 + bucket].astype(jnp.int32)
    msglen = lnb[0] | (lnb[1] << 8) | (lnb[2] << 16) | (lnb[3] << 24)
    knb = buf[100 + bucket : 104 + bucket].astype(jnp.int32)
    key_ids = knb[0] | (knb[1] << 8) | (knb[2] << 16) | (knb[3] << 24)
    return verify_kernel_keyed(
        pub, sig, msg, msglen, key_ids, table, key_valid, nblocks,
        window_bits,
    )


def verify_kernel_packed(buf, bucket: int, nblocks: int):
    """Single-buffer variant: (32+64+bucket+4, B) u8 -> (B,) bool.

    One fused input buffer means ONE host->device transfer per launch —
    on links where per-transfer latency dominates (PCIe dispatch),
    4 separate transfers would quadruple the
    fixed cost.  Row layout: pub[32] | sig[64] | msg[bucket] |
    msglen_le[4].
    """
    pub = buf[:32]
    sig = buf[32:96]
    msg = buf[96 : 96 + bucket]
    lnb = buf[96 + bucket : 100 + bucket].astype(jnp.int32)
    msglen = lnb[0] | (lnb[1] << 8) | (lnb[2] << 16) | (lnb[3] << 24)
    return verify_kernel(pub, sig, msg, msglen, nblocks)


_kernel_cache: dict[tuple[int, int], object] = {}


def _compiled(batch: int, bucket: int):
    # F.trace_config() in the key: program-shaping flags (COLS_IMPL /
    # SQUARE_IMPL / _DEBUG_CHECKS) flipping mid-process must recompile
    # (counted, and raised after jitguard.seal()), never silently
    # serve the stale program
    key = (batch, bucket, _F.trace_config())
    fn = _kernel_cache.get(key)
    if fn is None:
        _jitguard.note_compile("generic", key)
        nblocks = nblocks_for_bucket(bucket)

        def run(buf):
            return verify_kernel_packed(buf, bucket, nblocks)

        # stable program names: profiler traces and compile logs find
        # the kernel by name after a refactor
        run.__name__ = f"verify_generic_b{bucket}"
        fn = jax.jit(run)
        _kernel_cache[key] = fn
    return fn


_chunked_cache: dict[tuple[int, int, int], object] = {}


def _compiled_chunked(batch: int, bucket: int, chunk: int):
    """One jit program that processes (F, batch) in ``chunk``-wide
    slices via lax.map: the working set stays small (the >8k memory
    cliff never hits) while the whole batch costs ONE dispatch and
    ONE result fetch — the winning trade wherever every launch/fetch
    pays a fixed round trip."""
    key = (batch, bucket, chunk, _F.trace_config())
    fn = _chunked_cache.get(key)
    if fn is None:
        _jitguard.note_compile("chunked", key)
        nblocks = nblocks_for_bucket(bucket)
        k = batch // chunk

        def run(buf):
            chunks = buf.reshape(buf.shape[0], k, chunk).transpose(1, 0, 2)
            out = jax.lax.map(
                lambda c: verify_kernel_packed(c, bucket, nblocks), chunks
            )
            return out.reshape(batch)

        run.__name__ = f"verify_generic_chunked_b{bucket}"
        fn = jax.jit(run)
        _chunked_cache[key] = fn
    return fn


def _next_pow2(n: int) -> int:
    return 1 << max(n - 1, 1).bit_length() if n > 1 else 1


def launch_lanes(n: int) -> tuple[int, int]:
    """-> (lanes, slices): the lanes a launch of ``n`` signatures is
    padded to, and the equal slices its program runs them in.  Up to
    MAX_LAUNCH signatures: the next power of two, one straight program.
    Above it: whole slices of ``min(WIDE_SLICE, MAX_LAUNCH)`` lanes.
    Both constants are read at the call (tests patch MAX_LAUNCH)."""
    if n <= MAX_LAUNCH:
        return max(_next_pow2(n), _MIN_BATCH), 1
    width = min(WIDE_SLICE, MAX_LAUNCH)
    slices = -(-n // width)
    return slices * width, slices


def pack_inputs(
    pub: np.ndarray, sig: np.ndarray, msgs: list[bytes], start: int = 0,
    end: int | None = None, key_ids: np.ndarray | None = None,
) -> tuple[np.ndarray, int]:
    """Pad + pack (pub, sig, msgs[start:end]) into the feature-first
    (100+bucket, batch) u8 layout of verify_kernel_packed — fully
    vectorized, no per-message Python loop. Returns (packed, bucket);
    batch is ``launch_lanes``'s for the messages packed.
    With ``key_ids`` (int32 per message), appends 4 LE id bytes per
    lane for the keyed kernel ((104+bucket, batch))."""
    if end is None:
        end = len(msgs)
    n = end - start
    lens = np.fromiter((len(msgs[i]) for i in range(start, end)),
                       dtype=np.int64, count=n)
    maxlen = int(lens.max()) if n else 0
    bucket = next((b for b in _BUCKETS if b >= maxlen), None)
    if bucket is None:
        raise ValueError(f"message too large for device path: {maxlen}")
    batch = launch_lanes(n)[0]
    tail = 100 if key_ids is None else 104
    packed = np.zeros((tail + bucket, batch), dtype=np.uint8)
    packed[:32, :n] = pub[start:end].T
    packed[32:96, :n] = sig[start:end].T
    flat = np.frombuffer(b"".join(msgs[start:end]), dtype=np.uint8)
    if n and (lens == lens[0]).all():
        if lens[0]:
            packed[96 : 96 + int(lens[0]), :n] = flat.reshape(n, -1).T
    elif n:
        offs = np.concatenate([[0], np.cumsum(lens)])
        col = np.repeat(np.arange(n), lens)
        row = 96 + (np.arange(len(flat)) - offs[col])
        packed[row, col] = flat
    packed[96 + bucket : 100 + bucket, :n] = (
        lens.astype("<u4").view(np.uint8).reshape(n, 4).T
    )
    if key_ids is not None:
        packed[100 + bucket : 104 + bucket, :n] = (
            key_ids[start:end].astype("<u4").view(np.uint8).reshape(n, 4).T
        )
    return packed, bucket


def _launch_span(kernel: str, packed: np.ndarray, sigs: int, slices: int,
                 bucket: int, **args):
    """One launch's counters and its ``device_launch`` span: ``batch``
    lanes carrying ``sigs`` signatures in ``slices`` slices, so a ring
    gives a launch's occupancy as sigs / batch and its padding as
    batch - sigs.  The span covers the (async) dispatch, not device
    compute — the synchronous wall time is the kernel_time_seconds
    histogram."""
    lanes = packed.shape[-1]
    cm = _crypto_metrics()
    cm.batch_verify_launches.labels(kernel=kernel).inc()
    cm.bytes_transferred.labels(direction="h2d").inc(packed.nbytes)
    return _tracer.span(
        "device_launch", cat="device", kernel=kernel, batch=lanes,
        sigs=sigs, slices=slices, bucket=bucket, **args,
    )


def _dispatch(pub, sig, msgs, start, end):
    with _tracer.span("verify/pack", cat="device", batch=end - start):
        packed, bucket = pack_inputs(pub, sig, msgs, start, end)
    fn = _compiled(packed.shape[-1], bucket)
    with _launch_span("generic", packed, end - start, 1, bucket):
        return fn(jax.device_put(packed))


_keyed_cache: dict[tuple[int, int, int], object] = {}


def _compiled_keyed(bucket: int, window_bits: int, slices: int):
    """Jit of the keyed kernel over (buf, table, key_valid); batch and
    table shapes retrace inside the one jit wrapper (jax caches per
    shape; table widths are pow2-padded by the table cache so the
    variant count stays small).  ``slices`` is ``launch_lanes``'s: 1
    is the straight program, more process the lanes in that many equal
    lax.map slices — bounded working set, one dispatch."""
    key = (bucket, window_bits, slices, _F.trace_config())
    fn = _keyed_cache.get(key)
    if fn is None:
        _jitguard.note_compile("keyed", key)
        nblocks = nblocks_for_bucket(bucket)

        def run(buf, table, key_valid):
            if slices == 1:
                return verify_kernel_keyed_packed(
                    buf, table, key_valid, bucket, nblocks, window_bits
                )
            rows, lanes = buf.shape
            chunks = buf.reshape(rows, slices, lanes // slices)
            out = jax.lax.map(
                lambda c: verify_kernel_keyed_packed(
                    c, table, key_valid, bucket, nblocks, window_bits
                ),
                chunks.transpose(1, 0, 2),
            )
            return out.reshape(lanes)

        run.__name__ = f"verify_keyed_w{window_bits}_b{bucket}"
        fn = jax.jit(run)
        _keyed_cache[key] = fn
    return fn


def verify_arrays_keyed_async(entry, key_ids, pub, sig, msgs):
    """Keyed dispatch: ``entry`` is a precompute.KeySetTables covering
    every key id in ``key_ids``.  Same contract as
    verify_arrays_async."""
    n = len(msgs)
    with _tracer.span("verify/pack", cat="device", batch=n):
        packed, bucket = pack_inputs(pub, sig, msgs, key_ids=key_ids)
    slices = launch_lanes(n)[1]
    fn = _compiled_keyed(bucket, entry.window_bits, slices)
    with _launch_span(
        "keyed", packed, n, slices, bucket, window_bits=entry.window_bits,
    ):
        # valid_device(): the per-entry device copy of the validity
        # mask — a jnp.asarray here paid an implicit h2d transfer per
        # LAUNCH (caught by the CMT_TPU_JITGUARD transfer window)
        out = fn(
            jax.device_put(packed), entry.table, entry.valid_device()
        )
    return [(out, n)]


def verify_arrays_async(pub: np.ndarray, sig: np.ndarray, msgs: list[bytes]):
    """Enqueue verification launches without waiting: returns a list of
    (device_array, chunk_len) pairs.  Batches over MAX_LAUNCH go out
    as ONE chunked launch (lax.map over ``launch_lanes``'s slices
    inside a single XLA program — bounded working set, single
    dispatch); CMT_TPU_MULTI_LAUNCH=1 restores the multi-launch split
    for comparison.  Synchronize through ``_finish`` (or
    verify_stream) — one explicit ``jax.device_get`` per batch, the
    idiom the CMT_TPU_JITGUARD transfer window admits.  Each device
    array is padded to ``launch_lanes`` — slice to its chunk_len."""
    n = len(msgs)
    homogeneous = n > MAX_LAUNCH and not flag_from_env(
        "CMT_TPU_MULTI_LAUNCH"
    )
    if homogeneous:
        # one outlier message would force the WHOLE batch to its
        # length bucket (SHA blocks + transfer scale with the bucket);
        # only take the single-launch path when every message shares
        # the bucket, else fall back to per-chunk bucketing below
        longest = max(len(m) for m in msgs)
        bucket_all = next((b for b in _BUCKETS if b >= longest), None)
        smallest = next(
            (b for b in _BUCKETS if b >= min(len(m) for m in msgs)), None
        )
        homogeneous = bucket_all is not None and bucket_all == smallest
    if homogeneous:
        with _tracer.span("verify/pack", cat="device", batch=n):
            packed, bucket = pack_inputs(pub, sig, msgs)
        lanes, slices = launch_lanes(n)
        fn = _compiled_chunked(lanes, bucket, lanes // slices)
        with _launch_span(
            "generic", packed, n, slices, bucket, chunked=True,
        ):
            return [(fn(jax.device_put(packed)), n)]
    parts = []
    for start in range(0, max(n, 1), MAX_LAUNCH):
        end = min(start + MAX_LAUNCH, n)
        parts.append((_dispatch(pub, sig, msgs, start, end), end - start))
    return parts


def _finish(parts) -> np.ndarray:
    """Synchronize a list of (device_array, chunk_len) parts with ONE
    device->host transfer: results are concatenated ON DEVICE first.
    Every blocking fetch pays a full host<->device round trip, so
    per-chunk fetches would dominate wall time; one eager
    jnp.concatenate dispatches asynchronously and
    the single EXPLICIT ``jax.device_get`` pays the RTT once (explicit
    so the CMT_TPU_JITGUARD transfer window — which disallows implicit
    transfers — recognizes it as the audited fetch)."""
    if len(parts) == 1:
        p, k = parts[0]
        # the device_fetch span is the wait in a profiler session's
        # host plane, beside the program it waits for
        with _tracer.span(
            "device_fetch", cat="device", thread_clock=True, batch=k,
        ):
            out = jax.device_get(p)  # host sync: the one audited per-batch result fetch
        _crypto_metrics().bytes_transferred.labels(
            direction="d2h"
        ).inc(out.nbytes)
        return out[:k]
    with _tracer.span(
        "device_fetch", cat="device", thread_clock=True,
        batch=sum(k for _, k in parts),
    ):
        combined = jax.device_get(  # host sync: single combined fetch for all parts
            jnp.concatenate([p for p, _ in parts])
        )
    _crypto_metrics().bytes_transferred.labels(
        direction="d2h"
    ).inc(combined.nbytes)
    out = []
    off = 0
    for p, k in parts:
        out.append(combined[off : off + k])
        off += p.shape[0]
    return np.concatenate(out)


def verify_arrays(pub: np.ndarray, sig: np.ndarray, msgs: list[bytes]):
    """Host entry: numpy (n,32), (n,64), list of n messages -> bool[n].

    Pads to (``launch_lanes``, length bucket); one device launch,
    or one per MAX_LAUNCH signatures where the messages span buckets.
    """
    return _finish(verify_arrays_async(pub, sig, msgs))


def verify_stream(jobs, max_in_flight: int = 8, dispatch=None):
    """Pipelined verification: ``jobs`` yields (pub, sig, msgs) tuples;
    yields bool[n] results in order, keeping up to ``max_in_flight``
    jobs outstanding so device compute overlaps host packing and
    transfers.  Completed windows synchronize with a single combined
    fetch (see _finish) instead of one round trip per job.

    ``dispatch`` overrides the async launcher — e.g. a closure over
    verify_arrays_keyed_async with a hot per-validator table entry, so
    replay planes stream through the precomputed path."""
    from collections import deque

    if dispatch is None:
        dispatch = verify_arrays_async
    pending: deque = deque()

    def flush(count: int):
        # one combined fetch for the oldest ``count`` jobs (they are
        # the most likely to have finished computing); newer jobs stay
        # in flight so the device never drains
        batch = [pending.popleft() for _ in range(count)]
        combined = _finish([pt for job_parts in batch for pt in job_parts])
        off = 0
        for job_parts in batch:
            n = sum(k for _, k in job_parts)
            yield combined[off : off + n]
            off += n

    for job in jobs:
        pending.append(dispatch(*job))
        if len(pending) >= max_in_flight:
            yield from flush(max(1, len(pending) // 2))
    if pending:
        yield from flush(len(pending))


# -- host or device: the one decision -----------------------------------
# ``TpuBatchVerifier._plan()`` decides which tiers a batch of n
# signatures is ELIGIBLE for, from what it can observe; the ladder
# (crypto/dispatch.LADDER.admissible) then says which of those are
# AVAILABLE, and the walk is ``admissible + ["host", "python"]``.  With
# threshold = ``device_min_batch`` (ACCELERATOR_MIN_BATCH on an
# accelerator, NO_DEVICE_DISPATCH on the CPU backend, or the
# CMT_TPU_DEVICE_MIN_BATCH / constructor override):
#
#   n < DEVICE_MIN_BATCH and n < threshold     host, ``batch_size``
#   DEVICE_MIN_BATCH <= n < threshold          keyed only if the set's
#       tables are already warm (TABLE_CACHE.peek; ``keyed_warm``), else
#       host, ``batch_size`` — a cold set is never built for here
#   n >= threshold                             keyed after
#       TABLE_CACHE.lookup_or_build, generic beside it, ``batch_size``
#   a message over the largest bucket          host, ``msg_too_large``
#   the CPU backend (no override)              host, ``cpu_backend``
#   every eligible tier demoted                host, ``ladder_demoted``
#
# CMT_TPU_DISABLE_PRECOMPUTE or a demoted keyed tier skips the table
# lookup (generic alone from the threshold up); a lookup that raises
# faults the keyed tier and the batch goes on without it.

#: The static floor: below this many signatures nothing goes to the
#: device on warm tables (a launch's fixed round trip is unchanged by
#: warm tables and can never pay for a 2-signature evidence check;
#: reference analog: types/validation.go:15 shouldBatchVerify).
DEVICE_MIN_BATCH = 64

#: The dispatch threshold on an accelerator.  A constant, not a
#: crossover model: it is what the retired formula (round trip / 10 us)
#: gave on the v5e's host at every start-up measured (1.06-1.20 ms ->
#: n* 106-120 -> 128, PR 22), frozen because a threshold rounded from a
#: tiny transfer's noisy round trip flips between pow2s — at exactly
#: the 150-validator size — and that round trip is not the chip's
#: launch floor anyway.  It is also the largest pow2 that still sends
#: a 150-validator commit to the chip.  Measured on either side of it:
#: the chip ~5.9 ms against the host's 7.9 ms at 256 signatures (PR
#: 27); between 65 and 127 on warm tables nothing has been measured
#: (ROADMAP Design 2a).  tools/derive_device_min_batch.py measures the
#: host/device crossover on the attached chip (1,024 for the generic
#: kernel on the v5e, PR 22).
ACCELERATOR_MIN_BATCH = 128

#: Threshold on the XLA-on-CPU backend: the "device" there IS the host
#: CPU running the XLA kernel — strictly slower than the host batch
#: verifier — so no batch size ever dispatches to it unless a caller
#: passes ``device_min_batch`` explicitly (the tests do).
NO_DEVICE_DISPATCH = 1 << 30


def measure_link_rtt() -> float:
    """Min of 3 tiny transfer round trips (device_put + host fetch).
    Start-up's proof that the device answers (crypto/batch.
    init_device_plane logs and exports it); raises if it does not."""
    probe = np.zeros(8, dtype=np.uint8)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        np.asarray(jax.device_put(probe))  # host sync: deliberate RTT probe — the round trip IS the measurement
        best = min(best, time.perf_counter() - t0)
    return best


def runtime_device_min_batch() -> int:
    """The dispatch threshold: env override > backend rule."""
    env = os.environ.get("CMT_TPU_DEVICE_MIN_BATCH")  # env ok: explicit 0 means "always device" — a minimum floor cannot express the unset-vs-0 distinction
    if env:
        try:
            return int(env)
        except ValueError:
            raise ValueError(
                f"CMT_TPU_DEVICE_MIN_BATCH={env!r} is not an integer"
            ) from None
    if jax.devices()[0].platform == "cpu":
        return NO_DEVICE_DISPATCH
    return ACCELERATOR_MIN_BATCH


class _VerifyPlan:
    """Host-phase output of :meth:`TpuBatchVerifier.plan`: the dispatch
    routing decision plus everything :meth:`TpuBatchVerifier.execute`
    needs to launch — packed pub/sig arrays, the key-set table entry
    and per-lane key ids for the keyed tier.  The split exists for the
    verify queue (crypto/verify_queue.py): its collector thread runs
    ``plan()`` for buffer N+1 while buffer N's ``execute()`` launch is
    in flight, so host packing overlaps device compute.  ``verify()``
    remains ``execute(plan())`` — single-threaded callers see the
    exact pre-split behavior."""

    __slots__ = (
        "n", "route", "reason", "entry", "key_ids", "pub", "sig",
        "msgs", "pubs", "sigs", "tiers",
    )

    def __init__(self) -> None:
        self.n = 0
        self.route = "empty"
        self.reason = "batch_size"
        self.entry = None
        self.key_ids = None
        self.pub = None
        self.sig = None
        self.msgs: list[bytes] = []
        self.pubs: list[bytes] = []
        self.sigs: list[bytes] = []
        #: ladder-admissible tiers for this batch, best first, always
        #: ending in the host/python floor (crypto/dispatch.py);
        #: execute() walks this list top-down
        self.tiers: list[str] = []


class TpuBatchVerifier(BatchVerifier):
    """BatchVerifier provider backed by the device kernel
    (the reference's crypto/ed25519/ed25519.go:190 BatchVerifier slot).
    """

    def __init__(self, device_min_batch: int | None = None) -> None:
        if device_min_batch is None:
            device_min_batch = runtime_device_min_batch()
        self._device_min_batch = device_min_batch
        self._pubs: list[bytes] = []
        self._msgs: list[bytes] = []
        self._sigs: list[bytes] = []
        # dispatch-ladder tier the last batch ACTUALLY ran on, set by
        # the _run_* seam that executed (mesh subclasses report their
        # own tiers); verify() feeds it to crypto_dispatch_tier
        self._last_tier: str | None = None

    def add(self, pub_key: PubKey, msg: bytes, sig: bytes) -> None:
        if pub_key.type() != _ed.KEY_TYPE:
            raise TypeError("TpuBatchVerifier requires ed25519 keys")
        if len(sig) != _ed.SIGNATURE_SIZE:
            raise ValueError("malformed signature size")
        self._pubs.append(pub_key.bytes())
        self._msgs.append(bytes(msg))
        self._sigs.append(bytes(sig))

    def __len__(self) -> int:
        return len(self._pubs)

    # -- ladder eligibility (crypto/dispatch.py owns admissibility) ------

    def _keyed_tiers(self) -> list[str]:
        """Keyed tiers this verifier can run, best first (the mesh
        verifier prepends keyed_mesh)."""
        return ["keyed"]

    def _generic_tiers(self) -> list[str]:
        return ["generic"]

    def plan(self) -> _VerifyPlan:
        """Host phase: the dispatch routing decision (ladder tier
        selection, keyed-table lookup/warm-peek) plus input packing —
        everything that happens BEFORE the device launch.  Safe to run
        on the verify queue's collector thread while another batch's
        :meth:`execute` launch is in flight."""
        with _tracer.span(
            "verify/plan", cat="crypto", batch=len(self._pubs),
        ) as sp:
            plan = self._plan()
            sp.set(route=plan.route)
            return plan

    def _plan(self) -> _VerifyPlan:
        plan = _VerifyPlan()
        n = plan.n = len(self._pubs)
        if n == 0:
            return plan
        plan.pubs, plan.msgs, plan.sigs = (
            self._pubs, self._msgs, self._sigs
        )
        cm = _crypto_metrics()
        ladder = _failover.LADDER
        device_usable = self._device_min_batch < NO_DEVICE_DISPATCH
        msg_fits = max(len(m) for m in self._msgs) <= _BUCKETS[-1]
        entry = None
        reason = "batch_size"
        keyed_admissible = any(
            ladder.active(t) for t in self._keyed_tiers()
        )
        if device_usable and msg_fits and keyed_admissible and (
            not flag_from_env("CMT_TPU_DISABLE_PRECOMPUTE")
        ):
            # when every keyed tier is demoted the lookup is skipped
            # entirely: a dead device must not stall the plan phase
            # behind a table build no admissible tier could use
            from cometbft_tpu.ops import precompute as _pr

            try:
                if n >= self._device_min_batch:
                    entry = _pr.TABLE_CACHE.lookup_or_build(self._pubs)
                elif n >= DEVICE_MIN_BATCH:
                    # the threshold guards a table build and the
                    # generic kernel's cost; with warm tables the
                    # device does only SHA-512 + R decompress + comb
                    # adds.  peek() never builds, so a cold set is not
                    # stalled behind an EC build it didn't ask for.
                    entry = _pr.TABLE_CACHE.peek(self._pubs)
                    if entry is not None:
                        reason = "keyed_warm"
            except Exception as exc:  # noqa: BLE001 — typed escalation:
                # a table lookup/build failure is a KEYED-tier fault;
                # the ladder demotes it (reason on the demotion metric
                # + crypto/dispatch_transition flight event) and this
                # batch walks on at the generic tier — the silent
                # swallow this block used to be is now a signal
                ladder.tier_fault(
                    "keyed",
                    reason=f"table_lookup:{type(exc).__name__}",
                    batch=n,
                )
                entry = None
        # eligible device tiers for THIS batch, ladder order
        eligible: list[str] = []
        if entry is not None:
            eligible += self._keyed_tiers()
        if device_usable and msg_fits and n >= self._device_min_batch:
            eligible += self._generic_tiers()
        admissible = ladder.admissible(eligible)
        if not admissible:
            # Host route: batch too small, message beyond the largest
            # device bucket (honor the BatchVerifier contract via the
            # host fallback instead of raising mid-verify), the
            # XLA-on-CPU backend (NO_DEVICE_DISPATCH), or every
            # eligible device tier currently demoted.
            if eligible:
                reason = "ladder_demoted"
            elif n >= self._device_min_batch:
                reason = "msg_too_large"
            elif not device_usable:
                reason = "cpu_backend"
            elif not msg_fits:
                reason = "msg_too_large"
            else:
                reason = "batch_size"
            cm.dispatch_decisions.labels(route="host", reason=reason).inc()
            plan.route = "host"
            plan.reason = reason
            plan.tiers = ["host", _failover.FLOOR_TIER]
            return plan
        cm.dispatch_decisions.labels(route="device", reason=reason).inc()
        cm.batch_verify_batch_size.observe(n)
        plan.route = "device"
        plan.reason = reason
        plan.entry = entry
        plan.tiers = admissible + ["host", _failover.FLOOR_TIER]
        if entry is not None:
            plan.key_ids = entry.key_ids(self._pubs)
        plan.pub = np.frombuffer(
            b"".join(self._pubs), dtype=np.uint8
        ).reshape(n, 32)
        plan.sig = np.frombuffer(
            b"".join(self._sigs), dtype=np.uint8
        ).reshape(n, 64)
        return plan

    def execute(self, plan: _VerifyPlan) -> tuple[bool, list[bool]]:
        """Device phase: walk the plan's ladder tiers top-down — chaos
        injection, launch + result fetch per device tier, typed fault
        escalation (a failing tier is demoted through
        crypto/dispatch.LADDER and the batch continues one rung down),
        with the host/python floor guaranteeing an answer.
        ``verify()`` is ``execute(plan())``."""
        if plan.route == "empty":
            return False, []
        cm = _crypto_metrics()
        ladder = _failover.LADDER
        n = plan.n
        self._last_tier = None
        last_exc: BaseException | None = None
        tiers = plan.tiers or ["host", _failover.FLOOR_TIER]
        for pos, tier in enumerate(tiers):
            if tier not in ("host", _failover.FLOOR_TIER) and (
                not ladder.active(tier)
            ):
                continue  # demoted since plan time (queue parked it)
            t_tier = time.perf_counter()
            try:
                if tier == _failover.FLOOR_TIER:
                    ok, results = self._run_python(plan)
                elif tier == "host":
                    ok, results = self._run_host(plan)
                else:
                    t0 = time.perf_counter()
                    results = self._launch_tier(tier, plan)
                    ok = all(results)
                    cm.kernel_time_seconds.observe(
                        time.perf_counter() - t0
                    )
            except Exception as exc:  # noqa: BLE001 — the escalation
                # seam: ANY tier failure (chaos fault, device loss,
                # RetraceError under a sealed guard, native-lib crash)
                # demotes the tier and walks one rung down; only the
                # python floor re-raises — if pure per-signature
                # verification raises, that is a programming error,
                # not an availability problem
                if tier == _failover.FLOOR_TIER:
                    raise
                last_exc = exc
                ladder.tier_fault(
                    tier, reason=_failover.fault_reason(exc), batch=n,
                    duplicate=getattr(
                        exc, "_ladder_watchdog_fired", False
                    ),
                )
                continue
            self._last_tier = tier
            # the wall is this tier's run only, never a failed rung
            # above it
            ladder.note_batch(
                tier, batch=n, seconds=time.perf_counter() - t_tier
            )
            return ok, results
        # unreachable while the python floor is in the walk; keep the
        # failure honest if a caller hands a floorless plan
        raise last_exc if last_exc is not None else RuntimeError(
            "dispatch ladder exhausted without a floor tier"
        )

    def verify(self) -> tuple[bool, list[bool]]:
        return self.execute(self.plan())

    # -- per-tier execution ----------------------------------------------

    def _launch_tier(self, tier: str, plan: _VerifyPlan) -> list[bool]:
        """One device-tier attempt: the ``batch_verify`` span over the
        sealed-transfer window, the watchdog and the tier's runner.
        Its steps are spans of their own on this thread:
        ``batch_verify/arm`` (the window entered, the watchdog armed,
        the chaos hook), the runner's ``verify/pack``,
        ``device_launch`` and ``device_fetch``, then
        ``batch_verify/settle`` (the watchdog disarmed, the verdict
        list).  Returns the per-signature verdict list."""
        n = plan.n
        wd = None
        try:
            with _tracer.span(
                "batch_verify", cat="crypto", thread_clock=True,
                kernel=tier, batch=n,
            ) as sp, contextlib.ExitStack() as armed:
                with _tracer.span("batch_verify/arm", cat="crypto"):
                    # steady-state window: once jitguard is armed and
                    # sealed, an implicit host<->device transfer
                    # anywhere in the dispatch raises at the offending
                    # line instead of silently paying the link RTT per
                    # batch
                    armed.enter_context(_jitguard.transfer_window())
                    # the launch watchdog: a wedged launch becomes
                    # crypto_device_hangs_total + a flight event inside
                    # its budget, not a silent stall
                    wd = armed.enter_context(
                        _health.WATCHDOG.watch(tier=tier, batch=n)
                    )
                    # chaos injects INSIDE the armed watchdog window: a
                    # launch_hang fault sleeps past the budget while
                    # the watchdog is watching, so the overrun fires
                    # (counter + flight event + ladder demotion) before
                    # the stalled "launch" returns — the r04 signature,
                    # reproduced end to end (crypto/dispatch.py)
                    _failover.CHAOS.inject(tier)
                out = self._run_tier(tier, plan)
                with _tracer.span("batch_verify/settle", cat="crypto"):
                    armed.close()  # the watchdog, then the window
                    results = [bool(v) for v in out]
                sp.set(ok=all(results), tier=tier)
            return results
        except Exception as exc:
            # the watchdog already demoted this launch's tier at the
            # overrun; mark the escalation so execute() records the
            # second signal WITHOUT advancing the back-off again
            if wd is not None and wd["fired"]:
                exc._ladder_watchdog_fired = True
            raise

    def _run_tier(self, tier: str, plan: _VerifyPlan) -> np.ndarray:
        """tier name -> runner (the mesh verifier extends this with
        the *_mesh tiers)."""
        if tier == "keyed":
            return self._run_keyed(
                plan.entry, plan.key_ids, plan.pub, plan.sig, plan.msgs
            )
        if tier == "generic":
            return self._run_generic(plan.pub, plan.sig, plan.msgs)
        raise _failover.TierUnavailable(tier, "no runner on this seam")

    def _run_host(self, plan: _VerifyPlan) -> tuple[bool, list[bool]]:
        """The native host batch tier (Pippenger/RLC MSM with the
        reference's per-signature re-verify for exact verdicts)."""
        cpu = _ed.CpuBatchVerifier()
        for p, m, s in zip(plan.pubs, plan.msgs, plan.sigs):
            cpu.add(_ed.Ed25519PubKey(p), m, s)
        return cpu.verify()

    def _run_python(self, plan: _VerifyPlan) -> tuple[bool, list[bool]]:
        """The pure per-signature floor — the tier consensus liveness
        rests on when everything above it is demoted."""
        results = [
            _ed.Ed25519PubKey(p).verify_signature(m, s)
            for p, m, s in zip(plan.pubs, plan.msgs, plan.sigs)
        ]
        return all(results), results

    # dispatch seam: the multi-chip verifier (parallel/mesh.py
    # ShardedTpuBatchVerifier) adds mesh-sharded runners on top of
    # these single-device ones; callers only ever see the
    # BatchVerifier interface.
    def _run_generic(self, pub, sig, msgs) -> np.ndarray:
        return _finish(verify_arrays_async(pub, sig, msgs))

    def _run_keyed(self, entry, key_ids, pub, sig, msgs) -> np.ndarray:
        return _finish(
            verify_arrays_keyed_async(entry, key_ids, pub, sig, msgs)
        )


#: shape/dtype contracts for the public kernels (PURE literals —
#: tools/jitcheck.py verifies them statically against the signatures;
#: tests/test_jitcheck.py sweeps them through jax.eval_shape across
#: the bucket ladder; grammar in ops/contracts.py).  Dims: B = batch
#: lanes, M = message bucket width, nblocks = SHA-512 blocks for the
#: bucket.  The int32-limb / uint8-packed-buffer representation is
#: load-bearing (docs/device_contracts.md) — a dtype drift here is a
#: silent perf or correctness regression on device.
_CONTRACTS = {
    "build_padded_input": {
        "args": {
            "r_enc": ("u8", (32, "B")),
            "a_enc": ("u8", (32, "B")),
            "msg": ("u8", ("M", "B")),
            "msglen": ("i32", ("B",)),
        },
        "static": ("nblocks",),
        "out": [("u8", ("nblocks*128", "B")), ("i64", ("B",))],
    },
    "verify_kernel": {
        "args": {
            "pub": ("u8", (32, "B")),
            "sig": ("u8", (64, "B")),
            "msg": ("u8", ("M", "B")),
            "msglen": ("i32", ("B",)),
        },
        "static": ("nblocks",),
        "out": ("bool", ("B",)),
    },
    "verify_kernel_packed": {
        "args": {"buf": ("u8", ("100+bucket", "B"))},
        "static": ("bucket", "nblocks"),
        "out": ("bool", ("B",)),
    },
    "verify_kernel_keyed": {
        "args": {
            "pub": ("u8", (32, "B")),
            "sig": ("u8", (64, "B")),
            "msg": ("u8", ("M", "B")),
            "msglen": ("i32", ("B",)),
            "key_ids": ("i32", ("B",)),
            "table": ("i32", ("cap", "nwin*nent", "ROW")),
            "key_valid": ("bool", ("cap",)),
        },
        "static": ("nblocks", "window_bits"),
        "out": ("bool", ("B",)),
    },
    "verify_kernel_keyed_packed": {
        "args": {
            "buf": ("u8", ("104+bucket", "B")),
            "table": ("i32", ("cap", "nwin*nent", "ROW")),
            "key_valid": ("bool", ("cap",)),
        },
        "static": ("bucket", "nblocks", "window_bits"),
        "out": ("bool", ("B",)),
    },
}

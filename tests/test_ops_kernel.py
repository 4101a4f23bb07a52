"""Differential tests for the device kernel stack: curve ops, SHA-512,
scalar reduction, and the assembled ed25519 batch verifier vs the
pure-Python ZIP-215 oracle (crypto/edwards.py).

The oracle-vs-kernel agreement here is the consensus-safety property:
the TPU path must never disagree with the reference semantics
(crypto/ed25519/ed25519.go:39 curve25519-voi ZIP-215).
"""

import hashlib
import os
import random

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import edwards as E
from cometbft_tpu.ops import curve as C
from cometbft_tpu.ops import field as F
from cometbft_tpu.ops import scalar as SC
from cometbft_tpu.ops import sha512 as SH
from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier


def to_dev(pt):
    x, y = E.pt_to_affine(pt)
    return tuple(jnp.asarray(F.from_int(v)) for v in (x, y, 1, x * y % E.P))


def comb_digits(ks, window_bits):
    """(nwin, len(ks)) int32 LE ``window_bits``-wide digits of ``ks``."""
    mask = (1 << window_bits) - 1
    return np.array(
        [
            [(k >> (window_bits * w)) & mask for k in ks]
            for w in range(256 // window_bits)
        ],
        dtype=np.int32,
    )


def affine_eq(dev_pt, ref_pt):
    x, y, z, _ = (F.to_int(np.asarray(c)) % E.P for c in dev_pt)
    zi = pow(z, E.P - 2, E.P)
    rx, ry = E.pt_to_affine(ref_pt)
    return (x * zi % E.P) == rx and (y * zi % E.P) == ry


class TestCurve:
    def test_add_double_vs_oracle(self, rng):
        for _ in range(3):
            p = E.pt_mul(rng.randrange(1, E.L), E.B_POINT)
            q = E.pt_mul(rng.randrange(1, E.L), E.B_POINT)
            assert affine_eq(jax.jit(C.pt_add)(to_dev(p), to_dev(q)), E.pt_add(p, q))
            assert affine_eq(jax.jit(C.pt_double)(to_dev(p)), E.pt_double(p))

    def test_decompress_zip215(self, rng):
        encs, expect = [], []
        pts = [E.pt_mul(rng.randrange(1, E.L), E.B_POINT) for _ in range(4)]
        for p in pts:
            encs.append(E.encode_point(p))
            expect.append(True)
        encs.append((E.P + 1).to_bytes(32, "little"))  # non-canonical y
        expect.append(True)
        minus_zero = bytearray((1).to_bytes(32, "little"))
        minus_zero[31] |= 0x80
        encs.append(bytes(minus_zero))  # "-0"
        expect.append(True)
        bad = next(
            y.to_bytes(32, "little")
            for y in range(2, 100)
            if E._recover_x(y, 0) is None
        )
        encs.append(bad)  # non-square
        expect.append(False)
        arr = jnp.asarray(
            np.frombuffer(b"".join(encs), dtype=np.uint8).reshape(len(encs), 32).T
        )
        pt_dev, valid = jax.jit(C.decompress)(arr)
        assert [bool(v) for v in np.asarray(valid)] == expect
        for i, p in enumerate(pts):
            assert affine_eq(tuple(c[:, i] for c in pt_dev), p)
        for i in (4, 5):  # ZIP-215 cases agree with the oracle decoder
            ref = E.decode_point(encs[i])
            assert affine_eq(tuple(c[:, i] for c in pt_dev), ref)

    def test_scalar_mults_vs_oracle(self, rng):
        scalars = [rng.randrange(0, E.L) for _ in range(4)]
        sb = jnp.asarray(
            np.stack(
                [
                    np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
                    for s in scalars
                ],
                axis=-1,
            )
        )
        comb = jax.jit(lambda b: C.comb_mul_base(C.nibbles_from_bytes_le(b)))(sb)
        pts = [E.pt_mul(rng.randrange(1, E.L), E.B_POINT) for _ in range(4)]
        p4 = tuple(
            jnp.stack([to_dev(p)[c] for p in pts], axis=-1) for c in range(4)
        )
        win = jax.jit(lambda b, p: C.window_mul(C.nibbles_from_bytes_le(b), p))(
            sb, p4
        )
        for i, s in enumerate(scalars):
            assert affine_eq(
                tuple(c[:, i] for c in comb), E.pt_mul(s, E.B_POINT)
            )
            assert affine_eq(tuple(c[:, i] for c in win), E.pt_mul(s, pts[i]))

    def test_identity_and_mul8(self):
        assert bool(np.asarray(C.pt_is_identity(C.identity(()))))
        torsion = E.decode_point(E.small_order_points()[3])
        assert bool(
            np.asarray(C.pt_is_identity(jax.jit(C.mul8)(to_dev(torsion))))
        )


class TestSha512:
    @pytest.mark.parametrize(
        "msg", [b"", b"abc", b"a" * 111, b"a" * 112, b"x" * 250]
    )
    def test_vs_hashlib(self, msg):
        buf, nblk = SH.pad_message(msg)
        got = np.asarray(
            jax.jit(SH.sha512_padded, static_argnums=1)(jnp.asarray(buf), nblk)
        )
        assert bytes(got) == hashlib.sha512(msg).digest()


class TestScalarModL:
    def test_reduce_digest(self):
        rng = random.Random(3)
        vals = [rng.randrange(0, 2**512) for _ in range(64)]
        vals[:6] = [0, 1, E.L - 1, E.L, E.L + 1, 2**512 - 1]
        digests = np.stack(
            [
                np.frombuffer(v.to_bytes(64, "little"), dtype=np.uint8)
                for v in vals
            ],
            axis=-1,
        )
        red = np.asarray(jax.jit(SC.reduce_digest)(jnp.asarray(digests)))
        nib = np.asarray(SC.limbs_to_nibbles(jnp.asarray(red)))
        for i, v in enumerate(vals):
            got = sum(int(red[j, i]) << (16 * j) for j in range(16))
            assert got == v % E.L
            assert sum(int(nib[j, i]) << (4 * j) for j in range(64)) == v % E.L

    def test_bytes_lt_l(self):
        vals = [0, 1, E.L - 1, E.L, E.L + 1, 2**256 - 1]
        sb = np.stack(
            [np.frombuffer(v.to_bytes(32, "little"), dtype=np.uint8) for v in vals],
            axis=-1,
        )
        lt = np.asarray(jax.jit(SC.bytes_lt_l)(jnp.asarray(sb)))
        assert [bool(v) for v in lt] == [v < E.L for v in vals]


class TestBatchVerifyKernel:
    def test_crafted_cases(self):
        bv = TpuBatchVerifier(device_min_batch=0)
        expected = []
        privs = [ed.gen_priv_key() for _ in range(6)]
        for i, priv in enumerate(privs):
            m = bytes([i]) * (10 + i * 23)
            sig = priv.sign(m)
            ok = True
            if i == 2:
                sig = sig[:-1] + bytes([sig[-1] ^ 1])
                ok = False
            if i == 4:
                m = m + b"!"
                ok = False
            bv.add(priv.pub_key(), m, sig)
            expected.append(ok)
        # ZIP-215 edge: identity pubkey, R=identity, S=0 verifies
        ident = E.encode_point(E.IDENTITY)
        bv.add(ed.Ed25519PubKey(ident), b"edge", ident + bytes(32))
        expected.append(True)
        # S >= L rejected
        bv.add(
            privs[0].pub_key(),
            b"m",
            E.encode_point(E.B_POINT) + E.L.to_bytes(32, "little"),
        )
        expected.append(False)
        ok, results = bv.verify()
        assert results == expected
        assert ok == all(expected)

    def test_differential_fuzz_vs_oracle(self, rng):
        bv = TpuBatchVerifier(device_min_batch=0)
        oracle = []
        for _ in range(24):
            priv = ed.gen_priv_key()
            m = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 150)))
            sig = bytearray(priv.sign(m))
            pub = bytearray(priv.pub_key().bytes())
            r = rng.random()
            if r < 0.3:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            elif r < 0.45:
                pub[rng.randrange(32)] ^= 1 << rng.randrange(8)
            elif r < 0.55:
                m = m + b"x"
            bv.add(ed.Ed25519PubKey(bytes(pub)), m, bytes(sig))
            oracle.append(E.verify_zip215(bytes(pub), m, bytes(sig)))
        _, results = bv.verify()
        assert results == oracle

    def test_empty_batch(self):
        ok, results = TpuBatchVerifier(device_min_batch=0).verify()
        assert not ok and results == []

    def test_cpu_and_tpu_verifiers_agree(self):
        priv = ed.gen_priv_key()
        m = b"agreement"
        sig = priv.sign(m)
        for cls in (ed.CpuBatchVerifier, TpuBatchVerifier):
            bv = cls() if cls is ed.CpuBatchVerifier else cls(device_min_batch=0)
            bv.add(priv.pub_key(), m, sig)
            bv.add(priv.pub_key(), m + b"?", sig)
            ok, res = bv.verify()
            assert not ok and res == [True, False]


class TestChunkedLaunches:
    def test_non_pow2_max_launch_alignment(self, rng, monkeypatch):
        """A batch over a MAX_LAUNCH that is no power of two pads to
        whole slices of it (``launch_lanes``: 23 signatures in 30
        lanes), and the verdicts of the padding are sliced off the end
        (regression: a non-pow2 MAX_LAUNCH misaligned every verdict
        after the first chunk)."""
        from cometbft_tpu.ops import ed25519_verify as ev

        monkeypatch.setattr(ev, "MAX_LAUNCH", 10)
        bv = TpuBatchVerifier(device_min_batch=0)
        oracle = []
        priv = ed.gen_priv_key()
        for i in range(23):  # 3 slices of 10: the last 3 signatures, 7 zeros
            m = bytes([i]) * 40
            sig = bytearray(priv.sign(m))
            ok = True
            if i in (9, 10, 22):  # straddle every chunk boundary
                sig[5] ^= 0x40
                ok = False
            bv.add(priv.pub_key(), m, bytes(sig))
            oracle.append(ok)
        _, results = bv.verify()
        assert results == oracle

    def test_generic_tier_pads_a_wide_batch_to_whole_slices(
        self, monkeypatch
    ):
        """The generic tier is the ladder's next rung for the same
        batch and pads by the same rule: ONE launch, 23 signatures in
        30 lanes run as three slices of 10, no power of two in sight;
        flipped signatures on both sides of each seam and in the last,
        partly empty slice."""
        from cometbft_tpu.ops import ed25519_verify as ev
        from cometbft_tpu.utils.trace import TRACER

        monkeypatch.setattr(ev, "MAX_LAUNCH", 10)
        assert ev.launch_lanes(23) == (30, 3)
        priv = ed.priv_key_from_secret(b"generic-wide")
        pubs = np.tile(
            np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8), (23, 1)
        )
        msgs = [bytes([i]) * 40 for i in range(23)]
        sigs = np.stack(
            [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
        )
        bad = {9, 10, 19, 20, 22}
        for i in bad:
            sigs[i, 5] ^= 0x40
        packed, bucket = ev.pack_inputs(pubs, sigs, msgs)
        assert packed.shape == (100 + bucket, 30)
        assert not packed[:, 23:].any()
        was = TRACER.enabled
        TRACER.set_enabled(True)
        try:
            TRACER.clear()
            parts = ev.verify_arrays_async(pubs, sigs, msgs)
            launches = [e["args"] for e in TRACER.events()
                        if e["name"] == "device_launch"]
        finally:
            TRACER.set_enabled(was)
        assert len(parts) == 1 and parts[0][0].shape == (30,)
        assert len(launches) == 1 and launches[0]["chunked"] is True
        assert (launches[0]["sigs"], launches[0]["batch"],
                launches[0]["slices"]) == (23, 30, 3)
        out = ev._finish(parts)
        assert out.tolist() == [i not in bad for i in range(23)]


@pytest.mark.slow
def test_chunked_single_launch_matches_multi_launch(monkeypatch):
    """Batches beyond MAX_LAUNCH go out as ONE lax.map-chunked launch;
    verdicts must match the multi-launch path bit-for-bit, including
    invalid signatures planted on both sides of every chunk boundary
    and a non-multiple-of-chunk tail.

    Soak tier (~4 min of chunk-variant compiles single-core); the
    chunk-boundary semantics stay covered in the default gate by
    test_non_pow2_max_launch_alignment."""
    import os

    import numpy as np

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519_verify as EV

    monkeypatch.setattr(EV, "MAX_LAUNCH", 64)
    n = 200  # chunked: 256 lanes in 4 slices; multi: 64, 64, 64 and 8
    rng = np.random.RandomState(5)
    priv = ed.priv_key_from_secret(b"chunked")
    pub_b = np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8)
    msgs = [rng.bytes(100) for _ in range(n)]
    sigs = np.stack(
        [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
    )
    bad = {0, 63, 64, 127, 128, 199}
    for i in bad:
        sigs[i, 3] ^= 0xFF
    pubs = np.tile(pub_b, (n, 1))

    out_chunked = EV.verify_arrays(pubs, sigs, msgs)
    monkeypatch.setenv("CMT_TPU_MULTI_LAUNCH", "1")
    out_multi = EV.verify_arrays(pubs, sigs, msgs)
    assert out_chunked.shape == out_multi.shape == (n,)
    assert (out_chunked == out_multi).all()
    for i in range(n):
        assert out_chunked[i] == (i not in bad), i


def test_mixed_bucket_batch_falls_back_to_per_chunk_bucketing(monkeypatch):
    """One oversized message must not drag the whole batch to its
    length bucket: mixed-bucket batches use the multi-launch path
    where each chunk buckets independently."""
    import numpy as np

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519_verify as EV

    monkeypatch.setattr(EV, "MAX_LAUNCH", 64)
    n = 130
    rng = np.random.RandomState(9)
    priv = ed.priv_key_from_secret(b"mixed")
    pub_b = np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8)
    msgs = [rng.bytes(100) for _ in range(n - 1)] + [rng.bytes(400)]
    sigs = np.stack(
        [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
    )
    pubs = np.tile(pub_b, (n, 1))
    parts = EV.verify_arrays_async(pubs, sigs, msgs)
    assert len(parts) > 1  # multi-launch, not one global-bucket launch
    out = EV._finish(parts)
    assert out.shape == (n,) and bool(out.all())


class TestPrecompute:
    """Per-validator device tables (ops/precompute.py) vs the oracle."""

    def test_comb_mul_base8_vs_oracle(self, rng):
        from cometbft_tpu.ops import precompute as PR

        scalars = [0, 1, E.L - 1, rng.getrandbits(256), rng.getrandbits(255)]
        s_bytes = np.stack(
            [
                np.frombuffer(s.to_bytes(32, "little"), dtype=np.uint8)
                for s in scalars
            ],
            axis=-1,
        )
        out = jax.jit(PR.comb_mul_base8)(jnp.asarray(s_bytes))
        for i, s in enumerate(scalars):
            dev_pt = tuple(np.asarray(c)[:, i] for c in out)
            assert affine_eq(dev_pt, E.pt_mul(s % E.L, E.B_POINT))

    @pytest.mark.parametrize("window_bits", [4, 8])
    def test_keyed_comb_vs_oracle(self, rng, window_bits):
        from cometbft_tpu.ops import precompute as PR

        keys = [E.pt_mul(rng.randrange(1, E.L), E.B_POINT) for _ in range(3)]
        pub = np.stack(
            [
                np.frombuffer(E.encode_point(p), dtype=np.uint8)
                for p in keys
            ],
            axis=-1,
        )
        table, valid = jax.jit(
            lambda p: PR.build_tables_kernel(p, window_bits)
        )(jnp.asarray(pub))
        assert bool(np.asarray(valid).all())
        # lanes hit keys in scrambled order with random scalars
        key_ids = np.array([2, 0, 1, 2], dtype=np.int32)
        ks = [rng.randrange(E.L) for _ in range(4)]
        wins = comb_digits(ks, window_bits)
        out = jax.jit(
            lambda t, i, w: PR.comb_mul_keyed(t, i, w, window_bits)
        )(table, jnp.asarray(key_ids), jnp.asarray(wins))
        for lane, k in enumerate(ks):
            dev_pt = tuple(np.asarray(c)[:, lane] for c in out)
            expect = E.pt_mul(k, E.pt_neg(keys[key_ids[lane]]))
            assert affine_eq(dev_pt, expect)

    @pytest.mark.parametrize("window_bits", [4, 8])
    def test_keyed_comb_over_pool_lifecycle_vs_oracle(
        self, rng, monkeypatch, window_bits
    ):
        """The slot-major row layout is one fact held in several
        places.  After each of them has moved pages — pool growth
        across a capacity step, a page write into a live pool, the
        strided four-device placement, compaction after eviction —
        ``comb_mul_keyed`` over the resulting table still gives the
        oracle's [k](-A) for every resident key."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel.mesh import DATA_AXIS, flat_mesh

        monkeypatch.setattr(PR, "KEY8_MAX", 256 if window_bits == 8 else 0)
        rows = PR.slot_rows(window_bits)
        points = [
            E.pt_mul(rng.randrange(1, E.L), E.B_POINT) for _ in range(7)
        ]
        pubs = [E.encode_point(p) for p in points]
        point_of = dict(zip(pubs, points))
        comb = jax.jit(PR.comb_mul_keyed, static_argnums=3)

        def check(table, page_of):
            """page_of: pubkey -> its page's index in ``table``; four
            lanes a call so one compile serves every table shape."""
            lanes = [list(page_of)[i % len(page_of)] for i in range(4)]
            ks = [rng.randrange(E.L) for _ in lanes]
            wins = comb_digits(ks, window_bits)
            ids = np.array([page_of[p] for p in lanes], dtype=np.int32)
            out = comb(jnp.asarray(table), ids, wins, window_bits)
            for lane, (p, k) in enumerate(zip(lanes, ks)):
                dev_pt = tuple(np.asarray(c)[:, lane] for c in out)
                assert affine_eq(dev_pt, E.pt_mul(k, E.pt_neg(point_of[p])))

        cache = PR.KeyTableCache()
        e1 = cache.lookup_or_build(pubs[:2])
        assert e1.window_bits == window_bits
        assert e1.table.shape == (2, rows, PR.ROW)
        check(e1.table, e1.key_index)

        # growth 2 -> 8 slots, three pages written beside the old two
        e2 = cache.lookup_or_build(pubs[:5])
        assert e2.table.shape == (8, rows, PR.ROW)
        assert cache.stats["keys_built"] == 5
        assert bool(e2.valid[list(e2.key_index.values())].all())
        check(e2.table, e2.key_index)
        check(e1.table, e1.key_index)  # the old snapshot still stands

        # strided placement on four devices: slot s is page s // 4 of
        # device s % 4's block
        mesh = flat_mesh(jax.devices()[:4])
        table, valid, per_cap = e2.sharded_tables(
            mesh,
            NamedSharding(mesh, P(DATA_AXIS, None, None)),
            NamedSharding(mesh, P(DATA_AXIS)),
            4,
        )
        assert per_cap == 2 and table.shape == (8, rows, PR.ROW)
        owners = {s % 4 for s in e2.key_index.values()}
        assert len(owners) > 1
        for shard in table.addressable_shards:
            d = shard.index[0].start // per_cap
            local = {
                p: s // 4 for p, s in e2.key_index.items() if s % 4 == d
            }
            assert shard.data.shape == (per_cap, rows, PR.ROW)
            if local:
                check(np.asarray(shard.data), local)
                assert bool(
                    np.asarray(valid)[d * per_cap + np.array(
                        list(local.values())
                    )].all()
                )

        # two more keys over a budget of nothing: the three oldest go,
        # and compaction re-pages the four that stay into 4 slots
        monkeypatch.setattr(cache, "_cap", 1)
        e3 = cache.lookup_or_build(pubs[3:])
        assert cache.stats["keys_evicted"] == 3
        assert e3.table.shape == (4, rows, PR.ROW)
        assert sorted(e3.key_index.values()) == [0, 1, 2, 3]
        assert bool(e3.valid.all())
        check(e3.table, e3.key_index)

    @pytest.mark.parametrize(
        "window_bits,pool,batch",
        [
            pytest.param(4, "built", (6,), id="4bit-built"),
            pytest.param(8, "built", (6,), id="8bit-built"),
            pytest.param(4, "compacted", (6,), id="4bit-compacted"),
            pytest.param(8, "compacted", (6,), id="8bit-compacted"),
            pytest.param(8, "built", (2, 3), id="8bit-built-2d-batch"),
        ],
    )
    def test_keyed_comb_block_gather_vs_oracle(
        self, rng, monkeypatch, window_bits, pool, batch
    ):
        """The comb's rows for every window, gathered in one block ahead
        of its scan, give the oracle's [k](-A) over a freshly built table
        or a pool after growth and compaction, for a flat batch of lanes
        or a 2-D one; a lane whose key id lies past the pool reads the
        clipped last row at every window."""
        from cometbft_tpu.ops import precompute as PR

        points = [
            E.pt_mul(rng.randrange(1, E.L), E.B_POINT)
            for _ in range(3 if pool == "built" else 7)
        ]
        pubs = [E.encode_point(p) for p in points]
        if pool == "built":
            pub = np.stack(
                [np.frombuffer(p, dtype=np.uint8) for p in pubs], axis=-1
            )
            table, _ = jax.jit(
                lambda p: PR.build_tables_kernel(p, window_bits)
            )(jnp.asarray(pub))
            slot_of = dict(zip(pubs, range(3)))
        else:
            monkeypatch.setattr(
                PR, "KEY8_MAX", 256 if window_bits == 8 else 0
            )
            cache = PR.KeyTableCache()
            cache.lookup_or_build(pubs[:2])
            cache.lookup_or_build(pubs[:5])  # growth 2 -> 8 slots
            monkeypatch.setattr(cache, "_cap", 1)
            entry = cache.lookup_or_build(pubs[3:])  # evict, compact
            assert cache.stats["keys_evicted"] == 3
            table, slot_of = entry.table, entry.key_index
        cap = table.shape[0]
        last = next(p for p, s in slot_of.items() if s == cap - 1)
        point_of = dict(zip(pubs, points))
        lanes = [list(slot_of)[i % len(slot_of)] for i in range(5)]
        ids = np.array([slot_of[p] for p in lanes] + [cap], dtype=np.int32)
        ks = [rng.randrange(E.L) for _ in ids]
        wins = comb_digits(ks, window_bits)
        comb = jax.jit(PR.comb_mul_keyed, static_argnums=3)
        out = comb(
            table, jnp.asarray(ids.reshape(batch)),
            jnp.asarray(wins.reshape((-1,) + batch)), window_bits,
        )
        nwin, nent = 256 // window_bits, 1 << window_bits
        clipped = nwin * (nent - 1) << (window_bits * (nwin - 1))
        expect = [
            E.pt_mul(k, E.pt_neg(point_of[p])) for p, k in zip(lanes, ks)
        ] + [E.pt_mul(clipped % E.L, E.pt_neg(point_of[last]))]
        flat = [np.asarray(c).reshape(c.shape[0], -1) for c in out]
        for lane, ref in enumerate(expect):
            assert affine_eq(tuple(c[:, lane] for c in flat), ref)

    def test_invalid_key_encoding_masked(self, rng):
        from cometbft_tpu.ops import precompute as PR

        good = E.encode_point(E.pt_mul(7, E.B_POINT))
        bad = next(
            bytes([i]) + bytes(31)
            for i in range(2, 255)
            if E.decode_point(bytes([i]) + bytes(31)) is None
        )
        pub = np.stack(
            [np.frombuffer(e, dtype=np.uint8) for e in (good, bad)], axis=-1
        )
        _, valid = jax.jit(lambda p: PR.build_tables_kernel(p, 4))(
            jnp.asarray(pub)
        )
        assert np.asarray(valid).tolist() == [True, False]

    def test_keyed_verifier_matches_generic_and_oracle(self, rng, monkeypatch):
        from cometbft_tpu.ops import precompute as PR

        PR.TABLE_CACHE.clear()
        privs = [ed.gen_priv_key() for _ in range(5)]
        cases, oracle = [], []
        for i in range(20):
            priv = privs[i % len(privs)]
            m = bytes(rng.randrange(256) for _ in range(rng.randrange(0, 120)))
            sig = bytearray(priv.sign(m))
            pub = bytearray(priv.pub_key().bytes())
            r = rng.random()
            if r < 0.3:
                sig[rng.randrange(64)] ^= 1 << rng.randrange(8)
            elif r < 0.45:
                pub[rng.randrange(32)] ^= 1 << rng.randrange(8)
            cases.append((bytes(pub), m, bytes(sig)))
            oracle.append(E.verify_zip215(bytes(pub), m, bytes(sig)))

        bv = TpuBatchVerifier(device_min_batch=0)
        for pub, m, sig in cases:
            bv.add(ed.Ed25519PubKey(pub), m, sig)
        _, keyed_results = bv.verify()
        assert keyed_results == oracle

        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        bv2 = TpuBatchVerifier(device_min_batch=0)
        for pub, m, sig in cases:
            bv2.add(ed.Ed25519PubKey(pub), m, sig)
        _, generic_results = bv2.verify()
        assert generic_results == oracle

    def test_key_cache_hit_and_eviction(self):
        from cometbft_tpu.ops import precompute as PR

        cache = PR.KeyTableCache(cap_bytes=1)  # evicts all non-active keys
        pubs_a = [ed.gen_priv_key().pub_key().bytes() for _ in range(2)]
        pubs_b = [ed.gen_priv_key().pub_key().bytes() for _ in range(2)]
        ea = cache.lookup_or_build(pubs_a)
        assert cache.stats["keys_built"] == 2
        assert cache.lookup_or_build(pubs_a) is ea  # memoized hit
        assert cache.stats["keys_built"] == 2
        cache.lookup_or_build(pubs_b)  # over budget: a's keys evicted
        assert cache.stats["keys_evicted"] == 2
        eb = cache.lookup_or_build(pubs_a)
        assert eb is not ea  # rebuilt after eviction
        assert cache.stats["keys_built"] == 6

    @pytest.mark.parametrize(
        "nval",
        [
            24,
            # the full Cosmos-Hub-sized set is soak-tier: its 4-bit
            # page build pads to 256 lanes (~4 min single-core)
            pytest.param(150, marks=pytest.mark.slow),
        ],
    )
    def test_per_key_incremental_rotation(self, monkeypatch, nval):
        """Rotating 1 of N validators builds ONE key's table page,
        not the whole set's (the reference's per-key LRU behavior,
        crypto/ed25519/ed25519.go:43,62-68)."""
        from cometbft_tpu.ops import ed25519_verify as EV
        from cometbft_tpu.ops import precompute as PR

        monkeypatch.setattr(PR, "KEY8_MAX", 4)  # 4-bit pages: small build
        cache = PR.KeyTableCache()
        privs = [ed.gen_priv_key() for _ in range(nval)]
        pubs = [p.pub_key().bytes() for p in privs]
        e1 = cache.lookup_or_build(pubs)
        assert e1 is not None and e1.window_bits == 4
        assert cache.stats["keys_built"] == nval

        # block N+1: one validator rotates out, one in
        new_priv = ed.gen_priv_key()
        privs2 = privs[1:] + [new_priv]
        pubs2 = [p.pub_key().bytes() for p in privs2]
        e2 = cache.lookup_or_build(pubs2)
        assert cache.stats["keys_built"] == nval + 1  # ONE new page
        assert cache.stats["keys_evicted"] == 0

        # the post-rotation entry verifies real signatures end to end
        # (old key kept its pooled page; new key's page is fresh)
        sel = [privs2[0], new_priv]
        msgs = [b"rotation block %d" % i for i in range(2)]
        sigs = np.stack(
            [
                np.frombuffer(p.sign(m), dtype=np.uint8)
                for p, m in zip(sel, msgs)
            ]
        )
        kpubs = np.stack(
            [
                np.frombuffer(p.pub_key().bytes(), dtype=np.uint8)
                for p in sel
            ]
        )
        key_ids = e2.key_ids([p.pub_key().bytes() for p in sel])
        out = EV._finish(
            EV.verify_arrays_keyed_async(e2, key_ids, kpubs, sigs, msgs)
        )
        assert bool(out.all())
        # and a corrupted sig still fails through the rotated entry
        bad = sigs.copy()
        bad[1, 3] ^= 1
        out = EV._finish(
            EV.verify_arrays_keyed_async(e2, key_ids, kpubs, bad, msgs)
        )
        assert out.tolist() == [True, False]

    def test_10k_validator_4bit_tables_fit_hbm_budget(self):
        """BASELINE config 5 shape: 10k validators take 4-bit pages and
        the whole pool fits the device-table budget (and v5e's 16 GB
        HBM) with room for verify batches."""
        from cometbft_tpu.ops import precompute as PR

        assert 10_000 > PR.KEY8_MAX  # policy: large sets use 4-bit
        pool = PR._KeyPool(4)
        pool_bytes = PR._pool_cap(10_000) * pool.key_bytes
        # what the device holds: 128-wide rows, 104 limbs used
        assert pool.key_bytes == 64 * 16 * PR.ROW * 4  # 512 KiB/key
        assert pool_bytes <= PR.TABLE_CACHE_MB << 20
        assert pool_bytes <= 5 << 30  # 5 GiB: fits v5e HBM w/ headroom


class TestDispatchThreshold:
    """The device dispatch threshold is a rule, not a calibration: a
    constant on an accelerator, never on the XLA-on-CPU backend
    (reference analog: types/validation.go shouldBatchVerify).  The
    crossover formula, its two constants from another machine and its
    file under $HOME are gone (PR 22)."""

    def _reset(self, monkeypatch):
        from cometbft_tpu.ops import ed25519_verify as EV

        monkeypatch.delenv("CMT_TPU_DEVICE_MIN_BATCH", raising=False)
        return EV

    class _FakeDev:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    def _fake_accel(self, monkeypatch, EV):
        monkeypatch.setattr(
            EV.jax, "devices", lambda *a, **k: [self._FakeDev()]
        )

    def test_round_trip_is_not_consulted(self, monkeypatch):
        """A 70 ms round trip used to push the threshold to 1024 and a
        150-validator commit off the chip for the life of the process,
        a 0.2 ms one dropped it to the floor; the round trip is
        start-up evidence now (crypto/batch.init_device_plane), and
        the threshold never asks for it."""
        EV = self._reset(monkeypatch)
        self._fake_accel(monkeypatch, EV)

        def consulted():
            raise AssertionError("the threshold measured a round trip")

        monkeypatch.setattr(EV, "measure_link_rtt", consulted)
        assert EV.runtime_device_min_batch() == 128
        assert EV.DEVICE_MIN_BATCH == 64 < EV.ACCELERATOR_MIN_BATCH == 128

    def test_no_file_under_home_is_read(self, tmp_path, monkeypatch):
        """A calibration left under $HOME by an older build — on
        another machine, for another backend — changes nothing."""
        import json as _json

        EV = self._reset(monkeypatch)
        self._fake_accel(monkeypatch, EV)
        cal = tmp_path / ".cache" / "cometbft_tpu"
        cal.mkdir(parents=True)
        (cal / "device_calibration.json").write_text(
            _json.dumps({"schema": 2, "t_cpu_per_sig": 100e-6,
                         "t_dev_per_sig": 5e-6})
        )
        monkeypatch.setenv("HOME", str(tmp_path))
        monkeypatch.setenv("CMT_TPU_CALIBRATION", str(cal))
        assert EV.runtime_device_min_batch() == EV.ACCELERATOR_MIN_BATCH
        assert not hasattr(EV, "CALIBRATION_PATH")

    def test_cpu_backend_never_dispatches_to_xla_path(self, monkeypatch):
        """On a cpu jax backend the XLA kernel can't beat the host
        verifier; the threshold must push everything to the CPU path."""
        EV = self._reset(monkeypatch)
        assert EV.runtime_device_min_batch() == EV.NO_DEVICE_DISPATCH
        assert EV.NO_DEVICE_DISPATCH >= 1 << 29

    def test_env_override_wins(self, monkeypatch):
        EV = self._reset(monkeypatch)
        monkeypatch.setenv("CMT_TPU_DEVICE_MIN_BATCH", "256")
        assert EV.runtime_device_min_batch() == 256

    def test_dead_device_is_loud(self, monkeypatch):
        """A device that cannot answer the start-up round trip fails
        the device plane: it is never turned into a quiet
        everything-on-the-host threshold."""
        from cometbft_tpu.crypto import batch as cbatch

        EV = self._reset(monkeypatch)
        self._fake_accel(monkeypatch, EV)
        monkeypatch.setattr(
            cbatch, "_device_state",
            {"status": "uninitialized", "ndev": 0, "platform": None,
             "kind": None},
        )

        def boom():
            raise RuntimeError("no backend")

        monkeypatch.setattr(EV, "measure_link_rtt", boom)
        with pytest.raises(RuntimeError, match="no backend"):
            cbatch.init_device_plane()
        assert cbatch.device_status()["status"] == "failed"
        # a device that answers comes up with both facts on record
        monkeypatch.setattr(EV, "measure_link_rtt", lambda: 0.001)
        state = cbatch.init_device_plane()
        assert state["link_rtt_s"] == 0.001
        assert state["device_min_batch"] == EV.ACCELERATOR_MIN_BATCH


def test_verify_stream_keyed_dispatch(rng):
    """verify_stream's dispatch hook with a hot per-set table — the
    pattern bench_all's replay streams use (key_ids tiled per job)."""
    import numpy as np

    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.ops.ed25519_verify import (
        verify_arrays_keyed_async,
        verify_stream,
    )

    PR.TABLE_CACHE.clear()
    privs = [ed.priv_key_from_secret(b"st%d" % i) for i in range(5)]
    pub_bytes = [p.pub_key().bytes() for p in privs]
    entry = PR.TABLE_CACHE.lookup_or_build(pub_bytes)
    key_ids1 = entry.key_ids(pub_bytes)
    nsig = len(privs)

    def dispatch(pub, sig, ms):
        k = len(ms) // nsig
        return verify_arrays_keyed_async(
            entry, np.concatenate([key_ids1] * k), pub, sig, ms
        )

    msgs = [b"commit-sig-%d" % i for i in range(nsig)]
    sigs = np.stack(
        [np.frombuffer(p.sign(m), dtype=np.uint8)
         for p, m in zip(privs, msgs)]
    )
    pubs = np.stack(
        [np.frombuffer(b, dtype=np.uint8) for b in pub_bytes]
    )

    def jobs():
        for k in (1, 2, 3):  # varying commits-per-launch
            yield (
                np.concatenate([pubs] * k),
                np.concatenate([sigs] * k),
                msgs * k,
            )

    total = 0
    for res in verify_stream(jobs(), max_in_flight=2, dispatch=dispatch):
        assert bool(res.all())
        total += len(res)
    assert total == nsig * 6


@pytest.mark.parametrize("impl", ["stack16", "pallas"])
def test_keyed_kernel_under_alternate_field_cores(impl, monkeypatch):
    """The keyed (precomputed-table) kernel is correct under every
    column-formation variant a device A/B would measure
    (tools/bench_kernel_ab.py) — chip time must never be spent
    discovering a correctness bug.  pallas runs in interpret mode,
    which re-executes every field op per trace (~10 min for the full
    keyed graph), so that variant runs in the slow lane
    (CMT_TPU_SLOW_TESTS=1, `make test-slow`); the pallas CORE's
    differential vs the big-int oracle stays in every run
    (tests/test_ops_field.py)."""
    if impl == "pallas" and not os.environ.get("CMT_TPU_SLOW_TESTS"):
        pytest.skip("pallas interpret-mode keyed trace: slow lane only")
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import ed25519_verify as EV
    from cometbft_tpu.ops import field as F
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.ops.ed25519_verify import (
        _finish,
        verify_arrays_keyed_async,
    )

    # fresh jit wrappers: the compiled-fn caches key only on shapes, so
    # without this the second param would reuse the first's traced
    # executable and never execute its own field core
    monkeypatch.setattr(EV, "_keyed_cache", {})
    monkeypatch.setattr(PR, "_build_cache", {})
    monkeypatch.setattr(F, "COLS_IMPL", impl)
    if impl == "pallas":
        monkeypatch.setattr(F, "_PALLAS_INTERPRET", True)
        monkeypatch.setattr(F, "_mul_pallas", None)
        monkeypatch.setattr(F, "_square_pallas", None)
    else:
        monkeypatch.setattr(F, "SQUARE_IMPL", "mul")
    rng = np.random.RandomState(11)
    privs = [ed.gen_priv_key() for _ in range(3)]
    pubs_b = [p.pub_key().bytes() for p in privs]
    PR.TABLE_CACHE.clear()
    try:
        entry = PR.TABLE_CACHE.lookup_or_build(pubs_b)
        idx = [i % 3 for i in range(8)]
        msgs = [rng.bytes(100) for _ in range(8)]
        sigs = np.stack(
            [
                np.frombuffer(privs[i].sign(m), dtype=np.uint8)
                for i, m in zip(idx, msgs)
            ]
        )
        pub = np.stack(
            [np.frombuffer(pubs_b[i], dtype=np.uint8) for i in idx]
        )
        kid = entry.key_ids([pubs_b[i] for i in idx])
        out = _finish(verify_arrays_keyed_async(entry, kid, pub, sigs, msgs))
        assert out.all()
        sigs[2, 7] ^= 1
        out2 = _finish(
            verify_arrays_keyed_async(entry, kid, pub, sigs, msgs)
        )
        assert not out2[2] and out2.sum() == 7
    finally:
        PR.TABLE_CACHE.clear()

"""Multi-chip sharding tests on the virtual 8-device CPU mesh
(conftest sets --xla_force_host_platform_device_count=8).

Exercises parallel/mesh.py the way the driver's dryrun does, but with
stronger assertions: invalid signatures planted at known (header, sig)
lanes must — and only they may — come back False through the sharded
kernel. This is the pjit sharding intent of SURVEY.md §7: the batch
(H, V) shards over a 2-D ("blocks", "sigs") mesh with zero collectives
in the verify body.
"""

import numpy as np
import pytest

import jax

from cometbft_tpu.models.commit import example_inputs
from cometbft_tpu.parallel import (
    all_valid,
    make_mesh,
    shard_batch,
    sharded_verify_fn,
)


@pytest.fixture(scope="module")
def mesh():
    devices = jax.devices()
    assert len(devices) >= 8, "conftest must provide 8 virtual devices"
    return make_mesh(devices[:8])


class TestMesh:
    def test_mesh_shape_and_axes(self, mesh):
        assert mesh.devices.size == 8
        assert mesh.axis_names == ("blocks", "sigs")

    def test_explicit_shape(self):
        m = make_mesh(jax.devices()[:8], shape=(2, 4))
        assert m.devices.shape == (2, 4)
        with pytest.raises(ValueError):
            make_mesh(jax.devices()[:8], shape=(3, 2))

    def test_sharded_verify_with_planted_invalid(self, mesh):
        hb, vb = mesh.devices.shape
        H, V = hb * 2, vb * 4
        ii, jj = np.meshgrid(np.arange(H), np.arange(V), indexing="ij")
        invalid = (ii + 2 * jj) % 3 == 0
        assert invalid.any() and not invalid.all()
        pub, sig, msg, msglen = example_inputs(
            shape=(H, V), msglen=90, invalid=invalid
        )
        fn = sharded_verify_fn(mesh, nblocks=2)
        args = (
            shard_batch(mesh, pub, (None, "blocks", "sigs")),
            shard_batch(mesh, sig, (None, "blocks", "sigs")),
            shard_batch(mesh, msg, (None, "blocks", "sigs")),
            shard_batch(mesh, msglen, ("blocks", "sigs")),
        )
        out = fn(*args)
        # output keeps the mesh sharding
        assert out.sharding.spec == jax.sharding.PartitionSpec(
            "blocks", "sigs"
        )
        got = np.asarray(jax.device_get(out))
        assert got.shape == (H, V)
        assert np.array_equal(got, ~invalid)
        assert not bool(jax.device_get(jax.jit(all_valid)(out)))

    def test_all_valid_on_clean_batch(self, mesh):
        hb, vb = mesh.devices.shape
        # SAME (H, V) shape as the planted-invalid test above: the two
        # share one compiled program (a second shape would pay its own
        # multi-second XLA compile/cache-load for no extra coverage)
        pub, sig, msg, msglen = example_inputs(
            shape=(hb * 2, vb * 4), msglen=64
        )
        fn = sharded_verify_fn(mesh, nblocks=2)
        args = (
            shard_batch(mesh, pub, (None, "blocks", "sigs")),
            shard_batch(mesh, sig, (None, "blocks", "sigs")),
            shard_batch(mesh, msg, (None, "blocks", "sigs")),
            shard_batch(mesh, msglen, ("blocks", "sigs")),
        )
        assert bool(jax.device_get(jax.jit(all_valid)(fn(*args))))


class TestShardedSeam:
    """The production dispatch path: crypto/batch.py selects the mesh
    verifier when >1 device is visible (VERDICT r3 #3), at light-client
    scale with shards that do NOT divide evenly into mesh tiles
    (VERDICT r3 #10 — the padding/masking path is the one that breaks
    in practice)."""

    def test_factory_selects_sharded(self):
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.crypto.batch import create_batch_verifier
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        bv = create_batch_verifier(ed.priv_key_from_secret(b"f").pub_key())
        assert isinstance(bv, ShardedTpuBatchVerifier)

    @pytest.mark.slow
    def test_10k_sigs_uneven_keyed(self):
        """Light-client shape: >=10k signatures over a 150-key set,
        batch size deliberately not a multiple of 8 devices or any
        pow2 tile; exact planted-invalid recovery.

        Soak tier (28 min single-core on the 8-device virtual mesh):
        the same mesh+keyed+uneven composition is covered at small
        shape by test_generic_path_uneven and the planted-invalid mesh
        tests in the default gate."""
        import numpy as np

        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        PR.TABLE_CACHE.clear()
        rng = np.random.RandomState(42)
        privs = [
            ed.priv_key_from_secret(b"v%03d" % i) for i in range(150)
        ]
        n = 10_007  # prime: never tiles evenly
        msgs = [b"h%d" % (i // 150) for i in range(n)]
        bv = ShardedTpuBatchVerifier(device_min_batch=0)
        expect = np.ones(n, dtype=bool)
        bad_idx = rng.choice(n, size=97, replace=False)
        expect[bad_idx] = False
        bad = set(int(i) for i in bad_idx)
        for i in range(n):
            priv = privs[i % 150]
            s = priv.sign(msgs[i])
            if i in bad:
                s = s[:-1] + bytes([s[-1] ^ 1])
            bv.add(priv.pub_key(), msgs[i], s)
        ok, results = bv.verify()
        assert not ok
        assert np.array_equal(np.array(results), expect)

    def test_generic_path_uneven(self, monkeypatch):
        """Mesh path with precompute disabled (generic kernel), uneven
        batch."""
        import numpy as np

        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        priv = ed.priv_key_from_secret(b"g")
        n = 101  # uneven vs the 8-device mesh; pow2-pads to 128
        bv = ShardedTpuBatchVerifier(device_min_batch=0)
        expect = []
        for i in range(n):
            m = b"m%d" % i
            s = priv.sign(m)
            good = i % 7 != 2
            if not good:
                m = m + b"!"
            bv.add(priv.pub_key(), m, s)
            expect.append(good)
        _, results = bv.verify()
        assert results == expect


# -- the sharded KEYED tier (PR 6 tentpole) -----------------------------


@pytest.fixture(scope="module")
def keyed_mesh_keys():
    """One shared 12-key set (8-bit pages, pool cap 16 over the 8
    virtual devices -> 2 slots/chip): every test in this section reuses
    the SAME pool/table/batch shapes so the XLA programs compile once
    for the whole section (tier-1 wall-clock discipline)."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import precompute as PR

    PR.TABLE_CACHE.clear()
    privs = [ed.priv_key_from_secret(b"km%03d" % i) for i in range(14)]
    # warm the 12-key pool here so every test (in any order) sees a
    # warm key set; keys 12/13 stay cold for the cache-miss case
    PR.TABLE_CACHE.lookup_or_build(
        [p.pub_key().bytes() for p in privs[:12]]
    )
    yield privs
    PR.TABLE_CACHE.clear()


def _fill(bv, privs, n, bad, nkeys):
    msgs = [b"keyed-mesh-%d" % i for i in range(n)]
    for i in range(n):
        p = privs[i % nkeys]
        s = p.sign(msgs[i])
        if i in bad:
            s = s[:-1] + bytes([s[-1] ^ 1])
        bv.add(p.pub_key(), msgs[i], s)
    return bv


class TestShardedKeyed:
    """The keyed tier sharded over the forced-8-device CPU mesh: table
    shards device-resident under a NamedSharding, lanes routed to their
    key's owning chip, results bit-identical to the single-device keyed
    path (`make mesh-smoke`; ISSUE 6 acceptance)."""

    NKEYS = 12
    N = 53

    def _verify(self, cls, privs, n=None, bad=(), nkeys=None, **kw):
        bv = _fill(
            cls(device_min_batch=0, **kw), privs,
            n if n is not None else self.N, set(bad),
            nkeys if nkeys is not None else self.NKEYS,
        )
        return bv, bv.verify()

    def test_sharded_keyed_bitmatch_single_device(self, keyed_mesh_keys):
        """Acceptance: sharded-keyed output identical to the
        single-device keyed path, with the crypto_dispatch_tier metric
        proving which tier each verifier ran (one test: every extra
        verify costs seconds on the virtual mesh)."""
        import numpy as np

        from cometbft_tpu.metrics import (
            CryptoMetrics,
            crypto_metrics,
            install_crypto_metrics,
        )
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        from cometbft_tpu.utils.metrics import Registry

        rng = np.random.RandomState(6)
        bad = set(int(i) for i in rng.choice(self.N, 9, replace=False))
        install_crypto_metrics(CryptoMetrics(Registry()))
        try:
            _, (ok1, r1) = self._verify(
                TpuBatchVerifier, keyed_mesh_keys, bad=bad
            )
            bv2, (ok2, r2) = self._verify(
                ShardedTpuBatchVerifier, keyed_mesh_keys, bad=bad
            )
            expect = [i not in bad for i in range(self.N)]
            assert r1 == expect        # single-device keyed == oracle
            assert r2 == r1            # sharded keyed bit-matches it
            assert not ok1 and not ok2  # planted invalids flip verdict
            assert bv2._last_tier == "keyed_mesh"
            cm = crypto_metrics()
            assert cm.dispatch_tier.labels(tier="keyed").get() == 1.0
            assert cm.dispatch_tier.labels(tier="keyed_mesh").get() == 1.0
            assert (
                cm.batch_verify_launches.labels(kernel="keyed_mesh").get()
                == 1.0
            )
        finally:
            install_crypto_metrics(None)

    def test_padded_tail_devices_without_lanes(self, keyed_mesh_keys):
        """Two keys sharing one chip's table shard: the other 7 devices
        run entirely on padded lanes, which must not leak into the
        results (the padded-tail acceptance case)."""
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        pubs = [p.pub_key().bytes() for p in keyed_mesh_keys[: self.NKEYS]]
        entry = PR.TABLE_CACHE.lookup_or_build(pubs)
        # pick two keys co-resident on ONE device's shard (strided
        # ownership: slot % ndev)
        ndev = 8
        by_owner: dict[int, list[bytes]] = {}
        for p in pubs:
            by_owner.setdefault(
                entry.key_index[p] % ndev, []
            ).append(p)
        owner, two = next(
            (o, ps[:2]) for o, ps in by_owner.items() if len(ps) >= 2
        )
        privs = [
            p for p in keyed_mesh_keys
            if p.pub_key().bytes() in two
        ]
        bv, (ok, results) = self._verify(
            ShardedTpuBatchVerifier, privs, n=13, bad={5, 11}, nkeys=2
        )
        assert bv._last_tier == "keyed_mesh"
        assert results == [i not in (5, 11) for i in range(13)]
        assert not ok

    def test_partial_key_set_cache_miss_rebuild(self, keyed_mesh_keys):
        """Cache-miss case: a superset batch (2 fresh keys) builds only
        the missing pages, re-places the new entry's shards on the
        mesh, and exactly recovers the planted-invalid lanes."""
        import numpy as np

        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        built_before = PR.TABLE_CACHE.stats["keys_built"]
        # 61 lanes over 14 keys keeps the fullest shard at 10 lanes —
        # the same pow2-16 shard width the other tests compiled
        n = 61
        rng = np.random.RandomState(7)
        bad = set(int(i) for i in rng.choice(n, 9, replace=False))
        bv, (_, r_mesh) = self._verify(
            ShardedTpuBatchVerifier, keyed_mesh_keys, n=n, bad=bad,
            nkeys=14,
        )
        assert bv._last_tier == "keyed_mesh"
        # only the 2 keys missing from the warm 12-key pool were built
        assert PR.TABLE_CACHE.stats["keys_built"] - built_before == 2
        # the planted-invalid oracle pins correctness (keyed-vs-sharded
        # bit-match is already pinned by the bitmatch test above)
        assert r_mesh == [i not in bad for i in range(n)]

    def test_zero_steady_state_retraces_under_jitguard(
        self, keyed_mesh_keys, monkeypatch
    ):
        """Acceptance: warm the sharded keyed path, seal the jitguard,
        verify again — zero retraces and no implicit transfers inside
        the armed window (CMT_TPU_JITGUARD=1 semantics)."""
        from cometbft_tpu.ops import jitguard
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        monkeypatch.setattr(jitguard, "_ENABLED", True)
        jitguard.reset()
        try:
            _, (ok, _) = self._verify(
                ShardedTpuBatchVerifier, keyed_mesh_keys
            )
            assert ok
            before = dict(jitguard.compile_counts())
            jitguard.seal()
            # same shapes -> no compile, no transfer trip, no raise
            bv, (ok, results) = self._verify(
                ShardedTpuBatchVerifier, keyed_mesh_keys
            )
            assert ok and all(results)
            assert bv._last_tier == "keyed_mesh"
            assert jitguard.compile_counts() == before
            # post-seal placement REBUILD (the rotation shape): drop
            # the cached mesh placement so the sealed verify must
            # re-place the table shards inside the armed transfer
            # window — every transfer in the placement path must be
            # explicit or this raises at the offending line
            from cometbft_tpu.ops import precompute as PR

            entry = PR.TABLE_CACHE.peek(
                [p.pub_key().bytes() for p in keyed_mesh_keys[:12]]
            )
            with entry._mtx:
                entry.placements.clear()
            bv, (ok, _) = self._verify(
                ShardedTpuBatchVerifier, keyed_mesh_keys
            )
            assert ok and bv._last_tier == "keyed_mesh"
            assert jitguard.compile_counts() == before
        finally:
            jitguard.reset()


class TestKeyedWarmPromotion:
    """Keyed-by-default dispatch: below the generic device threshold a
    batch whose key-set tables are WARM still takes the keyed tier
    (reason=keyed_warm); a cold set is not promoted (and never stalls
    behind a build it didn't ask for)."""

    def test_warm_table_promotes_small_batch(
        self, keyed_mesh_keys, monkeypatch
    ):
        from cometbft_tpu.metrics import (
            CryptoMetrics,
            crypto_metrics,
            install_crypto_metrics,
        )
        from cometbft_tpu.ops import ed25519_verify as EV
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier
        from cometbft_tpu.utils.metrics import Registry

        # the 53-lane batch shares its compiled shape with the rest of
        # the module; lower the static floor so it clears the
        # promotion's RTT guard
        monkeypatch.setattr(EV, "DEVICE_MIN_BATCH", 16)
        pubs = [p.pub_key().bytes() for p in keyed_mesh_keys[:12]]
        assert PR.TABLE_CACHE.peek(pubs) is not None  # warm from module
        install_crypto_metrics(CryptoMetrics(Registry()))
        try:
            # threshold far above the batch: only the warm-table
            # promotion can route this to the device
            bv = _fill(
                TpuBatchVerifier(device_min_batch=100_000),
                keyed_mesh_keys, 53, set(), 12,
            )
            ok, results = bv.verify()
            assert ok and all(results)
            cm = crypto_metrics()
            assert cm.dispatch_tier.labels(tier="keyed").get() == 1.0
            assert (
                cm.dispatch_decisions.labels(
                    route="device", reason="keyed_warm"
                ).get()
                == 1.0
            )
        finally:
            install_crypto_metrics(None)

    def test_warm_batch_below_static_floor_stays_host(
        self, keyed_mesh_keys
    ):
        """Warm tables do not change the per-launch link RTT: a batch
        under the static DEVICE_MIN_BATCH floor stays on the host path
        even with every key's table hot (a 2-sig evidence check must
        never pay a device launch's round trip)."""
        from cometbft_tpu.metrics import (
            CryptoMetrics,
            crypto_metrics,
            install_crypto_metrics,
        )
        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.ops.ed25519_verify import (
            DEVICE_MIN_BATCH,
            TpuBatchVerifier,
        )
        from cometbft_tpu.utils.metrics import Registry

        pubs = [p.pub_key().bytes() for p in keyed_mesh_keys[:12]]
        assert PR.TABLE_CACHE.peek(pubs) is not None
        install_crypto_metrics(CryptoMetrics(Registry()))
        try:
            bv = _fill(
                TpuBatchVerifier(device_min_batch=100_000),
                keyed_mesh_keys, DEVICE_MIN_BATCH - 1, set(), 12,
            )
            ok, results = bv.verify()
            assert ok and all(results)
            cm = crypto_metrics()
            assert cm.dispatch_tier.labels(tier="host").get() == 1.0
            assert cm.dispatch_tier.labels(tier="keyed").get() == 0.0
        finally:
            install_crypto_metrics(None)

    def test_cold_set_not_promoted(self):
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.metrics import (
            CryptoMetrics,
            crypto_metrics,
            install_crypto_metrics,
        )
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier
        from cometbft_tpu.utils.metrics import Registry

        priv = ed.priv_key_from_secret(b"cold-promotion")
        install_crypto_metrics(CryptoMetrics(Registry()))
        try:
            bv = TpuBatchVerifier(device_min_batch=100_000)
            for i in range(8):
                m = b"cold-%d" % i
                bv.add(priv.pub_key(), m, priv.sign(m))
            ok, results = bv.verify()
            assert ok and all(results)
            cm = crypto_metrics()
            assert cm.dispatch_tier.labels(tier="host").get() == 1.0
        finally:
            install_crypto_metrics(None)


class TestKeyPoolMeshAccounting:
    """_KeyPool budget honesty on a mesh: per-device placements
    (sharded shards / replicated copies) hung off live entries count
    against TABLE_CACHE_MB, and the post-compaction sweep releases the
    bytes stale entries pinned."""

    def test_placement_bytes_counted_and_released(self):
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.ops import precompute as PR

        pubs_a = [
            ed.priv_key_from_secret(b"pa%d" % i).pub_key().bytes()
            for i in range(2)
        ]
        pubs_b = [
            ed.priv_key_from_secret(b"pb%d" % i).pub_key().bytes()
            for i in range(2)
        ]
        pool_bytes = PR._pool_cap(2) * PR._KeyPool(8).key_bytes
        # budget fits both 2-key pools easily WITHOUT placements...
        cache = PR.KeyTableCache(cap_bytes=8 * pool_bytes)
        ea = cache.lookup_or_build(pubs_a)
        with cache._lock:
            assert cache.placement_bytes() == 0
        # ...but an 8-chip replica of a's tables blows it
        ea.placements[("replicated", "meshX")] = (
            object(), 9 * pool_bytes
        )
        with cache._lock:
            assert cache.placement_bytes() == 9 * pool_bytes
        cache.lookup_or_build(pubs_b)
        # b's build staled a's entry (version bump), so the eviction
        # pass released the placement bytes by SWEEPING the stale
        # entry — no key eviction (the pools themselves fit: evicting
        # live pages to pay for dead placements would be thrash)
        with cache._lock:
            assert cache.placement_bytes() == 0
        assert cache.stats["keys_evicted"] == 0
        assert cache.lookup_or_build(pubs_a) is not ea  # fresh entry
        assert cache.stats["keys_built"] == 4  # a's pages stayed pooled

    def test_sharded_placement_is_cached_and_accounted(
        self, keyed_mesh_keys
    ):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel.mesh import DATA_AXIS, flat_mesh

        pubs = [p.pub_key().bytes() for p in keyed_mesh_keys[:12]]
        entry = PR.TABLE_CACHE.lookup_or_build(pubs)
        mesh = flat_mesh(jax.devices()[:8])
        t_sh = NamedSharding(mesh, P(DATA_AXIS, None, None))
        v_sh = NamedSharding(mesh, P(DATA_AXIS))
        table, valid, per_cap = entry.sharded_tables(mesh, t_sh, v_sh, 8)
        assert per_cap * 8 >= len(entry.valid)
        assert table.shape == (
            per_cap * 8, PR.slot_rows(entry.window_bits), PR.ROW
        )
        # cached per (entry, mesh): the second call is the same arrays
        again = entry.sharded_tables(mesh, t_sh, v_sh, 8)
        assert again[0] is table
        assert entry.placement_bytes() >= int(table.nbytes)

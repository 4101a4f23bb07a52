"""Device-path correctness toolchain: the static jit/contract lint
(tools/jitcheck.py), the runtime retrace + transfer guard
(CMT_TPU_JITGUARD, cometbft_tpu/ops/jitguard.py), and the deviceless
jax.eval_shape kernel-contract sweep — the device-plane analog of the
PR 3 concurrency toolchain (docs/device_contracts.md)."""

from __future__ import annotations

import textwrap

import numpy as np
import pytest

import jax

from cometbft_tpu.metrics import (
    CryptoMetrics,
    crypto_metrics,
    install_crypto_metrics,
)
from cometbft_tpu.ops import contracts as contracts_mod
from cometbft_tpu.ops import jitguard
from cometbft_tpu.ops.jitguard import RetraceError
from cometbft_tpu.utils.metrics import Registry

import tools.jitcheck as jitcheck


def lint(src: str, rel: str = "cometbft_tpu/ops/fixture.py"):
    return jitcheck.check_source(textwrap.dedent(src), rel)


class TestJitSeamLint:
    """AST fixture cases for the jax.jit seam discipline."""

    def test_unregistered_jit_call_flagged(self):
        rep = lint(
            """
            import jax

            def helper(x):
                return jax.jit(lambda a: a + x)
            """
        )
        assert len(rep.violations) == 1
        v = rep.violations[0]
        assert "registered compile-cache seam" in v.message
        assert v.line == 5

    def test_module_level_jit_flagged(self):
        rep = lint("import jax\nfn = jax.jit(abs)\n")
        assert len(rep.violations) == 1
        assert "<module>" in rep.violations[0].message

    def test_registered_seam_clean(self):
        rep = lint(
            """
            import jax
            from cometbft_tpu.ops import jitguard

            _sharded_cache = {}

            def sharded_verify_fn(mesh, nblocks=2):
                key = (mesh, nblocks)
                fn = _sharded_cache.get(key)
                if fn is not None:
                    return fn
                jitguard.note_compile("sharded", key)
                fn = jax.jit(lambda p: p)
                _sharded_cache[key] = fn
                return fn

            def verify_keyed_shard(buf, bucket):
                return buf

            _CONTRACTS = {
                "verify_keyed_shard": {
                    "args": {"buf": ("u8", ("104+bucket", "B//ndev"))},
                    "static": ("bucket",),
                    "out": ("u8", ("104+bucket", "B//ndev")),
                },
            }
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert rep.ok, rep.violations
        assert rep.seams == 1

    def test_seam_without_cache_flagged(self):
        rep = lint(
            """
            import jax
            from cometbft_tpu.ops import jitguard

            def sharded_verify_fn(mesh, nblocks=2):
                jitguard.note_compile("sharded", (mesh, nblocks))
                return jax.jit(lambda p: p)
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert any("*_cache" in v.message for v in rep.violations)

    def test_seam_off_ladder_param_flagged(self):
        rep = lint(
            """
            import jax
            from cometbft_tpu.ops import jitguard

            _sharded_cache = {}

            def sharded_verify_fn(mesh, msglen):
                jitguard.note_compile("sharded", (mesh, msglen))
                fn = jax.jit(lambda p: p)
                _sharded_cache[(mesh, msglen)] = fn
                return fn
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert any(
            "non-ladder" in v.message and "msglen" in v.message
            for v in rep.violations
        )

    def test_seam_without_note_compile_flagged(self):
        rep = lint(
            """
            import jax

            _sharded_cache = {}

            def sharded_verify_fn(mesh, nblocks=2):
                fn = jax.jit(lambda p: p)
                _sharded_cache[(mesh, nblocks)] = fn
                return fn
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert any("note_compile" in v.message for v in rep.violations)

    def test_closure_capturing_rebound_global_flagged(self):
        """A module global flipped via `global` is baked into the
        traced program — the silent divergence trace_config() exists
        to prevent."""
        rep = lint(
            """
            import jax
            from cometbft_tpu.ops import jitguard

            _MODE = "fast"
            _sharded_cache = {}

            def set_mode(m):
                global _MODE
                _MODE = m

            def sharded_verify_fn(mesh, nblocks=2):
                jitguard.note_compile("sharded", (mesh, nblocks))

                def run(p):
                    if _MODE == "fast":
                        return p
                    return p + 1

                fn = jax.jit(run)
                _sharded_cache[(mesh, nblocks)] = fn
                return fn
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert any(
            "mutable module global '_MODE'" in v.message
            for v in rep.violations
        )

    def test_closure_over_locals_and_functions_clean(self):
        rep = lint(
            """
            import jax
            from cometbft_tpu.ops import jitguard

            _sharded_cache = {}

            def kernel(p, k):
                return p + k

            def sharded_verify_fn(mesh, nblocks=2):
                jitguard.note_compile("sharded", (mesh, nblocks))
                k = nblocks * 2

                def run(p):
                    return kernel(p, k)

                fn = jax.jit(run)
                _sharded_cache[(mesh, nblocks)] = fn
                return fn

            def verify_keyed_shard(buf, bucket):
                return buf

            _CONTRACTS = {
                "verify_keyed_shard": {
                    "args": {"buf": ("u8", ("104+bucket", "B//ndev"))},
                    "static": ("bucket",),
                    "out": ("u8", ("104+bucket", "B//ndev")),
                },
            }
            """,
            rel="cometbft_tpu/parallel/mesh.py",
        )
        assert rep.ok, rep.violations


class TestHostSyncLint:
    """np.asarray / .item() / float-on-device sites need audited
    waivers in the device-plane files; waivers cannot go stale."""

    def test_unwaived_np_asarray_flagged(self):
        rep = lint(
            """
            import numpy as np

            def fetch(parts):
                return np.asarray(parts[0])
            """
        )
        assert len(rep.violations) == 1
        assert "host-sync site np.asarray" in rep.violations[0].message

    def test_waiver_counted_not_flagged(self):
        rep = lint(
            """
            import numpy as np

            def fetch(parts):
                return np.asarray(parts[0])  # host sync: the one audited fetch
            """
        )
        assert rep.ok
        assert len(rep.waivers) == 1
        assert rep.waivers[0].reason == "the one audited fetch"

    def test_module_scope_sync_flagged_and_waivable(self):
        """A module-init sync site is just as real as one in a
        function — flagged unwaived, honored (not stale) waived."""
        rep = lint(
            """
            import numpy as np

            _TABLE = np.asarray(_build())
            """
        )
        assert len(rep.violations) == 1
        assert "<module>" in rep.violations[0].message
        rep = lint(
            """
            import numpy as np

            _TABLE = np.asarray(_build())  # host sync: one-time module-init table upload
            """
        )
        assert rep.ok and len(rep.waivers) == 1

    def test_nested_function_sites_reported_once(self):
        rep = lint(
            """
            def outer(parts):
                def flush():
                    return parts[0].item()
                return flush
            """
        )
        assert len(rep.violations) == 1

    def test_stale_waiver_flagged(self):
        rep = lint(
            """
            def fetch(parts):
                out = parts[0]  # host sync: leftover annotation
                return out
            """
        )
        assert len(rep.violations) == 1
        assert "stale" in rep.violations[0].message

    def test_float_on_device_tainted_value_flagged(self):
        """Local dataflow: a value produced by a compiled-seam callable
        is device-resident; float() on it is a blocking round trip."""
        rep = lint(
            """
            import jax

            def run(packed, batch, bucket):
                fn = _compiled(batch, bucket)
                out = fn(jax.device_put(packed))
                return float(out[0])
            """
        )
        assert len(rep.violations) == 1
        assert "float() on device value 'out'" in rep.violations[0].message

    def test_item_and_block_until_ready_flagged(self):
        rep = lint(
            """
            def sync(x):
                x.block_until_ready()
                return x.item()
            """
        )
        assert len(rep.violations) == 2

    def test_float_on_host_value_clean(self):
        rep = lint(
            """
            def parse(cal):
                return float(cal["t_cpu"])
            """
        )
        assert rep.ok

    def test_sync_scope_excludes_host_planes(self):
        """np.asarray is everyday numpy in the host packages — only
        the device-plane files carry the waiver discipline."""
        rep = lint(
            """
            import numpy as np

            def pack(xs):
                return np.asarray(xs)
            """,
            rel="cometbft_tpu/rpc/helpers.py",
        )
        assert rep.ok


class TestContractLint:
    def test_missing_required_contract_flagged(self):
        rep = lint(
            """
            def sha512_padded(buf, nblocks, nblocks_lane=None):
                return buf
            """,
            rel="cometbft_tpu/ops/sha512.py",
        )
        assert any(
            "no _CONTRACTS entry" in v.message for v in rep.violations
        )

    def test_signature_mismatch_flagged(self):
        rep = lint(
            """
            def kernel(a, b):
                return a

            _CONTRACTS = {
                "kernel": {
                    "args": {"a": ("u8", (32, "B"))},
                    "static": (),
                    "out": ("u8", (32, "B")),
                },
            }
            """
        )
        assert any("signature" in v.message for v in rep.violations)

    def test_bad_dtype_flagged(self):
        rep = lint(
            """
            def kernel(a):
                return a

            _CONTRACTS = {
                "kernel": {
                    "args": {"a": ("f32", (32, "B"))},
                    "static": (),
                    "out": ("f32", (32, "B")),
                },
            }
            """
        )
        assert any("'f32' not in the audited set" in v.message
                   for v in rep.violations)

    def test_unknown_dim_symbol_flagged(self):
        rep = lint(
            """
            def kernel(a):
                return a

            _CONTRACTS = {
                "kernel": {
                    "args": {"a": ("u8", ("width", "B"))},
                    "static": (),
                    "out": ("u8", (32, "B")),
                },
            }
            """
        )
        assert any("unknown symbol(s) ['width']" in v.message
                   for v in rep.violations)

    def test_non_literal_contracts_flagged(self):
        rep = lint(
            """
            SIZE = 32

            def kernel(a):
                return a

            _CONTRACTS = {
                "kernel": {
                    "args": {"a": ("u8", (SIZE, "B"))},
                    "static": (),
                    "out": ("u8", (32, "B")),
                },
            }
            """
        )
        assert any("pure literal" in v.message for v in rep.violations)

    def test_vocabulary_in_lockstep_with_contracts_module(self):
        """jitcheck mirrors the grammar without importing ops (a lint
        must not initialize jax) — this pin keeps them identical."""
        assert jitcheck.DTYPES_OK == set(contracts_mod.DTYPES)
        assert jitcheck.DIM_SYMBOLS == contracts_mod.DIM_SYMBOLS


class TestJitcheckTree:
    """Tier-1 wiring: the real tree must lint clean — the same gate
    `make jitcheck` and tools/metrics_lint.py main() run."""

    def test_repo_is_clean(self):
        rep = jitcheck.check_tree()
        assert rep.ok, "\n".join(str(v) for v in rep.violations)
        # the sweep is real, not vestigial
        assert rep.jit_calls >= 5
        assert rep.seams >= 5
        assert rep.contracts >= 20
        assert len(rep.waivers) >= 6

    def test_main_exit_zero(self, capsys):
        assert jitcheck.main([]) == 0
        assert "registered seams" in capsys.readouterr().out


# -- deviceless kernel-contract sweep ----------------------------------


def _sweep(modules, env) -> list[str]:
    errs: list[str] = []
    for mod in modules:
        errs.extend(contracts_mod.check_module(mod, env))
    return errs


class TestContractEvalShape:
    """jax.eval_shape (abstract eval: no device, no FLOPs) checks every
    declared kernel contract; shape/dtype regressions fail here, in
    tier-1 CPU CI, before ever touching a TPU."""

    def test_all_kernels_at_base_rung(self):
        from cometbft_tpu.ops import (curve, ed25519_verify, field,
                                      precompute, scalar, sha512)

        env = contracts_mod.ladder_env(8, 128, window_bits=8, cap=16)
        errs = _sweep(
            (ed25519_verify, field, curve, scalar, sha512, precompute), env
        )
        assert not errs, "\n".join(errs)

    def test_keyed_kernels_at_4bit_windows(self):
        """Only the window_bits-shaped kernels — re-tracing the whole
        generic verify graph at wb=4 would add ~25s for zero new
        coverage (their dims don't mention nwin/nent)."""
        from cometbft_tpu.ops import ed25519_verify, precompute

        env = contracts_mod.ladder_env(16, 128, window_bits=4, cap=32)
        errs = []
        for mod, names in (
            (ed25519_verify,
             ("verify_kernel_keyed", "verify_kernel_keyed_packed")),
            (precompute, ("build_tables_kernel", "comb_mul_keyed")),
        ):
            for name in names:
                errs.extend(
                    contracts_mod.check_contract(
                        getattr(mod, name), mod._CONTRACTS[name], env
                    )
                )
        assert not errs, "\n".join(errs)

    @pytest.mark.parametrize("bucket", [256, 512, 1024, 4096])
    def test_bucket_ladder_for_bucket_shaped_kernels(self, bucket):
        """The kernels whose shapes derive from the message bucket,
        swept across the remaining ladder rungs (128 is covered by the
        all-kernel rung above)."""
        from cometbft_tpu.ops import ed25519_verify, sha512

        env = contracts_mod.ladder_env(8, bucket, window_bits=8, cap=16)
        errs = []
        for mod, names in (
            (ed25519_verify, ("build_padded_input", "verify_kernel_packed")),
            (sha512, ("sha512_padded", "bytes_to_words")),
        ):
            for name in names:
                errs.extend(
                    contracts_mod.check_contract(
                        getattr(mod, name), mod._CONTRACTS[name], env
                    )
                )
        assert not errs, "\n".join(errs)

    def test_sharded_keyed_kernel_across_mesh_shapes(self):
        """The shard-local keyed kernel's contract (dims are
        global//ndev), swept across mesh sizes and both window
        widths — deviceless, no FLOPs."""
        from cometbft_tpu.parallel import mesh as M

        # three rungs cover: no-mesh, full-mesh at both window widths
        # (each env is a full abstract trace of the keyed kernel graph
        # — ~3s apiece, so the matrix stays deliberately small)
        errs = []
        for ndev, wb, cap in (
            (1, 8, 16), (8, 4, 32), (8, 8, 16),
        ):
            env = contracts_mod.ladder_env(
                64, 128, window_bits=wb, cap=cap, ndev=ndev
            )
            errs.extend(
                contracts_mod.check_contract(
                    M.verify_keyed_shard,
                    M._CONTRACTS["verify_keyed_shard"],
                    env,
                )
            )
        assert not errs, "\n".join(errs)

    def test_keyed_mesh_seam_eval_shape_across_mesh_shapes(self):
        """The whole _compiled_keyed_mesh seam (shard_map + jit with
        in/out shardings + donation) abstractly evaluated at GLOBAL
        shapes over 1/2/4/8-device meshes — shape/dtype/sharding
        plumbing verified without executing a single kernel."""
        import numpy as np

        import jax.numpy as jnp

        from cometbft_tpu.ops import precompute as PR
        from cometbft_tpu.parallel import mesh as M

        devs = jax.devices()
        for ndev in (1, 8):
            mesh = jax.sharding.Mesh(
                np.array(devs[:ndev]), (M.DATA_AXIS,)
            )
            fn = M._compiled_keyed_mesh(mesh, 128, 8, 8192)
            batch, cap = 64, 16
            out = jax.eval_shape(
                fn,
                jax.ShapeDtypeStruct((104 + 128, batch), jnp.uint8),
                jax.ShapeDtypeStruct(
                    (cap, PR.slot_rows(8), PR.ROW), jnp.int32
                ),
                jax.ShapeDtypeStruct((cap,), jnp.bool_),
            )
            assert tuple(out.shape) == (batch,)
            assert np.dtype(out.dtype) == np.dtype(bool)

    @pytest.mark.slow
    def test_full_matrix(self):
        from cometbft_tpu.ops import (curve, ed25519_verify, field,
                                      precompute, scalar, sha512)

        mods = (ed25519_verify, field, curve, scalar, sha512, precompute)
        errs = []
        for bucket in (128, 256, 512, 1024, 4096):
            for batch in (8, 64):
                for wb in (8, 4):
                    env = contracts_mod.ladder_env(
                        batch, bucket, window_bits=wb, cap=batch
                    )
                    errs.extend(_sweep(mods, env))
        assert not errs, "\n".join(errs)

    def test_contract_catches_seeded_drift(self):
        """A deliberately wrong contract must fail the sweep — the
        check has teeth."""
        from cometbft_tpu.ops import scalar

        env = contracts_mod.ladder_env(8, 128)
        bad = {
            "args": {"s_bytes": ("u8", (32, "B"))},
            "static": (),
            "out": ("i32", ("B",)),  # really bool
        }
        errs = contracts_mod.check_contract(scalar.bytes_lt_l, bad, env)
        assert errs and "dtype" in errs[0]


# -- runtime guard: CMT_TPU_JITGUARD ------------------------------------


class TestJitGuard:
    @pytest.fixture(autouse=True)
    def guard_mode(self, monkeypatch):
        monkeypatch.setattr(jitguard, "_ENABLED", True)
        jitguard.reset()
        reg = Registry()
        install_crypto_metrics(CryptoMetrics(reg))
        yield
        install_crypto_metrics(None)
        jitguard.reset()

    def test_seeded_retrace_raises_with_both_stacks(self, monkeypatch):
        from cometbft_tpu.ops import ed25519_verify as EV

        monkeypatch.setattr(EV, "_kernel_cache", {})
        EV._compiled(8, 128)          # warmup compile — recorded
        jitguard.seal()
        with pytest.raises(RetraceError) as exc:
            EV._compiled(16, 128)     # off-warmup signature -> retrace
        msg = str(exc.value)
        assert "RETRACE after warmup at seam 'generic'" in msg
        assert "(16, 128" in msg      # the offending key signature
        assert "this compile request" in msg
        assert "previous compile" in msg
        # both stacks name this test as the compile site
        assert msg.count("test_seeded_retrace_raises_with_both_stacks") >= 2
        assert (
            crypto_metrics().guard_trips.labels(kind="retrace").get() == 1.0
        )

    def test_compile_counts_per_seam(self, monkeypatch):
        from cometbft_tpu.ops import ed25519_verify as EV

        monkeypatch.setattr(EV, "_kernel_cache", {})
        monkeypatch.setattr(EV, "_chunked_cache", {})
        EV._compiled(8, 128)
        EV._compiled(8, 128)          # cache hit: not a compile
        EV._compiled(8, 256)
        EV._compiled_chunked(16, 128, 8)
        counts = jitguard.compile_counts()
        assert counts["generic"] == 2
        assert counts["chunked"] == 1
        assert (
            crypto_metrics().jit_cache_misses.labels(seam="generic").get()
            == 2.0
        )

    def test_transfer_window_trips_on_implicit_transfer(self):
        jitguard.seal()
        with pytest.raises(Exception, match="[Dd]isallow"):
            with jitguard.transfer_window():
                # a numpy operand reaching a jit function is an
                # IMPLICIT h2d transfer — the exact silent-stall bug
                jax.jit(lambda a: a + 1)(np.arange(4))
        assert (
            crypto_metrics().guard_trips.labels(kind="transfer").get() == 1.0
        )

    def test_transfer_window_allows_explicit_idiom(self):
        """The audited dispatch idiom — device_put in, device_get out —
        passes the sealed window untouched."""
        jitguard.seal()
        with jitguard.transfer_window():
            dev = jax.device_put(np.arange(8, dtype=np.int32))
            out = jax.device_get(jax.jit(lambda a: a * 2)(dev))
        assert list(out) == list(range(0, 16, 2))

    def test_window_passthrough_before_seal(self):
        # warmup legitimately stages trace-time constants; the window
        # only arms once sealed
        with jitguard.transfer_window():
            jax.jit(lambda a: a + 1)(np.arange(4))

    def test_verify_path_clean_under_sealed_guard(self, monkeypatch):
        """End-to-end: warm the real device path once, seal, verify
        again inside the armed window — the steady state must make no
        implicit transfer and no recompile (this is the check the
        _finish/valid_device explicit-transfer fixes keep green)."""
        monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

        priv = ed.priv_key_from_secret(b"jitguard")
        pub = priv.pub_key()
        msgs = [b"msg-%d" % i for i in range(8)]
        sigs = [priv.sign(m) for m in msgs]

        def run() -> list[bool]:
            bv = TpuBatchVerifier(device_min_batch=1)
            for m, s in zip(msgs, sigs):
                bv.add(pub, m, s)
            ok, results = bv.verify()
            assert ok
            return results

        run()                         # warmup: compiles + transfers
        jitguard.seal()
        assert run() == [True] * 8    # steady state: clean under guard


class TestJitGuardZeroCostOff:
    @pytest.fixture(autouse=True)
    def guard_off(self, monkeypatch):
        monkeypatch.setattr(jitguard, "_ENABLED", False)
        jitguard.reset()
        yield
        jitguard.reset()

    def test_counts_but_no_stacks_no_raises(self):
        jitguard.note_compile("generic", (8, 128))
        jitguard.seal()
        jitguard.note_compile("generic", (16, 128))  # no raise when off
        assert jitguard.compile_counts()["generic"] == 2
        assert not jitguard._last_site  # stacks never recorded

    def test_transfer_window_is_passthrough(self):
        jitguard.seal()
        with jitguard.transfer_window():
            # implicit transfer passes untouched when the guard is off
            jax.jit(lambda a: a + 1)(np.arange(4))


class TestKeySetTablesValidDevice:
    def test_device_copy_is_cached(self):
        from cometbft_tpu.ops.precompute import KeySetTables

        entry = KeySetTables(
            sethash=b"h", window_bits=8, key_index={},
            table=None, valid=np.array([True, False]), nbytes=0,
        )
        dev = entry.valid_device()
        assert entry.valid_device() is dev  # one transfer per entry
        assert list(np.asarray(dev)) == [True, False]

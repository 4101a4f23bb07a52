"""chip_smoke.py's contract and its phases, rehearsed without the chip.

The script as a command always stops at its device check here (it has
no switch that lets it pass on a CPU); its phase functions run at tiny
sizes on the CPU backend, steered by the TEST (a dispatch-threshold
override so the XLA-on-CPU kernels stand in for the chip, one device
for the one-chip phases) — never by an option of the program.  Also
here: the start-up rules the smoke leans on — the device plane comes
up in-process and loudly, and one place decides the compile cache.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import chip_smoke as S  # noqa: E402 — the repo root is on sys.path


# -- the last line -------------------------------------------------------


class _FakeDevice:
    platform = "tpu"
    device_kind = "TPU v5 lite"


def test_last_line_is_exactly_the_contract():
    line = S.format_last_line([_FakeDevice()])
    assert "\n" not in line
    doc = json.loads(line)
    assert list(doc) == ["ok", "device"]
    assert doc["ok"] is True
    assert doc["device"] == {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
    }
    four = json.loads(S.format_last_line([_FakeDevice()] * 4))
    assert four["device"]["count"] == 4 and list(four) == ["ok", "device"]


def test_command_fails_at_the_device_check_without_a_chip():
    """Run as the driver runs it, on the CPU backend: non-zero, the
    device check named as the reason, and no result on stdout."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "device check failed" in proc.stderr
    assert "'cpu'" in proc.stderr
    assert proc.stdout == ""


# -- start-up rules ------------------------------------------------------


@pytest.mark.parametrize(
    "env_dir", [None, "/nonexistent/jax-cache"], ids=["unset", "env-set"]
)
def test_one_place_decides_the_compile_cache(env_dir):
    """JAX_COMPILATION_CACHE_DIR set -> no directory is set in code;
    unset -> <checkout>/.xla_cache.  Read from a fresh interpreter:
    the rule runs at import."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.pop("CMT_TPU_NO_COMPILE_CACHE", None)
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run(
        [sys.executable, "-c",
         "import jax, cometbft_tpu.ops; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=env, cwd="/", capture_output=True, text=True, timeout=300,
        check=True,
    ).stdout.strip()
    assert out == (env_dir or os.path.join(REPO, ".xla_cache"))


class TestDevicePlaneStartUp:
    """crypto/batch.py: the backend is initialised in-process, once,
    and a failure is loud — "no device yet" is not a state."""

    @pytest.fixture
    def cold(self, monkeypatch):
        from cometbft_tpu.crypto import batch as cbatch

        monkeypatch.setattr(
            cbatch, "_device_state",
            {"status": "uninitialized", "ndev": 0, "platform": None,
             "kind": None},
        )
        return cbatch

    def test_init_reports_the_backend_and_is_idempotent(self, cold):
        assert cold.device_status()["status"] == "uninitialized"
        state = cold.init_device_plane()
        assert state == {
            "status": "ready", "ndev": len(jax.devices()),
            "platform": "cpu", "kind": jax.devices()[0].device_kind,
        }
        assert cold.init_device_plane() == state
        assert cold.device_status() == state

    def test_factory_brings_the_plane_up_in_process(self, cold):
        from cometbft_tpu.crypto import ed25519 as ed
        from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

        bv = cold.create_batch_verifier(ed.gen_priv_key().pub_key())
        assert isinstance(bv, TpuBatchVerifier)  # never a host stand-in
        assert cold.device_status()["status"] == "ready"

    def test_a_backend_that_cannot_start_is_loud(self, cold, monkeypatch):
        from cometbft_tpu.crypto import ed25519 as ed

        def boom(*a, **k):
            raise RuntimeError("Unable to initialize backend 'tpu'")

        monkeypatch.setattr(jax, "devices", boom)
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            cold.init_device_plane()
        assert cold.device_status()["status"] == "failed"
        assert "Unable to initialize" in cold.device_status()["error"]
        # and the factory does not paper over it with the host verifier
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            cold.create_batch_verifier(ed.gen_priv_key().pub_key())

    def test_no_child_process_is_asked_about_devices(self, cold,
                                                     monkeypatch):
        def no_children(*a, **k):
            raise AssertionError("device start-up spawned a process")

        monkeypatch.setattr(subprocess, "Popen", no_children)
        assert cold.init_device_plane()["status"] == "ready"


def test_native_artefact_is_keyed_by_content(tmp_path, monkeypatch):
    """A library built from other source (or on another CPU) has
    another name: whatever sits in native/build is never loaded unless
    it was built from exactly this source, with these flags, here."""
    from cometbft_tpu.utils import native_build as NB

    src = tmp_path / "a.cpp"
    src.write_text('extern "C" int f() { return 1; }\n')
    lib = NB.NativeLib(str(src), "liba.so", "CMT_TPU_TEST_NO_LIBA")
    first = lib._artefact_path()
    assert first.endswith(".so") and "liba-" in os.path.basename(first)
    src.write_text('extern "C" int f() { return 2; }\n')
    assert lib._artefact_path() != first
    monkeypatch.setattr(NB, "_cpu_identity", lambda: b"another-cpu")
    other_cpu = lib._artefact_path()
    src.write_text('extern "C" int f() { return 1; }\n')
    assert lib._artefact_path() not in (first, other_cpu)
    assert lib.status == "unloaded"


# -- the phases at tiny size on the CPU backend ---------------------------
#
# XLA-on-CPU compiles of the verify kernels are the cost here (tens of
# seconds each, on every core the compiler can find), so the phases are
# sized to SHARE programs: every one-chip batch is at most 16
# signatures of one 12-key set — one table-build program and one
# 16-lane keyed program for commit150 and replay1k, plus
# the mesh program.  The 4-bit table width the real replay1k and mesh
# phases run at is a constant of the same programs; its kernels are
# proved on the CPU by tests/test_ops_kernel.py and at their real
# shapes by tests/test_chip_compile.py.

N_VALS = 12  # full commit 12, verify_commit_light 9: both 16 lanes


@pytest.fixture(scope="module")
def smoke_env():
    """What the phases need to run the device path here: a threshold
    override (the script itself refuses to run with one), one device
    for the one-chip phases, a prefetch depth of one block (so the
    coalesced prefetch is a 16-lane batch too), fresh process-wide
    state — all put back."""
    from cometbft_tpu import metrics as M
    from cometbft_tpu.crypto import dispatch
    from cometbft_tpu.ops import precompute as PR

    mp = pytest.MonkeyPatch()
    mp.setenv("CMT_TPU_DEVICE_MIN_BATCH", "2")
    mp.setenv("CMT_TPU_DISABLE_MESH_VERIFY", "1")
    mp.setenv("CMT_TPU_VERIFY_PREFETCH", "1")
    PR.TABLE_CACHE.clear()
    dispatch.reset_for_tests()
    compiles = S.CompileLog()
    yield compiles
    mp.undo()
    PR.TABLE_CACHE.clear()
    dispatch.reset_for_tests()
    M.install_crypto_metrics(None)


@pytest.fixture(scope="module")
def running_node(smoke_env):
    import shutil

    node, home, line = S.phase_node(smoke_env, seed=0, n_txs=3,
                                    wait_prober=False)
    yield line
    node.stop()
    shutil.rmtree(home, ignore_errors=True)


def test_phase_node(running_node):
    line = running_node
    assert line["ok"] and line["acked"] == line["read_back"] == 3
    assert line["height"] >= 3 and line["verify_queue"] == "installed"
    json.dumps(line)


def test_phase_commit150(running_node, smoke_env):
    line = S.phase_commit150(
        smoke_env, seed=0, platform="cpu", n_vals=N_VALS, n_full=6,
        n_light=3, oracle_sample=8,
    )
    assert line["ok"] and line["retraces_after_warmup"] == 0
    assert line["oracle"]["rejected"] == 5
    assert {b["tier"] for b in line["run"]["batches"]
            if b["bucket"] > 1} == {"keyed"}
    assert line["table"]["window_bits"] == 8
    json.dumps(line)


def test_phase_replay1k(running_node, smoke_env):
    n_blocks = 6
    line = S.phase_replay1k(
        smoke_env, seed=0, platform="cpu", n_vals=N_VALS,
        n_blocks=n_blocks, oracle_sample=8,
    )
    assert line["ok"] and line["retraces_after_warmup"] == 0
    assert line["oracle"]["rejected"] == 2
    assert line["prefetch_depth"] == 1
    assert line["queue_launched_sigs"] >= (n_blocks - 1) * N_VALS
    assert line["queue_failed_batches"] == 0
    json.dumps(line)


def test_phase_replay1k_fails_when_nothing_was_recorded(
    running_node, smoke_env, monkeypatch
):
    """The phase's pass criteria look at recorded batches; a counter
    that stopped recording must fail it, not pass it unchecked."""
    from cometbft_tpu.crypto import dispatch

    monkeypatch.setattr(
        dispatch.DispatchLadder, "cost_snapshot",
        lambda self: {"table": []},
    )
    with pytest.raises(S.SmokeFailure, match="no commit-sized batch"):
        S.phase_replay1k(
            smoke_env, seed=0, platform="cpu", n_vals=N_VALS,
            n_blocks=3, oracle_sample=2,
        )


def test_phase_mesh_on_virtual_devices(smoke_env, monkeypatch):
    """The --chips 4 phase's function over every virtual CPU device
    the suite runs with (eight here; four when rehearsed by hand with
    --xla_force_host_platform_device_count=4)."""
    from cometbft_tpu.ops import precompute as PR

    monkeypatch.delenv("CMT_TPU_DISABLE_MESH_VERIFY")
    PR.TABLE_CACHE.clear()
    n = len(jax.devices())
    assert n > 1
    line = S.phase_mesh(
        smoke_env, seed=0, platform="cpu", n_devices=n, n_vals=N_VALS,
        n_commits=3, oracle_sample=6,
    )
    assert line["ok"] and len(line["sharded_table"]["shard_bytes"]) == n
    json.dumps(line)

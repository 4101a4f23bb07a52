"""Test configuration.

Tests run on the CPU backend with eight virtual devices, so the
multi-chip sharding paths are exercised without a chip: the two
variables below are set before anything imports jax, which is all the
CPU backend needs.  Nothing here ever runs on an accelerator — the
program is proved on the chip by ``python chip_smoke.py`` (through the
chip tool; ``--chips 4`` for the mesh tier), and the chip's compiler is
asked about the real shapes in tests/test_chip_compile.py.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

# NB: kernel-compile caching for the suite is provided by
# cometbft_tpu/ops/__init__.py — JAX_COMPILATION_CACHE_DIR when set,
# else <checkout>/.xla_cache: warm runs skip recompiles of unchanged
# kernels at known shapes.  No second cache dir is configured here.

import random
import sys

import pytest


def pytest_configure(config):
    config.addinivalue_line(
        "markers",
        "slow: soak-tier test (fuzz soaks, WAN/e2e nets, kernel "
        "tortures) — skipped unless CMT_TPU_SLOW_TESTS=1; the default "
        "gate stays under 15 min single-core (reference analog: the "
        "CI package splits in tests.mk:66-87)",
    )


def _have_fast_crypto() -> bool:
    """True when the optional `cryptography` (OpenSSL) package is
    importable.  Without it the gated pure-Python ed25519/X25519
    fallback is ~100x slower per op — correct, and fine for the unit
    suites, but the multi-node localnet/e2e suites assume native
    signing speed and blow the tier-1 wall-clock budget."""
    try:
        import cryptography  # noqa: F401

        return True
    except ImportError:
        return False


#: modules whose tests spin multi-node localnets (block production =
#: continuous signing) — skipped without `cryptography`, runnable
#: anywhere it is installed
_LOCALNET_MODULES = {
    "test_blocksync",
    "test_consensus",
    "test_e2e_wan",
    "test_grpc",
    "test_light_proxy",
    "test_pbts",
    "test_reactors",
    "test_rpc",
    "test_statesync",
}

#: individual localnet tests inside otherwise-fast modules (the
#: e2e_perturb entries are its three longest node-rotation scenarios —
#: ~220s combined under pure-Python signing)
_LOCALNET_TESTS = {
    "test_node_prunes_behind_app_retain_height",
    "test_chain_commits_through_external_process",
    "test_fresh_node_discovers_localnet_via_seed",
    "test_validator_signs_via_external_signer_process",
    "test_wipe_and_resync_twice",
    "test_wiped_node_restores_via_statesync",
    "test_live_equivocation_detected_and_committed",
}


def pytest_collection_modifyitems(config, items):
    slow_ok = os.environ.get("CMT_TPU_SLOW_TESTS")
    skip_slow = pytest.mark.skip(
        reason="soak tier; run with CMT_TPU_SLOW_TESTS=1 (make test-slow)"
    )
    skip_localnet = pytest.mark.skip(
        reason="localnet suite needs native-speed signing: install the "
        "optional `cryptography` package (pure-Python fallback is "
        "~100x slower and breaks the suite's timing budget)"
    )
    fast_crypto = _have_fast_crypto()
    for item in items:
        if not slow_ok and item.get_closest_marker("slow"):
            item.add_marker(skip_slow)
        if fast_crypto:
            continue
        mod = getattr(item, "module", None)
        modname = mod.__name__.rpartition(".")[2] if mod else ""
        if (
            modname in _LOCALNET_MODULES
            or item.name.split("[")[0] in _LOCALNET_TESTS
        ):
            item.add_marker(skip_localnet)


@pytest.fixture
def rng():
    return random.Random(0x5EED)


# -- tier-1 wall-clock harvest (attribution plane, ISSUE 16) --------------
#
# The tier-1 gate has a 15-minute single-core budget (pytest_configure
# above) but nothing was MEASURING it — suite growth eats the budget
# silently until the gate times out.  Harvest per-module durations
# (setup + call + teardown, the real wall a module costs the gate) and,
# when CMT_TPU_TIER1_LEDGER=1 marks an intentional full green run,
# append a perfdiff-gated ``tier1_wall_seconds`` ledger row — unit "s",
# so the gate treats it as latency (regresses UP).  Top-cost modules
# ride along as provenance: a regression names the module that grew.

_module_seconds: dict[str, float] = {}


def pytest_runtest_logreport(report):
    mod = report.nodeid.split("::", 1)[0]
    _module_seconds[mod] = _module_seconds.get(mod, 0.0) + float(
        getattr(report, "duration", 0.0) or 0.0
    )


def pytest_sessionfinish(session, exitstatus):
    if os.environ.get("CMT_TPU_TIER1_LEDGER") != "1":
        return
    if exitstatus != 0 or not _module_seconds:
        return  # only green runs become ledger points
    total = sum(_module_seconds.values())
    top = sorted(
        _module_seconds.items(), key=lambda kv: -kv[1]
    )[:5]
    try:
        import time as _time

        from tools import perfledger

        perfledger.append_rows(
            [
                {
                    "config": "tier1_wall_seconds",
                    "value": round(total, 1),
                    "unit": "s",
                    "note": "top modules: " + ", ".join(
                        f"{os.path.basename(m)} {s:.1f}s"
                        for m, s in top
                    ),
                    "measured": _time.strftime("%Y-%m-%d %H:%M"),
                }
            ],
            source="tier1",
        )
    except Exception as exc:  # noqa: BLE001 — provenance only
        print(f"tier1 ledger append failed (ignored): {exc}",
              file=sys.stderr)

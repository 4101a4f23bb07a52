"""A commit wider than one straight launch, through ``verify_commit``
(ISSUEs 33, 34): with ``MAX_LAUNCH`` cut to 8, 20 signatures over 20
keys pad to 24 lanes and run as three 8-lane slices of one keyed
program, the last of them half empty — the shape a 10,000-signature
commit takes at 10,240 lanes in five slices of ``WIDE_SLICE``.  And the
rule itself, ``launch_lanes``: the next power of two up to
``MAX_LAUNCH`` signatures, whole slices above it.  CPU backend, 4-bit
tables (the wide path's own width)."""

from __future__ import annotations

from dataclasses import replace

import pytest

from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import dispatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import verify_queue as vq
from cometbft_tpu.ops import ed25519_verify as EV
from cometbft_tpu.ops import precompute as PR
from cometbft_tpu.types import validation
from cometbft_tpu.utils.trace import TRACER

from tests.helpers import CHAIN_ID, make_block_id, make_commit, make_val_set

N = 20
SLICE = 8
LANES = 24  # three slices; the last carries signatures 16-19 and 4 zeros


@pytest.mark.parametrize(
    "max_launch,n,lanes,slices",
    [
        # up to MAX_LAUNCH signatures: the power-of-two ladder, straight
        (8192, 0, 8, 1), (8192, 1, 8, 1), (8192, 150, 256, 1),
        (8192, 1_000, 1_024, 1), (8192, 8_000, 8_192, 1),
        (8192, 8_192, 8_192, 1),
        # above it: whole slices of WIDE_SLICE, one shape to the ceiling
        (8192, 8_193, 10_240, 5), (8192, 10_000, 10_240, 5),
        (8192, 10_241, 12_288, 6),
        # MAX_LAUNCH below WIDE_SLICE (the tests', the rehearsal's): the
        # slice is MAX_LAUNCH itself
        (16, 24, 32, 2), (8, 20, 24, 3), (64, 200, 256, 4),
        # a MAX_LAUNCH that is no power of two: straight up to it, its
        # own multiples above
        (10, 10, 16, 1), (10, 23, 30, 3),
    ],
)
def test_lanes_of_a_launch(monkeypatch, max_launch, n, lanes, slices):
    assert EV.WIDE_SLICE == 2048 and 8192 % EV.WIDE_SLICE == 0
    monkeypatch.setattr(EV, "MAX_LAUNCH", max_launch)
    assert EV.launch_lanes(n) == (lanes, slices)
    assert lanes >= n and lanes % slices == 0


@pytest.fixture(scope="module")
def signed():
    assert vq._installed() is None  # make_commit would speculate votes
    vals, keys = make_val_set(N)
    bid = make_block_id(b"wide")
    return vals, bid, make_commit(vals, keys, bid)


@pytest.fixture
def wide_route(monkeypatch):
    """Every ed25519 batch on the keyed tier, a launch slice of 8."""
    dispatch.reset_for_tests()
    PR.TABLE_CACHE.clear()
    monkeypatch.setenv("CMT_TPU_DISABLE_MESH_VERIFY", "1")
    monkeypatch.setattr(PR, "KEY8_MAX", 0)
    monkeypatch.setattr(EV, "MAX_LAUNCH", SLICE)
    monkeypatch.setitem(
        crypto_batch.REGISTRY, ed.KEY_TYPE,
        lambda: EV.TpuBatchVerifier(device_min_batch=1),
    )
    was = TRACER.enabled
    TRACER.set_enabled(True)
    yield
    TRACER.set_enabled(was)
    PR.TABLE_CACHE.clear()
    dispatch.reset_for_tests()


def flipped(commit, idx: int):
    sigs = list(commit.signatures)
    s = sigs[idx].signature
    sigs[idx] = replace(
        sigs[idx], signature=s[:5] + bytes([s[5] ^ 0x04]) + s[6:]
    )
    return replace(commit, signatures=tuple(sigs))


def oracle_first_bad(vals, commit) -> int | None:
    """The first signature ``crypto/ed25519.py`` rejects, on the host."""
    for i, cs in enumerate(commit.signatures):
        pk = vals.get_by_index(i).pub_key
        if not pk.verify_signature(
            commit.vote_sign_bytes(CHAIN_ID, i), cs.signature
        ):
            return i
    return None


@pytest.mark.parametrize("bad", [None, 0, 7, 8, 19, 16],
                         ids=["honest", "first", "before_the_seam",
                              "after_the_seam", "last",
                              "first_of_the_half_empty_slice"])
def test_verdict_across_the_slices_equals_the_oracle(wide_route, signed, bad):
    vals, bid, commit = signed
    if bad is not None:
        commit = flipped(commit, bad)
    assert oracle_first_bad(vals, commit) == bad
    TRACER.clear()
    if bad is None:
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    else:
        with pytest.raises(
            validation.InvalidCommitSignatures, match=rf"\(#{bad}\)$"
        ):
            validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    by = {e["name"]: e["args"] for e in TRACER.events()}
    # one keyed launch of 24 lanes for 20 signatures: three slices
    launch = by["device_launch"]
    assert launch["kernel"] == "keyed" and launch["window_bits"] == 4
    assert launch["batch"] == LANES == 3 * SLICE
    assert by["batch_verify"]["batch"] == N
    assert by["verify_commit"]["sigs"] == N
    assert not dispatch.LADDER.snapshot()["transitions"]


def test_the_launch_span_and_the_counter_say_what_the_lanes_carry(
    wide_route, signed
):
    """Occupancy and padding are readable from a ring (sigs / batch
    and batch - sigs a launch, off the ``device_launch`` span), and the
    launches from /metrics, on both tiers."""
    from cometbft_tpu.metrics import (
        CryptoMetrics, crypto_metrics, install_crypto_metrics,
    )
    from cometbft_tpu.utils.metrics import Registry

    vals, bid, commit = signed
    install_crypto_metrics(CryptoMetrics(Registry()))
    try:
        TRACER.clear()
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        dispatch.LADDER.tier_fault("keyed", reason="test", batch=N)
        validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
        cm = crypto_metrics()
        for kernel in ("keyed", "generic"):
            assert cm.batch_verify_launches.labels(kernel=kernel).get() == 1
    finally:
        install_crypto_metrics(None)
    launches = [e["args"] for e in TRACER.events()
                if e["name"] == "device_launch"]
    assert [a["kernel"] for a in launches] == ["keyed", "generic"]
    for a in launches:
        assert (a["sigs"], a["batch"], a["slices"]) == (N, LANES, 3)
        assert a["batch"] - a["sigs"] == LANES - N  # the padded lanes

"""A commit wider than one launch slice, through ``verify_commit`` (ISSUE
33): with ``MAX_LAUNCH`` cut to 8, 20 signatures over 20 keys pad to 32
lanes and run as four 8-lane slices of one keyed program — the shape a
10,000-signature commit takes at 16,384 lanes in two slices of 8,192.
CPU backend, 4-bit tables (the wide path's own width)."""

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


@pytest.mark.parametrize("bad", [None, 0, 7, 8, 19],
                         ids=["honest", "first", "before_the_seam",
                              "after_the_seam", "last"])
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
    # one keyed launch of 32 lanes for 20 signatures: four slices
    launch = by["device_launch"]
    assert launch["kernel"] == "keyed" and launch["window_bits"] == 4
    assert launch["batch"] == 32 == 4 * SLICE
    assert by["batch_verify"]["batch"] == N
    assert by["verify_commit"]["sigs"] == N
    assert not dispatch.LADDER.snapshot()["transitions"]

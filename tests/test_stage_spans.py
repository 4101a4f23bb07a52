"""The verify path's stage spans (ISSUE 26): each fires once per call,
on the thread that does the work, with the verify queue off and on —
tiny sizes, the XLA-on-CPU kernel standing in for the chip."""

from __future__ import annotations

import threading
import time
from collections import Counter
from dataclasses import replace

import pytest

from cometbft_tpu.blocksync.reactor import commit_prefetch_items
from cometbft_tpu.crypto import batch as crypto_batch
from cometbft_tpu.crypto import dispatch
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.crypto import verify_queue as vq
from cometbft_tpu.types import PRECOMMIT_TYPE, VoteSet, validation
from cometbft_tpu.utils.trace import TRACER

from tests.helpers import (
    CHAIN_ID,
    make_block_id,
    make_commit,
    make_val_set,
    signed_vote,
)

#: what one device batch records, whoever asked for it
LAUNCH = ["batch_verify", "verify/pack", "device_launch", "device_fetch"]


@pytest.fixture
def device_route(monkeypatch):
    """Every ed25519 batch takes the generic kernel (6 signatures are
    far under any production threshold)."""
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

    dispatch.reset_for_tests()
    monkeypatch.setenv("CMT_TPU_DISABLE_PRECOMPUTE", "1")
    monkeypatch.setitem(
        crypto_batch.REGISTRY, ed.KEY_TYPE,
        lambda: TpuBatchVerifier(device_min_batch=1),
    )
    was = TRACER.enabled
    TRACER.set_enabled(True)
    yield
    TRACER.set_enabled(was)
    q = vq._installed()
    if q is not None and q.is_running():
        q.stop()
    vq.install_queue(None)
    dispatch.reset_for_tests()


def _commit(tag: bytes):
    # built with no queue installed: make_commit drives add_vote,
    # which would otherwise speculate every vote into the cache
    assert vq._installed() is None
    vals, keys = make_val_set(6)
    bid = make_block_id(tag)
    return vals, bid, make_commit(vals, keys, bid)


def _spans_of(fn) -> tuple[Counter, list[dict]]:
    TRACER.clear()
    fn()
    events = TRACER.events()
    return Counter(e["name"] for e in events), events


def test_queue_off_each_stage_fires_once_a_commit(device_route):
    vals, bid, commit = _commit(b"stage-off")
    names, events = _spans_of(
        lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    )
    expected = ["verify_commit", "verify_commit/collect",
                "verify_commit/sign_bytes", "verify/plan"] + LAUNCH
    assert {n: names[n] for n in expected} == dict.fromkeys(expected, 1)
    # nothing speculates without a queue
    assert "verify_commit/spec_lookup" not in names
    assert "verify_commit/record" not in names
    assert not any(n.startswith("verify_queue/") for n in names)
    by = {e["name"]: e for e in events}
    root = by["verify_commit"]
    assert root["args"]["mode"] == "full" and root["args"]["sigs"] == 6
    assert root["args"]["groups"] == 1
    assert by["device_fetch"]["args"]["batch"] == 6
    assert by["verify/plan"]["args"]["route"] == "device"
    # one thread, and every stage inside the root's interval
    for name in expected[1:]:
        e = by[name]
        assert e["tid"] == root["tid"], name
        assert root["ts"] <= e["ts"], name
        assert e["ts"] + e["dur"] <= root["ts"] + root["dur"] + 0.2, name
    for name in LAUNCH[1:]:
        assert by[name]["args"]["parent"] == "batch_verify"


@pytest.mark.parametrize("fn, mode", [
    (validation.verify_commit_light, "light"),
    (lambda c, v, b, h, commit:
     validation.verify_commit_light_trusting(c, v, commit), "trusting"),
])
def test_the_root_names_its_mode(device_route, fn, mode):
    vals, bid, commit = _commit(b"stage-" + mode.encode())
    names, events = _spans_of(lambda: fn(CHAIN_ID, vals, bid, 1, commit))
    assert names["verify_commit"] == 1
    root = next(e for e in events if e["name"] == "verify_commit")
    assert root["args"]["mode"] == mode
    # the light modes stop past the threshold: 5 of 6 at two thirds,
    # 3 of 6 at one third
    assert root["args"]["sigs"] == {"light": 5, "trusting": 3}[mode]


@pytest.mark.parametrize("needed, encoded", [
    ("first_sight", 6), (None, 0), (6 * 10 * 2 // 3, 1),
], ids=["first_sight", "prefetched", "prefetched_to_two_thirds"])
def test_the_sign_bytes_span_says_what_it_encoded(
    device_route, needed, encoded,
):
    """``encoded`` beside ``sigs``: all of them on a commit seen for the
    first time, none after a prefetch of the same object, the votes
    beyond the cut after a prefetch that stopped at two thirds."""
    vals, bid, commit = _commit(b"stage-encoded")
    if needed != "first_sight":
        triples = validation.commit_check_triples(
            CHAIN_ID, vals, commit, needed
        )
        assert len(triples) == 6 - encoded

    def sign_bytes_args(check) -> dict:
        _, events = _spans_of(
            lambda: check(CHAIN_ID, vals, bid, 1, commit)
        )
        return next(
            e for e in events if e["name"] == "verify_commit/sign_bytes"
        )["args"]

    args = sign_bytes_args(validation.verify_commit)
    assert args["sigs"] == 6 and args["encoded"] == encoded
    # a second check of the same object encodes nothing
    args = sign_bytes_args(validation.verify_commit_light)
    assert args["sigs"] == 5 and args["encoded"] == 0


@pytest.mark.parametrize("negative", [0, 1], ids=["fresh", "negative_time"])
def test_the_sign_bytes_spans_count_the_fallback(device_route, negative):
    """``generic`` beside ``encoded``: how many of the encoded votes the
    commit's template left to ``canonical.vote_sign_bytes`` — none on a
    fresh commit, one where a vote was signed at a negative time, whose
    verdict is the same (accepted) — on the check's span and on the
    prefetch's."""
    vals, keys = make_val_set(6)
    bid = make_block_id(b"stage-generic")
    times = [1_700_000_000_000_000_000 + i for i in range(6)]
    if negative:
        times[2] = -1_500_000_001
    vs = VoteSet(CHAIN_ID, 1, 0, PRECOMMIT_TYPE, vals)
    for i, key in enumerate(keys):
        vs.add_vote(signed_vote(key, i, bid, time_ns=times[i]))
    commit = vs.make_commit()
    _, events = _spans_of(
        lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    )
    args = next(
        e for e in events if e["name"] == "verify_commit/sign_bytes"
    )["args"]
    assert (args["sigs"], args["encoded"], args["generic"]) == (6, 6, negative)
    _, events = _spans_of(
        lambda: commit_prefetch_items(CHAIN_ID, vals, replace(commit))
    )
    args = next(
        e for e in events if e["name"] == "blocksync/prefetch_items"
    )["args"]
    assert (args["encoded"], args["generic"]) == (6, negative)


def test_queue_on_the_commit_consults_and_records(device_route):
    vals, bid, commit = _commit(b"stage-on")
    q = vq.VerifyQueue()
    q.start()
    vq.install_queue(q)
    names, _ = _spans_of(
        lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    )
    expected = ["verify_commit", "verify_commit/collect",
                "verify_commit/sign_bytes", "verify_commit/spec_lookup",
                "verify_commit/record", "verify/plan"] + LAUNCH
    assert {n: names[n] for n in expected} == dict.fromkeys(expected, 1)
    # again: every signature is in the cache, nothing launches
    names, events = _spans_of(
        lambda: validation.verify_commit(CHAIN_ID, vals, bid, 1, commit)
    )
    assert names["verify_commit/spec_lookup"] == 1
    assert "verify_commit/record" not in names
    assert not any(n in names for n in LAUNCH)
    root = next(e for e in events if e["name"] == "verify_commit")
    assert root["args"]["tier"] == "speculative"


def test_queue_on_a_prefetch_fires_every_queue_stage_once(device_route):
    vals, _, commit = _commit(b"stage-prefetch")
    q = vq.VerifyQueue()
    q.start()
    vq.install_queue(q)
    caller = threading.get_ident()

    def prefetch():
        items = commit_prefetch_items(CHAIN_ID, vals, commit)
        assert len(items) == 6
        futures = q.submit_many(items, vq.PRIORITY_PREFETCH)
        assert all(f.result(120) for f in futures)
        deadline = time.monotonic() + 10
        while q.busy() and time.monotonic() < deadline:
            time.sleep(0.005)
        assert not q.busy()

    names, events = _spans_of(prefetch)
    expected = ["blocksync/prefetch_items", "verify_queue/submit",
                "verify_queue/pending_wait", "verify_queue/prepare",
                "verify_queue/prehash", "verify/plan",
                "verify_queue/prepared_wait", "verify_queue/launch",
                "verify_queue/resolve"] + LAUNCH
    assert {n: names[n] for n in expected} == dict.fromkeys(expected, 1)
    by = {e["name"]: e for e in events}
    collector = by["verify_queue/prepare"]["tid"]
    launcher = by["verify_queue/launch"]["tid"]
    assert len({caller, collector, launcher}) == 3
    where = {
        caller: ["blocksync/prefetch_items", "verify_queue/submit"],
        collector: ["verify_queue/pending_wait", "verify_queue/prehash",
                    "verify/plan"],
        launcher: ["verify_queue/prepared_wait", "verify_queue/resolve"]
        + LAUNCH,
    }
    for tid, mine in where.items():
        for name in mine:
            assert by[name]["tid"] == tid, name
    assert by["verify_queue/prehash"]["args"]["parent"] == (
        "verify_queue/prepare"
    )
    assert by["verify_queue/resolve"]["args"]["parent"] == (
        "verify_queue/launch"
    )
    assert by["verify_queue/submit"]["args"]["batch"] == 6
    # the handoffs run from a stamp on the ring's clock to the pop:
    # arrival (inside submit) -> the collector's pop -> prepare;
    # the collector's append (inside prepare's tail) -> the launcher's
    # pop -> launch
    sub, pend = by["verify_queue/submit"], by["verify_queue/pending_wait"]
    prep, park = by["verify_queue/prepare"], by["verify_queue/prepared_wait"]
    assert sub["ts"] <= pend["ts"] <= sub["ts"] + sub["dur"]
    assert pend["ts"] + pend["dur"] <= prep["ts"] + 1.0
    assert prep["ts"] + prep["dur"] <= park["ts"] + 1.0
    assert park["ts"] + park["dur"] <= by["verify_queue/launch"]["ts"] + 1.0
    q.stop()


def test_a_chunked_table_build_leaves_a_span_a_chunk(monkeypatch):
    """One ``table_build`` span a build call, numbered ``chunk`` of
    ``of``, and one ``table_build/place`` span a placement into the
    pool (ISSUE 33): 5 keys in chunks of 4 are two of each."""
    from cometbft_tpu.ops import precompute as PR

    monkeypatch.setattr(PR, "KEY8_MAX", 0)  # 4-bit pages: small builds
    monkeypatch.setattr(PR, "BUILD_CHUNK", 4)
    pubs = [ed.priv_key_from_secret(b"span/%d" % i).pub_key().bytes()
            for i in range(5)]
    cache = PR.KeyTableCache()
    was = TRACER.enabled
    TRACER.set_enabled(True)
    try:
        names, events = _spans_of(lambda: cache.lookup_or_build(pubs))
    finally:
        TRACER.set_enabled(was)
    assert names["table_build"] == 2 == names["table_build/place"]
    assert cache.stats["build_chunks"] == 2
    builds = [e for e in events if e["name"] == "table_build"]
    places = [e for e in events if e["name"] == "table_build/place"]
    assert [(e["args"]["keys"], e["args"]["chunk"], e["args"]["of"])
            for e in builds] == [(4, 1, 2), (1, 2, 2)]
    assert [(e["args"]["keys"], e["args"]["slots"], e["args"]["cap"])
            for e in places] == [(4, 4, 8), (1, 5, 8)]
    # build, place, build, place: a chunk is placed before the next
    # is built
    order = sorted(builds + places, key=lambda e: e["ts"])
    assert [e["name"] for e in order] == [
        "table_build", "table_build/place"] * 2
    for b, p in zip(builds, places):
        assert b["ts"] + b["dur"] <= p["ts"] + 0.2

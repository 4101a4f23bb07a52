"""The key-table build in chunks (``ops/precompute.py`` ``BUILD_CHUNK``,
ISSUE 33): a set with more missing keys than one build call takes is
built and placed a chunk at a time, and the pool that comes out is the
one-call build's bit for bit.  CPU backend, the chunk cut to 4 keys so
that 13 keys are four build calls; the build programs compiled here
hold 1, 4, 8 and 16 lanes at each window width (a file of its own: they
are most of its time)."""

from __future__ import annotations

import numpy as np
import pytest

from cometbft_tpu import metrics as M
from cometbft_tpu.crypto import ed25519 as ed
from cometbft_tpu.ops import precompute as PR
from cometbft_tpu.utils.metrics import Registry
from cometbft_tpu.utils.trace import TRACER

CHUNK = 4


def pubs_of(n: int, tag: bytes = b"chunk") -> list[bytes]:
    return [
        ed.priv_key_from_secret(b"%s/%d" % (tag, i)).pub_key().bytes()
        for i in range(n)
    ]


@pytest.fixture
def cm():
    """A crypto-metrics sink that counts (the default one is a no-op)."""
    sink = M.CryptoMetrics(Registry())
    M.install_crypto_metrics(sink)
    yield sink
    M.install_crypto_metrics(None)


def width(monkeypatch, window_bits: int) -> None:
    """Every set takes ``window_bits``-wide pages, whatever its size."""
    monkeypatch.setattr(PR, "KEY8_MAX", 0 if window_bits == 4 else 1 << 14)


def retraces(cm, window_bits: int) -> int:
    return int(
        cm.key_pool_retraces.labels(window_bits=str(window_bits)).get()
    )


@pytest.mark.parametrize("window_bits", [4, 8])
@pytest.mark.parametrize("n", [1, 4, 5, 9, 13])
def test_chunked_build_equals_the_one_call_build(
    monkeypatch, cm, n, window_bits
):
    width(monkeypatch, window_bits)
    pubs = pubs_of(n)
    whole = PR.KeyTableCache()
    want = whole.lookup_or_build(pubs)
    assert whole.stats["build_chunks"] == 1

    monkeypatch.setattr(PR, "BUILD_CHUNK", CHUNK)
    cache = PR.KeyTableCache()
    before = retraces(cm, window_bits)
    got = cache.lookup_or_build(pubs)
    n_chunks = -(-n // CHUNK)
    assert cache.stats == {
        "keys_built": n, "keys_evicted": 0, "build_chunks": n_chunks,
    }
    # grown once, to hold every missing key — not once a chunk
    assert retraces(cm, window_bits) == before + 1
    assert got.window_bits == window_bits == want.window_bits
    # slot for slot, bit for bit
    assert got.key_index == want.key_index
    assert np.array_equal(got.valid, want.valid)
    assert got.table.shape == want.table.shape == (
        PR._pool_cap(n), PR.slot_rows(window_bits), PR.ROW
    )
    assert np.array_equal(np.asarray(got.table), np.asarray(want.table))
    pool = cache._pools[window_bits]
    assert sorted(pool.free + list(pool.slots.values())) == list(
        range(pool.cap)
    )
    # a second lookup builds nothing and hands back the same entry
    assert cache.lookup_or_build(pubs) is got
    assert cache.peek(pubs) is got
    assert cache.stats["build_chunks"] == n_chunks
    assert retraces(cm, window_bits) == before + 1
    assert not cache._pending


def test_only_the_missing_keys_are_built_in_chunks(monkeypatch, cm):
    """3 of 9 keys already pooled: the other 6 are two build calls."""
    width(monkeypatch, 8)
    monkeypatch.setattr(PR, "BUILD_CHUNK", CHUNK)
    pubs = sorted(pubs_of(9, b"partial"))
    cache = PR.KeyTableCache()
    first = cache.lookup_or_build(pubs[:3])
    assert cache.stats["build_chunks"] == 1
    before = retraces(cm, 8)
    TRACER.clear()
    entry = cache.lookup_or_build(pubs)
    assert cache.stats == {
        "keys_built": 9, "keys_evicted": 0, "build_chunks": 3,
    }
    assert retraces(cm, 8) == before + 1
    builds = [e["args"] for e in TRACER.events()
              if e["name"] == "table_build"]
    assert [(a["keys"], a["chunk"], a["of"]) for a in builds] == [
        (4, 1, 2), (2, 2, 2),
    ]
    # the pooled keys kept their slots and their pages
    for p in pubs[:3]:
        assert entry.key_index[p] == first.key_index[p]
        assert np.array_equal(
            np.asarray(entry.table[entry.key_index[p]]),
            np.asarray(first.table[first.key_index[p]]),
        )
    assert entry.valid[[entry.key_index[p] for p in pubs]].all()


@pytest.mark.parametrize("failing", [1, 2, 3])
def test_a_chunk_that_raises_leaves_no_latch(monkeypatch, failing):
    """The chunks before it stay resident, every latch is released, and
    the next lookup builds only what is still missing."""
    width(monkeypatch, 4)
    monkeypatch.setattr(PR, "BUILD_CHUNK", CHUNK)
    pubs = sorted(pubs_of(9, b"fault"))
    cache = PR.KeyTableCache()
    real = cache._build_pages

    def faulty(missing, window_bits, chunk, of):
        if chunk == failing:
            raise RuntimeError("RESOURCE_EXHAUSTED: planted")
        return real(missing, window_bits, chunk, of)

    monkeypatch.setattr(cache, "_build_pages", faulty)
    with pytest.raises(RuntimeError, match="planted"):
        cache.lookup_or_build(pubs)
    done = CHUNK * (failing - 1)
    assert cache._pending == {}
    assert cache.stats["keys_built"] == done
    pool = cache._pools[4]
    assert sorted(pool.slots) == pubs[:done]
    assert cache.peek(pubs) is None
    if done:
        assert cache.peek(pubs[:done]) is not None
    assert sorted(pool.free + list(pool.slots.values())) == list(
        range(pool.cap)
    )
    monkeypatch.setattr(cache, "_build_pages", real)
    entry = cache.lookup_or_build(pubs)
    assert cache.stats["keys_built"] == 9
    assert sorted(entry.key_index) == pubs and entry.valid[
        [entry.key_index[p] for p in pubs]
    ].all()

"""Driver ``blocksync_replay``: the blocksync sync step, one block after
another (``blocksync/reactor.py`` ``_try_sync_step`` /
``_prefetch_commit_verifies``, as ``chip_smoke.replay`` drives it):
``verify_commit_light`` on the next block's commit, then
``commit_prefetch_items`` + the queue's prefetch submission for the
heights up to ``CMT_TPU_VERIFY_PREFETCH`` ahead — each height once, one
coalesced submission a step — then the wait for the queue to drain,
which stands in for the apply (store + ABCI) the reactor overlaps with
it.  The submission is ``submit_prefetch``'s own call with its futures
kept, so that the wait can sleep on them instead of polling."""

from __future__ import annotations

import time
from dataclasses import dataclass

import jax

from benchmark import gen
from benchmark.drivers import common

_DRAIN_TIMEOUT_S = 120.0
_DRAIN_POLL_S = 0.0005


def plan(config: dict, params: dict, seed: int) -> gen.Chain:
    return common.plan(config, params, seed, params["blocks"])


@dataclass
class State(common.State):
    depth: int = 0  # heights the prefetch runs ahead
    #: index, warm-up blocks counted, of the highest block submitted
    prefetched: int = 0


def prepare(chain: gen.Chain, config: dict, params: dict) -> State:
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.types.validation import verify_commit_light

    if not vq.speculation_active():
        raise RuntimeError("no verify queue to prefetch through")
    depth = vq.prefetch_depth_from_env()
    if len(chain.warm) < depth + 2:
        raise RuntimeError(
            f"{len(chain.warm)} warm blocks do not cover a prefetch "
            f"burst of {depth}"
        )
    return State(
        chain, gen.validator_set(chain), verify_commit_light,
        # verify_commit_light stops once the tally passes two thirds;
        # the prefetch lane verifies every vote of a block
        checked=chain.n_vals * 2 // 3 + 1, sigs_per_item=chain.n_vals,
        depth=depth,
    )


def control(st: State) -> None:
    """Breaks "the first past two thirds of power are valid": the
    program's own trusting mode at one third, by address."""
    from cometbft_tpu.types.validation import verify_commit_light_trusting

    def one_third(chain_id, vals, bid, height, commit):
        verify_commit_light_trusting(chain_id, vals, commit)

    common.swap_entry(st, one_third)


def _block(st: State, k: int) -> tuple:
    """Block ``k`` of the sync, the warm-up's counted: read where it is
    kept, so that no second list holds what the window has let go."""
    n = len(st.warm)
    return st.warm[k] if k < n else st.commits[k - n]


def _step(st: State, k: int, prefetched: int,
          parts: dict | None = None) -> tuple:
    """-> (rejection text or None, index of the highest block now
    submitted to the prefetch lane); the seconds of the step's three
    parts added to ``parts``."""
    from cometbft_tpu.blocksync.reactor import commit_prefetch_items
    from cometbft_tpu.crypto import verify_queue as vq

    n_blocks = len(st.warm) + len(st.commits)
    bid, commit = _block(st, k)
    t0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("entry.verify_commit_light"):
        err = common.run_verify(st.entry, st.vals, bid, commit)
    t1 = time.perf_counter()
    q = vq._installed()
    with jax.profiler.TraceAnnotation("entry.prefetch_submit"):
        items = []
        hi = min(k + st.depth, n_blocks - 1)
        for j in range(max(prefetched, k) + 1, hi + 1):
            got = commit_prefetch_items(gen.CHAIN_ID, st.vals,
                                        _block(st, j)[1])
            if got is None:
                raise RuntimeError("validator set does not line up")
            items.extend(got)
        futures = []
        if items:
            # what ``vq.submit_prefetch`` does, the futures kept
            futures = q.submit_many(items, vq.PRIORITY_PREFETCH)
            prefetched = hi
    t2 = time.perf_counter()
    with jax.profiler.TraceAnnotation("entry.queue_drain"):
        # asleep until the launcher resolves the batch's last future
        # (a poll here took a fifth of a core and the GIL from the
        # queue's threads: PERF.md), then until the queue says idle
        for f in reversed(futures):
            f.result(_DRAIN_TIMEOUT_S)
        give_up = time.monotonic() + _DRAIN_TIMEOUT_S
        while q.busy():
            if time.monotonic() > give_up:
                raise RuntimeError("verify queue stuck busy")
            time.sleep(_DRAIN_POLL_S)
    if parts is not None:
        for key, sec in (("verify_commit_light", t1 - t0),
                         ("prefetch_submit", t2 - t1),
                         ("queue_drain", time.perf_counter() - t2)):
            parts[key] = parts.get(key, 0.0) + sec
    return err, prefetched


def warm(st: State) -> None:
    """The sync's start: the warm-up blocks, the prefetch running ahead
    of them into the window's first blocks — the one 8-block burst a
    sync begins with (bucket 8192) is set-up, and the window opens on
    the steady state, one block submitted a step."""
    for k in range(len(st.warm)):
        err, st.prefetched = _step(st, k, st.prefetched)
        common.expect_warm(st.chain.warm[k], err)


def run(st: State, seconds: float) -> common.Window:
    win = common.Window()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while st.cursor < len(st.commits) and time.perf_counter() < deadline:
        k = st.cursor
        st.cursor += 1
        t = time.perf_counter()
        err, st.prefetched = _step(st, len(st.warm) + k,
                                   st.prefetched, win.parts)
        win.latencies.append(time.perf_counter() - t)
        win.outcomes.append((k, err))
        # the step that checked block k is over and the queue has
        # drained: the prefetch of k ran eight steps ago, its check
        # just now, and nothing asks for it again
        st.consumed(k)
    win.elapsed = time.perf_counter() - t0
    win.ran_out = st.cursor >= len(st.commits)
    return win


def metrics(win: common.Window) -> dict:
    return {"replay_blocks_per_s": len(win.outcomes) / win.elapsed}

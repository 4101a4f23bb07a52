"""A driver for the tests of the harness's chain model (PR 35), not a
cell's: a few validators and a few blocks, with everything a chain of
REAL blocks needs and the four cells' chains do not —

- ``build``: the chain made in order.  An item's block hash holds the
  hash of the signatures of the item before it (as a header's
  ``last_commit_hash`` does), so no hash is known before that commit is
  signed; ``gen.plan``'s hashes are all replaced.
- ``parts_total`` 2 on every second item: the votes are over a block id
  of two parts.
- one item of a second ``epoch``: one key of the set replaced (the set
  re-sorted by address, so indices move).
- ``compare``: one exact count of its own beside ``check.compare``'s.

``test_chain_model.py`` drives it through ``run.plan_chain``,
``check.compare`` and ``run.run_cell``.  Like a cell's driver, this
module imports neither JAX nor ``cometbft_tpu`` at its top, so that
``build``'s worker process stays clear of both.
"""

from __future__ import annotations

import hashlib
import multiprocessing
import sys
import time

from benchmark import gen
from benchmark.drivers import common


def _sha(*parts: bytes) -> bytes:
    return hashlib.sha256(b"/".join(parts)).digest()


def plan(config: dict, params: dict, seed: int) -> gen.Chain:
    chain = gen.plan(
        seed, config["validators"], params["blocks"], 0, stride=1,
        tamper_every=params["tamper_every"],
        strata=params["tamper_strata"],
    )
    # epoch 1: validator 0's key replaced, the set in canonical order
    seeds = [_sha(b"chain_driver/rotated", b"%d" % seed)] + chain.key_seeds[1:]
    pairs = sorted(
        ((ks, key.public_key().public_bytes_raw())
         for ks, key in zip(seeds, gen.private_keys(seeds))),
        key=lambda p: gen.address(p[1]),
    )
    chain.epochs.append(([p[0] for p in pairs], [p[1] for p in pairs]))
    for j, item in enumerate(chain.items):
        item.parts_total = 1 + j % 2
        item.epoch = int(j == params["rotate_at"])
    return chain


def block_hash(item: gen.Item, prev: gen.Item | None) -> bytes:
    """What a block's hash is made of here: its height, its signer
    set's epoch, and the block before it WITH its signatures."""
    last = (_sha(prev.block_hash, *prev.sigs) if prev is not None
            else b"genesis")
    return _sha(b"block", b"%d" % item.height, b"%d" % item.epoch, last)


def build_in_order(chain: gen.Chain) -> tuple[list, int, bool]:
    """The worker: hash, then sign, one item after another.  -> (the
    filled fields of every item, total sign-bytes length, whether this
    process has imported JAX or the program)."""
    keys = [gen.private_keys(seeds) for seeds, _ in chain.epochs]
    out, total, prev = [], 0, None
    for item in chain.warm + chain.items:
        item.block_hash = block_hash(item, prev)
        item.parts_hash = _sha(b"parts", item.block_hash,
                               b"%d" % item.parts_total)
        item.sigs, n = gen.sign_item(keys[item.epoch], item)
        total += n
        out.append((item.block_hash, item.parts_hash, item.parts_total,
                    item.sigs))
        prev = item
    dirty = any(m in sys.modules for m in ("jax", "cometbft_tpu"))
    return out, total, dirty


class Built:
    """What ``build`` returns: ``Signing``'s interface."""

    def __init__(self, chain: gen.Chain, workers: int) -> None:
        self.chain = chain
        self.pool = self.pending = None
        self.worker_was_dirty = None
        if workers > 1:  # in order: one process, started at once
            self.pool = multiprocessing.get_context("spawn").Pool(1)
            self.pending = self.pool.apply_async(build_in_order, (chain,))

    def finish(self) -> None:
        if self.pool is None:
            filled, total, _ = build_in_order(self.chain)
        else:
            filled, total, self.worker_was_dirty = self.pending.get(
                timeout=120
            )
        todo = self.chain.warm + self.chain.items
        for item, fields in zip(todo, filled, strict=True):
            (item.block_hash, item.parts_hash, item.parts_total,
             item.sigs) = fields
        self.chain.sign_bytes_total += total
        self.close()

    def close(self) -> None:
        if self.pool is not None:
            self.pool.terminate()
            self.pool.join()
            self.pool = None


def build(chain: gen.Chain, workers: int) -> Built:
    return Built(chain, workers)


def prepare(chain: gen.Chain, config: dict, params: dict) -> common.State:
    from cometbft_tpu.types.validation import verify_commit

    st = common.State(chain, None, verify_commit, checked=chain.n_vals,
                      sigs_per_item=chain.n_vals)
    st.vals = [gen.validator_set(chain, e) for e in range(len(chain.epochs))]
    #: what the tests plant for ``compare`` to return
    st.planted = {}
    return st


def control(st: common.State) -> None:
    from cometbft_tpu.types.validation import verify_commit_light

    st.entry = verify_commit_light


def warm(st: common.State) -> None:
    pass


def run(st: common.State, seconds: float) -> common.Window:
    win = common.Window()
    t0 = time.perf_counter()
    while st.cursor < len(st.commits):
        k = st.cursor
        st.cursor += 1
        bid, commit = st.commits[k]
        t = time.perf_counter()
        err = common.run_verify(
            st.entry, st.vals[st.chain.items[k].epoch], bid, commit
        )
        win.latencies.append(time.perf_counter() - t)
        win.outcomes.append((k, err))
        st.consumed(k)
    win.elapsed = time.perf_counter() - t0
    win.ran_out = True
    return win


def metrics(win: common.Window) -> dict:
    return {"replay_blocks_per_s": len(win.outcomes) / win.elapsed}


def compare(st: common.State, win: common.Window) -> dict:
    """One guarantee of a chain built in order: every block the window
    accepted holds the commit of the block before it."""
    items = st.chain.items
    unlinked = sum(
        items[k].block_hash != block_hash(items[k], items[k - 1] if k else None)
        for k, err in win.outcomes if err is None
    )
    out = {"accepted_blocks_unlinked": {"value": unlinked, "limit": 0}}
    out.update(st.planted)
    return out

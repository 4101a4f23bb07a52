"""Driver ``light_sync``: one light client catching up in skipping mode
(``light/client.py``, light/verifier.go ``VerifyNonAdjacent``): a known
list of target heights ``stride`` apart, walked from a trusted root
through ``Client.verify_light_blocks_at_heights`` as fast as verdicts
return (closed loop, one client).  The client fetches ahead from its
primary, submits the signatures the two commit checks will look at to
the verify queue's ``light_client`` lane, and verifies each target from
the cached verdicts; after a rejection it goes on from its last trusted
header.  An item is one target's verdict.

**The chain** (for the next driver that needs headers).  ``gen.plan``
gives the validators, the heights, the tamper schedule and the part-set
hashes; this driver's ``plan`` then makes every item a REAL header and
replaces the item's block hash by that header's hash, before signing:

- the header of height h (``header_of``, plain data, ``reference_light.
  Header``): chain id ``gen.CHAIN_ID``, time ``gen.vote_time(h, 0)``, a
  last block id, last-commit, consensus, last-results hashes and an app
  hash drawn from (seed, h), the empty data and evidence roots, the
  static set's hash as ``validators_hash`` and ``next_validators_hash``,
  validator ``h mod n`` as proposer, block protocol 11;
- hashed by the REFERENCE (``reference_light.header_hash`` and
  ``validator_set_hash``, written from the published encoding), never by
  the program; the generator's workers then sign the canonical
  precommit over that hash (``gen.sign_items``, the reference's
  sign-bytes);
- ``prepare`` hands the program the same fields in its own types
  (``Header``, ``Commit``, ``LightBlock``) and two providers that serve
  them from memory.  The program hashes each header itself when it
  validates a fetched light block: where its encoding differs from the
  reference's, ``commit signs a different header`` rejects every target
  and the comparison reads ``schedule_mismatches``.

The first warm-up item is the client's trusted root; the rest of the
warm-up are targets (the last tampered), walked by the same iterator the
window goes on with, so the look-ahead is already running ahead of the
window's first target when it opens, as it is all through a catch-up.

**What the providers stop serving** (PR 35).  A full node prunes, and a
generator that kept 10,000 light blocks alive made the window retain
what no deployment holds.  Once a target's verdict is in the window's
outcomes the driver lets go of its own ``(BlockID, Commit)``; once a
target is ACCEPTED, the providers (primary and witness serve one dict)
drop every light block BELOW it.  What stays served, because a step
after a rejection can still ask for it: the last trusted header's own
height and everything above it — the targets fetched ahead, and a
rejected target's height, on which the pivot of a bisection from the
last trusted header to the next target lands (the heights are evenly
spaced).  Nothing below the last trusted header can be asked for: the
client reads its anchor from its store, goes forward only, and compares
with the witness at the height it is verifying.
"""

from __future__ import annotations

import hashlib
import time
from collections import deque
from dataclasses import dataclass

import jax

from benchmark import gen, reference_light
from benchmark.drivers import common

SECOND_NS = 1_000_000_000


def _drawn(seed: int, height: int, what: bytes, size: int = 32) -> bytes:
    return hashlib.sha256(
        b"%s/%d/%d/" % (gen.CHAIN_ID.encode(), seed, height) + what
    ).digest()[:size]


def header_of(seed: int, pubs: list, vals_hash: bytes,
              height: int) -> reference_light.Header:
    """The header of ``height``, as plain data."""
    return reference_light.Header(
        chain_id=gen.CHAIN_ID,
        height=height,
        time_ns=gen.vote_time(height, 0),
        last_block_hash=_drawn(seed, height, b"last_block"),
        last_parts_total=1,
        last_parts_hash=_drawn(seed, height, b"last_parts"),
        last_commit_hash=_drawn(seed, height, b"last_commit"),
        data_hash=hashlib.sha256(b"").digest(),
        validators_hash=vals_hash,
        next_validators_hash=vals_hash,
        consensus_hash=_drawn(seed, 0, b"consensus"),
        app_hash=_drawn(seed, height, b"app", 8),
        last_results_hash=_drawn(seed, height, b"last_results"),
        evidence_hash=hashlib.sha256(b"").digest(),
        proposer_address=gen.address(pubs[height % len(pubs)]),
    )


def _vals_hash(chain: gen.Chain) -> bytes:
    return reference_light.validator_set_hash(reference_light.ValidatorSet(
        tuple(chain.pubs), (gen.POWER,) * chain.n_vals
    ))


def plan(config: dict, params: dict, seed: int) -> gen.Chain:
    from cometbft_tpu.light.client import Client

    if not hasattr(Client, "verify_light_blocks_at_heights"):
        # before a signature is made: a program without the list entry
        # point (PR 28) cannot run this cell, and says so at once
        raise SystemExit(
            "light_sync: this program's light client has no "
            "verify_light_blocks_at_heights"
        )
    chain = gen.plan(
        seed, config["validators"], params["headers"], params["warm"],
        stride=params["stride"], tamper_every=params["tamper_every"],
        strata=params["tamper_strata"],
        first_group=params.get("tamper_first_group"),
    )
    vals_hash = _vals_hash(chain)
    for item in chain.warm + chain.items:
        item.block_hash = reference_light.header_hash(
            header_of(seed, chain.pubs, vals_hash, item.height)
        )
    return chain


@dataclass
class State(common.State):
    client: object = None
    #: the catch-up's iterator: warm-up targets, then the window's
    walk: object = None
    #: height -> light block: what both providers serve
    served: dict = None
    #: the heights still served, ascending (a dict popped from its
    #: front again and again walks its dead slots: the deque does not)
    serving: deque = None


def program_header(h: reference_light.Header):
    """The same header in the program's type."""
    from cometbft_tpu.types.block import BlockID, Header, PartSetHeader

    return Header(
        chain_id=h.chain_id, height=h.height, time_ns=h.time_ns,
        last_block_id=BlockID(
            hash=h.last_block_hash,
            part_set_header=PartSetHeader(
                total=h.last_parts_total, hash=h.last_parts_hash
            ),
        ),
        last_commit_hash=h.last_commit_hash, data_hash=h.data_hash,
        validators_hash=h.validators_hash,
        next_validators_hash=h.next_validators_hash,
        consensus_hash=h.consensus_hash, app_hash=h.app_hash,
        last_results_hash=h.last_results_hash,
        evidence_hash=h.evidence_hash,
        proposer_address=h.proposer_address,
        version_block=h.version_block, version_app=h.version_app,
    )


class FromMemory:
    """A provider (``light/provider.py`` ``Provider``'s interface) that
    serves the chain's targets, and nothing else, from memory."""

    def __init__(self, blocks: dict) -> None:
        self.blocks = blocks

    def chain_id(self) -> str:
        return gen.CHAIN_ID

    def light_block(self, height: int):
        from cometbft_tpu.light.provider import LightBlockNotFoundError

        if height not in self.blocks:
            # a midpoint: a client that bisects where it should reject
            # shows here, as a rejection that names no index
            raise LightBlockNotFoundError(f"no block at {height}")
        return self.blocks[height]

    def report_evidence(self, ev) -> None:
        raise RuntimeError("the providers serve one chain")


def prepare(chain: gen.Chain, config: dict, params: dict) -> State:
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.light.client import SKIPPING, Client, TrustOptions
    from cometbft_tpu.light.store import LightStore
    from cometbft_tpu.types.light_block import LightBlock, SignedHeader
    from cometbft_tpu.utils.db import MemDB

    if not vq.speculation_active():
        raise RuntimeError("no verify queue for the light lane")
    vals = gen.validator_set(chain)
    # both commit checks stop past their threshold; the self-commit
    # check's two thirds is the most either looks at, and what the
    # verify-ahead submits for a header
    checked = chain.n_vals * 2 // 3 + 1
    st = State(chain, vals, None, checked=checked, sigs_per_item=checked)
    vals_hash = _vals_hash(chain)
    blocks = {
        commit.height: LightBlock(
            SignedHeader(
                program_header(header_of(
                    chain.seed, chain.pubs, vals_hash, commit.height
                )),
                commit,
            ),
            vals,
        )
        for _, commit in st.warm + st.commits
    }
    st.served, st.serving = blocks, deque(sorted(blocks))
    root = blocks[chain.warm[0].height]
    st.client = Client(
        gen.CHAIN_ID,
        TrustOptions(
            period_ns=config["trusting_period_s"] * SECOND_NS,
            height=root.height, hash=root.hash(),
        ),
        FromMemory(blocks), [FromMemory(blocks)], LightStore(MemDB()),
        verification_mode=SKIPPING,
    )
    heights = [it.height for it in chain.warm[1:] + chain.items]
    st.walk = st.client.verify_light_blocks_at_heights(
        heights, now=lambda lb: lb.time_ns + SECOND_NS
    )
    return st


def control(st: State) -> None:
    """Breaks "more than two thirds of its own set signed it": the
    program's own trusting check alone (one third of the trusted set,
    by address), without the self-commit check."""
    from cometbft_tpu.types.validation import verify_commit_light_trusting

    def trusting_alone(chain_id, vals, bid, height, commit):
        verify_commit_light_trusting(chain_id, vals, commit)

    common.swap_entry(st, trusting_alone)


def _next_verdict(st: State, item: gen.Item, pair: tuple) -> str | None:
    if st.entry is not None:  # the control
        return common.run_verify(st.entry, st.vals, *pair)
    height, _, err = next(st.walk)
    if height != item.height:
        raise RuntimeError(
            f"the walk returned height {height}, the chain's next "
            f"target is {item.height}"
        )
    return None if err is None else f"{type(err).__name__}: {err}"


def _stop_serving_below(st: State, trusted_height: int) -> None:
    while st.serving and st.serving[0] < trusted_height:
        del st.served[st.serving.popleft()]


def warm(st: State) -> None:
    """The catch-up's start: the look-ahead fills with full batches,
    their shape compiles, and the last warm-up target is rejected."""
    for item, pair in zip(st.chain.warm[1:], st.warm[1:]):
        common.expect_warm(item, _next_verdict(st, item, pair))


def run(st: State, seconds: float) -> common.Window:
    win = common.Window()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while st.cursor < len(st.commits) and time.perf_counter() < deadline:
        k = st.cursor
        st.cursor += 1
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("entry.light_verify"):
            err = _next_verdict(st, st.chain.items[k], st.commits[k])
        win.latencies.append(time.perf_counter() - t)
        win.outcomes.append((k, err))
        st.consumed(k)
        if err is None:
            _stop_serving_below(st, st.chain.items[k].height)
    win.elapsed = time.perf_counter() - t0
    win.ran_out = st.cursor >= len(st.commits)
    return win


def metrics(win: common.Window) -> dict:
    return {"replay_blocks_per_s": len(win.outcomes) / win.elapsed}

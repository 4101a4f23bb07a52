"""The generator's chain model (PRs 32 and 35): what an item may be, and
that the four cells' chains did not move when it got there.

- the chains of the four cells' drivers, at the rehearsals' sizes, hash
  to what the PARENT commit's ``gen`` made (``6483fc5``, before
  ``Item.parts_total`` / ``Item.epoch`` / ``gen.signers`` existed; the
  digests below were taken from that commit's tree and written in here);
- a chain of real blocks — built in order by a driver's ``build``, parts
  totals over 1, a second signer set, a count of the driver's own —
  goes through ``run.plan_chain``, ``check.compare`` and ``run.run_cell``
  on the CPU with no cell built (``chain_driver.py``).
"""

from __future__ import annotations

import copy
import hashlib
import os
import random
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import check, gen, reference, run  # noqa: E402
from tests.benchmark import chain_driver  # noqa: E402

SEED = 2**31 + 7
#: (validators, the traffic's params at the rehearsal's size, items
#: hashed, SHA-256 at the parent commit)
PARENT_CHAINS = {
    "cosmoshub150.commit": (
        12,
        {"commits": 12, "warm": 2, "tamper_every": 4,
         "tamper_strata": [[9, 12], [0, 4], [4, 9]]},
        12,
        "5d0a5c5c4a3e8b5b32d3370a1eb11387c3871a37147d9609fe8cf4cebfe4098f",
    ),
    "blocksync1k.replay": (
        12,
        {"blocks": 12, "warm": 3, "tamper_every": 4,
         "tamper_strata": [[5, 9], [0, 5]], "tamper_first_group": [1, 4]},
        None,
        "7a352fe79745b959a2d639ed5eead9cc9dacbd5af3a3bbc4f94cb276e70ea458",
    ),
    "lightsync10k.stride100": (
        12,
        {"headers": 40, "warm": 9, "stride": 100, "tamper_every": 8,
         "tamper_strata": [[5, 9], [0, 5], [9, 12]]},
        None,
        "029e38e5854a87f3b092f1feae56a1d247728a0fae0832e2300a1a47f1ab5960",
    ),
    "megacommit10k.commit": (
        24,
        {"commits": 6, "warm": 3, "tamper_every": 2,
         "tamper_strata": [[0, 4], [14, 18], [20, 24]],
         "tamper_first_group": [0, 2]},
        None,
        "546e82bfd8b06172ce9f2edc0408aace23c8514adf882e763e941c1580bce68a",
    ),
}


def chain_digest(items: list) -> str:
    h = hashlib.sha256()
    for it in items:
        h.update(repr((it.height, it.block_hash, it.parts_hash,
                       tuple(it.bad), list(it.sigs))).encode())
    return h.hexdigest()


@pytest.mark.parametrize("name", sorted(PARENT_CHAINS))
def test_the_cells_chains_did_not_move(name):
    """Same keys, block hashes, sign-bytes and signatures for a seed:
    the warm-up items and the window's (the commit cell's first 12,
    which its length does not touch at this size)."""
    n_vals, params, first, digest = PARENT_CHAINS[name]
    cell = run.load_cell(name)
    cell["config"] = dict(cell["config"], validators=n_vals)
    cell["traffic"] = copy.deepcopy(cell["traffic"])
    cell["traffic"]["params"].update(params)
    signing = run.plan_chain(cell, SEED, sign_workers=1)
    assert isinstance(signing, run.Signing)  # no ``build``: today's path
    signing.finish()
    chain = signing.chain
    assert chain_digest(chain.warm + chain.items[:first]) == digest
    assert all(it.parts_total == 1 and it.epoch == 0
               for it in chain.warm + chain.items)
    assert chain.epochs == [(chain.key_seeds, chain.pubs)]
    assert chain.sign_bytes_mean == pytest.approx(
        chain.sign_bytes_total
        / (n_vals * (len(chain.warm) + len(chain.items)))
    )


# -- a chain of real blocks, through the harness -------------------------

N_VALS = 4
PARAMS = {"blocks": 6, "tamper_every": 3,
          "tamper_strata": [[3, 4], [0, 3]], "rotate_at": 4}


def real_cell() -> dict:
    return {
        "cell": {"name": "chain_driver.test", "chips": 1},
        "traffic": {"driver": "chain_driver", "params": dict(PARAMS),
                    "reference_sample": 128, "reference_scans": 6},
        "config": {"validators": N_VALS},
        "driver": chain_driver,
        "end_to_end": [
            {"name": "replay_blocks_per_s", "unit": "blocks/s"},
            {"name": "setup_s", "unit": "s"},
        ],
        "per_layer": [],
    }


@pytest.fixture(scope="module")
def built() -> gen.Chain:
    signing = run.plan_chain(real_cell(), SEED, sign_workers=1)
    assert isinstance(signing, chain_driver.Built)  # ``build`` was taken
    signing.finish()
    return signing.chain


def true_answers(chain: gen.Chain) -> list:
    """The plain reference's verdict on every item, as the program
    words a rejection."""
    out = []
    for k, it in enumerate(chain.items):
        msgs = [gen.sign_bytes(it, i) for i in range(N_VALS)]
        bad = reference.first_bad(gen.signers(chain, it)[1], msgs, it.sigs,
                                  N_VALS)
        out.append((k, None if bad is None
                    else f"CommitError: wrong signature (#{bad})"))
    return out


def compared(chain: gen.Chain, outcomes: list) -> dict:
    got, looked_at = check.compare(chain, outcomes, N_VALS, sample=128,
                                   max_scans=6, rng=random.Random(5))
    assert looked_at["reference_scans"] >= 1
    return {k: v["value"] for k, v in got.items()}


def test_the_chain_was_built_in_order(built):
    items = built.items
    assert [it.parts_total for it in items] == [1, 2, 1, 2, 1, 2]
    assert [it.epoch for it in items] == [0, 0, 0, 0, 1, 0]
    assert len(built.epochs) == 2
    old, new = (set(pubs) for _, pubs in built.epochs)
    assert len(old - new) == 1 == len(new - old)  # one key replaced
    for prev, it in zip([None] + items, items):
        assert it.block_hash == chain_driver.block_hash(it, prev)
        assert len(it.sigs) == N_VALS
    # a hash depends on the signatures before it: sign item 0 otherwise
    # (its tampered twin) and item 1's hash is another
    twin = copy.copy(items[0])
    twin.sigs = [gen.tamper(items[0].sigs[0])] + items[0].sigs[1:]
    assert chain_driver.block_hash(items[1], twin) != items[1].block_hash
    assert built.sign_bytes_total == sum(
        len(gen.sign_bytes(it, i)) for it in items for i in range(N_VALS)
    )
    assert sum(bool(it.bad) for it in items) == 2


def test_build_works_in_a_process_clear_of_jax_and_the_program(built):
    signing = run.plan_chain(real_cell(), SEED, sign_workers=2)
    try:
        signing.finish()
    finally:
        signing.close()
    assert signing.worker_was_dirty is False
    assert chain_digest(signing.chain.items) == chain_digest(built.items)
    assert ([(it.parts_total, it.epoch) for it in signing.chain.items]
            == [(it.parts_total, it.epoch) for it in built.items])


def test_true_answers_read_zero_on_all_six_counts(built):
    got = compared(built, true_answers(built))
    assert len(got) == 6 and set(got.values()) == {0}


def test_a_verdict_altered_turns_a_count(built):
    answers = true_answers(built)
    k = next(k for k, err in answers if err is None)
    answers[k] = (k, "CommitError: wrong signature (#1)")
    got = compared(built, answers)
    assert got["schedule_mismatches"] == 1
    assert got["reference_verdict_mismatches"] == 1


def test_the_replaced_key_checked_against_epoch_0_turns_a_count(built):
    """A comparison that read ``chain.pubs`` for every item, as it did
    before ``gen.signers``: the rotated item's signatures do not verify
    under the old set."""
    blind = copy.deepcopy(built)
    for it in blind.items:
        it.epoch = 0
    got = compared(blind, true_answers(built))
    assert got["reference_accepted_invalid"] >= 1


def test_a_parts_total_ignored_turns_a_count(built):
    """A comparison that signed over the constant total 1: every vote
    of a two-part block is for another block id."""
    blind = copy.deepcopy(built)
    for it in blind.items:
        it.parts_total = 1
    got = compared(blind, true_answers(built))
    assert got["reference_accepted_invalid"] >= 1


# -- the same chain through the program, and the driver's own counts -----


@pytest.fixture
def program_on_the_host():
    """``run_cell`` on the CPU backend as shipped: four-signature
    commits stay on the host rung, nothing compiles.  The process-wide
    state a run installs is put back."""
    from cometbft_tpu import metrics as M
    from cometbft_tpu.crypto import dispatch

    dispatch.reset_for_tests()
    yield
    dispatch.reset_for_tests()
    M.install_crypto_metrics(None)


def drive(after_warm=None) -> tuple[dict, dict]:
    import jax

    cell = real_cell()
    seen = {}

    def keep(state):
        seen["state"] = state
        if after_warm is not None:
            after_warm(state)

    line = run.run_cell(cell, run.plan_chain(cell, SEED, sign_workers=1),
                        5.0, False, jax.devices()[:1], after_warm=keep)
    return line, seen["state"]


def test_real_blocks_through_the_program_are_correct(
    program_on_the_host, capfd
):
    line, state = drive()
    assert line["correct"] is True
    assert line["attempted"] == 6 and line["failed"] == 0
    assert list(line["compared"]) == [
        "missing_verdicts", "schedule_mismatches",
        "reference_verdict_mismatches", "reference_index_mismatches",
        "reference_accepted_invalid", "unexercised_checks",
        "accepted_blocks_unlinked",
    ]
    assert all(v == {"value": 0, "limit": 0}
               for v in line["compared"].values())
    # the driver's count prints beside the six, last on standard error
    err = capfd.readouterr().err.rstrip().splitlines()
    assert err[-1] == "compared accepted_blocks_unlinked: 0 (limit 0)"
    assert [ln.startswith("compared ") for ln in err[-7:]] == [True] * 7
    # the window let go of what it consumed; the plain data stayed
    assert state.commits == [None] * 6
    assert len(state.chain.items) == 6
    assert all(len(it.sigs) == N_VALS for it in state.chain.items)


def test_a_drivers_count_over_its_limit_is_not_correct(program_on_the_host):
    def plant(state):
        state.planted = {"blocks_not_read_back": {"value": 1, "limit": 0}}

    line, _ = drive(plant)
    assert line["correct"] is False
    assert line["compared"]["blocks_not_read_back"] == {"value": 1,
                                                        "limit": 0}
    assert all(v["value"] == 0 for k, v in line["compared"].items()
               if k != "blocks_not_read_back")


def test_a_drivers_count_under_its_limit_stays_correct(program_on_the_host):
    def plant(state):
        state.planted = {"blocks_late": {"value": 1, "limit": 2}}

    line, _ = drive(plant)
    assert line["correct"] is True


def test_a_colliding_name_raises(program_on_the_host):
    def plant(state):
        state.planted = {"missing_verdicts": {"value": 0, "limit": 0}}

    with pytest.raises(RuntimeError, match="already counts"):
        drive(plant)


def test_the_control_fails_on_real_blocks_too(program_on_the_host):
    """``verify_commit_light`` in ``verify_commit``'s place stops past
    two thirds: the bit flipped in a fourth signature is accepted."""
    line, _ = drive(chain_driver.control)
    assert line["correct"] is False
    assert line["compared"]["schedule_mismatches"]["value"] == 1

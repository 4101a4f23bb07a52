"""The benchmark's manifest and yardstick, checked without JAX: names
and units keep to the contract, every cell's files resolve by name, the
bytes function counts what was counted by hand, the comparison that
decides ``correct`` fails on altered answers, and the reference agrees
with the program's own oracle and encoder."""

from __future__ import annotations

import importlib
import json
import os
import random
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from benchmark import check, gen, reference, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest() -> dict:
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        return json.load(f)


def test_names_and_units_keep_to_the_contract(manifest):
    assert set(manifest) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= manifest["run_seconds"] <= 51
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names += [e["name"] for e in manifest[group]]
    for w in manifest["workloads"]:
        names += [w["config"], w["traffic"]]
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
    for c in manifest["configs"]:
        names += c["reduced"]
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads"):
        got = [e["name"] for e in manifest[group]]
        assert len(got) == len(set(got))
    metrics = manifest["end_to_end"] + manifest["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for m in metrics:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_cell_resolves_by_name(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert {w["config"] for w in manifest["workloads"]} == set(configs)
    for c in configs.values():
        assert c["file"].startswith(tuple(p + "/" for p in manifest["paths"]))
        with open(os.path.join(REPO, c["file"])) as f:
            doc = json.load(f)
        assert doc["reduced"] == c["reduced"] and doc["guarantees"]
    for w in manifest["workloads"]:
        with open(os.path.join(REPO, "benchmark", "traffic",
                               w["name"] + ".json")) as f:
            traffic = json.load(f)
        assert traffic["config"] == w["config"]
        driver = importlib.import_module(
            "benchmark.drivers." + traffic["driver"]
        )
        for fn in ("plan", "prepare", "warm", "run", "metrics", "control"):
            assert callable(getattr(driver, fn)), (traffic["driver"], fn)
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert set(m.get("workloads", cells)) <= cells, m
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        # each cell a layer metric lists reports the metric it moves
        moved = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= moved, m
        with open(os.path.join(REPO, "benchmark", "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        reader = importlib.import_module("benchmark.readers." + spec["reader"])
        assert callable(reader.read)
    for w in cells:  # setup_s, one more end-to-end metric, one per layer
        mine = [m["name"] for m in manifest["end_to_end"]
                if w in m.get("workloads", cells)]
        assert "setup_s" in mine and len(mine) >= 2
        assert any(w in m.get("workloads", cells)
                   for m in manifest["per_layer"])


def test_command_fails_at_the_device_check_without_a_chip(manifest):
    cell = manifest["workloads"][0]["name"]
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "benchmark", "run.py"),
         "--workload", cell, "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert "device check failed" in proc.stderr and "'cpu'" in proc.stderr
    assert proc.stdout == ""


def test_bytes_function_on_hand_counted_shapes():
    # one signature over a 113-byte precommit: 32 + 64 + 113 + 1
    assert work.verify_bytes(1, 113) == 210
    # a 150-validator commit, every sign-bytes 113 long
    assert work.verify_bytes(150, 150 * 113) == 150 * 210 == 31_500
    # 1,000 signatures at the v5e's 819 GB/s: 210,000 B -> 256.4 ns
    t = work.least_seconds(work.verify_bytes(1000, 113_000), "TPU v5 lite")
    assert t == pytest.approx(210_000 / 819e9)
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks("TPU v9 imaginary")


def test_reference_encodes_and_verifies_like_the_programs_oracle():
    from cometbft_tpu.crypto import edwards
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import BlockID, PartSetHeader

    chain = gen.plan(7, n_vals=3, n_items=2, n_warm=0, stride=100,
                     tamper_every=2, strata=[[0, 3]])
    gen.attach(chain, [gen.sign_items(j) for j in gen.sign_jobs(chain, 1)])
    for it in chain.items:
        bid = BlockID(hash=it.block_hash, part_set_header=PartSetHeader(
            total=1, hash=it.parts_hash))
        for i, (pub, sig) in enumerate(zip(chain.pubs, it.sigs)):
            msg = gen.sign_bytes(it, i)
            assert msg == canonical.vote_sign_bytes(
                gen.CHAIN_ID, canonical.PRECOMMIT_TYPE, it.height, 0, bid,
                gen.vote_time(it.height, i),
            )
            ok = reference.verify_zip215(pub, msg, sig)
            assert ok == edwards.verify_zip215(pub, msg, sig)
            assert ok == (i not in it.bad)
    assert sum(bool(it.bad) for it in chain.items) == 1


def test_every_seed_gets_the_same_work_in_another_order():
    kw = dict(n_vals=12, n_items=64, n_warm=2, stride=1, tamper_every=8,
              strata=[[8, 12], [0, 4], [4, 8]], first_group=[1, 4])
    a, b = gen.plan(3, **kw), gen.plan(2**31 + 11, **kw)
    bad_a = {k: it.bad for k, it in enumerate(a.items) if it.bad}
    bad_b = {k: it.bad for k, it in enumerate(b.items) if it.bad}
    assert len(bad_a) == len(bad_b) == 8 and bad_a != bad_b
    for bad in (bad_a, bad_b):
        assert min(bad) in range(1, 4)
        for n, k in enumerate(sorted(bad)):
            lo, hi = kw["strata"][n % 3]
            assert k // 8 == n and lo <= bad[k][0] < hi
    assert a.pubs != b.pubs and a.warm[-1].bad and not a.warm[0].bad


def test_a_window_counts_its_items_by_quarter():
    from benchmark.drivers import common

    win = common.Window(latencies=[1, 1, 1, 1, 2, 2])
    # ends at 1, 2, 3, 4, 6, 8 of 8 seconds
    assert win.per_quarter() == [1, 2, 1, 2]
    assert common.Window().per_quarter() == [0, 0, 0, 0]
    assert common.percentile([1, 2, 3, 4], 50) == 2
    assert common.percentile(list(range(1, 101)), 95) == 95


@pytest.fixture(scope="module")
def small_chain() -> gen.Chain:
    chain = gen.plan(5, n_vals=6, n_items=8, n_warm=0, stride=1,
                     tamper_every=4, strata=[[4, 6], [0, 4]])
    gen.attach(chain, [gen.sign_items(j) for j in gen.sign_jobs(chain, 2)])
    return chain


def _truth(chain: gen.Chain, checked: int) -> list:
    return [
        (k, f"InvalidCommitSignatures: wrong signature (#{it.bad[0]})"
         if it.bad and it.bad[0] < checked else None)
        for k, it in enumerate(chain.items)
    ]


def _compare(chain, outcomes, checked=6):
    compared, looked = check.compare(
        chain, outcomes, checked, sample=8, max_scans=4,
        rng=random.Random(1),
    )
    return {k: v["value"] for k, v in compared.items()}, looked


def test_compare_passes_the_true_answers(small_chain):
    got, looked = _compare(small_chain, _truth(small_chain, 6))
    assert set(got.values()) == {0}
    assert looked == {"reference_scans": 2, "reference_sampled": 8}
    # a call bound to the first 4 signatures does not see a flip at 4 or 5
    got, _ = _compare(small_chain, _truth(small_chain, 4), checked=4)
    assert set(got.values()) == {0}


@pytest.mark.parametrize("fault, number", [
    ("tampered_accepted", "reference_verdict_mismatches"),
    ("valid_rejected", "reference_verdict_mismatches"),
    ("index_altered", "reference_index_mismatches"),
    ("index_missing", "reference_index_mismatches"),
    ("verdict_missing", "missing_verdicts"),
    ("verdict_twice", "missing_verdicts"),
    ("nothing_rejected", "unexercised_checks"),
])
def test_compare_fails_an_altered_answer(small_chain, fault, number):
    out = _truth(small_chain, 6)
    bad = [n for n, (_, err) in enumerate(out) if err is not None]
    good = [n for n, (_, err) in enumerate(out) if err is None]
    if fault == "tampered_accepted":
        out[bad[0]] = (out[bad[0]][0], None)
    elif fault == "valid_rejected":
        out[good[0]] = (out[good[0]][0],
                        "InvalidCommitSignatures: wrong signature (#0)")
    elif fault == "index_altered":
        k, err = out[bad[0]]
        idx = int(err.rsplit("#", 1)[1].rstrip(")"))
        out[bad[0]] = (k, err.replace(f"#{idx}", f"#{(idx + 1) % 6}"))
    elif fault == "index_missing":
        out[bad[0]] = (out[bad[0]][0], "NotEnoughVotingPower: tallied 0")
    elif fault == "verdict_missing":
        del out[good[1]]
    elif fault == "verdict_twice":
        out.insert(good[1], out[good[1]])
    elif fault == "nothing_rejected":
        out = [o for o in out if o[1] is None][:2]
    got, _ = _compare(small_chain, out)
    assert got[number] >= 1, got


def test_a_wrongly_signed_chain_fails_the_accepted_sample(small_chain):
    """A program that accepted signatures the reference rejects."""
    forged = gen.plan(5, n_vals=6, n_items=8, n_warm=0, stride=1,
                      tamper_every=4, strata=[[4, 6], [0, 4]])
    for it, src in zip(forged.items, small_chain.items):
        it.sigs = [gen.tamper(s) for s in src.sigs]
    outcomes = [(k, None) for k in range(8) if not forged.items[k].bad]
    got, _ = _compare(forged, outcomes)
    assert got["reference_accepted_invalid"] == 8

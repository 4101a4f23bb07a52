#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, run from the root of a plain copy of the tree (no git, no
network), data made from ``--seed``:

    python chip_smoke.py             # one TPU chip: node + verify plane
    python chip_smoke.py --chips 4   # four chips: the keyed_mesh tier only

It fails the moment ``jax.devices()[0].platform`` is not ``"tpu"`` and
never selects a platform itself.  Phases (one JSON line each on stdout;
logs stay on stderr):

- ``node``      a home built by ``init``, a Node started the way
                ``start`` does (kvstore app, sqlite stores, JSON-RPC on
                an ephemeral port, prometheus on); 20 transactions
                committed and read back; the health prober's first
                canary round over the device tiers.  The node keeps
                committing through the verify phases, which therefore
                ride the node's own verify queue in the process that
                owns the chip.
- ``commit150`` 150 validators: 64 commits through ``verify_commit``,
                16 through ``verify_commit_light``, five tampered.
- ``replay1k``  1,000 validators: 32 blocks' commits the way blocksync
                replays them (prefetch lane, then
                ``verify_commit_light``), two tampered.

Verdicts and first-bad indices must equal the pure-Python ZIP-215
oracle.  The LAST line of stdout is exactly
``{"ok": true, "device": {"platform", "kind", "count"}}`` — printed
once, after the node has stopped, only when every phase passed.
No number printed here is a benchmark.
"""

from __future__ import annotations

import argparse
import base64
import contextlib
import hashlib
import importlib.metadata
import json
import os
import random
import re
import shutil
import sys
import tempfile
import threading
import time

#: compiles of at least this many seconds are listed by name on the
#: phase lines; smaller ones (eager one-op programs) are only counted
_LISTED_COMPILE_S = 1.0
#: threads the node's health prober runs its canaries on — their
#: compiles are the prober's, reported apart from the phase's own
_PROBER_THREAD_PREFIX = "probe-"


class SmokeFailure(Exception):
    """A phase did not meet its pass criteria."""


def format_last_line(devices) -> str:
    """The contract line: two top-level keys, three inner keys, the
    device as JAX reports it."""
    return json.dumps(
        {
            "ok": True,
            "device": {
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
                "count": len(devices),
            },
        }
    )


def emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# -- observation ---------------------------------------------------------


class CompileLog:
    """Every XLA compile the process makes, from JAX's own monitoring
    events: program name, seconds, persistent-cache outcome, thread."""

    _BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self) -> None:
        import jax

        self.events: list[dict] = []
        self._tls = threading.local()
        jax.monitoring.register_event_listener(self._on_event)
        jax.monitoring.register_event_duration_secs_listener(
            self._on_duration
        )

    def _on_event(self, event: str, **kw) -> None:
        if event == self._HIT:
            self._tls.outcome = "hit"
        elif event == self._MISS:
            self._tls.outcome = "miss"

    def _on_duration(self, event: str, seconds: float, **kw) -> None:
        if event != self._BACKEND_COMPILE:
            return
        self.events.append(
            {
                "program": kw.get("fun_name", "?"),
                "seconds": round(seconds, 3),
                "cache": getattr(self._tls, "outcome", "uncached"),
                "thread": threading.current_thread().name,
            }
        )
        self._tls.outcome = "uncached"

    def mark(self) -> int:
        return len(self.events)

    def between(self, start: int, end: int | None = None,
                prober: bool = False) -> list[dict]:
        return [
            e for e in self.events[start:end]
            if e["thread"].startswith(_PROBER_THREAD_PREFIX) == prober
        ]


def summarize_compiles(events: list[dict], programs: bool = True) -> dict:
    out = {
        "count": len(events),
        "seconds": round(sum(e["seconds"] for e in events), 3),
        "cache_hits": sum(e["cache"] == "hit" for e in events),
    }
    if programs:
        out["programs"] = [
            {k: e[k] for k in ("program", "seconds", "cache")}
            for e in events if e["seconds"] >= _LISTED_COMPILE_S
            or e["cache"] == "hit"
        ]
    return out


def ensure_crypto_metrics():
    """The installed crypto metrics sink, or a private registry-backed
    one when nothing installed any (no node in this process)."""
    from cometbft_tpu import metrics as M
    from cometbft_tpu.utils.metrics import Registry

    cm = M.crypto_metrics()
    if not hasattr(cm.dispatch_decisions, "children"):
        cm = M.CryptoMetrics(Registry())
        M.install_crypto_metrics(cm)
    return cm


class Probe:
    """Counters at one instant; ``delta`` gives what a phase added."""

    def __init__(self, compiles: CompileLog) -> None:
        from cometbft_tpu.crypto import dispatch
        from cometbft_tpu.ops import jitguard

        self._compiles = compiles
        self.t = time.perf_counter()
        self.compile_mark = compiles.mark()
        self.jit = jitguard.compile_counts()
        self.transitions = dispatch.LADDER.snapshot()["transitions"]
        self.batches = {
            (r["tier"], r["bucket"]): r["samples"]
            for r in dispatch.LADDER.cost_snapshot()["table"]
            if r["family"] == dispatch.ROUTE_FAMILY_ED25519
        }
        self.decisions = {
            "/".join(k): int(c.get())
            for k, c in ensure_crypto_metrics()
            .dispatch_decisions.children().items()
        }

    def delta(self, since: "Probe") -> dict:
        def diff(now: dict, was: dict) -> dict:
            return {
                k: v - was.get(k, 0) for k, v in now.items()
                if v - was.get(k, 0)
            }

        transitions = self.transitions[len(since.transitions):]
        return {
            "seconds": round(self.t - since.t, 3),
            "batches": [
                {"tier": t, "bucket": b, "n": n}
                for (t, b), n in sorted(
                    diff(self.batches, since.batches).items()
                )
            ],
            "decisions": diff(self.decisions, since.decisions),
            "transitions": [
                {k: tr.get(k) for k in ("kind", "from", "to", "reason")}
                for tr in transitions
            ],
            "tier_faults": sum(
                tr["kind"] == "demote" for tr in transitions
            ),
            "watchdog_firings": sum(
                tr["reason"] == "watchdog" for tr in transitions
            ),
            "jit_seam_compiles": diff(self.jit, since.jit),
            "compiles": summarize_compiles(
                self._compiles.between(
                    since.compile_mark, self.compile_mark
                )
            ),
            "prober_compiles": summarize_compiles(
                self._compiles.between(
                    since.compile_mark, self.compile_mark, prober=True
                )
            ),
        }


def hbm_stats() -> dict | None:
    import jax

    stats = jax.devices()[0].memory_stats()
    if not stats:
        return None
    return {
        k: stats[k] for k in ("peak_bytes_in_use", "bytes_in_use",
                              "bytes_limit") if k in stats
    }


def native_status() -> dict:
    from cometbft_tpu.crypto import bls_native, ed25519_native
    from cometbft_tpu.p2p.conn import frame_native
    from cometbft_tpu.utils import kv_native

    return {
        lib.out_name: lib.status
        for lib in (ed25519_native._LIB, bls_native._NATIVE,
                    kv_native._NATIVE, frame_native._LIB)
    }


# -- data ----------------------------------------------------------------

_TS0 = 1_700_000_000_000_000_000


def make_validators(seed: int, n: int):
    """-> (ValidatorSet, private keys in the set's canonical order)."""
    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.types.validator import Validator, ValidatorSet

    keys = [
        ed.priv_key_from_secret(b"chip-smoke/%d/%d/%d" % (seed, n, i))
        for i in range(n)
    ]
    vals = ValidatorSet([Validator(k.pub_key(), 10) for k in keys])
    by_addr = {k.pub_key().address(): k for k in keys}
    return vals, [by_addr[v.address] for v in vals.validators]


def make_commit(chain_id: str, keys, height: int, bad=()):
    """A commit at ``height`` in which every validator signs its
    canonical precommit; signatures at the ``bad`` indices get one
    flipped bit.  -> (BlockID, Commit)."""
    from cometbft_tpu.types import canonical
    from cometbft_tpu.types.block import (
        BLOCK_ID_FLAG_COMMIT,
        BlockID,
        Commit,
        CommitSig,
        PartSetHeader,
    )

    h = hashlib.sha256(b"%s/%d" % (chain_id.encode(), height)).digest()
    bid = BlockID(hash=h, part_set_header=PartSetHeader(total=1, hash=h[::-1]))
    sigs = []
    for i, k in enumerate(keys):
        ts = _TS0 + height * 1_000_000 + i
        msg = canonical.vote_sign_bytes(
            chain_id, canonical.PRECOMMIT_TYPE, height, 0, bid, ts
        )
        sig = k.sign(msg)
        if i in bad:
            sig = sig[:5] + bytes([sig[5] ^ 0x04]) + sig[6:]
        sigs.append(
            CommitSig(
                block_id_flag=BLOCK_ID_FLAG_COMMIT,
                validator_address=k.pub_key().address(),
                timestamp_ns=ts,
                signature=sig,
            )
        )
    return bid, Commit(
        height=height, round=0, block_id=bid, signatures=tuple(sigs)
    )


def oracle_ok(chain_id: str, vals, commit, i: int) -> bool:
    """Signature ``i`` under the pure-Python ZIP-215 oracle."""
    from cometbft_tpu.crypto import edwards

    return edwards.verify_zip215(
        vals.get_by_index(i).pub_key.bytes(),
        commit.vote_sign_bytes(chain_id, i),
        commit.signatures[i].signature,
    )


def oracle_bad_indices(chain_id: str, vals, commit, upto: int) -> list[int]:
    """Indices < ``upto`` whose signature the oracle rejects."""
    return [
        i for i in range(upto) if not oracle_ok(chain_id, vals, commit, i)
    ]


def check_against_oracle(
    rng: random.Random, chain_id: str, vals, cases: list[dict],
    sample: int,
) -> dict:
    """``cases``: {"commit", "checked" (signatures the call looks at),
    "error" (None or the InvalidCommitSignatures text)}.  Every commit
    the system rejected, and every tampered one, is checked in full
    against the oracle — verdict and first-bad index; of the accepted
    ones a seeded sample of ``sample`` signatures must be valid."""
    n_rejected = 0
    for c in cases:
        if c["error"] is None and not c["tampered"]:
            continue
        bad = oracle_bad_indices(chain_id, vals, c["commit"], c["checked"])
        check(
            bool(bad) == (c["error"] is not None),
            f"height {c['commit'].height}: oracle bad indices {bad}, "
            f"system said {c['error']!r}",
        )
        if bad:
            n_rejected += 1
            m = re.search(r"#(\d+)", c["error"])
            check(
                m is not None and int(m.group(1)) == bad[0],
                f"height {c['commit'].height}: oracle first bad index "
                f"{bad[0]}, system said {c['error']!r}",
            )
    good = [c for c in cases if c["error"] is None]
    picks = [
        (c, rng.randrange(c["checked"]))
        for c in (rng.choice(good) for _ in range(sample if good else 0))
    ]
    for c, i in picks:
        check(
            oracle_ok(chain_id, vals, c["commit"], i),
            f"height {c['commit'].height} #{i}: system accepted, "
            "oracle rejects",
        )
    return {"rejected": n_rejected, "oracle_sample": len(picks)}


def run_verify(fn, chain_id, vals, bid, commit) -> str | None:
    """One commit through an entry point; the rejection text or None."""
    from cometbft_tpu.types.validation import InvalidCommitSignatures

    try:
        fn(chain_id, vals, bid, commit.height, commit)
    except InvalidCommitSignatures as exc:
        return str(exc)
    return None


def light_checked(n_vals: int) -> int:
    """Signatures ``verify_commit_light`` looks at when everyone signed
    with equal power: it stops once the tally passes two thirds."""
    return n_vals * 2 // 3 + 1


# -- pass criteria shared by the verify phases ---------------------------


def check_device_plane(d: dict, full_buckets: set[int],
                       allow_small_host: int | None) -> None:
    """``d``: a Probe delta taken AFTER the phase's warm-up."""
    from cometbft_tpu.crypto.dispatch import shape_bucket

    for b in d["batches"]:
        if b["bucket"] < 2:
            continue  # the node's own one-signature commits
        if b["bucket"] in full_buckets:
            check(b["tier"] == "keyed",
                  f"full-commit batch off the keyed tier: {b}")
        else:
            ok = b["tier"] == "keyed" or (
                b["tier"] == "host" and allow_small_host is not None
                and b["bucket"] <= shape_bucket(allow_small_host)
            )
            check(ok, f"batch neither keyed nor host-by-batch_size: {b}")
    check(d["tier_faults"] == 0 and not d["transitions"],
          f"ladder transitions: {d['transitions']}")
    check(d["watchdog_firings"] == 0, "launch watchdog fired")
    check(d["compiles"]["count"] == 0 and not d["jit_seam_compiles"],
          f"compiles after warm-up: {d['compiles']} "
          f"{d['jit_seam_compiles']}")


def table_placement(pubs: list[bytes], platform: str) -> dict:
    from cometbft_tpu.ops import precompute as PR

    entry = PR.TABLE_CACHE.peek(pubs)
    check(entry is not None, "key tables are not resident")
    devs = sorted(str(d) for d in entry.table.devices())
    check(
        all(d.platform == platform for d in entry.table.devices()),
        f"table is on {devs}, expected platform {platform}",
    )
    return {
        "window_bits": entry.window_bits,
        "slots": len(entry.valid),
        "table_bytes": int(entry.table.nbytes),
        "devices": devs,
    }


# -- phases --------------------------------------------------------------


def phase_node(compiles: CompileLog, seed: int, n_txs: int = 20,
               wait_prober: bool = True):
    """-> (node, home, line).  The caller stops the node."""
    from cometbft_tpu import cmd
    from cometbft_tpu.config import Config
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.rpc import HTTPClient

    t0 = time.perf_counter()
    before = compiles.mark()
    home = tempfile.mkdtemp(prefix="chip_smoke_home_")
    with contextlib.redirect_stdout(sys.stderr):
        rc = cmd.main(
            ["--home", home, "init", "--chain-id", f"chip-smoke-{seed}"]
        )
    check(rc == 0, f"init exited {rc}")
    # prometheus is a config.toml setting (start has no flag for it)
    cfg = Config.load(home)
    cfg.instrumentation.prometheus = True
    cfg.instrumentation.prometheus_listen_addr = "127.0.0.1:0"
    cfg.save()
    node = cmd.node_from_args(
        argparse.Namespace(
            home=home, proxy_app="", persistent_peers="", block_sync=None,
            p2p_laddr="tcp://127.0.0.1:0", rpc_laddr="tcp://127.0.0.1:0",
        )
    )
    node.start()
    try:
        check(vq.speculation_active(),
              "the node did not install its verify queue")
        c = HTTPClient(
            f"http://{node.rpc_server.host}:{node.rpc_server.port}",
            timeout=60.0,
        )
        rng = random.Random(seed)
        read_back = 0
        for i in range(n_txs):
            key = b"smoke-%d-%d" % (seed, i)
            val = b"%016x" % rng.getrandbits(64)
            res = c.broadcast_tx_commit(
                tx=(key + b"=" + val).hex(), timeout=60.0
            )
            check(
                res["check_tx"]["code"] == 0
                and res["tx_result"]["code"] == 0
                and int(res["height"]) > 0,
                f"tx {i} was not committed: {res}",
            )
            q = c.abci_query(data=key.hex())
            got = base64.b64decode(q["response"]["value"] or "")
            check(got == val, f"tx {i}: wrote {val!r}, read back {got!r}")
            read_back += 1
        status = c.status()
        height = int(status["sync_info"]["latest_block_height"])
        check(height >= 1 and not status["sync_info"]["catching_up"],
              f"/status: {status['sync_info']}")
        prober = None
        if wait_prober and node.health_prober is not None:
            # the first canary round over the device tiers compiles
            # its own small programs; let it finish here so the verify
            # phases' compile counts are their own
            from cometbft_tpu.crypto.health import default_tier_probes

            want = set(default_tier_probes())
            deadline = time.monotonic() + 600
            while not want <= set(
                node.health_prober.snapshot()["tiers"]
            ):
                check(time.monotonic() < deadline,
                      f"health prober did not cover {sorted(want)} "
                      "in 600 s")
                time.sleep(0.5)
            prober = {
                t: {k: st[k] for k in ("healthy", "last_probe_s", "error")}
                for t, st in
                node.health_prober.snapshot()["tiers"].items()
            }
            check(all(st["healthy"] for st in prober.values()),
                  f"a canary probe failed: {prober}")
        line = {
            "phase": "node", "ok": True,
            "seconds": round(time.perf_counter() - t0, 3),
            "acked": n_txs, "read_back": read_back,
            "height": height,
            "chain_id": status["node_info"]["network"],
            "rpc_port": node.rpc_server.port,
            "metrics_port": node.metrics_server.port,
            "verify_queue": "installed",
            "device_batches": (
                "none: one validator signs one precommit and "
                "types/validation batches from two signatures — this "
                "phase proves the node, its stores, RPC, health "
                "prober and the device plane coexist in one process"
            ),
            "prober": prober,
            "compiles": summarize_compiles(compiles.between(before)),
            "prober_compiles": summarize_compiles(
                compiles.between(before, prober=True)
            ),
            "hbm": hbm_stats(),
        }
    except BaseException:
        node.stop()
        shutil.rmtree(home, ignore_errors=True)
        raise
    return node, home, line


def phase_commit150(
    compiles: CompileLog, seed: int, platform: str, n_vals: int = 150,
    n_full: int = 64, n_light: int = 16, oracle_sample: int = 256,
) -> dict:
    from cometbft_tpu.ops import ed25519_verify as EV
    from cometbft_tpu.types.validation import (
        verify_commit,
        verify_commit_light,
    )

    t_setup = time.perf_counter()
    rng = random.Random(seed * 1000 + 150)
    chain_id = f"chip-smoke-{seed}"
    vals, keys = make_validators(seed, n_vals)
    n_checked = light_checked(n_vals)
    # tampered commits, seeded: three full + one light with one bad
    # signature each, one full with two
    full_bad = {j: {rng.randrange(n_vals)}
                for j in rng.sample(range(n_full), min(4, n_full))}
    two = sorted(full_bad)[-1]
    full_bad[two] = set(rng.sample(range(n_vals), 2))
    light_bad = {rng.randrange(n_light): {rng.randrange(n_checked - 1)}}
    base = 1_000_000 + seed * 10_000
    warm = [make_commit(chain_id, keys, base + j) for j in (0, 1)]
    full = [make_commit(chain_id, keys, base + 100 + j,
                        full_bad.get(j, ())) for j in range(n_full)]
    light = [make_commit(chain_id, keys, base + 1000 + j,
                         light_bad.get(j, ())) for j in range(n_light)]
    msg_len = len(full[0][1].vote_sign_bytes(chain_id, 0))
    setup_s = time.perf_counter() - t_setup

    p0 = Probe(compiles)
    check(run_verify(verify_commit, chain_id, vals, *warm[0]) is None,
          "warm-up commit rejected")
    check(run_verify(verify_commit_light, chain_id, vals, *warm[1]) is None,
          "warm-up light commit rejected")
    p1 = Probe(compiles)
    cases = []
    for j, (bid, commit) in enumerate(full):
        cases.append({
            "commit": commit, "checked": n_vals, "tampered": j in full_bad,
            "error": run_verify(verify_commit, chain_id, vals, bid, commit),
        })
    for j, (bid, commit) in enumerate(light):
        cases.append({
            "commit": commit, "checked": n_checked,
            "tampered": j in light_bad,
            "error": run_verify(
                verify_commit_light, chain_id, vals, bid, commit
            ),
        })
    p2 = Probe(compiles)
    warmup, run = p1.delta(p0), p2.delta(p1)
    threshold = EV.runtime_device_min_batch()
    from cometbft_tpu.crypto.dispatch import shape_bucket

    check_device_plane(
        run, {shape_bucket(n_vals)},
        allow_small_host=threshold if n_checked < threshold else None,
    )
    full_batches = sum(b["n"] for b in run["batches"]
                       if b["bucket"] == shape_bucket(n_vals))
    check(full_batches >= n_full,
          f"{full_batches} full-commit batches for {n_full} commits")
    oracle = check_against_oracle(rng, chain_id, vals, cases, oracle_sample)
    check(oracle["rejected"] == len(full_bad) + len(light_bad),
          f"{oracle['rejected']} commits rejected, "
          f"{len(full_bad) + len(light_bad)} tampered")
    return {
        "phase": "commit150", "ok": True, "validators": n_vals,
        "commits": {"verify_commit": n_full,
                    "verify_commit_light": n_light},
        "signatures": n_full * n_vals + n_light * n_checked,
        "sign_bytes_len": msg_len,
        "tampered": {"full": {str(k): sorted(v)
                              for k, v in full_bad.items()},
                     "light": {str(k): sorted(v)
                               for k, v in light_bad.items()}},
        "setup_seconds": round(setup_s, 3),
        "seconds": run["seconds"],
        "compile_seconds": warmup["compiles"]["seconds"],
        "device_min_batch": threshold,
        "warmup": warmup, "run": run, "oracle": oracle,
        "table": table_placement(
            [v.pub_key.bytes() for v in vals.validators], platform
        ),
        "retraces_after_warmup": run["compiles"]["count"],
        "hbm": hbm_stats(),
    }


def replay(chain_id, vals, blocks, depth: int) -> list[str | None]:
    """The blocksync sync step over ``blocks`` [(BlockID, Commit)]:
    verify the first unapplied block's commit with
    ``verify_commit_light``, then queue the next ``depth`` commits on
    the prefetch lane — each height once, one coalesced submission per
    step (blocksync/reactor.py _try_sync_step /
    _prefetch_commit_verifies).  The reactor then applies the block
    (store + ABCI) while the prefetch runs; here the wait for the
    queue to drain stands in for the apply."""
    from cometbft_tpu.blocksync.reactor import commit_prefetch_items
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.types.validation import verify_commit_light

    errors: list[str | None] = []
    prefetched = 0  # index of the highest block already submitted
    for k, (bid, commit) in enumerate(blocks):
        errors.append(
            run_verify(verify_commit_light, chain_id, vals, bid, commit)
        )
        items = []
        hi = min(k + depth, len(blocks) - 1)
        for j in range(max(prefetched, k) + 1, hi + 1):
            got = commit_prefetch_items(chain_id, vals, blocks[j][1])
            check(got is not None, "validator set does not line up")
            items.extend(got)
        if items:
            check(vq.submit_prefetch(items) == len(items),
                  "the verify queue refused a prefetch")
            prefetched = hi
        q = vq._installed()
        deadline = time.monotonic() + 300
        while q is not None and q.busy():
            check(time.monotonic() < deadline, "verify queue stuck busy")
            time.sleep(0.0005)
    return errors


def phase_replay1k(
    compiles: CompileLog, seed: int, platform: str, n_vals: int = 1000,
    n_blocks: int = 32, oracle_sample: int = 256,
) -> dict:
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.crypto.dispatch import shape_bucket

    check(vq.speculation_active(), "no verify queue to prefetch through")
    t_setup = time.perf_counter()
    rng = random.Random(seed * 1000 + 1000)
    chain_id = f"chip-smoke-{seed}"
    depth = vq.prefetch_depth_from_env()
    vals, keys = make_validators(seed, n_vals)
    n_checked = light_checked(n_vals)
    bad = {j: {rng.randrange(n_checked - 1)}
           for j in rng.sample(range(n_blocks), min(2, n_blocks))}
    base = 2_000_000 + seed * 10_000
    warm = [make_commit(chain_id, keys, base + j) for j in range(depth + 2)]
    blocks = [make_commit(chain_id, keys, base + 1000 + j, bad.get(j, ()))
              for j in range(n_blocks)]
    setup_s = time.perf_counter() - t_setup

    p0 = Probe(compiles)
    check(not any(replay(chain_id, vals, warm, depth)),
          "warm-up replay rejected a commit")
    p1 = Probe(compiles)
    stats0 = vq._installed().stats()
    errors = replay(chain_id, vals, blocks, depth)
    p2 = Probe(compiles)
    stats1 = vq._installed().stats()
    warmup, run = p1.delta(p0), p2.delta(p1)
    full_buckets = {
        shape_bucket(n_checked), shape_bucket(n_vals),
        shape_bucket(min(depth, n_blocks - 1) * n_vals),
    }
    check_device_plane(run, full_buckets, allow_small_host=None)
    # and the checks above looked at something: commit-sized batches
    # ran, every vote of every block but the first went through the
    # node's queue (the prefetch lane), and none of its batches failed
    check(any(b["bucket"] in full_buckets for b in run["batches"]),
          f"no commit-sized batch was recorded: {run['batches']}")
    launched = stats1["launched_sigs"] - stats0["launched_sigs"]
    check(launched >= (n_blocks - 1) * n_vals,
          f"the queue launched {launched} signatures, "
          f"{(n_blocks - 1) * n_vals} were prefetched")
    failed_batches = stats1["failed_batches"] - stats0["failed_batches"]
    check(failed_batches == 0, f"{failed_batches} queue batches failed")
    cases = [
        {"commit": c, "checked": n_checked, "tampered": j in bad,
         "error": errors[j]}
        for j, (_, c) in enumerate(blocks)
    ]
    oracle = check_against_oracle(rng, chain_id, vals, cases, oracle_sample)
    check(oracle["rejected"] == len(bad),
          f"{oracle['rejected']} commits rejected, {len(bad)} tampered")
    return {
        "phase": "replay1k", "ok": True, "validators": n_vals,
        "blocks": n_blocks, "signatures": n_blocks * n_vals,
        "prefetch_depth": depth,
        "tampered": {str(k): sorted(v) for k, v in bad.items()},
        "setup_seconds": round(setup_s, 3),
        "seconds": run["seconds"],
        "compile_seconds": warmup["compiles"]["seconds"],
        "queue_launched_sigs": launched,
        "queue_failed_batches": failed_batches,
        "warmup": warmup, "run": run, "oracle": oracle,
        "table": table_placement(
            [v.pub_key.bytes() for v in vals.validators], platform
        ),
        "retraces_after_warmup": run["compiles"]["count"],
        "hbm": hbm_stats(),
    }


def phase_mesh(
    compiles: CompileLog, seed: int, platform: str, n_devices: int = 4,
    n_vals: int = 1000, n_commits: int = 8, oracle_sample: int = 256,
) -> dict:
    """The multi-chip path and what it is compared with: the same
    commits through the verifier ``crypto/batch.py`` hands out when
    more than one device is visible (``keyed_mesh`` tier) and through
    the one-device ``keyed`` tier; verdict bits must be equal, and
    equal to the oracle."""
    import jax

    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier
    from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier
    from cometbft_tpu.types.validation import verify_commit

    check(len(jax.devices()) == n_devices,
          f"{len(jax.devices())} devices visible, expected {n_devices}")
    t_setup = time.perf_counter()
    rng = random.Random(seed * 1000 + 4)
    chain_id = f"chip-smoke-{seed}"
    vals, keys = make_validators(seed, n_vals)
    bad = {j: set(rng.sample(range(n_vals), 2))
           for j in rng.sample(range(n_commits), min(2, n_commits))}
    base = 4_000_000 + seed * 10_000
    commits = [make_commit(chain_id, keys, base + j, bad.get(j, ()))
               for j in range(n_commits + 1)]
    pks = [v.pub_key for v in vals.validators]
    setup_s = time.perf_counter() - t_setup

    def bits(verifier, commit):
        for i, pk in enumerate(pks):
            verifier.add(pk, commit.vote_sign_bytes(chain_id, i),
                         commit.signatures[i].signature)
        _, got = verifier.verify()
        return [bool(b) for b in got], verifier._last_tier

    def both(commit):
        mesh_v = crypto_batch.create_batch_verifier(pks[0])
        check(isinstance(mesh_v, ShardedTpuBatchVerifier),
              f"factory handed out {type(mesh_v).__name__}")
        return bits(mesh_v, commit), bits(TpuBatchVerifier(), commit)

    p0 = Probe(compiles)
    both(commits[-1][1])  # warm-up: table build, placement, two programs
    p1 = Probe(compiles)
    picks = []
    for j, (bid, commit) in enumerate(commits[:-1]):
        (mesh_bits, mesh_tier), (one_bits, one_tier) = both(commit)
        check(mesh_tier == "keyed_mesh" and one_tier == "keyed",
              f"commit {j}: tiers {mesh_tier!r} / {one_tier!r}")
        check(mesh_bits == one_bits,
              f"commit {j}: keyed_mesh and keyed verdict bits differ")
        want_bad = sorted(bad.get(j, ()))
        check([i for i, b in enumerate(mesh_bits) if not b] == want_bad,
              f"commit {j}: bad lanes differ from the tampered {want_bad}")
        for i in want_bad + [rng.randrange(n_vals) for _ in range(
                oracle_sample // n_commits)]:
            picks.append((j, i))
            check(
                oracle_ok(chain_id, vals, commit, i) == mesh_bits[i],
                f"commit {j} #{i}: verdict differs from the oracle",
            )
        # and through the entry point a node calls
        err = run_verify(verify_commit, chain_id, vals, bid, commit)
        check((err is not None) == bool(want_bad)
              and (not want_bad or f"#{want_bad[0]})" in err),
              f"commit {j}: verify_commit said {err!r}")
    run = Probe(compiles).delta(p1)
    warmup = p1.delta(p0)
    check(run["tier_faults"] == 0 and not run["transitions"],
          f"ladder transitions: {run['transitions']}")
    check(run["compiles"]["count"] == 0 and not run["jit_seam_compiles"],
          f"compiles after warm-up: {run['compiles']}")
    # the sharded table: every device holds its quarter of the bytes
    entry = PR.TABLE_CACHE.peek([pk.bytes() for pk in pks])
    check(entry is not None, "key tables are not resident")
    placed = [v for (kind, _), v in entry.placements.items()
              if kind == "sharded"]
    check(len(placed) == 1, f"{len(placed)} sharded placements")
    table = placed[0][0][0]
    shards = {str(s.device): int(s.data.nbytes)
              for s in table.addressable_shards}
    check(len(table.sharding.device_set) == n_devices
          and len(shards) == n_devices,
          f"sharded table spans {sorted(shards)}")
    check(all(d.platform == platform for d in table.sharding.device_set),
          f"sharded table is not on {platform}")
    check(all(b * n_devices == table.nbytes for b in shards.values()),
          f"shard bytes {shards} of {table.nbytes}")
    return {
        "phase": "mesh", "ok": True, "validators": n_vals,
        "commits": n_commits, "devices": n_devices,
        "tampered": {str(k): sorted(v) for k, v in bad.items()},
        "setup_seconds": round(setup_s, 3), "seconds": run["seconds"],
        "compile_seconds": warmup["compiles"]["seconds"],
        "warmup": warmup, "run": run,
        "oracle_checked": len(picks),
        "sharded_table": {"bytes": int(table.nbytes),
                          "shard_bytes": shards},
        "hbm": hbm_stats(),
    }


# -- the command ---------------------------------------------------------


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs ONLY the multi-chip phase")
    args = ap.parse_args(argv)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(
            f"chip_smoke: device check failed: jax.devices()[0].platform "
            f"is {devices[0].platform!r}, not 'tpu'", file=sys.stderr,
        )
        return 2
    if len(devices) != args.chips:
        print(
            f"chip_smoke: device check failed: {len(devices)} chips "
            f"visible, --chips {args.chips} asked", file=sys.stderr,
        )
        return 2
    for var in ("CMT_TPU_DEVICE_MIN_BATCH", "CMT_TPU_DISABLE_DEVICE_VERIFY"):
        if os.environ.get(var):
            print(f"chip_smoke: {var} is set; the smoke runs without "
                  "overrides", file=sys.stderr)
            return 2
    compiles = CompileLog()
    from cometbft_tpu import ops  # noqa: F401 — x64 + the compile cache
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519_native

    plane = crypto_batch.init_device_plane()
    if ed25519_native.load() is None:
        print("chip_smoke: the native host verifier did not build/load "
              f"({ed25519_native._LIB.status}); the host rung would run "
              "on the pure-Python fallback", file=sys.stderr)
        return 1
    emit({
        "phase": "start", "seed": args.seed, "chips": args.chips,
        "versions": {
            "jax": jax.__version__,
            "jaxlib": importlib.metadata.version("jaxlib"),
            "libtpu": importlib.metadata.version("libtpu"),
        },
        "device_kind": devices[0].device_kind,
        "platform": devices[0].platform,
        "compile_cache_dir": jax.config.jax_compilation_cache_dir,
        "compile_cache_env": bool(
            os.environ.get("JAX_COMPILATION_CACHE_DIR")
        ),
        "device_plane": plane,
        "native": native_status(),
    })
    platform = devices[0].platform
    node = home = None
    failed = None
    try:
        if args.chips == 4:
            emit(phase_mesh(compiles, args.seed, platform))
        else:
            node, home, line = phase_node(compiles, args.seed)
            emit(line)
            emit(phase_commit150(compiles, args.seed, platform))
            emit(phase_replay1k(compiles, args.seed, platform))
    except Exception as exc:  # noqa: BLE001 — report, stop the node, fail
        import traceback

        traceback.print_exc(file=sys.stderr)
        failed = f"{type(exc).__name__}: {exc}"
    finally:
        if node is not None:
            node.stop()
            stuck = [t.name for t in threading.enumerate()
                     if t is not threading.current_thread()
                     and not t.daemon and t.is_alive()]
            if stuck and failed is None:
                failed = f"threads alive after node.stop(): {stuck}"
        if home is not None:
            shutil.rmtree(home, ignore_errors=True)
    emit({"phase": "end", "ok": failed is None, "error": failed,
          "native": native_status(), "hbm": hbm_stats(),
          "compiles_total": summarize_compiles(
              compiles.events, programs=False)})
    if failed is not None:
        return 1
    print(format_last_line(devices), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Driver ``commit_loop``: one caller, ``verify_commit`` on distinct full
commits back to back (closed loop).  Each sample is the host clock
around one call, ending when the verdict is in hand."""

from __future__ import annotations

import time

import jax

from benchmark import gen
from benchmark.drivers import common


def plan(config: dict, params: dict, seed: int) -> gen.Chain:
    return common.plan(config, params, seed, params["commits"])


def prepare(chain: gen.Chain, config: dict, params: dict) -> common.State:
    from cometbft_tpu.types.validation import verify_commit

    return common.State(chain, gen.validator_set(chain), verify_commit,
                        checked=chain.n_vals, sigs_per_item=chain.n_vals)


def control(st: common.State) -> None:
    """Breaks "all signatures for ``verify_commit``": the program's own
    weaker mode, which stops past two thirds of the power."""
    from cometbft_tpu.types.validation import verify_commit_light

    common.swap_entry(st, verify_commit_light)


def warm(st: common.State) -> None:
    for item, (bid, commit) in zip(st.chain.warm, st.warm):
        common.expect_warm(
            item, common.run_verify(st.entry, st.vals, bid, commit)
        )


def run(st: common.State, seconds: float) -> common.Window:
    win = common.Window()
    t0 = time.perf_counter()
    deadline = t0 + seconds
    while st.cursor < len(st.commits) and time.perf_counter() < deadline:
        with jax.profiler.TraceAnnotation("gen.next"):
            k = st.cursor
            bid, commit = st.commits[k]
            st.cursor += 1
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("entry.verify_commit"):
            err = common.run_verify(st.entry, st.vals, bid, commit)
        win.latencies.append(time.perf_counter() - t)
        win.outcomes.append((k, err))
        st.consumed(k)
    win.elapsed = time.perf_counter() - t0
    win.ran_out = st.cursor >= len(st.commits)
    return win


def metrics(win: common.Window) -> dict:
    lat = sorted(win.latencies)
    return {
        "commit_verify_p50_ms": 1e3 * common.percentile(lat, 50),
        "commit_verify_p95_ms": 1e3 * common.percentile(lat, 95),
    }

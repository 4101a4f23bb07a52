#!/usr/bin/env python3
"""One run of one benchmark cell on the attached TPU.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One new process: sign the cell's chain from ``--seed`` (in worker
processes that never import JAX), bring the device plane and a verify
queue up the way node assembly does, warm up the cell's own traffic,
measure for ``--seconds``, read the device's peak memory, stop the
queue, check what the window returned against the plain reference, and
print ONE JSON object as the last line of standard output.  Earlier
lines (standard output, one JSON object each) say what was set up and
what the program counted; the numbers compared for ``correct`` are
also the last lines of standard error.

It exits non-zero, with no result line, unless ``jax.devices()`` are
TPUs and as many as the cell asks for.  It never selects a platform and
sets no ``CMT_TPU_*`` variable: the system runs as shipped.

Everything that belongs to one cell is data found by name:
``BENCHMARK.json`` -> ``traffic/<cell>.json`` -> ``configs/<config>.json``,
``drivers/<driver>.py``; per-layer metrics -> ``layer_metrics/<metric>.json``
-> ``readers/<reader>.py``.

What a driver may define beyond ``plan`` / ``prepare`` / ``warm`` / ``run``
/ ``metrics`` / ``control`` (PR 35):

- ``build(chain, workers)``, for a chain that has to be made in order (a
  real block's header holds the hash of the commit before it, so its
  hash cannot be fixed before that commit is signed).  ``plan_chain``
  starts it in ``Signing``'s place and ``run_cell`` calls the same
  ``finish()`` / ``close()`` on what it returns (and reads its
  ``.chain``).  The contract: the work runs in processes that import
  neither JAX nor ``cometbft_tpu`` (``gen.sign_item`` and the references
  are there for them), it starts at once, before the backend does, and
  ``finish()`` returns with ``item.sigs``, ``item.block_hash``,
  ``item.parts_hash``, ``item.parts_total`` of every item, and
  ``chain.sign_bytes_total``, filled in; ``close()`` stops whatever
  still runs, in any state.
- ``compare(state, win)`` -> ``{name: {"value", "limit"}}``: exact counts
  of the guarantees the deployment adds (what was stored, applied, read
  back), merged into ``compared`` after ``check.compare``'s six, so they
  print on standard error and decide ``correct`` like those.  A name
  that is already there is an error.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import multiprocessing
import os
import random
import shutil
import sys
import tempfile
import time

T_PROCESS = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import check, gen  # noqa: E402 — neither imports JAX

#: signing pool size: enough to hide signing behind JAX's own start-up
#: on the chip's host (13 cores), few enough to leave it cores.  6 hid
#: 345k signatures; the commit cell's 900k read 4.6-6.3 s of
#: ``sign_wait_s`` with 6 and 0.0-0.5 with 10 (PR 32, same call)
SIGN_WORKERS = 10


class NoChip(Exception):
    """The devices are not what the cell asks for."""


def emit(line: dict) -> None:
    print(json.dumps(line, sort_keys=True), flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(workload: str) -> dict:
    """The cell's manifest entry, traffic, configuration, driver and
    the per-layer metrics that name it."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise SystemExit(
            f"run.py: no workload {workload!r} in BENCHMARK.json "
            f"(have {sorted(cells)})"
        )
    cell = cells[workload]
    traffic = load_json(HERE, "traffic", f"{cell['name']}.json")
    cfg_entry = next(c for c in manifest["configs"]
                     if c["name"] == cell["config"])
    config = load_json(ROOT, cfg_entry["file"])
    driver = importlib.import_module(f"benchmark.drivers.{traffic['driver']}")

    def listed(metric: dict) -> bool:
        return "workloads" not in metric or workload in metric["workloads"]

    return {
        "cell": cell, "traffic": traffic, "config": config,
        "driver": driver,
        "end_to_end": [m for m in manifest["end_to_end"] if listed(m)],
        "per_layer": [m for m in manifest["per_layer"] if listed(m)],
    }


def require_chip(chips: int):
    """The cell's devices, or NoChip.  Never selects a platform."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(
            f"jax.devices()[0].platform is {devices[0].platform!r}, "
            "not 'tpu'"
        )
    if len(devices) != chips:
        raise NoChip(f"{len(devices)} chips visible, the cell asks {chips}")
    return devices


class Signing:
    """The chain being signed.  With ``workers`` > 1 the work starts
    at once, in spawned processes that import only ``benchmark.gen``;
    ``finish`` returns once the signatures are on the chain, ``close``
    stops the pool whatever state it is in."""

    def __init__(self, chain: gen.Chain, workers: int) -> None:
        self.chain = chain
        self.pool = self.pending = None
        if workers > 1:
            self.pool = multiprocessing.get_context("spawn").Pool(workers)
            self.pending = self.pool.map_async(
                gen.sign_items, gen.sign_jobs(chain, workers * 4)
            )

    def finish(self) -> None:
        if self.pool is None:
            results = [gen.sign_items(j)
                       for j in gen.sign_jobs(self.chain, 1)]
        else:
            results = self.pending.get(timeout=600)
        gen.attach(self.chain, results)
        self.close()

    def close(self) -> None:
        if self.pool is not None:
            self.pool.terminate()
            self.pool.join()
            self.pool = None


def start_queue():
    """The device plane and a verify queue, as node assembly brings
    them up (``node/__init__.py``: init_device_plane, the env knobs
    validated, VerifyQueue started and installed) — no node, no health
    prober."""
    from cometbft_tpu.crypto import batch as crypto_batch
    from cometbft_tpu.crypto import ed25519_native
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.light.serve import header_cache_capacity_from_env
    from cometbft_tpu.utils.log import default_logger

    logger = default_logger()
    plane = crypto_batch.init_device_plane(
        logger=logger.with_fields(module="device")
    )
    if ed25519_native.load() is None:
        raise RuntimeError(
            "the native host verifier did not build/load "
            f"({ed25519_native._LIB.status}); the host rung would run on "
            "the pure-Python fallback"
        )
    vq.checktx_batch_from_env()
    vq.checktx_wait_ms_from_env()
    vq.light_batch_from_env()
    vq.light_wait_ms_from_env()
    header_cache_capacity_from_env()
    queue = vq.VerifyQueue(logger=logger.with_fields(module="verify_queue"))
    queue.start()
    vq.install_queue(queue)
    return plane, queue


def traced(fn, trace_dir: str) -> tuple:
    """``fn()`` under the profiler; the host's Python frames left out
    (the benchmark's own annotations are what names the host side).
    -> (fn's result, seconds the profiler took to stop and write)."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        out = fn()
    finally:
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
    return out, time.perf_counter() - t0


def read_layers(cell: dict, ctx: dict) -> dict:
    """Each per-layer metric through its reader; one that finds nothing
    to read is left out."""
    out = {}
    for m in cell["per_layer"]:
        spec = load_json(HERE, "layer_metrics", f"{m['name']}.json")
        reader = importlib.import_module(
            f"benchmark.readers.{spec['reader']}"
        )
        value = reader.read(ctx, spec.get("params", {}))
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def plan_chain(cell: dict, seed: int, sign_workers: int = SIGN_WORKERS):
    """-> the cell's chain, being signed: by the driver's ``build``
    where it has one (a chain made in order), by ``Signing`` where not.
    Called before the backend starts, so that signing runs meanwhile."""
    driver = cell["driver"]
    chain = driver.plan(cell["config"], cell["traffic"]["params"], seed)
    if hasattr(driver, "build"):
        return driver.build(chain, sign_workers)
    return Signing(chain, sign_workers)


def run_cell(cell: dict, signing, seconds: float, trace: bool,
             devices, after_warm=None) -> dict:
    """Everything after the look for a chip.  -> the result line.
    ``after_warm(state)`` is the control's and the tests' seam: it
    may swap what the window drives."""
    import jax

    from benchmark import observe, trace_reduce

    driver, traffic, config = cell["driver"], cell["traffic"], cell["config"]
    name = cell["cell"]["name"]
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    shipped_env = {k: v for k, v in os.environ.items()
                   if k.startswith("CMT_TPU_")}

    chain = signing.chain
    seed = chain.seed
    try:
        compiles = observe.CompileLog()
        from cometbft_tpu import ops  # noqa: F401 — x64 + the compile cache

        plane, queue = start_queue()
    except BaseException:
        signing.close()
        raise
    try:
        emit({
            "phase": "start", "workload": name, "seed": seed, **dev,
            "cmt_tpu_env": shipped_env,
            "compile_cache_dir": jax.config.jax_compilation_cache_dir,
            "device_plane": plane,
            "chain": {"validators": chain.n_vals, "items": len(chain.items),
                      "warm": len(chain.warm)},
        })
        # The program's objects of the chain (a million ``CommitSig``s)
        # are made with the collector off: they are frozen out of its
        # sight below, and its passes over the growing heap took half
        # of the time of making them.  They are made AFTER the signing,
        # not as the signatures arrive: with this thread busy the
        # pool's results came in four to five times slower (PR 32,
        # light cell: ``sign_wait_s`` 8-10 -> 40-51 s).
        collecting = gc.isenabled()
        gc.disable()
        try:
            t_sign = time.perf_counter()
            signing.finish()
            sign_wait_s = time.perf_counter() - t_sign
            state = driver.prepare(chain, config, traffic["params"])
        finally:
            if collecting:
                gc.enable()
        t_warm = time.perf_counter()
        driver.warm(state)
        warm_s = time.perf_counter() - t_warm
        if after_warm is not None:
            after_warm(state)
        # the chain's objects (hundreds of thousands of signatures) are
        # the generator's, not a node's: out of the collector's sight,
        # so that no full collection walks them inside the window
        gc.collect()
        gc.freeze()
        spans_s = observe.program_spans()
        mark = compiles.mark()
        before = observe.counters()
        cpu_before = observe.host_cpu()
        setup_s = time.perf_counter() - T_PROCESS
        emit({
            "phase": "setup", "workload": name, **dev,
            "setup_s": setup_s, "sign_wait_s": sign_wait_s,
            "warm_s": warm_s,
            "table_build_spans_s": spans_s.get("table_build", []),
            "compiles": compiles.summary(0, mark),
        })

        # -- the window ---------------------------------------------------
        slice_s = min(float(traffic.get("trace_seconds", 2.0)), seconds)
        win = driver.run(state, seconds - slice_s if trace else seconds)
        reduced = None
        slice_counters = slice_items = None
        if trace:
            trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
            try:
                c0 = observe.counters()
                part, stop_s = traced(lambda: driver.run(state, slice_s),
                                      trace_dir)
                slice_counters = observe.delta(observe.counters(), c0)
                slice_items = len(part.outcomes)
                win.extend(part)
                xplane = trace_reduce.find_xplane(trace_dir)
                xplane_bytes = os.path.getsize(xplane)
                t_load = time.perf_counter()
                planes = trace_reduce.load(xplane)
                load_s = time.perf_counter() - t_load
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            reduced = trace_reduce.reduce(planes)
            emit({"phase": "trace", "workload": name, **dev,
                  "slice_s": slice_s, "slice_items": slice_items,
                  "profiler_stop_s": stop_s, "xplane_bytes": xplane_bytes,
                  "load_s": load_s,
                  "planes": trace_reduce.describe(planes),
                  "programs": reduced["programs"]})
        counted = observe.delta(observe.counters(), before)
        host_cpu = observe.delta(observe.host_cpu(), cpu_before)
        in_window = compiles.summary(mark)
        peak = observe.hbm_peak_bytes()
    finally:
        gc.unfreeze()
        queue.stop()
        signing.close()

    # -- after the window: the plain reference ----------------------------
    t_ref = time.perf_counter()
    compared, looked_at = check.compare(
        chain, win.outcomes, state.checked,
        sample=int(traffic.get("reference_sample", 256)),
        max_scans=int(traffic.get("reference_scans", 12)),
        rng=random.Random(seed ^ 0x5EED),
    )
    if hasattr(driver, "compare"):
        for key, v in driver.compare(state, win).items():
            if key in compared:
                raise RuntimeError(
                    f"{traffic['driver']}.compare returned {key!r}, which "
                    "check.compare already counts"
                )
            compared[key] = {"value": v["value"], "limit": v["limit"]}
    reference_s = time.perf_counter() - t_ref
    attempted = len(win.outcomes)
    failed = compared["missing_verdicts"]["value"]
    if in_window["count"]:
        failed = attempted  # a compile inside the window voids its timings
    emit({
        "phase": "window", "workload": name, **dev,
        "samples": attempted, "elapsed_s": win.elapsed,
        "per_quarter": win.per_quarter(), "parts_s": win.parts,
        "host_cpu": host_cpu,
        "chain_ran_out": win.ran_out,
        "rejected": sum(err is not None for _, err in win.outcomes),
        "compiles_in_window": in_window, "counters": counted,
        "peak_bytes_in_use": peak, "reference_s": reference_s,
        **looked_at,
    })

    correct = all(v["value"] <= v["limit"] for v in compared.values())
    if trace:
        ctx = {
            "counters": counted, "trace": reduced,
            "slice_counters": slice_counters, "slice_items": slice_items,
            "sigs_per_item": state.sigs_per_item,
            "sign_bytes_mean": chain.sign_bytes_mean,
            "spans_s": spans_s,
            "device_kind": dev["kind"],
        }
        metrics = read_layers(cell, ctx)
        dev = dict(dev, busy_s=reduced["busy_s"],
                   window_s=reduced["window_s"])
    else:
        values = dict(driver.metrics(win), setup_s=setup_s)
        metrics = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell["end_to_end"]
        }
    line = {
        "correct": correct, "attempted": attempted, "failed": failed,
        "metrics": metrics,
        "device": dict(dev, memory_peak_bytes=peak),
    }
    if trace:
        line["breakdown"] = {"device_ops": reduced["device_ops"],
                             "idle_gaps": reduced["idle_gaps"]}
    line["compared"] = compared
    for key, v in compared.items():
        print(f"compared {key}: {v['value']} (limit {v['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    return line


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    signing = plan_chain(cell, args.seed)
    try:
        devices = require_chip(cell["cell"]["chips"])
    except NoChip as exc:
        signing.close()
        print(f"run.py: device check failed: {exc}", file=sys.stderr)
        return 2
    line = run_cell(cell, signing, args.seconds, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

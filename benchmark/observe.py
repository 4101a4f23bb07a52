"""What the harness reads from the program: counts, spans, compile events.

Copies of ``chip_smoke.py``'s ``CompileLog`` and ``Probe`` (PR 22 ran
them on the chip), cut to what the benchmark's readers use.  Nothing
here is host-clock bookkeeping around launches (``DeviceUsage``, the
overlap ratio): device time comes from the profiler trace alone
(``trace_reduce.py``).
"""

from __future__ import annotations

import threading


class CompileLog:
    """Every XLA compile the process makes, from JAX's own monitoring
    events: program name, seconds, persistent-cache outcome."""

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
        self.events.append({
            "program": kw.get("fun_name", "?"),
            "seconds": seconds,
            "cache": getattr(self._tls, "outcome", "uncached"),
        })
        self._tls.outcome = "uncached"

    def mark(self) -> int:
        return len(self.events)

    def summary(self, start: int = 0, end: int | None = None,
                listed_s: float = 1.0) -> dict:
        """Count and seconds of the compiles in ``[start, end)``, cold
        (cache miss or uncached) and warm (cache hit) apart, and by
        name those of at least ``listed_s`` seconds or loaded from the
        cache."""
        evs = self.events[start:end]
        hits = [e for e in evs if e["cache"] == "hit"]
        cold = [e for e in evs if e["cache"] != "hit"]
        return {
            "count": len(evs),
            "cold_seconds": round(sum(e["seconds"] for e in cold), 3),
            "warm_seconds": round(sum(e["seconds"] for e in hits), 3),
            "cache_hits": len(hits),
            "programs": [
                {"program": e["program"], "cache": e["cache"],
                 "seconds": round(e["seconds"], 3)}
                for e in evs
                if e["seconds"] >= listed_s or e["cache"] == "hit"
            ],
        }


def ensure_crypto_metrics():
    """The installed crypto metrics sink, or a registry-backed one when
    nothing installed any (no node runs in a benchmark process)."""
    from cometbft_tpu import metrics as M
    from cometbft_tpu.utils.metrics import Registry

    cm = M.crypto_metrics()
    if not hasattr(cm.dispatch_decisions, "children"):
        cm = M.CryptoMetrics(Registry())
        M.install_crypto_metrics(cm)
    return cm


def counters() -> dict:
    """The program's counts at one instant."""
    from cometbft_tpu.crypto import dispatch
    from cometbft_tpu.crypto import verify_queue as vq
    from cometbft_tpu.ops import jitguard

    cm = ensure_crypto_metrics()
    q = vq._installed()
    qs = q.stats() if q is not None else {}
    return {
        "batches": {
            f"{r['tier']}/{r['bucket']}": r["samples"]
            for r in dispatch.LADDER.cost_snapshot()["table"]
            if r["family"] == dispatch.ROUTE_FAMILY_ED25519
        },
        "tiers": {
            "/".join(k): int(c.get())
            for k, c in cm.dispatch_tier.children().items()
        },
        "decisions": {
            "/".join(k): int(c.get())
            for k, c in cm.dispatch_decisions.children().items()
        },
        "jit_seam_compiles": dict(jitguard.compile_counts()),
        "queue": {
            k: qs.get(k, 0)
            for k in ("launched_sigs", "launched_batches",
                      "cache_resolved", "failed_batches")
        },
        # the same launches by the lane they left from (PR 28)
        "queue_lane_sigs": dict(qs.get("launched_sigs_by_lane", {})),
        "queue_lane_batches": dict(qs.get("launched_batches_by_lane", {})),
        "transitions": len(dispatch.LADDER.snapshot()["transitions"]),
    }


def delta(now: dict, was: dict) -> dict:
    """What a window added to ``counters()``; zero entries dropped from
    the nested maps."""
    out = {}
    for key, val in now.items():
        if isinstance(val, dict):
            prev = was.get(key, {})
            out[key] = {
                k: v - prev.get(k, 0) for k, v in val.items()
                if v - prev.get(k, 0)
            }
        else:
            out[key] = val - was.get(key, 0)
    return out


def program_spans() -> dict[str, list[float]]:
    """Durations in seconds of the program's own spans still in its
    ring (``cometbft_tpu/utils/trace.py``), by span name."""
    from cometbft_tpu.utils.trace import TRACER

    out: dict[str, list[float]] = {}
    for e in TRACER.events():
        out.setdefault(e["name"], []).append(e["dur"] / 1e6)
    return out


def host_cpu() -> dict:
    """Seconds of CPU this process has used (all threads) and, where
    the kernel tells, the seconds the machine's CPUs were stolen from
    it by its host: their growth over a window says whether a slow run
    worked more or waited more."""
    import os
    import time

    out = {"process_cpu_s": time.process_time()}
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        out["machine_steal_s"] = int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        pass
    return out


def hbm_peak_bytes() -> int | None:
    """Peak bytes in use on the fullest device, as the backend has it."""
    import jax

    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.local_devices()
    ]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None

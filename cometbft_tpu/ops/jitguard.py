"""Runtime jit/retrace + transfer guard — the device-path analog of
the concurrency runtime modes in utils/sync.py (CMT_TPU_LOCKGRAPH /
CMT_TPU_RACE).  Static half: tools/jitcheck.py; manual:
docs/device_contracts.md.

The throughput story (PAPERS.md: committee-signature verification
lives or dies on a stable compiled program staying on-device) has two
silent failure modes that neither tests nor dashboards saw before
this module:

- **Silent retraces.**  Every compiled kernel is memoized behind a
  registered seam (the ``_compiled*`` functions in ops/ed25519_verify,
  ops/precompute, parallel/mesh).  A key drifting off the
  pow2/bucket/chunk ladder recompiles a multi-second XLA program in
  the middle of the steady state — ~100ms of verify work stalls for
  the compile and the jit cache grows without bound.
- **Implicit host<->device transfers.**  A stray ``np.asarray`` on a
  device value, or a numpy operand reaching a compiled function
  without ``jax.device_put``, silently pays the host-device round
  trip per call.

``CMT_TPU_JITGUARD=1`` arms both checks, zero-cost when off:

- every compile-cache miss is counted per seam (CryptoMetrics
  ``crypto_jit_cache_misses{seam=...}``) and its call stack recorded;
- after ``seal()`` (the warmup boundary — benches call it once their
  first launches have compiled), ANY further compile raises
  ``RetraceError`` carrying the offending key signature, the seam,
  this compile's stack AND the seam's previous compile-site stack;
- ``transfer_window()`` (armed by TpuBatchVerifier.verify around the
  device dispatch) applies ``jax.transfer_guard("disallow")`` once
  sealed, so an implicit transfer raises at the offending line
  instead of stalling; trips increment
  ``crypto_guard_trips{kind=transfer}``.

Compile-cache miss COUNTING is always on (an int increment plus a
no-op metrics call) so bench provenance can report warmup compile
counts without the guard armed; stacks are recorded and errors raised
only under CMT_TPU_JITGUARD=1.
"""

from __future__ import annotations

import os
import traceback
from contextlib import contextmanager

from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.utils.env import flag_from_env

_ENABLED = flag_from_env("CMT_TPU_JITGUARD")

def _is_transfer_guard_error(exc: Exception) -> bool:
    """Attribute a trip to the metrics counter only for the error the
    jax.transfer_guard context actually raises (jax.errors.
    JaxRuntimeError whose message anchors on 'Disallowed ... transfer')
    — a stray exception that merely mentions 'transfer' must not fire
    the dashboard counter.  The original exception always propagates
    unchanged."""
    import jax

    msg = str(exc).lower()
    return (
        isinstance(exc, jax.errors.JaxRuntimeError)
        and "disallow" in msg
        and "transfer" in msg
    )


class RetraceError(Exception):
    """A compile-cache seam recompiled after the warmup boundary —
    steady state hit a multi-second XLA compile.  Carries the seam,
    the offending key signature, and both compile-site stacks (this
    one and the seam's previous compile)."""


_counts: dict[str, int] = {}          # seam -> lifetime compile count
_last_site: dict[str, tuple] = {}     # seam -> (key, stack) of last compile
_sealed = False


def enabled() -> bool:
    return _ENABLED


def note_compile(seam: str, key) -> None:
    """Record a compile-cache miss at a registered seam.  Called by
    the ``_compiled*`` memoizers BEFORE building the jit wrapper, so a
    post-warmup retrace raises before any compile time is spent."""
    _counts[seam] = _counts.get(seam, 0) + 1
    _crypto_metrics().jit_cache_misses.labels(seam=seam).inc()
    if not _ENABLED:
        return
    stack = "".join(traceback.format_stack(limit=16)[:-1])
    if _sealed:
        prior_key, prior_stack = _last_site.get(
            seam, (None, "<no compile before seal()>")
        )
        _crypto_metrics().guard_trips.labels(kind="retrace").inc()
        from cometbft_tpu.utils.flight import flight_tail

        raise RetraceError(
            f"RETRACE after warmup at seam '{seam}': key {key!r} has no "
            f"compiled program (cache warmed with e.g. {prior_key!r}).\n"
            "A steady-state arg signature drifted off the "
            "pow2/bucket/chunk ladder — see docs/device_contracts.md.\n"
            f"--- this compile request:\n{stack}"
            f"--- previous compile at seam '{seam}':\n{prior_stack}"
            + flight_tail()
        )
    _last_site[seam] = (key, stack)


def compile_counts() -> dict[str, int]:
    """Per-seam lifetime compile counts — BENCH provenance reads this
    after warmup so future perf PRs can assert steady state compiled
    nothing new."""
    return dict(_counts)


def sealed() -> bool:
    return _sealed


def seal() -> None:
    """End the warmup phase: from here on (with CMT_TPU_JITGUARD=1)
    any compile-cache miss raises RetraceError and transfer_window()
    arms jax.transfer_guard("disallow")."""
    global _sealed
    _sealed = True


def reset() -> None:
    """Test/bench helper: forget counts, sites and the seal."""
    global _sealed
    _sealed = False
    _counts.clear()
    _last_site.clear()


@contextmanager
def transfer_window():
    """Arm ``jax.transfer_guard("disallow")`` around a steady-state
    verify window: implicit host<->device transfers (a numpy operand
    reaching a compiled call, ``float()``/``np.asarray`` on a device
    value) raise at the offending line instead of silently paying the
    link RTT.  Explicit ``jax.device_put`` / ``jax.device_get`` — the
    audited transfer idioms of the dispatch path — stay allowed.

    A no-op until the guard is enabled AND sealed: warmup compiles
    legitimately stage trace-time constants, so only the steady state
    is held to the no-implicit-transfers bar.
    """
    if not (_ENABLED and _sealed):
        yield
        return
    import jax

    try:
        with jax.transfer_guard("disallow"):
            yield
    except Exception as exc:
        if _is_transfer_guard_error(exc):
            _crypto_metrics().guard_trips.labels(kind="transfer").inc()
        raise


__all__ = [
    "RetraceError",
    "compile_counts",
    "enabled",
    "note_compile",
    "reset",
    "seal",
    "sealed",
    "transfer_window",
]

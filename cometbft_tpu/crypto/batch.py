"""Batch-verifier dispatch by key type (reference: crypto/batch/batch.go:10).

``create_batch_verifier`` returns the device-capable verifier for a key
type (the JAX/XLA batch kernel behind the dispatch ladder; the host
verifier only when the operator disabled the device plane). The
selection is behind this single seam so every caller
(VerifyCommit, light client, blocksync replay, consensus addVote) gets
the device path for free.

This file sits in tools/jitcheck.py's host-sync scan scope (with
ops/ and parallel/): any np.asarray / .item() / device fetch added on
the dispatch path must carry an audited ``# host sync:`` waiver
(docs/device_contracts.md) — today it has none, by design: all device
I/O lives behind the verifier seams it selects.
"""

from __future__ import annotations

from typing import Callable

from cometbft_tpu.crypto import BatchVerifier, PubKey
from cometbft_tpu.crypto import ed25519 as _ed
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.utils import sync as cmtsync
from cometbft_tpu.utils.env import flag_from_env
from cometbft_tpu.utils.log import Logger, default_logger

# The JAX backend is initialised IN THIS PROCESS, exactly once: by the
# node when it starts its verify plane (node/__init__.py), or by the
# first batch-verifier request in a process that runs no node (tools,
# tests).  A chip belongs to one process at a time, so nothing here
# ever asks a child process what devices exist — a parent that has
# touched JAX holds the chip and the child would fail or hang.  A
# backend that cannot initialise RAISES: the host verifier is the
# ladder's safety rung for faults AFTER start-up, never a silent
# stand-in for a device plane that did not come up.
_init_lock = cmtsync.Mutex()
_device_state: dict = {
    "status": "uninitialized", "ndev": 0, "platform": None, "kind": None,
}


def init_device_plane(logger: Logger | None = None) -> dict:
    """Initialise the JAX backend in-process (idempotent) and return
    the device-plane state: ``{"status": "ready", "ndev", "platform",
    "kind"}`` plus, on an accelerator, the ``link_rtt_s`` of a tiny
    transfer and the ``device_min_batch`` in force.  Logged once; the same
    state is served on /debug/perf through :func:`device_status`.
    Raises whatever backend initialisation raises."""
    if _device_state["status"] == "ready":  # every factory call asks
        return dict(_device_state)
    with _init_lock:
        if _device_state["status"] == "ready":
            return dict(_device_state)
        try:
            import jax

            devices = jax.devices()
            found = {
                "ndev": len(devices),
                "platform": devices[0].platform,
                "kind": devices[0].device_kind,
            }
            if found["platform"] != "cpu":
                # one tiny transfer round trip: a device that cannot
                # answer fails start-up here, and the round trip it
                # took is on record beside the threshold in force
                from cometbft_tpu.ops import ed25519_verify as _ev

                found["link_rtt_s"] = _ev.measure_link_rtt()
                found["device_min_batch"] = _ev.runtime_device_min_batch()
        except Exception as exc:
            _device_state.update(status="failed", error=repr(exc))
            raise
        _device_state.pop("error", None)
        _device_state.update(found, status="ready")
        state = dict(_device_state)
    (logger or default_logger()).info(
        "device plane initialised",
        **{k: v for k, v in state.items() if k != "status"},
    )
    return state


def device_status() -> dict:
    """Read-only snapshot of the device plane for the health surfaces
    (/debug/perf): status ``uninitialized | ready | failed``, visible
    device count, platform and device kind.  Never initialises the
    backend itself."""
    return dict(_device_state)


def _ed25519_factory() -> BatchVerifier:
    # Routing decisions that end at the host verifier are recorded
    # here, where they are made; a device-capable verifier defers its
    # decision to batch time (TpuBatchVerifier.plan — it may still
    # fall back on batch size / the cpu backend / ladder demotion).  Tier
    # ACCOUNTING is uniform either way: every verifier this factory
    # returns records crypto_dispatch_tier per BATCH at the ladder's
    # decision point (dispatch.LADDER.note_batch — host-only routes
    # via LadderHostVerifier.verify, device routes via
    # TpuBatchVerifier.execute), so counts are comparable across
    # tiers instead of mixing factory-time and batch-time samples.
    from cometbft_tpu.crypto.dispatch import LadderHostVerifier

    if flag_from_env("CMT_TPU_DISABLE_DEVICE_VERIFY"):
        _crypto_metrics().dispatch_decisions.labels(
            route="host", reason="disabled"
        ).inc()
        return LadderHostVerifier()
    ndev = init_device_plane()["ndev"]
    if ndev > 1 and not flag_from_env("CMT_TPU_DISABLE_MESH_VERIFY"):
        # multi-chip: shard the batch over a 1-D mesh — every
        # caller of this seam scales across chips transparently
        from cometbft_tpu.parallel.mesh import ShardedTpuBatchVerifier

        return ShardedTpuBatchVerifier()
    from cometbft_tpu.ops.ed25519_verify import TpuBatchVerifier

    return TpuBatchVerifier()


def _bls_factory() -> BatchVerifier:
    # ladder-routed since ISSUE 13: bls_native -> host RLC -> python
    # floor with demotion/watchdog/chaos/accounting inherited — the
    # bare BlsBatchVerifier this used to hand out verified the same
    # math but was invisible to crypto_dispatch_tier and kept running
    # a faulting native library forever
    from cometbft_tpu.crypto.bls_dispatch import BlsLadderVerifier

    return BlsLadderVerifier()


REGISTRY: dict[str, Callable[[], BatchVerifier]] = {
    _ed.KEY_TYPE: _ed25519_factory,
    "bls12_381": _bls_factory,
}


def create_batch_verifier(pub_key: PubKey) -> BatchVerifier:
    """(batch.go:10 CreateBatchVerifier) — raises KeyError for key types
    without a batch implementation; callers fall back to single verify."""
    return REGISTRY[pub_key.type()]()


def supports_batch_verifier(pub_key: PubKey | None) -> bool:
    return pub_key is not None and pub_key.type() in REGISTRY

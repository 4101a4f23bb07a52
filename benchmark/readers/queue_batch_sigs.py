"""Signatures per batch the verify queue launched in the window, from
``VerifyQueue.stats()`` deltas."""


def read(ctx: dict, params: dict) -> float | None:
    q = ctx["counters"]["queue"]
    if not q.get("launched_batches"):
        return None
    return q.get("launched_sigs", 0) / q["launched_batches"]

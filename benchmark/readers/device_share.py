"""Share of the window's verification that ran on a device tier, from
the dispatch ladder's cost table (``LADDER.cost_snapshot()`` deltas by
tier and shape bucket).  The program counts batches by bucket, not
signatures, so each batch is weighed by its bucket (the pow2 ceiling of
its size): exact where a cell's batches share one bucket or fill their
buckets alike."""


def share(batches: dict, device_tiers: list) -> float | None:
    """``batches``: {"<tier>/<bucket>": count} -> percent on a device
    tier, or None where there was no batch."""
    on_device = total = 0
    for key, n in batches.items():
        tier, bucket = key.rsplit("/", 1)
        total += n * int(bucket)
        if tier in device_tiers:
            on_device += n * int(bucket)
    return 100.0 * on_device / total if total else None


def read(ctx: dict, params: dict) -> float | None:
    return share(ctx["counters"]["batches"], params["device_tiers"])

"""Share of its memory roofline that the programs matching
``params["pattern"]`` reach in the traced slice: the least time the
chip's HBM needs for the bytes the *problem* needs (``work.py``: per
signature verified on the device its key, signature, sign-bytes and
verdict — from counts, never from lanes or table layout) over the
programs' device time.  Bounded by bytes; the v5e publishes no
integer-vector peak, so no compute bound is claimed.

Signatures verified on the device in the slice: the items the slice
completed times the signatures the program verifies for each, times the
slice's device share."""

from benchmark import work
from benchmark.readers import device_share, program_ms


def read(ctx: dict, params: dict) -> float | None:
    launches, seconds = program_ms.matching(ctx, params["pattern"])
    share = device_share.share(
        (ctx["slice_counters"] or {}).get("batches", {}),
        params["device_tiers"],
    )
    if not launches or seconds <= 0 or not share or not ctx["slice_items"]:
        return None
    sigs = ctx["slice_items"] * ctx["sigs_per_item"] * share / 100.0
    n_bytes = work.verify_bytes(sigs, sigs * ctx["sign_bytes_mean"])
    return 100.0 * work.least_seconds(n_bytes, ctx["device_kind"]) / seconds

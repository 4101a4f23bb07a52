"""Benchmark the keyed (precomputed-table) verify path on the device.

Shapes mirror BASELINE configs: a 150-validator commit reused across
many blocks (table cache hot), and a light-sync style batch of
H commits x 150 validators in one launch.  Prints device-side marginal
sigs/s via the K-dispatch difference method.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    import jax

    # the compile cache is the one cometbft_tpu/ops/__init__.py sets

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.ops import precompute as PR
    from cometbft_tpu.ops.ed25519_verify import (
        _finish,
        verify_arrays_keyed_async,
    )

    nval = int(os.environ.get("KB_NVAL", 150))
    nsigs = int(os.environ.get("KB_NSIGS", 4096))
    rng = np.random.RandomState(0)
    privs = [ed.gen_priv_key() for _ in range(nval)]
    pubs_b = [p.pub_key().bytes() for p in privs]

    t0 = time.time()
    entry = PR.TABLE_CACHE.lookup_or_build(pubs_b)
    np.asarray(jax.device_get(entry.table[0, 0, :4]))  # sync build
    print(
        f"table build: {nval} keys, {entry.window_bits}-bit windows, "
        f"{entry.nbytes / 1e6:.0f} MB, {time.time() - t0:.1f}s "
        "(incl. compile)",
        file=sys.stderr,
    )

    # light-sync-style batch: nsigs votes round-robin over the set
    idx = [i % nval for i in range(nsigs)]
    msgs = [rng.bytes(120) for _ in range(nsigs)]
    sigs = np.stack(
        [
            np.frombuffer(privs[i].sign(m), dtype=np.uint8)
            for i, m in zip(idx, msgs)
        ]
    )
    pub = np.stack(
        [np.frombuffer(pubs_b[i], dtype=np.uint8) for i in idx]
    )
    key_ids = entry.key_ids([pubs_b[i] for i in idx])

    t0 = time.time()
    out = _finish(verify_arrays_keyed_async(entry, key_ids, pub, sigs, msgs))
    print(f"first keyed launch (compile): {time.time() - t0:.1f}s",
          file=sys.stderr)
    assert bool(out.all()), "keyed verification failed"

    k = 6
    best = float("inf")
    for _ in range(3):
        t0 = time.time()
        parts = []
        for _ in range(k):
            parts.extend(
                verify_arrays_keyed_async(entry, key_ids, pub, sigs, msgs)
            )
        _finish(parts)
        t_k = time.time() - t0
        t0 = time.time()
        _finish(verify_arrays_keyed_async(entry, key_ids, pub, sigs, msgs))
        t_1 = time.time() - t0
        best = min(best, max(t_k - t_1, 1e-9) / (k - 1))
    print(
        f"keyed {nsigs} sigs x {nval} validators: "
        f"{nsigs / best:,.0f} sigs/s device-side ({best * 1e3:.1f} ms/launch)",
        flush=True,
    )
    # provenance line: the warmup compile count per seam (steady trials above should
    # have added none — docs/device_contracts.md)
    import json

    from cometbft_tpu.ops import jitguard

    print(f"JITGUARD compiles: {json.dumps(jitguard.compile_counts())}",
          flush=True)


if __name__ == "__main__":
    main()

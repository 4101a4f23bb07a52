"""A/B the verify kernel end-to-end under the current env flags.

Prints one line: device-side marginal sigs/s (K-dispatch difference
method, cancels the link RTT).  Drive with:

    for cols in stack stack16 tree pallas; do for sq in fast mul; do
      CMT_TPU_COLS_IMPL=$cols CMT_TPU_SQUARE_IMPL=$sq \
        python tools/bench_kernel_ab.py; done; done

(stack16 halves the stacked operand's HBM bytes and only changes mul,
so pair it with CMT_TPU_SQUARE_IMPL=mul; pallas fuses the whole field
op into one VMEM-resident program.)
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
    from cometbft_tpu.ops.ed25519_verify import (
        _finish,
        verify_arrays,
        verify_arrays_async,
    )

    n = int(os.environ.get("AB_N", 4096))
    rng = np.random.RandomState(0)
    priv = ed.gen_priv_key()
    pub_b = np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8)
    msgs = [
        rng.randint(0, 256, size=120, dtype=np.uint8).tobytes()
        for _ in range(n)
    ]
    sigs = np.stack(
        [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
    )
    pubs = np.tile(pub_b, (n, 1))

    t0 = time.time()
    out = verify_arrays(pubs, sigs, msgs)
    compile_s = time.time() - t0
    assert bool(out.all())

    k = 6
    best = float("inf")
    for _ in range(2):
        t0 = time.time()
        parts = []
        for _ in range(k):
            parts.extend(verify_arrays_async(pubs, sigs, msgs))
        _finish(parts)
        t_k = time.time() - t0
        t0 = time.time()
        _finish(verify_arrays_async(pubs, sigs, msgs))
        t_1 = time.time() - t0
        best = min(best, max(t_k - t_1, 1e-9) / (k - 1))
    rate = n / best
    print(
        f"cols={os.environ.get('CMT_TPU_COLS_IMPL', 'stack'):5s} "
        f"square={os.environ.get('CMT_TPU_SQUARE_IMPL', 'fast'):4s} "
        f"{rate:10,.0f} sigs/s device-side "
        f"({best * 1e3:.1f} ms/launch, compile {compile_s:.0f}s)",
        flush=True,
    )


if __name__ == "__main__":
    main()

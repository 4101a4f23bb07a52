"""Device throughput of the field core: mul / square chains.

Times a lax.fori_loop chain of dependent field ops at kernel batch
width, at two iteration counts; the difference cancels dispatch + link
RTT.  Prints muls/s and the
implied effective element-ops/s for the MFU analysis.
"""

from __future__ import annotations

import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from cometbft_tpu.ops import field as F

    dev = jax.devices()[0]
    print(f"device: {dev}")
    batch = 8192
    rng = np.random.RandomState(1)
    a = jnp.asarray(
        rng.randint(0, 1 << 10, size=(F.NLIMBS, batch)), dtype=F.DTYPE
    )

    def timed(fn, x, trials=3):
        np.asarray(fn(x))
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            np.asarray(fn(x))
            best = min(best, time.perf_counter() - t0)
        return best

    def bench(name, body, k=1 << 9, est_ops=None):
        def make(iters):
            @jax.jit
            def run(x):
                v = jax.lax.fori_loop(0, iters, lambda _, v: body(v), x)
                return v[:, :4]

            return run

        t1 = timed(make(k), a)
        t4 = timed(make(4 * k), a)
        dt = max(t4 - t1, 1e-9)
        rate = 3 * k * batch / dt  # lane-ops/s
        line = (
            f"{name:18s} {rate / 1e6:9.1f} M/s "
            f"(K={t1 * 1e3:.1f} ms, 4K={t4 * 1e3:.1f} ms)"
        )
        if est_ops:
            line += f"  ~{rate * est_ops / 1e12:.3f} Tops/s eff"
        print(line)
        return rate

    mul_rate = bench("field.mul", lambda v: F.mul(v, v + 1), est_ops=2800)
    sq_rate = bench("field.square", F.square, est_ops=1900)
    bench("mul(a,a) (ref)", lambda v: F.mul(v, v), est_ops=2800)
    print(f"square/mul speedup: {sq_rate / mul_rate:.2f}x")


if __name__ == "__main__":
    main()

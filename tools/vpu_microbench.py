"""VPU arithmetic microbenchmark — decides the field-core number system.

Measures sustained element-op throughput for the candidate limb
arithmetics on the live device:
  - int32 multiply (current field core)
  - fp32 multiply-add (candidate radix-2^8 float core)
  - int32 add / shift (carry machinery)
  - emulated int64 multiply, for scale

Method: a result fetch pays the host<->device round trip, so each
flavor is
timed at two iteration counts (K and 4K) with a host fetch of a scalar
reduction, and the throughput comes from the DIFFERENCE, cancelling
dispatch + RTT + fetch.  Ops are dependent (loop-carried) so XLA cannot
collapse the chain.
"""

from __future__ import annotations

import time

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    print(f"device: {dev}")
    shape = (8, 128, 512)
    numel = int(np.prod(shape))

    def timed(fn, x, trials=3):
        fn(x)  # compile
        best = float("inf")
        for _ in range(trials):
            t0 = time.perf_counter()
            np.asarray(fn(x))  # host fetch = true sync
            best = min(best, time.perf_counter() - t0)
        return best

    def bench(name, dtype, body, k=1 << 14):
        x = jnp.asarray(
            np.random.randint(1, 200, size=shape), dtype=dtype
        )

        def make(iters):
            @jax.jit
            def run(x):
                v = jax.lax.fori_loop(0, iters, lambda _, v: body(v), x)
                return v.reshape(-1)[:8]  # tiny fetch

            return run

        t1 = timed(make(k), x)
        t4 = timed(make(4 * k), x)
        dt = max(t4 - t1, 1e-9)
        rate = 3 * k * numel / dt
        print(
            f"{name:24s} {rate / 1e12:8.3f} Tops/s   "
            f"(K={t1 * 1e3:.1f} ms, 4K={t4 * 1e3:.1f} ms)"
        )
        return rate

    bench("int32 mul", jnp.int32, lambda v: (v * v) & 0x7FF)
    bench("int32 add", jnp.int32, lambda v: (v + 3) ^ 1)
    bench("int32 mul+add+mask", jnp.int32, lambda v: ((v * v + v) & 0x7FF))
    bench("int32 shift", jnp.int32, lambda v: ((v >> 2) ^ v) | 1)
    bench(
        "fp32 fma+clamp",
        jnp.float32,
        lambda v: jnp.minimum(v * v + v, 199.0),
    )
    bench(
        "fp32 carry step",
        jnp.float32,
        lambda v: jnp.minimum(v - 256.0 * jnp.floor(v * (1.0 / 256.0)), 199.0),
    )
    bench("uint32 mul (emu64 half)", jnp.uint32, lambda v: (v * v) & 0x7FF)


if __name__ == "__main__":
    main()

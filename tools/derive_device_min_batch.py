"""Measure the host-vs-device batch-verify crossover on THIS machine:
per-signature marginal costs of the host batch verifier and of the
generic device kernel, the fixed cost a launch leaves over, and the
batch size where the device path starts to win.

Runs the REAL paths — ed25519.CpuBatchVerifier vs
ops.ed25519_verify.verify_arrays — at growing batch sizes (transfers,
packing, and round trips included).  Run on the target hardware — it
refuses the CPU backend:

    python tools/derive_device_min_batch.py

Each of the four sizes is one cold compile of the generic kernel
(about a minute).
The output is evidence for whoever sets dispatch policy (the
constants ops/ed25519_verify.DEVICE_MIN_BATCH and
ACCELERATOR_MIN_BATCH); no code reads it.  On the v5e of PR 22 the
generic kernel won from 1,024 signatures up (CHANGES.md).
"""

from __future__ import annotations

import json
import sys
import time

sys.path.insert(0, ".")

import numpy as np

SIZES = (64, 256, 1024, 4096)


def main() -> None:
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        raise SystemExit(
            "derive_device_min_batch needs the accelerator it calibrates "
            "for; jax.devices()[0].platform is 'cpu'"
        )

    from cometbft_tpu.crypto import ed25519 as ed
    from cometbft_tpu.crypto import ed25519_native
    from cometbft_tpu.ops.ed25519_verify import verify_arrays

    rng = np.random.RandomState(3)
    priv = ed.gen_priv_key()
    pub = priv.pub_key()
    pub_b = np.frombuffer(pub.bytes(), dtype=np.uint8)

    rows = []
    crossover = None
    # prepare the largest fixture once; slice per size
    nmax = SIZES[-1]
    msgs = [
        rng.randint(0, 256, size=120, dtype=np.uint8).tobytes()
        for _ in range(nmax)
    ]
    print("signing fixture...", file=sys.stderr)
    sigs_all = np.stack(
        [np.frombuffer(priv.sign(m), dtype=np.uint8) for m in msgs]
    )
    pubs_all = np.tile(pub_b, (nmax, 1))

    for n in SIZES:
        pubs, sigs, ms = pubs_all[:n], sigs_all[:n], msgs[:n]

        def cpu_run():
            bv = ed.CpuBatchVerifier()
            for m, s in zip(ms, sigs):
                bv.add(pub, m, s.tobytes())
            ok, _ = bv.verify()
            assert ok

        def dev_run():
            assert bool(verify_arrays(pubs, sigs, ms).all())

        dev_run()  # compile/warm this shape
        t_cpu = min(
            (lambda: (lambda t0: (cpu_run(), time.perf_counter() - t0)[1])(
                time.perf_counter()
            ))()
            for _ in range(3)
        )
        t_dev = min(
            (lambda: (lambda t0: (dev_run(), time.perf_counter() - t0)[1])(
                time.perf_counter()
            ))()
            for _ in range(3)
        )
        winner = "device" if t_dev < t_cpu else "cpu"
        rows.append(
            {
                "batch": n,
                "cpu_ms": round(t_cpu * 1e3, 2),
                "device_ms": round(t_dev * 1e3, 2),
                "winner": winner,
            }
        )
        print(json.dumps(rows[-1]), file=sys.stderr)
        if winner == "device" and crossover is None:
            crossover = n
        if winner == "cpu":
            crossover = None  # must win from here on up

    # per-sig slopes + the fixed round trip they leave over
    big = rows[-1]
    mid = next(
        (r for r in rows if r["batch"] >= 1024 and r is not big), rows[0]
    )
    t_dev_sig = max(
        (big["device_ms"] - mid["device_ms"])
        / 1e3
        / max(big["batch"] - mid["batch"], 1),
        1e-7,
    )
    t_cpu_sig = big["cpu_ms"] / 1e3 / big["batch"]
    rtt = max(mid["device_ms"] / 1e3 - mid["batch"] * t_dev_sig, 0.0)
    cal = {
        "device_kind": dev.device_kind,
        "native_host_verifier": ed25519_native.load() is not None,
        "t_cpu_per_sig": round(t_cpu_sig, 9),
        "t_dev_per_sig": round(t_dev_sig, 9),
        "fitted_link_rtt_s": round(rtt, 6),
    }

    print(
        json.dumps(
            {
                "recommended_device_min_batch": crossover or nmax * 2,
                "note": (
                    "device never won at measured sizes; keep CPU"
                    if crossover is None
                    else "smallest batch where the device path wins "
                    "end-to-end, stable through the largest measured"
                ),
                "calibration": cal,
                "rows": rows,
            }
        )
    )


if __name__ == "__main__":
    main()

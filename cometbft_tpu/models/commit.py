"""The flagship model: commit verification as a jittable forward step.

One "forward pass" = verify every signature of a commit (or a batch of
commits) in a single device launch — the hot path behind VerifyCommit
(types/validation.go:220), light-client header sync (light/verifier.go),
and blocksync replay (internal/blocksync/reactor.go:550).

Arrays are feature-first (see ops/field.py design notes): byte strings
lead with their byte axis and the batch axes follow, so the batch rides
the TPU vector lanes and shards cleanly over a device mesh.
"""

from __future__ import annotations

import numpy as np

from cometbft_tpu.ops.ed25519_verify import verify_kernel

# Vote sign-bytes are ~120 bytes (canonical proto + chain id); bucket 128
# needs ceil((64+128+17)/128) = 2 SHA-512 blocks.
MSG_BUCKET = 128
NBLOCKS = 2


def commit_verify_step(pub, sig, msg, msglen):
    """Jittable forward step.

    Shapes: pub (32, ...) u8, sig (64, ...) u8, msg (128, ...) u8,
    msglen (...,) i32 -> (...,) bool. Trailing batch dims are free:
    (V,) for one commit of V validators, (H, V) for H headers x V
    validators (the light-client / blocksync batch shapes).  On the
    served path the (H, V) batch is formed by the verify queue, flat:
    blocksync's prefetch submits H commits' triples as one buffer, and
    the light client's verify-ahead (``light/client.py``
    ``_VerifyAhead``) cuts the stream of its next targets' triples into
    buffers of the ``light_client`` lane's target, so H headers' checks
    ride one keyed launch of H x V' lanes (V' = the votes to past two
    thirds) without the step ever seeing two batch axes.
    """
    return verify_kernel(pub, sig, msg, msglen, nblocks=NBLOCKS)


def example_inputs(
    shape: tuple[int, ...] = (64,),
    msglen: int = 120,
    seed: int = 7,
    invalid: np.ndarray | None = None,
):
    """(pub, sig, msg, msglen) example batch, host-generated,
    feature-first: pub (32, *shape), sig (64, *shape), msg
    (128, *shape), msglen *shape.

    ``invalid`` (bool array of ``shape``) flips a signature byte in the
    marked lanes so callers can assert the verifier reports exactly
    those lanes false — a constant-true kernel fails such a check.
    """
    from cometbft_tpu.crypto import ed25519 as ed

    rng = np.random.RandomState(seed)
    n = int(np.prod(shape))
    pub = np.zeros((n, 32), dtype=np.uint8)
    sig = np.zeros((n, 64), dtype=np.uint8)
    msg = np.zeros((n, MSG_BUCKET), dtype=np.uint8)
    lens = np.full((n,), msglen, dtype=np.int32)
    priv = ed.gen_priv_key()  # one key, distinct messages: sign cost O(n)
    for i in range(n):
        m = rng.randint(0, 256, size=msglen, dtype=np.uint8).tobytes()
        pub[i] = np.frombuffer(priv.pub_key().bytes(), dtype=np.uint8)
        sig[i] = np.frombuffer(priv.sign(m), dtype=np.uint8)
        msg[i, :msglen] = np.frombuffer(m, dtype=np.uint8)
    if invalid is not None:
        flat = np.asarray(invalid, dtype=bool).reshape(n)
        sig[flat, 40] ^= 0x55  # corrupt S — marked lanes must verify False
    return (
        pub.T.reshape(32, *shape).copy(),
        sig.T.reshape(64, *shape).copy(),
        msg.T.reshape(MSG_BUCKET, *shape).copy(),
        lens.reshape(shape),
    )

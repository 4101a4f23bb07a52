"""Device mesh construction and sharded batch verification.

Capability parity note: the reference's concurrency for this workload is
a single machine's batch verifier (crypto/ed25519/ed25519.go:190) — the
multi-chip path here is the designed-for-TPU replacement, scaling the
same BatchVerifier seam over ICI instead of SIMD lanes.

The KEYED mesh path (``_compiled_keyed_mesh`` + ``verify_keyed_shard``)
shards the per-validator comb TABLE itself — not just the batch —
across the 1-D data mesh: device ``d`` holds the comb pages of pool
slots ``{d, d+ndev, d+2*ndev, ...}`` (strided round-robin ownership,
gathered into per-device-contiguous order at placement time) under a
``NamedSharding`` (precompute.KeySetTables.sharded_tables), the host
routes each batch lane to the device owning its key's shard (rebasing
ids to shard-local slots), and a ``shard_map``-wrapped jit with
explicit ``in_shardings``/``out_shardings`` and ``donate_argnums`` on
the packed tuple buffer runs the whole launch with ZERO collectives
and no per-launch buffer copy.  On a single-device mesh the ladder
never offers the tier and the single-device keyed path runs — see
docs/device_kernel_perf.md §3.95.
"""

from __future__ import annotations

import os

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from cometbft_tpu.utils.env import flag_from_env
from cometbft_tpu.metrics import crypto_metrics as _crypto_metrics
from cometbft_tpu.ops import field as _field
from cometbft_tpu.ops import jitguard as _jitguard
from cometbft_tpu.ops.ed25519_verify import (
    TpuBatchVerifier,
    _next_pow2,
    nblocks_for_bucket,
    verify_kernel,
    verify_kernel_keyed_packed,
)
from cometbft_tpu.utils.trace import TRACER as _tracer

BLOCK_AXIS = "blocks"
SIG_AXIS = "sigs"


def _factor2(n: int) -> tuple[int, int]:
    """Most-square 2-D factorization of the device count."""
    best = (n, 1)
    for a in range(1, int(n**0.5) + 1):
        if n % a == 0:
            best = (n // a, a)
    return best


def make_mesh(devices=None, shape: tuple[int, int] | None = None) -> Mesh:
    """A 2-D ("blocks", "sigs") mesh over the given (or all) devices."""
    devices = list(devices if devices is not None else jax.devices())
    if shape is None:
        shape = _factor2(len(devices))
    if shape[0] * shape[1] != len(devices):
        raise ValueError(f"mesh shape {shape} != {len(devices)} devices")
    arr = np.array(devices).reshape(shape)
    return Mesh(arr, (BLOCK_AXIS, SIG_AXIS))


def shard_batch(mesh: Mesh, arr, axes: tuple[str | None, ...]):
    """Place an array with the given per-dimension axis names."""
    return jax.device_put(arr, NamedSharding(mesh, P(*axes)))


_sharded_cache: dict[tuple, object] = {}


def sharded_verify_fn(mesh: Mesh, nblocks: int = 2):
    """jit of the batch-verify kernel over feature-first arrays with a
    (blocks, sigs) trailing batch: byte arrays are (nbytes, H, V) with H
    sharded over the ``blocks`` mesh axis and V over ``sigs``. Returns
    per-signature validity (H, V) with the same sharding.

    The kernel body is pure elementwise/gather compute, so XLA partitions
    it with zero cross-chip collectives — each chip verifies its shard of
    the validator set; only consumers that reduce to a scalar verdict
    trigger communication.

    Memoized on (mesh, nblocks): a fresh ``jax.jit`` wrapper per call
    would retrace per CALLER even at identical shapes (jit caches on
    wrapper identity) — the silent-retrace failure mode jitcheck and
    CMT_TPU_JITGUARD exist to catch.
    """
    key = (mesh, nblocks, _field.trace_config())
    fn = _sharded_cache.get(key)
    if fn is not None:
        return fn
    _jitguard.note_compile("sharded", (tuple(mesh.shape.items()), nblocks))
    data_spec = P(BLOCK_AXIS, SIG_AXIS)

    def step(pub, sig, msg, msglen):
        return verify_kernel(pub, sig, msg, msglen, nblocks=nblocks)

    in_shardings = tuple(
        NamedSharding(mesh, P(None, BLOCK_AXIS, SIG_AXIS)) for _ in range(3)
    ) + (NamedSharding(mesh, data_spec),)
    fn = jax.jit(
        step,
        in_shardings=in_shardings,
        out_shardings=NamedSharding(mesh, data_spec),
    )
    _sharded_cache[key] = fn
    return fn


def all_valid(results) -> jax.Array:
    """Scalar verdict — the one collective (psum-of-ands over the mesh)."""
    return jnp.all(results)


# -- the production multi-chip seam ------------------------------------

DATA_AXIS = "d"


def verify_keyed_shard(
    buf, table, key_valid, bucket: int, nblocks: int, window_bits: int
):
    """Shard-local body of the sharded keyed kernel: one device's slice
    of the batch against ITS resident table shard.  ``buf`` rows are
    the keyed packed layout (pub | sig | msg | msglen_le | key_id_le)
    with key ids REBASED to shard-local slots (``slot - d*per_cap``) by
    the host-side lane routing, so the comb gather touches only local
    HBM — zero collectives across the mesh."""
    return verify_kernel_keyed_packed(
        buf, table, key_valid, bucket, nblocks, window_bits
    )


_keyed_mesh_cache: dict[tuple, object] = {}


def _compiled_keyed_mesh(mesh: Mesh, bucket: int, window_bits: int,
                         chunk: int):
    """jit of the sharded keyed kernel over (buf, table, key_valid):
    the batch shards on its lane axis, the TABLE shards on its leading
    (slot) axis — contiguous per-device blocks of whole pages — and the
    shard-local body runs under ``shard_map`` so the comb gather stays
    local (the SPMD partitioner would otherwise all-gather the table
    per launch).  Explicit ``in_shardings``/``out_shardings`` on the
    jit wrapper keep placements canonical (the pjit pattern of
    SNIPPETS.md [2]), and ``donate_argnums=(0,)`` donates the packed
    tuple buffer — the one big per-launch operand — so XLA reuses its
    pages instead of copying.  Batch shapes retrace inside the one
    wrapper (pow2 shard widths bound the variant count, like
    _compiled_keyed); per-device slices wider than ``chunk`` process in
    lax.map slices."""
    key = (mesh, bucket, window_bits, chunk, _field.trace_config())
    fn = _keyed_mesh_cache.get(key)
    if fn is not None:
        return fn
    _jitguard.note_compile(
        "keyed_mesh",
        (tuple(mesh.shape.items()), bucket, window_bits, chunk),
    )
    nblocks = nblocks_for_bucket(bucket)

    def local(buf, table, key_valid):
        batch = buf.shape[-1]
        if batch <= chunk:
            return verify_keyed_shard(
                buf, table, key_valid, bucket, nblocks, window_bits
            )
        k = batch // chunk
        chunks = buf.reshape(buf.shape[0], k, chunk).transpose(1, 0, 2)
        out = jax.lax.map(
            lambda c: verify_keyed_shard(
                c, table, key_valid, bucket, nblocks, window_bits
            ),
            chunks,
        )
        return out.reshape(batch)

    in_specs = (
        P(None, DATA_AXIS),
        P(DATA_AXIS, None, None),
        P(DATA_AXIS),
    )
    out_spec = P(DATA_AXIS)
    body = jax.shard_map(
        local, mesh=mesh, in_specs=in_specs, out_specs=out_spec,
        check_vma=False,
    )
    # the virtual-CPU test mesh cannot donate (XLA:CPU keeps the input
    # alive) and would warn per compile; real accelerator meshes reuse
    # the donated buffer's pages instead of copying them per launch
    donate = () if mesh.devices.flat[0].platform == "cpu" else (0,)
    body.__name__ = f"verify_keyed_mesh_w{window_bits}_b{bucket}"
    fn = jax.jit(
        body,
        in_shardings=tuple(NamedSharding(mesh, s) for s in in_specs),
        out_shardings=NamedSharding(mesh, out_spec),
        donate_argnums=donate,
    )
    _keyed_mesh_cache[key] = fn
    return fn


_FLAT_MESH: Mesh | None = None


def flat_mesh(devices=None) -> Mesh:
    """1-D data mesh over all (or the given) devices — the layout the
    BatchVerifier seam shards its flat signature batch over.  The
    all-devices mesh is cached: verifiers are constructed per
    VerifyCommit, and a fresh Mesh per call would defeat the
    per-mesh table-shard placements and the keyed_mesh compile cache
    keyed on it."""
    global _FLAT_MESH
    if devices is not None:
        return Mesh(np.array(list(devices)), (DATA_AXIS,))
    if _FLAT_MESH is None:
        _FLAT_MESH = Mesh(np.array(jax.devices()), (DATA_AXIS,))
    return _FLAT_MESH


class ShardedTpuBatchVerifier(TpuBatchVerifier):
    """Multi-chip BatchVerifier: the packed (features, batch) buffer is
    sharded on the batch axis over a 1-D device mesh; the kernel is
    elementwise across lanes, so XLA partitions it with ZERO
    collectives — each chip verifies its shard and only the result
    gather touches the ICI.

    Selected by crypto/batch.py's create_batch_verifier when more than
    one device is visible, so every caller (VerifyCommit, light client,
    blocksync replay) scales across chips through the same seam the
    reference routes through crypto/batch/batch.go:10.  Per-validator
    precompute tables SHARD across the mesh with the batch lanes routed
    to their key's owning chip (see _run_keyed / the module docstring);
    each chip holds 1/ndev of the table instead of a full replica, so a
    10k-validator 4-bit pool (5 GiB) costs 640 MiB of HBM per chip
    rather than 5 GiB on every one.
    """

    def __init__(self, mesh: Mesh | None = None, **kwargs) -> None:
        super().__init__(**kwargs)
        self._mesh = mesh or flat_mesh()
        self._ndev = int(self._mesh.devices.size)

    # -- ladder eligibility (crypto/dispatch.py owns admissibility) ------

    def _mesh_capable(self) -> bool:
        """Can the sharded keyed tier run at all here?  The ladder
        consumes this as ELIGIBILITY (capability), as opposed to
        ADMISSIBILITY (health) — what used to be an in-runner silent
        fallback is now a tier the ladder simply never offers."""
        return (
            self._ndev > 1
            and not flag_from_env("CMT_TPU_DISABLE_SHARDED_KEYED")
        )

    def _keyed_tiers(self) -> list[str]:
        if self._mesh_capable():
            return ["keyed_mesh", "keyed"]
        return ["keyed"]

    def _generic_tiers(self) -> list[str]:
        if self._ndev > 1:
            return ["generic_mesh", "generic"]
        return ["generic"]

    def _run_tier(self, tier, plan):
        if tier == "keyed_mesh":
            return self._run_keyed_mesh(
                plan.entry, plan.key_ids, plan.pub, plan.sig, plan.msgs
            )
        if tier == "generic_mesh":
            return self._run_generic_mesh(plan.pub, plan.sig, plan.msgs)
        # the single-device keyed/generic rungs (tables and batch on
        # the default device) come from the base seam
        return super()._run_tier(tier, plan)

    def _pad_cols(
        self, packed: np.ndarray, chunk: int | None = None
    ) -> np.ndarray:
        """Pad the batch axis to a multiple of the device count — and,
        when the batch exceeds ``chunk`` (the lax.map slice width), to
        a multiple of the chunk itself: a non-pow2 device count makes
        chunk a non-pow2 number that the pow2-padded batch does not
        divide."""
        b = packed.shape[-1]
        mult = self._ndev
        if chunk is not None and b > chunk:
            mult = chunk
        if b % mult:
            packed = np.pad(packed, [(0, 0), (0, mult - b % mult)])
        return packed

    def _sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self._mesh, P(*spec))

    def _run_generic_mesh(self, pub, sig, msgs) -> np.ndarray:
        from cometbft_tpu.ops.ed25519_verify import (
            MAX_LAUNCH,
            _compiled,
            _compiled_chunked,
            pack_inputs,
        )

        n = len(msgs)
        # per-device slices must respect the same >MAX_LAUNCH working-
        # set cliff the single-device paths chunk for
        chunk = MAX_LAUNCH * self._ndev
        with _tracer.span("verify/pack", cat="device", batch=n):
            packed, bucket = pack_inputs(pub, sig, msgs)
            packed = self._pad_cols(packed, chunk=chunk)
        batch = packed.shape[-1]
        if batch > chunk:
            fn = _compiled_chunked(batch, bucket, chunk)
        else:
            fn = _compiled(batch, bucket)
        with _tracer.span(
            "device_launch", cat="device", kernel="generic_mesh",
            batch=batch, bucket=bucket, ndev=self._ndev,
        ):
            out = fn(
                jax.device_put(packed, self._sharding(None, DATA_AXIS))
            )
        with _tracer.span(
            "device_fetch", cat="device", thread_clock=True, batch=n,
        ):
            res = jax.device_get(out)  # host sync: single per-batch result gather off the mesh
        return res[:n]

    def _run_keyed_mesh(self, entry, key_ids, pub, sig, msgs) -> np.ndarray:
        from cometbft_tpu.ops.ed25519_verify import (
            MAX_LAUNCH,
            pack_inputs,
        )

        ndev = self._ndev
        # per-chip shards of the table (and validity mask), resident
        # under a NamedSharding; built once per (entry, mesh)
        table, valid, per_cap = entry.sharded_tables(
            self._mesh,
            self._sharding(DATA_AXIS, None, None),
            self._sharding(DATA_AXIS),
            ndev,
        )
        # route each lane to the device whose shard owns its key slot
        # (STRIDED ownership, slot % ndev — matching the page
        # permutation sharded_tables applied, and balanced even though
        # live slots cluster at the low end of the pool), rebasing ids
        # to shard-local slots; every device gets the same lane count W
        # (pow2 of the fullest shard, padded lanes are discarded on
        # unscatter) so the sharded batch stays rectangular
        n = len(msgs)
        with _tracer.span("verify/pack", cat="device", batch=n):
            owner = key_ids % ndev
            local_ids = (key_ids // ndev).astype(np.int32)
            counts = np.bincount(owner, minlength=ndev)
            w = _next_pow2(int(counts.max()))
            chunk = MAX_LAUNCH
            if w > chunk and w % chunk:
                w += chunk - w % chunk
            order = np.argsort(owner, kind="stable")
            offs = np.concatenate([[0], np.cumsum(counts)])[:-1]
            dest = np.empty(n, dtype=np.int64)
            dest[order] = owner[order] * w + (
                np.arange(n) - offs[owner[order]]
            )
            batch = ndev * w
            pub_r = np.zeros((batch, 32), dtype=np.uint8)
            sig_r = np.zeros((batch, 64), dtype=np.uint8)
            ids_r = np.zeros(batch, dtype=np.int32)
            msgs_r = [b""] * batch
            pub_r[dest] = pub
            sig_r[dest] = sig
            ids_r[dest] = local_ids
            for i, d in enumerate(dest):
                msgs_r[d] = msgs[i]
            packed, bucket = pack_inputs(pub_r, sig_r, msgs_r, key_ids=ids_r)
            # pack_inputs pow2-pads past ndev*w on non-pow2 meshes; the
            # shard boundaries live at multiples of w, so slice back
            packed = packed[:, :batch]
        fn = _compiled_keyed_mesh(
            self._mesh, bucket, entry.window_bits, chunk
        )
        cm = _crypto_metrics()
        cm.batch_verify_launches.labels(kernel="keyed_mesh").inc()
        cm.bytes_transferred.labels(direction="h2d").inc(packed.nbytes)
        with _tracer.span(
            "device_launch", cat="device", kernel="keyed_mesh",
            batch=batch, bucket=bucket, ndev=ndev,
            window_bits=entry.window_bits,
        ):
            out = fn(
                jax.device_put(packed, self._sharding(None, DATA_AXIS)),
                table,
                valid,
            )
        with _tracer.span(
            "device_fetch", cat="device", thread_clock=True, batch=n,
        ):
            res = jax.device_get(out)  # host sync: single per-batch result gather off the mesh
        cm.bytes_transferred.labels(direction="d2h").inc(res.nbytes)
        return res[dest]  # unscatter to original lane order


#: shape/dtype contract for the sharded keyed kernel body (grammar:
#: ops/contracts.py; statically checked by tools/jitcheck.py, swept by
#: the mesh-shape eval_shape matrix in tests/test_jitcheck.py).  Dims
#: are SHARD-LOCAL: the global batch B and pool capacity ``cap`` (both
#: padded to device-count multiples by the lane router / table
#: placement) divide by the mesh size ``ndev``.
_CONTRACTS = {
    "verify_keyed_shard": {
        "args": {
            "buf": ("u8", ("104+bucket", "B//ndev")),
            "table": ("i32", ("cap//ndev", "nwin*nent", "ROW")),
            "key_valid": ("bool", ("cap//ndev",)),
        },
        "static": ("bucket", "nblocks", "window_bits"),
        "out": ("bool", ("B//ndev",)),
    },
}

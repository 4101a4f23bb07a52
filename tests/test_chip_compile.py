"""The main path's device programs, compiled at their REAL shapes by
the TPU v5e compiler — for a chip that is described, not attached.

Nothing runs, so nothing here is a time or a result: a passing compile
says the chip's compiler accepts the program and that it fits one
chip's memory (on-chip-measurement guide §2.3).  Three programs are
kept — the two keyed-verify programs ``chip_smoke.py`` spends its time
in, and the four-chip ``keyed_mesh`` program — each a cold compile of
about half a minute, so the file stays on one worker for a few minutes.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file.
"""

from __future__ import annotations

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cometbft_tpu.ops import field as F

BUCKET = 128  # canonical precommit sign-bytes are ~115 bytes
V5E_HBM_BYTES = 16 << 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without the chip; keep these out."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def keyed_shapes(lanes: int, slots: int, window_bits: int, sharding=None):
    """(packed batch, key table, validity mask) of the keyed kernel."""
    nwin, nent = 256 // window_bits, 1 << window_bits

    def sds(shape, dtype, s):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    s = sharding or (None, None, None)
    return (
        sds((104 + BUCKET, lanes), jnp.uint8, s[0]),
        sds((nwin, 4, F.NLIMBS, slots * nent), jnp.int32, s[1]),
        sds((slots,), jnp.bool_, s[2]),
    )


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
    )


@pytest.mark.parametrize(
    "lanes,slots,window_bits",
    [
        pytest.param(256, 256, 8, id="keyed-8bit-150val"),
        pytest.param(1024, 1024, 4, id="keyed-4bit-1000val"),
    ],
)
def test_keyed_verify_compiles_for_one_v5e(
    topo, no_persistent_cache, lanes, slots, window_bits
):
    from cometbft_tpu.ops.ed25519_verify import MAX_LAUNCH, _compiled_keyed

    one_chip = SingleDeviceSharding(topo.devices[0])
    fn = _compiled_keyed(BUCKET, window_bits, MAX_LAUNCH)
    compiled = fn.lower(
        *keyed_shapes(lanes, slots, window_bits, (one_chip,) * 3)
    ).compile()
    assert device_bytes(compiled) < V5E_HBM_BYTES


def test_keyed_mesh_compiles_for_four_v5e(topo, no_persistent_cache):
    from cometbft_tpu.ops.ed25519_verify import MAX_LAUNCH
    from cometbft_tpu.parallel.mesh import DATA_AXIS, _compiled_keyed_mesh

    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    shardings = tuple(
        NamedSharding(mesh, spec)
        for spec in (
            P(None, DATA_AXIS), P(None, None, None, DATA_AXIS), P(DATA_AXIS)
        )
    )
    fn = _compiled_keyed_mesh(mesh, BUCKET, 4, MAX_LAUNCH)
    compiled = fn.lower(*keyed_shapes(1024, 1024, 4, shardings)).compile()
    # per device: a quarter of the table and of the lanes
    assert device_bytes(compiled) < V5E_HBM_BYTES
    # the table never crosses chips: the shard-local gather is local
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text

"""The main path's device programs, compiled at their REAL shapes by
the TPU v5e compiler — for a chip that is described, not attached.

Nothing runs, so nothing here is a time or a result: a passing compile
says the chip's compiler accepts the program and that it fits one
chip's memory (on-chip-measurement guide §2.3).  Three programs are
kept — the two keyed-verify programs ``chip_smoke.py`` spends its time
in, and the four-chip ``keyed_mesh`` program — each a cold compile of
about half a minute, so the file stays on one worker for a few minutes.
A fourth test holds the 10,000-validator commit's shapes (ISSUEs 33, 34):
the 10,240-lane launch in slices of ``WIDE_SLICE`` over the 10,240-slot
table, and one chunk's write into that pool.

The topology is described inside a module-scoped fixture, never at
import: only one process may load the TPU library, and every xdist
worker imports every test file.
"""

from __future__ import annotations

import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from cometbft_tpu.ops import precompute as PR

BUCKET = 128  # canonical precommit sign-bytes are ~115 bytes
V5E_HBM_BYTES = 16 << 30
#: scratch a keyed launch may hold beside its arguments and its comb's
#: block of gathered rows; the window-major table's per-window relayout
#: alone was 135 MB at 256 slots x 8 bits
KEYED_TEMP_BYTES = 64 << 20


def keyed_temp_bound(lanes: int, window_bits: int) -> int:
    """KEYED_TEMP_BYTES and the block of table rows the keyed comb
    gathers for ``lanes`` lanes (one slice's, in a wide program) ahead
    of its scan: ``nwin x lanes x ROW`` int32."""
    return KEYED_TEMP_BYTES + (256 // window_bits) * lanes * PR.ROW * 4


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

    def sds(shape, dtype, s):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=s)

    s = sharding or (None, None, None)
    return (
        sds((104 + BUCKET, lanes), jnp.uint8, s[0]),
        sds((slots, PR.slot_rows(window_bits), PR.ROW), jnp.int32, s[1]),
        sds((slots,), jnp.bool_, s[2]),
    )


def device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes + m.generated_code_size_in_bytes
    )


_MOVER = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*?) (copy|dynamic-slice|fusion)\("
)
_ARRAY = re.compile(r"\b[a-z]+\d+\[([\d,]+)\]")


def lane_block_elems(lanes: int, window_bits: int) -> frozenset[int]:
    """Element counts of the block of rows the keyed comb gathers for
    ``lanes`` lanes, whole rows or cut to an entry's limbs: it grows
    with the lanes, never with the table."""
    rows = (256 // window_bits) * lanes
    return frozenset({rows * PR.ROW, rows * PR.ENTRY_LIMBS})


def table_sized_movers(
    hlo_text: str, min_elems: int, lane_block: frozenset[int] = frozenset()
) -> list[str]:
    """The ``copy``, ``dynamic-slice`` and fusion instructions of a
    compiled program whose output holds ``min_elems`` elements or more
    — what a relayout or a slice of the key table shows up as — other
    than the comb's gathered block of rows (``lane_block``)."""
    found = []
    for line in hlo_text.splitlines():
        m = _MOVER.match(line)
        if m is None:
            continue
        name, out_type, op = m.groups()
        for dims in _ARRAY.findall(out_type):
            elems = math.prod(int(d) for d in dims.split(","))
            if elems >= min_elems and elems not in lane_block:
                found.append(f"{op} {name} [{dims}]")
    return found


def test_table_sized_movers_finds_the_window_major_relayout():
    """The reader has teeth: three lines of the 8-bit program as the
    v5e compiler wrote it over the window-major table, and one of
    today's — a slice, its relayout, the fusion that feeds it; a row
    gather's output is none of them."""
    window = 256 * 256 * PR.ENTRY_LIMBS
    text = """
  ROOT %dynamic_slice.97 = s32[1,4,26,65536]{3,1,2,0:T(4,128)S(1)} dynamic-slice(%param_0.30659, %param_1.40611), dynamic_slice_sizes={1,4,26,65536}
  %copy.3562 = s32[1,4,26,65536]{2,1,3,0:T(4,128)} copy(%constant_dynamic-slice_fusion.14), metadata={op_name="while/body/dynamic_slice"}
  %constant_dynamic-slice_fusion.14 = s32[1,4,26,65536]{3,1,2,0:T(4,128)S(1)} fusion(%get-tuple-element.7761, %convert.1604), kind=kLoop
  %gather.8 = s32[256,128]{1,0:T(8,128)} gather(%param_0.1948, %transpose.26), offset_dims={1}, slice_sizes={1,128}
  %fusion.156 = (s32[104,256]{0,1:T(8,128)S(1)}, s32[26,256]{0,1}) fusion(%fusion.155), kind=kLoop
"""
    assert table_sized_movers(text, window) == [
        "dynamic-slice dynamic_slice.97 [1,4,26,65536]",
        "copy copy.3562 [1,4,26,65536]",
        "fusion constant_dynamic-slice_fusion.14 [1,4,26,65536]",
    ]


def test_table_sized_movers_passes_over_the_lane_block_only():
    """At 1,024 lanes over the 1,024-slot 4-bit pool the comb's block of
    rows (64 x 1,024 of them, as the v5e compiler wrote its gather) is
    larger than one window of the table, and is passed over; a copy of
    one window, or of the table, is still found."""
    window = 1024 * 16 * PR.ENTRY_LIMBS
    text = """
  %fusion.5 = s32[65536,128]{1,0:T(8,128)S(1)} fusion(%bitcast, %broadcast_clamp_fusion.1), kind=kCustom, calls=%fused_computation.5
  %copy.7 = s32[16384,128]{1,0:T(8,128)} copy(%param_1.2)
  %copy.8 = s32[1024,1024,128]{2,1,0:T(8,128)} copy(%param_1.3)
"""
    block = lane_block_elems(1024, 4)
    assert 65536 * 128 in block
    assert table_sized_movers(text, window, block) == [
        "copy copy.7 [16384,128]",
        "copy copy.8 [1024,1024,128]",
    ]
    assert len(table_sized_movers(text, window)) == 3


@pytest.mark.parametrize(
    "lanes,slots,window_bits",
    [
        pytest.param(256, 256, 8, id="keyed-8bit-150val"),
        pytest.param(1024, 1024, 4, id="keyed-4bit-1000val"),
        # the light lane's batch: 1,024 signatures over a 150-key set
        pytest.param(1024, 256, 8, id="keyed-8bit-150val-light-lane"),
    ],
)
def test_keyed_verify_compiles_for_one_v5e(
    topo, no_persistent_cache, lanes, slots, window_bits
):
    from cometbft_tpu.ops.ed25519_verify import _compiled_keyed, launch_lanes

    one_chip = SingleDeviceSharding(topo.devices[0])
    assert launch_lanes(lanes) == (lanes, 1)  # one straight program
    fn = _compiled_keyed(BUCKET, window_bits, 1)
    compiled = fn.lower(
        *keyed_shapes(lanes, slots, window_bits, (one_chip,) * 3)
    ).compile()
    assert device_bytes(compiled) < V5E_HBM_BYTES
    # the launch reads rows where they lie: nothing as large as one
    # window's entries of the table is copied, sliced or fused out
    window = slots * (1 << window_bits) * PR.ENTRY_LIMBS
    block = lane_block_elems(lanes, window_bits)
    assert table_sized_movers(compiled.as_text(), window, block) == []
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < keyed_temp_bound(lanes, window_bits)


def test_mega_commit_shapes_fit_one_v5e(topo, no_persistent_cache):
    """10,000 signatures pad to 10,240 lanes in five slices of
    ``WIDE_SLICE`` over the 5 GiB table, which stays an argument (no
    copy of it, no slice); and a chunk of 1,024 built pages written
    into that pool is the pool twice and the chunk, with no scratch."""
    from cometbft_tpu.ops.ed25519_verify import (
        WIDE_SLICE, _compiled_keyed, launch_lanes,
    )

    one_chip = SingleDeviceSharding(topo.devices[0])
    (lanes, slices), slots, window_bits = (
        launch_lanes(10_000), PR._pool_cap(10_000), 4
    )
    assert (lanes, slices, slots) == (10_240, 5, 10_240)
    assert lanes == slices * WIDE_SLICE
    pool_bytes = slots * PR.slot_rows(window_bits) * PR.ROW * 4
    assert pool_bytes == 5 << 30
    fn = _compiled_keyed(BUCKET, window_bits, slices)
    compiled = fn.lower(
        *keyed_shapes(lanes, slots, window_bits, (one_chip,) * 3)
    ).compile()
    assert device_bytes(compiled) < V5E_HBM_BYTES
    window = slots * (1 << window_bits) * PR.ENTRY_LIMBS
    block = lane_block_elems(WIDE_SLICE, window_bits)
    assert table_sized_movers(compiled.as_text(), window, block) == []
    # one slice's working set (its arithmetic and its 64 MB block of
    # rows at 2,048 lanes), not the launch's and not the table's: the
    # bound of a straight program of one slice's lanes holds
    temp = compiled.memory_analysis().temp_size_in_bytes
    assert temp < keyed_temp_bound(WIDE_SLICE, window_bits)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    page = (PR.slot_rows(window_bits), PR.ROW)
    place = jax.jit(lambda table, at, pages: table.at[at].set(pages))
    m = place.lower(
        sds((slots,) + page, jnp.int32), sds((PR.BUILD_CHUNK,), jnp.int32),
        sds((PR.BUILD_CHUNK,) + page, jnp.int32),
    ).compile().memory_analysis()
    assert m.temp_size_in_bytes == 0
    assert m.output_size_in_bytes == pool_bytes
    assert (m.argument_size_in_bytes + m.output_size_in_bytes
            < V5E_HBM_BYTES - (2 << 30))  # room for the next build


def test_keyed_mesh_compiles_for_four_v5e(topo, no_persistent_cache):
    from cometbft_tpu.ops.ed25519_verify import MAX_LAUNCH
    from cometbft_tpu.parallel.mesh import DATA_AXIS, _compiled_keyed_mesh

    assert len(topo.devices) == 4
    mesh = Mesh(np.array(topo.devices), (DATA_AXIS,))
    shardings = tuple(
        NamedSharding(mesh, spec)
        for spec in (
            P(None, DATA_AXIS), P(DATA_AXIS, None, None), P(DATA_AXIS)
        )
    )
    fn = _compiled_keyed_mesh(mesh, BUCKET, 4, MAX_LAUNCH)
    compiled = fn.lower(*keyed_shapes(1024, 1024, 4, shardings)).compile()
    # per device: a quarter of the table and of the lanes
    assert device_bytes(compiled) < V5E_HBM_BYTES
    # the table never crosses chips: the shard-local gather is local
    text = compiled.as_text()
    assert "all-gather" not in text and "all-reduce" not in text

"""GF(2^255-19) arithmetic on 26x10-bit limbs of int32 — the kernel's
number system.

Design notes (TPU-first):
- **Limbs-first layout**: a field element is an int32 array of shape
  (26, *batch) — the small limb axis leads and the batch axis is LAST,
  so the batch dimension maps onto the TPU's 128-wide vector lanes.
  (Batch-last limbs would put the 26-limb axis in the lane dimension,
  padding every tile to 128 lanes — 20% utilization; round-3 profiling
  measured the full kernel at ~3% of VPU peak in that layout, and large
  batches miscompiled on the device backend. Limbs-first fixed both.)
- TPU VPUs are 32-bit machines: int64 is emulated (pairs of i32 with
  synthesized wide multiplies) at ~6.6x the cost of native i32 ops for
  this workload, so limbs are int32.
- Radix 10 is chosen so that (a) schoolbook product columns — up to 26
  products of two 13-bit limbs — stay under 2^31, and (b) the modular
  wrap factor is SMALL: capacity is 26*10 = 260 bits and 2^260 ≡ 608
  (mod p), so a carry-relaxation pass can multiply a full-size carry by
  the wrap without overflowing i32.
- add/sub are single vector adds with NO carry work. Budget: **mul
  inputs may carry at most 2 chained add/subs** (limbs grow 2^11 ->
  2^13; 26·2^13·2^13 = 2^30.7 < 2^31). The curve formulas
  (ops/curve.py) never chain more than 2.
- Carry resolution is *vectorized relaxation*: every limb releases its
  carry simultaneously; carries shift up one limb per iteration, the
  top carry folding into limb 0 as x608. mul's high columns are first
  relaxed as their own 27-limb block (2 passes, shift-only), folded
  x608 (block overflow limb x608^2), then 4 low passes leave limbs
  < 2^11.

Lazy limbs may be signed; all shifts are arithmetic (floor division).
The semantic ground truth is cometbft_tpu.crypto.edwards (pure-Python
big-int oracle); tests differential-fuzz every op against it
(tests/test_ops_field.py).
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from cometbft_tpu.crypto.edwards import P

NLIMBS = 26
LIMB_BITS = 10
MASK = (1 << LIMB_BITS) - 1
CAPACITY = NLIMBS * LIMB_BITS  # 260

DTYPE = jnp.int32

# 2^260 = 2^5 * 2^255 ≡ 32 * 19 = 608 (mod p); carries out of limb 25
# re-enter at limb 0 with this weight.
WRAP = (1 << (CAPACITY - 255)) * 19  # 608
assert pow(2, CAPACITY, P) == WRAP

_WRAP_VEC = np.ones(NLIMBS, dtype=np.int32)
_WRAP_VEC[0] = WRAP


# -- host-side conversions (tests, table generation) -------------------

def from_int(x: int) -> np.ndarray:
    """Python int -> (26,) limb array (host helper)."""
    if x < 0 or x >= 1 << 256:
        raise ValueError("field element out of range")
    return np.array(
        [(x >> (LIMB_BITS * i)) & MASK for i in range(NLIMBS)], dtype=np.int32
    )


def to_int(limbs) -> int:
    """(26, ...) limb array -> python int of lane 0 if batched, or of
    the single element (host helper; accepts lazy/signed limbs)."""
    arr = np.asarray(limbs, dtype=np.int64)  # host sync: host helper for tests/table generation, never on the verify path
    return sum(int(arr[i]) << (LIMB_BITS * i) for i in range(NLIMBS))


def batch_from_ints(xs: list[int]) -> np.ndarray:
    """ints -> (26, n) limbs-first batch."""
    return np.stack([from_int(x) for x in xs], axis=-1)


P_LIMBS = from_int(P)
ZERO = from_int(0)
ONE = from_int(1)


def cvec(c: np.ndarray, ndim: int):
    """Broadcast a host (26,)-constant against a (26, *batch) element:
    numpy/jnp broadcasting aligns trailing axes, so leading-limb layout
    needs the constant reshaped to (26, 1, ..., 1)."""
    return jnp.asarray(c).reshape((c.shape[0],) + (1,) * (ndim - 1))


def _shift_up(carry):
    """Row j of the result is carry[j-1]; row 0 is zero (no wrap)."""
    pad = [(1, 0)] + [(0, 0)] * (carry.ndim - 1)
    return jnp.pad(carry, pad)[: carry.shape[0]]


# -- carry machinery ---------------------------------------------------

def relax(c, iters: int = 4):
    """Vectorized carry relaxation: all limbs release their carry at
    once; carries travel one limb per iteration, the top carry folding
    into limb 0 as x608. Signed-safe (arithmetic shift = floor div).

    Because WRAP < 2^10, the fold never overflows: a first-pass carry
    is < 2^21 and 608 * 2^21 < 2^31. Four passes take mul columns
    (< 2^31) down to limbs < 2^11.
    """
    w = cvec(_WRAP_VEC, c.ndim)
    for _ in range(iters):
        carry = c >> LIMB_BITS
        lo = c - (carry << LIMB_BITS)
        c = lo + jnp.roll(carry, 1, axis=0) * w
    return c


def add(a, b):
    """Lazy add: no carries (grows the limb bound by one bit)."""
    return a + b


def sub(a, b):
    """Lazy subtract: no carries (limbs may go negative)."""
    return a - b


def neg(a):
    return -a


import os as _os

from cometbft_tpu.utils.env import choice_from_env, flag_from_env

#: column-formation strategy; the full verify kernel is HBM-bound, so
#: the winner is whichever materializes fewest bytes inside XLA's big
#: fused graphs — measured end-to-end (tools/bench_kernel_ab.py), not
#: in isolated loops (where all variants fuse perfectly).
COLS_IMPL = choice_from_env(
    "CMT_TPU_COLS_IMPL", "stack", ("stack", "stack16", "tree", "pallas")
)
SQUARE_IMPL = choice_from_env("CMT_TPU_SQUARE_IMPL", "fast", ("fast", "mul"))
#: debug-mode runtime guards (host callbacks; never on in production)
_DEBUG_CHECKS = flag_from_env("CMT_TPU_DEBUG_CHECKS")


def trace_config() -> tuple:
    """The module globals that shape the TRACED program (column
    strategy, square strategy, the debug-check insertion).  The
    ``_compiled*`` memoizers (ops/ed25519_verify, ops/precompute,
    parallel/mesh) fold this tuple into their cache keys: flipping any
    of these flags mid-process then used to silently serve the STALE
    compiled program (the memoizer key was shape-only); now it is a
    counted — and, under CMT_TPU_JITGUARD after seal(), loudly raised
    — recompile instead.  Debug builds therefore cannot silently run
    without their checks, and A/B flips (bench.py stack16 section)
    cannot silently run the old core."""
    return (COLS_IMPL, SQUARE_IMPL, _DEBUG_CHECKS)


#: latched copy of a debug-guard failure: on asynchronously-dispatched
#: backends the OverflowError raised inside the callback surfaces as a
#: generic XlaRuntimeError at sync time — ``consume_debug_failures()``
#: recovers the real report (bounded: newest _MAX_DEBUG_FAILURES kept)
_debug_failures: list[str] = []
_MAX_DEBUG_FAILURES = 8


def consume_debug_failures() -> list[str]:
    """Drain the latched CMT_TPU_DEBUG_CHECKS guard reports.  Call
    after a sync that raised a generic XlaRuntimeError to recover the
    real limb-overflow message(s) the async dispatch swallowed."""
    out = _debug_failures[:]
    _debug_failures.clear()
    return out


def _limb_magnitude_check(maxabs) -> None:
    """Host-side guard behind CMT_TPU_DEBUG_CHECKS: stack16 narrows
    limbs to int16, valid only under the documented 2^13 magnitude
    budget — fail loudly instead of wrapping to wrong arithmetic.

    Runs as a ``jax.debug.callback`` so it is jit-safe (traceable
    inside the compiled kernel, including under lax.scan/fori_loop
    bodies); the raise propagates synchronously on the CPU backend and
    is latched into ``_debug_failures`` for backends where dispatch is
    async and the exception would otherwise be swallowed into a
    generic runtime error."""
    if int(maxabs) >= 1 << 15:
        msg = (
            f"stack16 limb overflow: max |limb| = {int(maxabs)} >= 2^15; "
            "an operand exceeded the 2-chained-add budget (field.py "
            "module docstring)"
        )
        while len(_debug_failures) >= _MAX_DEBUG_FAILURES:
            _debug_failures.pop(0)
        _debug_failures.append(msg)
        raise OverflowError(msg)


def _tree_sum(terms):
    while len(terms) > 1:
        nxt = [
            terms[k] + terms[k + 1] for k in range(0, len(terms) - 1, 2)
        ]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


def _columns_stack(a, b, stack_dtype=DTYPE):
    """Stack 26 shifted (51, *batch) views of b, multiply, reduce: one
    concatenate materialized, mul+sum fuse into the reduce.

    ``stack_dtype=int16`` (CMT_TPU_COLS_IMPL=stack16): the kernel is
    HBM-bound on this materialized stack (docs/device_kernel_perf.md
    §1), and mul's operand budget bounds limbs by 2^13 in magnitude —
    they fit int16, halving the stack's bytes.  The widening convert
    fuses into the multiply-reduce, so HBM sees half the traffic while
    all arithmetic stays int32.  A caller exceeding the documented
    budget would silently wrap to WRONG field arithmetic;
    CMT_TPU_DEBUG_CHECKS=1 turns the cast into a loud failure."""
    if stack_dtype != DTYPE and _DEBUG_CHECKS:
        # debug builds insert this callback into the traced program —
        # visible (not silent) because trace_config() is part of every
        # compile-cache key
        jax.debug.callback(_limb_magnitude_check, jnp.max(jnp.abs(b)))  # host sync: debug-only limb-magnitude guard (CMT_TPU_DEBUG_CHECKS)
    pad = [(NLIMBS - 1, NLIMBS - 1)] + [(0, 0)] * (b.ndim - 1)
    bp = jnp.pad(b.astype(stack_dtype), pad)  # (76, *batch)
    s = jnp.stack(
        [
            bp[NLIMBS - 1 - i : NLIMBS - 1 - i + 2 * NLIMBS - 1]
            for i in range(NLIMBS)
        ]
    )  # (26, 51, *batch); s[i, j] = b[j - i]
    return (a[:, None] * s.astype(DTYPE)).sum(axis=0, dtype=DTYPE)


def _columns_tree(a, b):
    """Balanced tree-sum of 26 row-shifted elementwise products — no
    (26, 51, batch) stack; computes only the 676 nonzero products."""
    spatial = [(0, 0)] * (b.ndim - 1)
    terms = [
        jnp.pad(a[i] * b, [(i, NLIMBS - 1 - i)] + spatial)
        for i in range(NLIMBS)
    ]
    return _tree_sum(terms)


def _columns(a, b):
    if COLS_IMPL == "tree":
        return _columns_tree(a, b)
    if COLS_IMPL == "stack16":
        return _columns_stack(a, b, stack_dtype=jnp.int16)
    return _columns_stack(a, b)


# -- pallas fused core (CMT_TPU_COLS_IMPL=pallas) ----------------------
#
# The measured wall for the XLA core is HBM traffic on materialized
# intermediates (docs/device_kernel_perf.md §1): each mul streams the
# (26, 51, B) column stack through HBM.  The pallas kernel fuses
# columns -> high fold -> relax into ONE program whose intermediates
# are plain vectors in VMEM/registers; HBM sees only the two operands
# and the result.  Formulation: limbs live as PYTHON LISTS of (T,)
# row vectors, so every "shift" in the carry machinery is list index
# arithmetic — no pad/roll/stack ops for the TPU dialect to choke on.

def _vec_tree_sum(terms):
    while len(terms) > 1:
        nxt = [terms[k] + terms[k + 1] for k in range(0, len(terms) - 1, 2)]
        if len(terms) % 2:
            nxt.append(terms[-1])
        terms = nxt
    return terms[0]


def _fold_high_rows(cols):
    """51 column rows -> 26 lazy rows (row-list _fold_high)."""
    zero = cols[0] - cols[0]
    low = cols[:NLIMBS]
    high = cols[NLIMBS:] + [zero, zero]  # 27 rows
    for _ in range(2):
        carry = [h >> LIMB_BITS for h in high]
        lo = [h - (c << LIMB_BITS) for h, c in zip(high, carry)]
        high = [lo[0]] + [
            lo[j] + carry[j - 1] for j in range(1, len(high))
        ]
    low = [low[i] + high[i] * WRAP for i in range(NLIMBS)]
    low[0] = low[0] + high[NLIMBS] * (WRAP * WRAP)
    return low


def _relax_rows(rows, iters: int = 4):
    for _ in range(iters):
        carry = [r >> LIMB_BITS for r in rows]
        lo = [r - (c << LIMB_BITS) for r, c in zip(rows, carry)]
        rows = [lo[0] + carry[NLIMBS - 1] * WRAP] + [
            lo[j] + carry[j - 1] for j in range(1, NLIMBS)
        ]
    return rows


def _mul_rows(a, b):
    cols = []
    for j in range(2 * NLIMBS - 1):
        lo_i = max(0, j - (NLIMBS - 1))
        hi_i = min(NLIMBS - 1, j)
        cols.append(
            _vec_tree_sum([a[i] * b[j - i] for i in range(lo_i, hi_i + 1)])
        )
    return _relax_rows(_fold_high_rows(cols))


def _square_rows(a):
    d = [x + x for x in a]
    cols = []
    for j in range(2 * NLIMBS - 1):
        terms = []
        if j % 2 == 0:
            terms.append(a[j // 2] * a[j // 2])
        for i in range(max(0, j - (NLIMBS - 1)), (j + 1) // 2):
            terms.append(d[i] * a[j - i])
        cols.append(_vec_tree_sum(terms))
    return _relax_rows(_fold_high_rows(cols))


_PALLAS_INTERPRET = flag_from_env("CMT_TPU_PALLAS_INTERPRET")


def _pallas_elementwise(rows_fn, nin: int):
    """Build a pallas-fused (26, *batch) field op from a row-list
    implementation.  The batch is flattened and tiled at the largest
    divisor from the ladder; tile=1 always divides, so every shape is
    accepted (tiny tiles are slow but correct — production batches are
    pow2 and land on 512)."""
    from jax.experimental import pallas as pl

    def run(*ops):
        shape = ops[0].shape
        flat = int(np.prod(shape[1:])) if len(shape) > 1 else 1
        tile = 1
        for t in (512, 256, 128, 64, 32, 16, 8):
            if flat % t == 0:
                tile = t
                break
        a2 = [o.reshape(NLIMBS, flat) for o in ops]

        def kernel(*refs):
            ins = refs[:nin]
            o_ref = refs[nin]
            rows_in = [
                [r[i, :] for i in range(NLIMBS)] for r in ins
            ]
            out = rows_fn(*rows_in)
            for i in range(NLIMBS):
                o_ref[i, :] = out[i]

        out = pl.pallas_call(
            kernel,
            grid=(flat // tile,),
            in_specs=[
                pl.BlockSpec((NLIMBS, tile), lambda i: (0, i))
                for _ in range(nin)
            ],
            out_specs=pl.BlockSpec((NLIMBS, tile), lambda i: (0, i)),
            out_shape=jax.ShapeDtypeStruct((NLIMBS, flat), DTYPE),
            interpret=_PALLAS_INTERPRET,
        )(*a2)
        return out.reshape(shape)

    return run


_mul_pallas = None
_square_pallas = None


def _get_mul_pallas():
    global _mul_pallas
    if _mul_pallas is None:
        _mul_pallas = _pallas_elementwise(_mul_rows, 2)
    return _mul_pallas


def _get_square_pallas():
    global _square_pallas
    if _square_pallas is None:
        _square_pallas = _pallas_elementwise(_square_rows, 1)
    return _square_pallas


def _fold_high(cols):
    """51 columns -> 26 lazy limbs: relax the 25 high columns as their
    own block (2 shift-only passes; the padded rows absorb the shifted
    carries), then fold x608 (x608^2 for the block's overflow row)."""
    ndim = cols.ndim
    low = cols[:NLIMBS]
    high = jnp.pad(
        cols[NLIMBS:], [(0, 2)] + [(0, 0)] * (ndim - 1)
    )  # (27, *batch); row j has weight 2^(260 + 10j)
    for _ in range(2):
        carry = high >> LIMB_BITS
        high = (high - (carry << LIMB_BITS)) + _shift_up(carry)
    low = low + high[:NLIMBS] * jnp.int32(WRAP)
    # row 26 has weight 2^(260+260) ≡ 608^2
    tail = high[NLIMBS : NLIMBS + 1] * jnp.int32(WRAP * WRAP)
    return low + jnp.pad(tail, [(0, NLIMBS - 1)] + [(0, 0)] * (ndim - 1))


def mul(a, b):
    """Field multiply: shifted-stack columns -> high fold -> 4
    relaxation passes. Budget: 26 * max|a_i| * max|b_j| < 2^31, i.e.
    each operand may be a mul output (< 2^11) plus up to 2 lazy
    add/subs. Output limbs < 2^11."""
    if COLS_IMPL == "pallas":
        shape = jnp.broadcast_shapes(a.shape, b.shape)
        return _get_mul_pallas()(
            jnp.broadcast_to(a, shape), jnp.broadcast_to(b, shape)
        )
    return relax(_fold_high(_columns(a, b)))


def _square_columns(a):
    """Columns of a*a using the symmetry cols[j] =
    2*sum_{2i<j} a[i]*a[j-i] + (j even) a[j/2]^2 — 351 products instead
    of 676.  Bound: 27 * max|a|^2 (13 doubled cross terms + diagonal),
    so the same operand budget as mul (< 2^13 limbs) stays < 2^31."""
    spatial = [(0, 0)] * (a.ndim - 1)
    d = a + a
    sq = a * a
    # diagonal a[i]^2 lands at even row 2i: interleave with zeros.
    diag = jnp.stack([sq, jnp.zeros_like(sq)], axis=1).reshape(
        2 * NLIMBS, *a.shape[1:]
    )[: 2 * NLIMBS - 1]
    terms = [diag]
    for i in range(NLIMBS - 1):
        # 2*a[i] * a[i+1:] occupies rows 2i+1 .. i+25
        prod = d[i] * a[i + 1 :]
        terms.append(jnp.pad(prod, [(2 * i + 1, NLIMBS - 1 - i)] + spatial))
    return _tree_sum(terms)


def square(a):
    """Field square — dedicated half-product column form (or plain
    mul(a, a) when CMT_TPU_SQUARE_IMPL=mul)."""
    if COLS_IMPL == "pallas" and SQUARE_IMPL != "mul":
        return _get_square_pallas()(a)
    if SQUARE_IMPL == "mul":
        return mul(a, a)
    return relax(_fold_high(_square_columns(a)))


def mul_small(a, k: int):
    """Multiply by a small host constant; lazy (adds log2(k) bits to
    the limb bound — callers budget accordingly)."""
    return a * k


# -- canonical form, comparisons ---------------------------------------

def _propagate_seq(c):
    """Exact sequential carry pass (canonical boundaries only): limbs to
    [0, 2^10), returning (limbs, signed_carry_out) with weight 2^260."""
    out = []
    carry = jnp.zeros_like(c[0])
    for i in range(NLIMBS):
        t = c[i] + carry
        out.append(t & MASK)
        carry = t >> LIMB_BITS
    return jnp.stack(out, axis=0), carry


def _narrow(a):
    """Lazy limbs -> limbs in [0, 2^10) with the value in [0, 2p)."""
    limbs, carry = _propagate_seq(relax(a, iters=2))
    limbs = limbs.at[0].add(WRAP * carry)
    limbs, carry = _propagate_seq(limbs)
    limbs = limbs.at[0].add(WRAP * carry)
    limbs, _ = _propagate_seq(limbs)
    # value < 2^260; split the top limb at bit 255: t*2^250 with t < 2^10
    # becomes 19*(t >> 5) at limb 0 + (t & 31)*2^250 — result < 2^255+608.
    t = limbs[NLIMBS - 1]
    limbs = limbs.at[NLIMBS - 1].set(t & 31)
    limbs = limbs.at[0].add(19 * (t >> 5))
    limbs, _ = _propagate_seq(limbs)
    return limbs


def _cond_sub_p(limbs):
    """Subtract p when limbs >= p; inputs/outputs in narrow form."""
    diff, borrow = _propagate_seq(limbs - cvec(P_LIMBS, limbs.ndim))
    ge = borrow >= 0
    return jnp.where(ge[None], diff, limbs)


def reduce_full(a):
    """Lazy form -> canonical [0, p)."""
    return _cond_sub_p(_cond_sub_p(_narrow(a)))


def eq(a, b):
    """Canonical equality of lazy elements."""
    return jnp.all(reduce_full(sub(a, b)) == 0, axis=0)


def is_zero(a):
    return jnp.all(reduce_full(a) == 0, axis=0)


def is_odd(a):
    """Low bit of the canonical value."""
    return (reduce_full(a)[0] & 1).astype(jnp.bool_)


def select(mask, a, b):
    """Per-lane select: mask shape (*batch,), a/b shape (26, *batch)."""
    return jnp.where(mask[None], a, b)


# -- byte conversions (device side; bytes are feature-first (32, *b)) --

# limb i covers bits [10i, 10i+10): three byte taps starting at 10i//8.
_FB_IDX = np.array([(10 * i) // 8 for i in range(NLIMBS)])
_FB_SHIFT = np.array([(10 * i) % 8 for i in range(NLIMBS)], dtype=np.int32)
# byte j covers bits [8j, 8j+8): two limb taps starting at 8j//10.
_TB_IDX = np.array([(8 * j) // 10 for j in range(32)])
_TB_SHIFT = np.array([(8 * j) % 10 for j in range(32)], dtype=np.int32)


def from_bytes_le(b):
    """(32, *batch) uint8 -> narrow limbs (value < 2^256, unreduced)."""
    ext = jnp.pad(
        b.astype(DTYPE), [(0, 2)] + [(0, 0)] * (b.ndim - 1)
    )  # (34, *batch)
    word = ext[_FB_IDX] | (ext[_FB_IDX + 1] << 8) | (ext[_FB_IDX + 2] << 16)
    return (word >> cvec(_FB_SHIFT, b.ndim)) & MASK


def to_bytes_le(a):
    """Canonical little-endian bytes, shape (32, *batch)."""
    r = jnp.pad(reduce_full(a), [(0, 1)] + [(0, 0)] * (a.ndim - 1))
    word = r[_TB_IDX] | (r[_TB_IDX + 1] << LIMB_BITS)
    return ((word >> cvec(_TB_SHIFT, a.ndim)) & 0xFF).astype(jnp.uint8)


# -- exponentiation chains ---------------------------------------------

def _pow2k(a, k: int):
    """k successive squarings as a fori_loop — one square body per call
    site in the traced graph, regardless of k (compile time)."""
    if k <= 2:
        for _ in range(k):
            a = square(a)
        return a
    return lax.fori_loop(0, k, lambda _, x: square(x), a)


def pow22523(z):
    """z^((p-5)/8), the square-root chain core (ref10-style addition
    chain: 254 squarings, 11 multiplies)."""
    t0 = square(z)                      # z^2
    t1 = _pow2k(square(t0), 1)          # z^8
    t1 = mul(z, t1)                     # z^9
    t0 = mul(t0, t1)                    # z^11
    t0 = square(t0)                     # z^22
    t0 = mul(t1, t0)                    # z^31 = z^(2^5-1)
    t1 = _pow2k(t0, 5)                  # z^(2^10-2^5)
    t0 = mul(t1, t0)                    # z^(2^10-1)
    t1 = _pow2k(t0, 10)
    t1 = mul(t1, t0)                    # z^(2^20-1)
    t2 = _pow2k(t1, 20)
    t1 = mul(t2, t1)                    # z^(2^40-1)
    t1 = _pow2k(t1, 10)
    t0 = mul(t1, t0)                    # z^(2^50-1)
    t1 = _pow2k(t0, 50)
    t1 = mul(t1, t0)                    # z^(2^100-1)
    t2 = _pow2k(t1, 100)
    t1 = mul(t2, t1)                    # z^(2^200-1)
    t1 = _pow2k(t1, 50)
    t0 = mul(t1, t0)                    # z^(2^250-1)
    t0 = _pow2k(t0, 2)                  # z^(2^252-4)
    return mul(t0, z)                   # z^(2^252-3) = z^((p-5)/8)


#: kernel shape/dtype contracts (grammar: ops/contracts.py; verified
#: statically by tools/jitcheck.py, swept devicelessly by
#: tests/test_jitcheck.py).  int32 limbs are load-bearing: int64 would
#: be emulated at ~6.6x on the TPU VPU (module docstring).
_CONTRACTS = {
    "from_bytes_le": {
        "args": {"b": ("u8", (32, "B"))},
        "static": (),
        "out": ("i32", ("NLIMBS", "B")),
    },
    "to_bytes_le": {
        "args": {"a": ("i32", ("NLIMBS", "B"))},
        "static": (),
        "out": ("u8", (32, "B")),
    },
    "reduce_full": {
        "args": {"a": ("i32", ("NLIMBS", "B"))},
        "static": (),
        "out": ("i32", ("NLIMBS", "B")),
    },
    "mul": {
        "args": {
            "a": ("i32", ("NLIMBS", "B")),
            "b": ("i32", ("NLIMBS", "B")),
        },
        "static": (),
        "out": ("i32", ("NLIMBS", "B")),
    },
    "square": {
        "args": {"a": ("i32", ("NLIMBS", "B"))},
        "static": (),
        "out": ("i32", ("NLIMBS", "B")),
    },
}


def invert(z):
    """z^(p-2) = z^(2^255-21) (ref10-style chain)."""
    t0 = square(z)                      # z^2
    t1 = _pow2k(square(t0), 1)          # z^8
    t1 = mul(z, t1)                     # z^9
    t0 = mul(t0, t1)                    # z^11
    t2 = square(t0)                     # z^22
    t1 = mul(t1, t2)                    # z^31
    t2 = _pow2k(t1, 5)
    t1 = mul(t2, t1)                    # z^(2^10-1)
    t2 = _pow2k(t1, 10)
    t2 = mul(t2, t1)                    # z^(2^20-1)
    t3 = _pow2k(t2, 20)
    t2 = mul(t3, t2)                    # z^(2^40-1)
    t2 = _pow2k(t2, 10)
    t1 = mul(t2, t1)                    # z^(2^50-1)
    t2 = _pow2k(t1, 50)
    t2 = mul(t2, t1)                    # z^(2^100-1)
    t3 = _pow2k(t2, 100)
    t2 = mul(t3, t2)                    # z^(2^200-1)
    t2 = _pow2k(t2, 50)
    t1 = mul(t2, t1)                    # z^(2^250-1)
    t1 = _pow2k(t1, 5)                  # z^(2^255-32)
    return mul(t1, t0)                  # z^(2^255-21) = z^(p-2)

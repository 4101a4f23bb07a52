"""Kernel shape/dtype contracts — deviceless verification of the
device-path ABI.

Every public kernel in ``cometbft_tpu/ops`` declares its traced-input
and output shapes/dtypes in a module-level ``_CONTRACTS`` dict of PURE
LITERALS (so tools/jitcheck.py can verify the declarations statically,
without importing jax), e.g.::

    _CONTRACTS = {
        "verify_kernel_packed": {
            "args": {"buf": ("u8", ("100+bucket", "B"))},
            "static": ("bucket", "nblocks"),
            "out": ("bool", ("B",)),
        },
    }

Spec grammar (checked by jitcheck, interpreted here):

- a LEAF spec is ``(dtype, shape)`` — dtype one of DTYPES, shape a
  tuple of dims; a dim is an int or a string arithmetic expression
  over the symbols in ``ladder_env`` (``B``, ``bucket``, ``nblocks``,
  ``NLIMBS``, ``nwin``, ``nent``, ``cap``, ``ROW``, ...);
- a LIST groups specs into a tuple-valued arg/output (e.g. an
  extended point is four ``("i32", ("NLIMBS", "B"))`` leaves).

``check_contract`` builds ``jax.ShapeDtypeStruct`` inputs from the
spec, runs the kernel through ``jax.eval_shape`` (abstract evaluation:
no device, no FLOPs — tier-1 CPU CI runs the whole bucket ladder in
milliseconds), and diffs the result leaves against the declared
output.  A shape or dtype regression in any kernel therefore fails in
CI before ever touching a TPU (the int32-limb / uint8-packed-buffer
representation is load-bearing: docs/device_contracts.md).
"""

from __future__ import annotations

import ast
import functools

DTYPES = {
    "u8": "uint8",
    "i32": "int32",
    "i64": "int64",
    "u64": "uint64",
    "bool": "bool_",
}

#: symbols a dim expression may reference (jitcheck enforces this
#: statically; ladder_env binds them for the eval_shape sweep).
#: ``ndev`` is the mesh device count — shard-local kernel contracts
#: (parallel/mesh.py) express their dims as global//ndev.
DIM_SYMBOLS = frozenset(
    {"B", "bucket", "nblocks", "NLIMBS", "nwin", "nent", "cap", "M",
     "ndev", "ROW"}
)


def eval_dim(dim, env: dict) -> int:
    """An int dim, or a string arithmetic expression over DIM_SYMBOLS
    (+ - * // and parentheses; ``/`` resolves as integer division)."""
    if isinstance(dim, int):
        return dim
    node = ast.parse(str(dim), mode="eval").body

    def ev(n) -> int:
        if isinstance(n, ast.Constant) and isinstance(n.value, int):
            return n.value
        if isinstance(n, ast.Name):
            return int(env[n.id])
        if isinstance(n, ast.BinOp):
            a, b = ev(n.left), ev(n.right)
            if isinstance(n.op, ast.Add):
                return a + b
            if isinstance(n.op, ast.Sub):
                return a - b
            if isinstance(n.op, ast.Mult):
                return a * b
            if isinstance(n.op, (ast.FloorDiv, ast.Div)):
                return a // b
        raise ValueError(f"unsupported dim expression: {dim!r}")

    return ev(node)


def dim_names(dim) -> set[str]:
    """The symbols a dim expression references (static check)."""
    if isinstance(dim, int):
        return set()
    return {
        n.id
        for n in ast.walk(ast.parse(str(dim), mode="eval"))
        if isinstance(n, ast.Name)
    }


def is_leaf(spec) -> bool:
    return (
        isinstance(spec, tuple)
        and len(spec) == 2
        and isinstance(spec[0], str)
    )


def _leaves(spec) -> list[tuple]:
    if is_leaf(spec):
        return [spec]
    out: list[tuple] = []
    for s in spec:
        out.extend(_leaves(s))
    return out


def _build(spec, env: dict):
    """Spec -> ShapeDtypeStruct (leaf) or tuple thereof (list)."""
    import jax
    import jax.numpy as jnp

    if is_leaf(spec):
        dtype, shape = spec
        return jax.ShapeDtypeStruct(
            tuple(eval_dim(d, env) for d in shape),
            getattr(jnp, DTYPES[dtype]),
        )
    return tuple(_build(s, env) for s in spec)


def ladder_env(batch: int, bucket: int = 128, window_bits: int = 8,
               cap: int | None = None, ndev: int = 1) -> dict:
    """The dim bindings for one rung of the batch/bucket ladder —
    exactly the quantities the dispatch path derives (ed25519_verify:
    nblocks from the bucket; precompute: nwin/nent from the window
    width, ROW the key table's row width; cap from the pool ladder;
    parallel/mesh: ndev the mesh
    device count, which must divide ``batch`` and ``cap`` the way the
    lane router / table placement pad them)."""
    from cometbft_tpu.ops import field as F
    from cometbft_tpu.ops.ed25519_verify import nblocks_for_bucket
    from cometbft_tpu.ops.precompute import ROW

    return {
        "B": batch,
        "bucket": bucket,
        "M": bucket,
        "nblocks": nblocks_for_bucket(bucket),
        "NLIMBS": F.NLIMBS,
        "window_bits": window_bits,
        "nwin": 256 // window_bits,
        "nent": 1 << window_bits,
        "cap": cap if cap is not None else batch,
        "ndev": ndev,
        "ROW": ROW,
    }


def check_contract(fn, contract: dict, env: dict) -> list[str]:
    """eval_shape ``fn`` against one contract at one env binding.
    Returns a list of mismatch descriptions (empty = conforming)."""
    import jax

    # traced args go by KEYWORD so static params interleaved in the
    # signature (sha512_padded(buf, nblocks, nblocks_lane)) bind right
    args = {
        name: _build(spec, env) for name, spec in contract["args"].items()
    }
    static = {name: env[name] for name in contract.get("static", ())}
    try:
        got = jax.eval_shape(functools.partial(fn, **static), **args)
    except Exception as exc:  # noqa: BLE001 — report, don't crash sweep
        return [f"{fn.__name__}: eval_shape failed at {env}: {exc!r}"]
    got_leaves = jax.tree_util.tree_leaves(got)
    want = _leaves(contract["out"])
    errors: list[str] = []
    if len(got_leaves) != len(want):
        errors.append(
            f"{fn.__name__}: {len(got_leaves)} output leaves, contract "
            f"declares {len(want)}"
        )
        return errors
    import numpy as np

    for i, (leaf, (dtype, shape)) in enumerate(zip(got_leaves, want)):
        want_shape = tuple(eval_dim(d, env) for d in shape)
        want_dtype = np.dtype(DTYPES[dtype])
        if tuple(leaf.shape) != want_shape:
            errors.append(
                f"{fn.__name__} out[{i}]: shape {tuple(leaf.shape)} != "
                f"contract {want_shape} (dims {shape}) at {env}"
            )
        if np.dtype(leaf.dtype) != want_dtype:
            errors.append(
                f"{fn.__name__} out[{i}]: dtype {leaf.dtype} != "
                f"contract {want_dtype} at {env}"
            )
    return errors


def check_module(module, env: dict) -> list[str]:
    """Sweep every contract a module declares at one env binding."""
    errors: list[str] = []
    for name, contract in getattr(module, "_CONTRACTS", {}).items():
        errors.extend(check_contract(getattr(module, name), contract, env))
    return errors

"""jitcheck: static device-path correctness lint — the compile-time
half of the jit/retrace toolchain (runtime half: CMT_TPU_JITGUARD in
cometbft_tpu/ops/jitguard.py; docs/device_contracts.md is the manual).

PR 3 gave the host-concurrency plane a `go test -race` analog
(tools/lockcheck.py + utils/sync.py runtime modes); this is the same
treatment for the device plane, where the silent failure modes are a
retrace, an implicit host<->device transfer, or a shape/dtype drift in
the kernel ABI — all of which degrade the hot path with no error and
no signal.  Four AST checks, lockcheck-style:

1. **Jit-seam check.**  Every ``jax.jit`` call in ``cometbft_tpu``
   must sit inside a REGISTERED compile-cache seam (``JIT_SEAMS`` —
   the ``_compiled*`` memoizers and the memoized
   ``sharded_verify_fn``).  A seam must (a) memoize through a
   module-level ``*_cache`` dict, (b) take only parameters from the
   audited pow2/bucket/chunk ladder (``LADDER_PARAMS``) so the jit
   cache stays bounded, and (c) report its misses through
   ``jitguard.note_compile`` so the runtime retrace guard and BENCH
   provenance see every compile.

2. **Closure-globals check.**  The callable handed to ``jax.jit`` may
   not load a module global that is REBOUND anywhere (a ``global``
   statement, or multiple module-scope assignments): such a value is
   captured at trace time, so later mutation silently diverges the
   compiled program from the source (program-shaping flags belong in
   the cache key — see field.trace_config()).

3. **Host-sync check** (device-plane files only: ``ops/``,
   ``parallel/``, ``crypto/batch.py``).  Host-synchronization sites —
   ``np.asarray``, ``jax.device_get``, ``.item()``,
   ``.block_until_ready()``, ``jax.debug.callback``, and
   ``float()``/``bool()``/``int()`` on a device-tainted local — must
   carry an audited ``# host sync: <reason>`` waiver (mirroring
   lockcheck's ``# unguarded:``).  Waivers are counted and reported;
   a waiver on a line with no sync site is a STALE-WAIVER error, so
   annotations cannot outlive the code they audit.

4. **Kernel-contract check.**  Every public kernel in
   ``REQUIRED_CONTRACTS`` must declare a ``_CONTRACTS`` entry (pure
   literals, grammar in ops/contracts.py) whose arg names match the
   function signature, whose dtypes come from the audited set (int32
   limbs, uint8 packed buffers...), and whose dims reference only the
   known ladder symbols.  The deviceless ``jax.eval_shape`` sweep in
   tests/test_jitcheck.py then verifies the declarations against the
   traced kernels across the bucket ladder.

Known static limits (the runtime guard covers these): host syncs
reached through helper calls, taint through attributes/containers,
and jit wrappers constructed outside the seams at runtime are not
seen; CMT_TPU_JITGUARD=1 catches them as retraces / transfer-guard
trips.

    python tools/jitcheck.py            # exit 0 clean, 1 with a report
    python tools/jitcheck.py -v         # also list waivers

Run in the tier-1 flow via tests/test_jitcheck.py and standalone via
``make jitcheck``; tools/metrics_lint.py main() gates on it too.
"""

from __future__ import annotations

import ast
import os
import sys
from dataclasses import dataclass

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from tools.lintlib import (  # noqa: E402 — path bootstrap above
    SCAN_ROOT,
    Violation,
    Waiver,
    check_stale_waivers,
    comments_by_line as _comments_by_line,
    dotted as _dotted,
    iter_py_files,
    run_main,
    waiver_re,
)
from tools import lintlib  # noqa: E402

#: the registered compile-cache seams: (file, function) pairs allowed
#: to call jax.jit — everything the runtime guard's note_compile sees
JIT_SEAMS = frozenset(
    {
        (os.path.join("cometbft_tpu", "ops", "ed25519_verify.py"),
         "_compiled"),
        (os.path.join("cometbft_tpu", "ops", "ed25519_verify.py"),
         "_compiled_chunked"),
        (os.path.join("cometbft_tpu", "ops", "ed25519_verify.py"),
         "_compiled_keyed"),
        (os.path.join("cometbft_tpu", "ops", "precompute.py"),
         "_compiled_build"),
        (os.path.join("cometbft_tpu", "parallel", "mesh.py"),
         "sharded_verify_fn"),
        (os.path.join("cometbft_tpu", "parallel", "mesh.py"),
         "_compiled_keyed_mesh"),
    }
)

#: parameter names a seam may key its cache on — the pow2/bucket/chunk
#: ladder (plus the mesh handle, itself drawn from the cached
#: flat_mesh, and the slice count ``launch_lanes`` gives a wide
#: launch).  Anything else is an unbounded cache dimension.
LADDER_PARAMS = frozenset(
    {"batch", "bucket", "chunk", "slices", "window_bits", "n", "nblocks",
     "mesh"}
)

#: device-plane files subject to the host-sync check
SYNC_SCOPE_DIRS = (
    os.path.join("cometbft_tpu", "ops") + os.sep,
    os.path.join("cometbft_tpu", "parallel") + os.sep,
)
SYNC_SCOPE_FILES = frozenset(
    {os.path.join("cometbft_tpu", "crypto", "batch.py")}
)

#: public kernels that MUST declare a _CONTRACTS entry
REQUIRED_CONTRACTS = {
    os.path.join("cometbft_tpu", "ops", "ed25519_verify.py"): frozenset(
        {"build_padded_input", "verify_kernel", "verify_kernel_packed",
         "verify_kernel_keyed", "verify_kernel_keyed_packed"}
    ),
    os.path.join("cometbft_tpu", "ops", "field.py"): frozenset(
        {"from_bytes_le", "to_bytes_le", "reduce_full", "mul", "square"}
    ),
    os.path.join("cometbft_tpu", "ops", "curve.py"): frozenset(
        {"decompress", "nibbles_from_bytes_le", "comb_mul_base",
         "window_mul", "mul8"}
    ),
    os.path.join("cometbft_tpu", "ops", "scalar.py"): frozenset(
        {"reduce_digest", "bytes_lt_l", "limbs_to_windows8",
         "limbs_to_nibbles"}
    ),
    os.path.join("cometbft_tpu", "ops", "sha512.py"): frozenset(
        {"sha512_padded", "bytes_to_words", "words_to_bytes"}
    ),
    os.path.join("cometbft_tpu", "ops", "precompute.py"): frozenset(
        {"build_tables_kernel", "comb_mul_base8", "comb_mul_keyed"}
    ),
    os.path.join("cometbft_tpu", "parallel", "mesh.py"): frozenset(
        {"verify_keyed_shard"}
    ),
}

_WAIVER_RE = waiver_re("host sync")

#: contract vocabulary — mirrored from ops/contracts.py WITHOUT
#: importing it (the ops package import initializes jax; a lint must
#: stay side-effect free).  tests/test_jitcheck.py asserts the two
#: stay in lockstep.
DTYPES_OK = frozenset({"u8", "i32", "i64", "u64", "bool"})
DIM_SYMBOLS = frozenset(
    {"B", "bucket", "nblocks", "NLIMBS", "nwin", "nent", "cap", "M",
     "ndev", "ROW"}
)
STATIC_PARAMS_OK = DIM_SYMBOLS | {"window_bits"}


def _dim_names(dim) -> set[str]:
    if isinstance(dim, int):
        return set()
    return {
        n.id
        for n in ast.walk(ast.parse(str(dim), mode="eval"))
        if isinstance(n, ast.Name)
    }


def _is_leaf_spec(spec) -> bool:
    return (
        isinstance(spec, tuple)
        and len(spec) == 2
        and isinstance(spec[0], str)
    )


@dataclass
class Report(lintlib.Report):
    jit_calls: int = 0
    seams: int = 0
    contracts: int = 0
    sync_sites: int = 0


def _is_jit_call(node: ast.Call) -> bool:
    d = _dotted(node.func)
    return d in {"jax.jit", "jit"}


# -- module-level binding census (closure-globals check) ----------------


def _module_rebound_names(tree: ast.Module) -> set[str]:
    """Module globals that are REBOUND: targets of a ``global``
    statement anywhere, or assigned more than once at module scope."""
    counts: dict[str, int] = {}
    rebound: set[str] = set()
    for stmt in tree.body:
        targets: list[ast.expr] = []
        if isinstance(stmt, ast.Assign):
            targets = stmt.targets
        elif isinstance(stmt, (ast.AnnAssign, ast.AugAssign)):
            targets = [stmt.target]
        for tgt in targets:
            elts = tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
            for el in elts:
                if isinstance(el, ast.Name):
                    counts[el.id] = counts.get(el.id, 0) + 1
    for node in ast.walk(tree):
        if isinstance(node, ast.Global):
            rebound.update(node.names)
    rebound.update(n for n, c in counts.items() if c > 1)
    return rebound


def _bound_names(fn: ast.AST) -> set[str]:
    """Names bound inside a function/lambda: params + assignments +
    comprehension targets + inner defs."""
    bound: set[str] = set()
    if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
        a = fn.args
        for arg in (
            a.posonlyargs + a.args + a.kwonlyargs
            + ([a.vararg] if a.vararg else [])
            + ([a.kwarg] if a.kwarg else [])
        ):
            bound.add(arg.arg)
    for node in ast.walk(fn):
        if isinstance(node, ast.Name) and isinstance(
            node.ctx, (ast.Store, ast.Del)
        ):
            bound.add(node.id)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            bound.add(node.name)
    return bound


class _FileChecker:
    def __init__(self, rel: str, source: str, report: Report):
        self.rel = rel
        self.source = source
        self.report = report
        self.comments = _comments_by_line(source)
        self.waived_lines: set[int] = set()   # lines with a USED waiver
        self.flagged_lines: set[int] = set()  # lines with any sync site

    def run(self) -> None:
        try:
            tree = lintlib.parse_cached(self.source)
        except SyntaxError as exc:
            self.report.violations.append(
                Violation(self.rel, exc.lineno or 0,
                          f"syntax error: {exc.msg}")
            )
            return
        self.rebound = _module_rebound_names(tree)
        self._check_jit_calls(tree)
        if self._in_sync_scope():
            self._check_host_syncs(tree)
            self._check_stale_waivers()
        self._check_contracts(tree)

    def _in_sync_scope(self) -> bool:
        return (
            self.rel in SYNC_SCOPE_FILES
            or any(self.rel.startswith(d) for d in SYNC_SCOPE_DIRS)
        )

    # -- jit seam + closure checks --------------------------------------

    def _check_jit_calls(self, tree: ast.Module) -> None:
        # map every jax.jit call to its innermost enclosing function
        def walk(node: ast.AST, fn_stack: tuple):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                fn_stack = fn_stack + (node,)
            for child in ast.iter_child_nodes(node):
                walk(child, fn_stack)
            if isinstance(node, ast.Call) and _is_jit_call(node):
                self.report.jit_calls += 1
                self._check_one_jit(node, fn_stack)

        walk(tree, ())

    def _check_one_jit(self, call: ast.Call, fn_stack: tuple) -> None:
        outer = fn_stack[0] if fn_stack else None
        seam_name = outer.name if outer is not None else "<module>"
        if (self.rel, seam_name) not in JIT_SEAMS:
            self.report.violations.append(
                Violation(
                    self.rel, call.lineno,
                    f"jax.jit called in {seam_name}() which is not a "
                    "registered compile-cache seam — route the compile "
                    "through a memoizer in JIT_SEAMS (tools/jitcheck.py) "
                    "so retraces are counted, guarded, and bounded",
                )
            )
            return
        self.report.seams += 1
        self._check_seam_discipline(outer)
        # the traced callable: first positional arg
        if call.args:
            self._check_closure_globals(call.args[0], fn_stack)

    def _check_seam_discipline(self, fn: ast.FunctionDef) -> None:
        params = {
            a.arg
            for a in fn.args.posonlyargs + fn.args.args + fn.args.kwonlyargs
        }
        off_ladder = params - LADDER_PARAMS
        if off_ladder:
            self.report.violations.append(
                Violation(
                    self.rel, fn.lineno,
                    f"seam {fn.name}() keys its cache on non-ladder "
                    f"parameter(s) {sorted(off_ladder)} — only the "
                    f"pow2/bucket/chunk ladder ({sorted(LADDER_PARAMS)}) "
                    "keeps the jit cache bounded",
                )
            )
        names = {
            n.id for n in ast.walk(fn) if isinstance(n, ast.Name)
        }
        attrs = {
            n.attr for n in ast.walk(fn) if isinstance(n, ast.Attribute)
        }
        if not any(n.endswith("_cache") for n in names | attrs):
            self.report.violations.append(
                Violation(
                    self.rel, fn.lineno,
                    f"seam {fn.name}() does not reference a module-level "
                    "*_cache memoizer — an unmemoized jax.jit wrapper "
                    "retraces per call",
                )
            )
        if "note_compile" not in attrs and "note_compile" not in names:
            self.report.violations.append(
                Violation(
                    self.rel, fn.lineno,
                    f"seam {fn.name}() does not call "
                    "jitguard.note_compile — cache misses would be "
                    "invisible to the retrace guard and BENCH provenance",
                )
            )

    def _check_closure_globals(self, fn_arg: ast.expr, fn_stack) -> None:
        target: ast.AST | None = None
        if isinstance(fn_arg, ast.Lambda):
            target = fn_arg
        elif isinstance(fn_arg, ast.Name):
            # a local `def` in any enclosing function scope
            for fn in reversed(fn_stack):
                for node in ast.walk(fn):
                    if (
                        isinstance(node, (ast.FunctionDef,
                                          ast.AsyncFunctionDef))
                        and node.name == fn_arg.id
                    ):
                        target = node
                        break
                if target is not None:
                    break
        if target is None:
            return
        bound = _bound_names(target)
        for fn in fn_stack:
            bound |= _bound_names(fn)
        for node in ast.walk(target):
            if (
                isinstance(node, ast.Name)
                and isinstance(node.ctx, ast.Load)
                and node.id not in bound
                and node.id in self.rebound
            ):
                self.report.violations.append(
                    Violation(
                        self.rel, node.lineno,
                        f"jit closure captures mutable module global "
                        f"'{node.id}' (rebound via `global` or multiple "
                        "module-scope assignments) — its value is baked "
                        "in at trace time; pass it as an argument or "
                        "fold it into the compile-cache key "
                        "(field.trace_config())",
                    )
                )

    # -- host-sync check ------------------------------------------------

    def _check_host_syncs(self, tree: ast.Module) -> None:
        # every def is its own scope, and so is the module body itself
        # (a module-init sync site is just as real as one in a
        # function — and its waiver must not read as stale)
        self._scan_scope(tree, "<module>")
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                self._scan_scope(node, node.name)

    @staticmethod
    def _walk_scope(root: ast.AST):
        """ast.walk restricted to ONE scope: does not descend into
        nested function/lambda bodies (each def is scanned as its own
        scope — descending would both double-report their sites and
        leak taint across scopes)."""
        stack = list(ast.iter_child_nodes(root))
        while stack:
            node = stack.pop()
            yield node
            if isinstance(
                node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
            ):
                continue
            stack.extend(ast.iter_child_nodes(node))

    def _scan_scope(self, scope: ast.AST, where: str) -> None:
        compiled_vars: set[str] = set()
        device_vars: set[str] = set()

        def rhs_taints(value: ast.expr) -> tuple[bool, bool]:
            """(is_compiled_fn, is_device_value) for an assignment RHS."""
            is_compiled = is_device = False
            for node in ast.walk(value):
                if isinstance(node, ast.Call):
                    d = _dotted(node.func)
                    base = d.split(".")[-1]
                    if base.startswith("_compiled"):
                        is_compiled = True
                    if d.startswith("jnp.") or d == "jax.device_put":
                        is_device = True
                    if (
                        isinstance(node.func, ast.Name)
                        and node.func.id in compiled_vars
                    ):
                        is_device = True
            return is_compiled, is_device

        for node in self._walk_scope(scope):
            if isinstance(node, ast.Assign):
                is_compiled, is_device = rhs_taints(node.value)
                for tgt in node.targets:
                    elts = (
                        tgt.elts if isinstance(tgt, ast.Tuple) else [tgt]
                    )
                    for el in elts:
                        if isinstance(el, ast.Name):
                            if is_compiled:
                                compiled_vars.add(el.id)
                            if is_device:
                                device_vars.add(el.id)

        for node in self._walk_scope(scope):
            if not isinstance(node, ast.Call):
                continue
            site = self._sync_site(node, device_vars)
            if site is not None:
                self._flag_sync(node, site, where)

    def _sync_site(self, node: ast.Call, device_vars: set[str]) -> str | None:
        d = _dotted(node.func)
        if d in {"np.asarray", "numpy.asarray"}:
            return d
        if d == "jax.device_get":
            return d
        if d == "jax.debug.callback":
            return d
        if isinstance(node.func, ast.Attribute):
            if node.func.attr == "item" and not node.args:
                return ".item()"
            if node.func.attr == "block_until_ready":
                return ".block_until_ready()"
        if (
            isinstance(node.func, ast.Name)
            and node.func.id in {"float", "bool", "int"}
            and len(node.args) == 1
        ):
            arg = node.args[0]
            name = None
            if isinstance(arg, ast.Name):
                name = arg.id
            elif isinstance(arg, ast.Subscript) and isinstance(
                arg.value, ast.Name
            ):
                name = arg.value.id
            if name in device_vars:
                return f"{node.func.id}() on device value '{name}'"
        return None

    def _flag_sync(self, node: ast.Call, site: str, where: str) -> None:
        self.report.sync_sites += 1
        self.flagged_lines.add(node.lineno)
        m = _WAIVER_RE.search(self.comments.get(node.lineno, ""))
        if m:
            if node.lineno not in self.waived_lines:
                self.waived_lines.add(node.lineno)
                self.report.waivers.append(
                    Waiver(self.rel, node.lineno, site, m.group(1).strip())
                )
            return
        self.report.violations.append(
            Violation(
                self.rel, node.lineno,
                f"host-sync site {site} in {where}() without an audited "
                "waiver — a blocking transfer here stalls the device "
                "pipeline for a full round trip; batch it "
                "through the documented single-fetch path (_finish) or "
                "waive with '# host sync: <reason>'",
            )
        )

    def _check_stale_waivers(self) -> None:
        check_stale_waivers(
            self.comments, self.flagged_lines, _WAIVER_RE,
            self.rel, self.report, "host sync",
        )

    # -- contract check -------------------------------------------------

    def _check_contracts(self, tree: ast.Module) -> None:
        contracts: dict = {}
        decl_line = 0
        for stmt in tree.body:
            if (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and stmt.targets[0].id == "_CONTRACTS"
            ):
                decl_line = stmt.lineno
                try:
                    contracts = ast.literal_eval(stmt.value)
                except (ValueError, SyntaxError):
                    self.report.violations.append(
                        Violation(
                            self.rel, stmt.lineno,
                            "_CONTRACTS must be a pure literal "
                            "(no names, calls, or comprehensions) so it "
                            "is statically checkable",
                        )
                    )
                    return
        required = REQUIRED_CONTRACTS.get(self.rel, frozenset())
        missing = required - set(contracts)
        if missing:
            self.report.violations.append(
                Violation(
                    self.rel, decl_line or 1,
                    f"public kernel(s) {sorted(missing)} have no "
                    "_CONTRACTS entry — shape/dtype regressions would "
                    "only surface on device",
                )
            )
        if not contracts:
            return
        fns = {
            n.name: n
            for n in tree.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        for fname, contract in contracts.items():
            self._check_one_contract(fname, contract, fns, decl_line)

    def _check_one_contract(
        self, fname: str, contract, fns: dict, line: int
    ) -> None:
        def bad(msg: str) -> None:
            self.report.violations.append(
                Violation(self.rel, line, f"_CONTRACTS[{fname!r}]: {msg}")
            )

        fn = fns.get(fname)
        if fn is None:
            bad("names no module-level function")
            return
        if not isinstance(contract, dict) or "args" not in contract or \
                "out" not in contract:
            bad("must be a dict with 'args' and 'out'")
            return
        params = [
            a.arg
            for a in fn.args.posonlyargs + fn.args.args
        ]
        static = tuple(contract.get("static", ()))
        declared = list(contract["args"]) + list(static)
        if set(declared) != set(params):
            bad(
                f"declares args {sorted(declared)} but the signature "
                f"has {params}"
            )
        for sname in static:
            if sname not in STATIC_PARAMS_OK:
                bad(
                    f"static arg {sname!r} is not a ladder symbol "
                    f"({sorted(STATIC_PARAMS_OK)}) — off-ladder statics "
                    "unbound the jit cache"
                )
        self.report.contracts += 1
        for spec in list(contract["args"].values()) + [contract["out"]]:
            self._check_spec(fname, spec, bad)

    def _check_spec(self, fname: str, spec, bad) -> None:
        if _is_leaf_spec(spec):
            dtype, shape = spec
            if dtype not in DTYPES_OK:
                bad(f"dtype {dtype!r} not in the audited set "
                    f"{sorted(DTYPES_OK)}")
            if not isinstance(shape, tuple):
                bad(f"shape {shape!r} must be a tuple of dims")
                return
            for dim in shape:
                if isinstance(dim, int):
                    continue
                try:
                    unknown = _dim_names(dim) - DIM_SYMBOLS
                except SyntaxError:
                    bad(f"unparseable dim expression {dim!r}")
                    continue
                if unknown:
                    bad(
                        f"dim {dim!r} references unknown symbol(s) "
                        f"{sorted(unknown)} (known: {sorted(DIM_SYMBOLS)})"
                    )
            return
        if isinstance(spec, list):
            for sub in spec:
                self._check_spec(fname, sub, bad)
            return
        bad(f"spec {spec!r} is neither a (dtype, shape) leaf nor a list")


def check_source(source: str, rel: str) -> Report:
    """Lint one file's source; ``rel`` is the path used in reports and
    scope decisions (fixtures pass cometbft_tpu/ops/... paths)."""
    report = Report()
    _FileChecker(rel, source, report).run()
    return report


def check_tree(root: str = SCAN_ROOT) -> Report:
    report = Report()
    seen: set[str] = set()
    for rel, source in iter_py_files(root):
        seen.add(rel)
        report.merge(check_source(source, rel))
    # coverage: a REQUIRED_CONTRACTS file that vanished entirely would
    # otherwise silently pass
    for rel in REQUIRED_CONTRACTS:
        if rel not in seen:
            report.violations.append(
                Violation(rel, 0, "REQUIRED_CONTRACTS file missing")
            )
    return report


def _summary(report: Report) -> str:
    return (
        f"{report.jit_calls} jax.jit calls through "
        f"{report.seams} registered seams; {report.contracts} kernel "
        f"contracts; {report.sync_sites} host-sync sites "
        f"({len(report.waivers)} audited waivers)"
    )


def main(argv: list[str] | None = None) -> int:
    return run_main("jitcheck", check_tree, _summary, argv)


if __name__ == "__main__":
    sys.exit(main())

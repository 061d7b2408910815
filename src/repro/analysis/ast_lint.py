"""AST passes (stdlib ``ast``): repo idiom enforcement.

Checks (family ``ast``, flake8 codes LAF3xx):

* ``ast-traced-branch`` — no Python ``if``/``assert``/``while`` on a
  traced value inside a jit-compiled function.  A value is *static*
  when it derives only from ``static_argnames``/``static_argnums``
  parameters, shape/dtype metadata (``x.shape``, ``x.ndim``, ``len(x)``,
  ...), literals, or names from outside the function; everything else
  reaching a branch predicate is a tracer and the branch is a trace
  error (or worse, a silent per-trace specialization).
* ``ast-wallclock-sync`` — no ``time.time()``/``perf_counter()`` pair
  bracketing a JAX-dispatching call without a sync (``block_until_ready``
  / ``jax.device_get`` / an ``obs.span`` with ``sync=``/``force=``) —
  an unsynced bracket measures dispatch, not execution.
* ``ast-raw-pallas-call`` — ``pl.pallas_call`` appears only in
  ``kernels/*/kernel.py``; wrappers/ops layers go through the kernel
  module's public entry points.
* ``ast-kernel-tile-contract`` — a kernel package's ``ops.py`` must not
  redefine or contradict ``kernel.py``'s ``DEFAULT_*_TILE`` constants,
  and each default must satisfy the divisibility asserts the kernel
  body itself states (e.g. ``db_tile % 32 == 0``).

Suppress a single site with ``# laf-lint: disable=<check-id>`` on the
flagged line (or the line above); whole-path suppressions belong in
``analysis/baseline.toml``.

The module doubles as a flake8 plugin (``LafLintPlugin``) so editors
wired to flake8 report the same findings with LAF3xx codes.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Set, Tuple

from .registry import Finding, register

__all__ = [
    "iter_py_files",
    "parse_file",
    "filter_inline_suppressed",
    "check_file_traced_branch",
    "check_file_wallclock_sync",
    "check_file_raw_pallas_call",
    "check_tree_kernel_tile_contract",
    "LafLintPlugin",
]


# ---------------------------------------------------------------------------
# shared file machinery
# ---------------------------------------------------------------------------


def iter_py_files(roots: Iterable[Path]) -> List[Path]:
    out = []
    for root in roots:
        root = Path(root)
        if root.is_file() and root.suffix == ".py":
            out.append(root)
        elif root.is_dir():
            out.extend(sorted(root.rglob("*.py")))
    return out


def parse_file(path: Path) -> Tuple[Optional[ast.AST], List[str]]:
    src = Path(path).read_text()
    lines = src.splitlines()
    try:
        return ast.parse(src), lines
    except SyntaxError:
        return None, lines


def filter_inline_suppressed(
    findings: List[Finding], lines: List[str]
) -> List[Finding]:
    """Drop findings whose line (or the one above) carries
    ``# laf-lint: disable=<check-id>``."""
    out = []
    for f in findings:
        tag = f"laf-lint: disable={f.check}"
        near = [
            lines[i]
            for i in (f.line - 1, f.line - 2)
            if 0 <= i < len(lines)
        ]
        if not any(tag in ln for ln in near):
            out.append(f)
    return out


def _call_name(node: ast.AST) -> str:
    """Trailing identifier of a call target: ``a.b.c(...)`` -> ``c``."""
    if isinstance(node, ast.Call):
        node = node.func
    if isinstance(node, ast.Attribute):
        return node.attr
    if isinstance(node, ast.Name):
        return node.id
    return ""


def _module_constants(tree: ast.AST) -> Dict[str, object]:
    """Module-level literal assignments (for resolving ``_STATIC``-style
    static_argnames constants)."""
    consts: Dict[str, object] = {}
    for stmt in getattr(tree, "body", []):
        if isinstance(stmt, ast.Assign) and len(stmt.targets) == 1:
            t = stmt.targets[0]
            if isinstance(t, ast.Name):
                try:
                    consts[t.id] = ast.literal_eval(stmt.value)
                except (ValueError, SyntaxError):
                    pass
    return consts


# ---------------------------------------------------------------------------
# ast-traced-branch
# ---------------------------------------------------------------------------

_STATIC_ATTRS = {
    "shape", "ndim", "dtype", "size", "itemsize", "sharding", "weak_type",
    "aval",
}
_STATIC_CALLS = {
    "len", "isinstance", "issubclass", "hasattr", "type", "callable",
    "range", "id",
}


def _resolve_static_spec(value: ast.AST, consts: Dict[str, object]):
    """static_argnames/static_argnums value -> python object or None."""
    try:
        return ast.literal_eval(value)
    except (ValueError, SyntaxError):
        if isinstance(value, ast.Name):
            return consts.get(value.id)
    return None


def _jit_call_static(call: ast.Call, consts: Dict[str, object]):
    """If ``call`` is ``jax.jit(...)``/``jit(...)`` (possibly through
    ``functools.partial``), return (names, nums); else None."""
    name = _call_name(call)
    if name == "partial" and call.args:
        inner = call.args[0]
        if _call_name(inner) == "jit":
            names, nums = _jit_kwargs(call, consts)
            return names, nums
        return None
    if name == "jit":
        return _jit_kwargs(call, consts)
    return None


def _jit_kwargs(call: ast.Call, consts) -> Tuple[Set[str], Set[int]]:
    names: Set[str] = set()
    nums: Set[int] = set()
    for kw in call.keywords:
        if kw.arg == "static_argnames":
            v = _resolve_static_spec(kw.value, consts)
            if isinstance(v, str):
                names.add(v)
            elif isinstance(v, (tuple, list)):
                names.update(x for x in v if isinstance(x, str))
        elif kw.arg == "static_argnums":
            v = _resolve_static_spec(kw.value, consts)
            if isinstance(v, int):
                nums.add(v)
            elif isinstance(v, (tuple, list)):
                nums.update(x for x in v if isinstance(x, int))
    return names, nums


def _jitted_functions(tree: ast.AST, consts) -> List[Tuple[ast.AST, Set[str]]]:
    """(function_def, static_param_names) for every function the module
    jit-compiles — via decorator or a module-level ``X = jax.jit(F, ...)``."""
    defs = {
        n.name: n
        for n in getattr(tree, "body", [])
        if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
    }
    out = []
    seen = set()

    def param_names(fn) -> List[str]:
        a = fn.args
        return [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]

    def add(fn, names: Set[str], nums: Set[int]):
        if id(fn) in seen:
            return
        seen.add(id(fn))
        params = param_names(fn)
        static = set(names)
        static.update(params[i] for i in nums if i < len(params))
        out.append((fn, static))

    for fn in defs.values():
        for dec in fn.decorator_list:
            if _call_name(dec) == "jit" and not isinstance(dec, ast.Call):
                add(fn, set(), set())
            elif isinstance(dec, ast.Call):
                spec = _jit_call_static(dec, consts)
                if spec is not None:
                    add(fn, *spec)
    for stmt in getattr(tree, "body", []):
        if not (isinstance(stmt, ast.Assign) and isinstance(stmt.value, ast.Call)):
            continue
        call = stmt.value
        if _call_name(call) != "jit":
            continue
        names, nums = _jit_kwargs(call, consts)
        if call.args and isinstance(call.args[0], ast.Name):
            fn = defs.get(call.args[0].id)
            if fn is not None:
                add(fn, names, nums)
    return out


def _expr_traced(node: ast.AST, traced: Set[str]) -> bool:
    """Does this expression's value depend on a traced name?  Shape/
    dtype extractors and type predicates prune to static."""
    if isinstance(node, ast.Attribute):
        if node.attr in _STATIC_ATTRS:
            return False
        return _expr_traced(node.value, traced)
    if isinstance(node, ast.Call):
        if _call_name(node) in _STATIC_CALLS:
            return False
        parts = [node.func, *node.args, *(kw.value for kw in node.keywords)]
        return any(_expr_traced(p, traced) for p in parts)
    if isinstance(node, ast.Name):
        return node.id in traced
    if isinstance(node, ast.Compare):
        # `x is None` / `x is not None` inspects the python object, not
        # the traced value — the idiomatic default-argument test
        if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            return False
    return any(_expr_traced(c, traced) for c in ast.iter_child_nodes(node))


def _assigned_names(target: ast.AST) -> List[str]:
    if isinstance(target, ast.Name):
        return [target.id]
    if isinstance(target, (ast.Tuple, ast.List)):
        out = []
        for el in target.elts:
            out.extend(_assigned_names(el.value if isinstance(el, ast.Starred) else el))
        return out
    return []


def _scan_traced_branches(
    fn: ast.AST, static: Set[str], path: str
) -> List[Finding]:
    a = fn.args
    params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    traced: Set[str] = {p for p in params if p not in static}

    def propagate(stmts, traced: Set[str]) -> Set[str]:
        for stmt in stmts:
            if isinstance(stmt, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
                targets = (
                    stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                )
                value = stmt.value
                if value is not None:
                    is_traced = _expr_traced(value, traced)
                    for t in targets:
                        for name in _assigned_names(t):
                            if is_traced:
                                traced.add(name)
                            else:
                                traced.discard(name)
            elif isinstance(stmt, ast.For):
                if _expr_traced(stmt.iter, traced):
                    traced.update(_assigned_names(stmt.target))
                traced = propagate(stmt.body, traced)
                traced = propagate(stmt.orelse, traced)
            elif isinstance(stmt, (ast.If, ast.While)):
                traced = propagate(stmt.body, traced)
                traced = propagate(stmt.orelse, traced)
            elif isinstance(stmt, ast.With):
                traced = propagate(stmt.body, traced)
            elif isinstance(stmt, ast.Try):
                for block in (stmt.body, stmt.orelse, stmt.finalbody):
                    traced = propagate(block, traced)
                for h in stmt.handlers:
                    traced = propagate(h.body, traced)
        return traced

    # fixpoint the assignment dataflow (loop-carried reassignments),
    # then report in a second pass
    for _ in range(3):
        before = set(traced)
        traced = propagate(fn.body, traced)
        if traced == before:
            break

    findings: List[Finding] = []

    def report(stmts, traced: Set[str]):
        for stmt in stmts:
            if isinstance(stmt, (ast.If, ast.While)) and _expr_traced(
                stmt.test, traced
            ):
                kind = "if" if isinstance(stmt, ast.If) else "while"
                findings.append(
                    Finding(
                        "ast-traced-branch", path, stmt.lineno,
                        f"python `{kind}` on a traced value inside jitted "
                        f"`{fn.name}` — the branch runs at trace time, not "
                        f"per element",
                        hint="use lax.cond/lax.select/jnp.where, or mark the "
                        "argument static (static_argnames)",
                    )
                )
            elif isinstance(stmt, ast.Assert) and _expr_traced(stmt.test, traced):
                findings.append(
                    Finding(
                        "ast-traced-branch", path, stmt.lineno,
                        f"`assert` on a traced value inside jitted `{fn.name}` "
                        f"— it checks the tracer, not runtime data",
                        hint="assert on .shape/.dtype (static), or use "
                        "checkify for runtime value checks",
                    )
                )
            elif isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # nested defs are traced bodies (scan/fori/cond callees):
                # their params are tracers
                inner = set(traced)
                ia = stmt.args
                inner.update(
                    p.arg for p in ia.posonlyargs + ia.args + ia.kwonlyargs
                )
                report(stmt.body, inner)
            for block in ("body", "orelse", "finalbody"):
                sub = getattr(stmt, block, None)
                if sub and not isinstance(
                    stmt, (ast.FunctionDef, ast.AsyncFunctionDef)
                ):
                    report(sub, traced)
            for h in getattr(stmt, "handlers", []):
                report(h.body, traced)

    report(fn.body, traced)
    return findings


def check_file_traced_branch(path: Path, tree: ast.AST, rel: str) -> List[Finding]:
    consts = _module_constants(tree)
    findings: List[Finding] = []
    for fn, static in _jitted_functions(tree, consts):
        findings.extend(_scan_traced_branches(fn, static, rel))
    return findings


# ---------------------------------------------------------------------------
# ast-wallclock-sync
# ---------------------------------------------------------------------------

_TIME_FNS = {"time", "perf_counter", "monotonic", "process_time"}

# call names that dispatch JAX work asynchronously — a wall-clock pair
# around any of these without a sync measures dispatch, not execution
DISPATCH_CALLS = {
    "laf_dbscan", "dbscan_parallel", "dbscan_pp", "laf_dbscan_pp",
    "sweep_counts", "sweep_bitmap", "sharded_sweep_launch",
    "sharded_sweep_marginals", "sharded_band_marginals",
    "hamming_filter_pallas", "hamming_filter_count", "hamming_filter_bitmap",
    "query_hits", "query_counts", "query_hits_subset", "query_hits_packed",
    "rescue_reduce",
    "partial_fit", "rmi_predict_counts", "predict_counts", "cluster_step",
}
_SYNC_CALLS = {"block_until_ready", "device_get", "sync_on"}
_SPAN_NAMES = {"span", "_span"}


def _is_time_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and _call_name(node) in _TIME_FNS and (
        isinstance(node.func, ast.Attribute)
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == "time"
        or isinstance(node.func, ast.Name)
    )


def _region_status(stmts: List[ast.stmt]) -> Tuple[Optional[str], Optional[int]]:
    """(dispatch_call_name, line) if the statements dispatch without a
    sync; (None, None) when clean."""
    dispatch: Optional[Tuple[str, int]] = None
    for stmt in stmts:
        for node in ast.walk(stmt):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if name in _SYNC_CALLS:
                return None, None
            if name in _SPAN_NAMES and any(
                kw.arg in ("sync", "force") for kw in node.keywords
            ):
                return None, None
            if name in DISPATCH_CALLS and dispatch is None:
                dispatch = (name, node.lineno)
    return dispatch if dispatch else (None, None)


def _scan_wallclock(fn_body: List[ast.stmt], rel: str) -> List[Finding]:
    findings: List[Finding] = []

    def flat(stmts) -> List[ast.stmt]:
        out = []
        for s in stmts:
            out.append(s)
            for block in ("body", "orelse", "finalbody"):
                sub = getattr(s, block, None)
                if sub and not isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.extend(flat(sub))
            for h in getattr(s, "handlers", []):
                out.extend(flat(h.body))
        return out

    stmts = flat(fn_body)
    for i, stmt in enumerate(stmts):
        if not (
            isinstance(stmt, ast.Assign)
            and len(stmt.targets) == 1
            and isinstance(stmt.targets[0], ast.Name)
            and _is_time_call(stmt.value)
        ):
            continue
        timer = stmt.targets[0].id
        for j in range(i + 1, len(stmts)):
            reads = any(
                isinstance(n, ast.Name)
                and n.id == timer
                and isinstance(n.ctx, ast.Load)
                for n in ast.walk(stmts[j])
            )
            if not reads:
                continue
            name, line = _region_status(stmts[i + 1 : j + 1])
            if name is not None:
                findings.append(
                    Finding(
                        "ast-wallclock-sync", rel, stmt.lineno,
                        f"wall-clock pair `{timer}` brackets async JAX "
                        f"dispatch `{name}(...)` (line {line}) without a "
                        f"sync — it measures dispatch, not execution",
                        hint="wrap the region in obs.span(..., force=True) "
                        "with .sync_on(outputs), or jax.block_until_ready "
                        "the results before reading the clock",
                    )
                )
            break
    return findings


def check_file_wallclock_sync(path: Path, tree: ast.AST, rel: str) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            findings.extend(_scan_wallclock(node.body, rel))
    return findings


# ---------------------------------------------------------------------------
# ast-raw-pallas-call
# ---------------------------------------------------------------------------


def _is_kernel_module(rel: str) -> bool:
    parts = Path(rel).parts
    return (
        len(parts) >= 3
        and parts[-1] == "kernel.py"
        and "kernels" in parts[:-1]
    )


def check_file_raw_pallas_call(path: Path, tree: ast.AST, rel: str) -> List[Finding]:
    if _is_kernel_module(rel):
        return []
    findings = []
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and _call_name(node) == "pallas_call":
            findings.append(
                Finding(
                    "ast-raw-pallas-call", rel, node.lineno,
                    "raw pl.pallas_call outside kernels/*/kernel.py — "
                    "kernel launches live in the kernel module, wrappers "
                    "go through its public entry points",
                    hint="move the pallas_call into the kernel package's "
                    "kernel.py and export a wrapper",
                )
            )
    return findings


# ---------------------------------------------------------------------------
# ast-kernel-tile-contract
# ---------------------------------------------------------------------------


def _tile_constants(tree: ast.AST) -> Dict[str, int]:
    return {
        k: v
        for k, v in _module_constants(tree).items()
        if k.startswith("DEFAULT_") and isinstance(v, int)
    }


def _param_defaults(tree: ast.AST) -> List[Tuple[str, int, int]]:
    """(param_name, literal_default, lineno) for tile-like params."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        a = node.args
        pairs = list(zip(reversed(a.args + a.posonlyargs), reversed(a.defaults)))
        pairs += [
            (p, d) for p, d in zip(a.kwonlyargs, a.kw_defaults) if d is not None
        ]
        for param, default in pairs:
            if not param.arg.endswith("_tile"):
                continue
            if isinstance(default, ast.Constant) and isinstance(
                default.value, int
            ):
                out.append((param.arg, default.value, default.lineno))
    return out


def _divisibility_asserts(tree: ast.AST) -> List[Tuple[str, int, int]]:
    """(name, modulus, lineno) from ``assert ... name % N == 0 ...``."""
    out = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Compare):
            continue
        if not (
            isinstance(node.left, ast.BinOp)
            and isinstance(node.left.op, ast.Mod)
            and isinstance(node.left.left, ast.Name)
            and isinstance(node.left.right, ast.Constant)
            and isinstance(node.left.right.value, int)
            and len(node.ops) == 1
            and isinstance(node.ops[0], ast.Eq)
            and isinstance(node.comparators[0], ast.Constant)
            and node.comparators[0].value == 0
        ):
            continue
        out.append((node.left.left.id, node.left.right.value, node.lineno))
    return out


def check_tree_kernel_tile_contract(roots: Iterable[Path], rel_to: Path) -> List[Finding]:
    findings: List[Finding] = []
    for kernel_py in iter_py_files(roots):
        if kernel_py.name != "kernel.py":
            continue
        k_tree, k_lines = parse_file(kernel_py)
        if k_tree is None:
            continue
        k_rel = _rel(kernel_py, rel_to)
        consts = _tile_constants(k_tree)
        # (3) the kernel's own divisibility asserts must hold for its
        # shipped defaults
        for name, mod, line in _divisibility_asserts(k_tree):
            const = consts.get("DEFAULT_" + name.upper())
            if const is not None and const % mod:
                findings.append(
                    Finding(
                        "ast-kernel-tile-contract", k_rel, line,
                        f"kernel asserts `{name} % {mod} == 0` but its own "
                        f"DEFAULT_{name.upper()} = {const} violates it",
                        hint=f"make DEFAULT_{name.upper()} a multiple of {mod}",
                    )
                )
        ops_py = kernel_py.with_name("ops.py")
        if not ops_py.exists():
            continue
        o_tree, _ = parse_file(ops_py)
        if o_tree is None:
            continue
        o_rel = _rel(ops_py, rel_to)
        # (1) ops.py must not redefine a kernel tile constant
        for name, val in _tile_constants(o_tree).items():
            if name in consts and val != consts[name]:
                findings.append(
                    Finding(
                        "ast-kernel-tile-contract", o_rel, 1,
                        f"ops.py redefines {name} = {val}, kernel.py has "
                        f"{consts[name]} — the padding math and the kernel "
                        f"grid disagree",
                        hint=f"import {name} from .kernel instead of "
                        "redefining it",
                    )
                )
        # (2) literal tile defaults in ops.py signatures must match
        for pname, val, line in _param_defaults(o_tree):
            const_name = "DEFAULT_" + pname.upper()
            if const_name in consts and val != consts[const_name]:
                findings.append(
                    Finding(
                        "ast-kernel-tile-contract", o_rel, line,
                        f"ops.py defaults {pname}={val} but kernel.py "
                        f"{const_name} = {consts[const_name]}",
                        hint=f"default the parameter to {const_name} "
                        "imported from .kernel",
                    )
                )
    return findings


def _rel(path: Path, rel_to: Path) -> str:
    try:
        return str(Path(path).resolve().relative_to(Path(rel_to).resolve()))
    except ValueError:
        return str(path)


# ---------------------------------------------------------------------------
# registered checks (ctx-driven)
# ---------------------------------------------------------------------------


def _run_file_check(ctx, per_file) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(ctx.ast_roots):
        tree, lines = parse_file(path)
        if tree is None:
            continue
        rel = _rel(path, ctx.repo_root)
        findings.extend(filter_inline_suppressed(per_file(path, tree, rel), lines))
    return findings


@register(
    "ast-traced-branch", family="ast", code="LAF301",
    description="no python if/assert/while on traced values in jitted code",
)
def _check_traced_branch(ctx) -> List[Finding]:
    return _run_file_check(ctx, check_file_traced_branch)


@register(
    "ast-wallclock-sync", family="ast", code="LAF302",
    description="no wall-clock timing around JAX dispatch without a sync",
)
def _check_wallclock(ctx) -> List[Finding]:
    return _run_file_check(ctx, check_file_wallclock_sync)


@register(
    "ast-raw-pallas-call", family="ast", code="LAF303",
    description="pl.pallas_call only inside kernels/*/kernel.py",
)
def _check_pallas(ctx) -> List[Finding]:
    return _run_file_check(ctx, check_file_raw_pallas_call)


@register(
    "ast-kernel-tile-contract", family="ast", code="LAF304",
    description="kernel.py/ops.py tile constants and divisibility agree",
)
def _check_tiles(ctx) -> List[Finding]:
    return check_tree_kernel_tile_contract(ctx.ast_roots, ctx.repo_root)


# ---------------------------------------------------------------------------
# flake8 plugin
# ---------------------------------------------------------------------------


class LafLintPlugin:
    """flake8 plugin entry point (AST-family checks only — jaxpr/HLO
    passes need live tracing and stay in ``python -m repro.analysis``).

    Register in setup.cfg/pyproject under ``flake8.extension`` as
    ``LAF = repro.analysis.ast_lint:LafLintPlugin``.
    """

    name = "laf-lint"
    version = "1.0.0"

    def __init__(self, tree: ast.AST, filename: str = "<unknown>"):
        self._tree = tree
        self._filename = filename

    def run(self):
        from .registry import CHECKS, load_all_checks

        load_all_checks()
        path = Path(self._filename)
        rel = str(path)
        findings: List[Finding] = []
        for per_file in (
            check_file_traced_branch,
            check_file_wallclock_sync,
            check_file_raw_pallas_call,
        ):
            findings.extend(per_file(path, self._tree, rel))
        try:
            lines = path.read_text().splitlines()
            findings = filter_inline_suppressed(findings, lines)
        except OSError:
            pass
        for f in findings:
            code = CHECKS[f.check].code if f.check in CHECKS else "LAF300"
            yield f.line, 0, f"{code} {f.message}", type(self)

"""jaxpr passes: invariants checked on the traced form of the real
entry points (the standard targets in :mod:`.targets`).

* ``jaxpr-donation-alias`` (LAF101) — every ``donate_argnums`` slot of a
  donated launch actually aliases an output in the lowered module
  (``tf.aliasing_output``).  XLA silently *drops* infeasible donations
  (shape/dtype mismatch between the donated operand and every output),
  so a refactor that breaks aliasing costs a slab copy per launch with
  no error anywhere — this is the only place it shows up.
* ``jaxpr-donation-reuse`` (LAF102) — no Python-level read of a buffer
  after it was passed into a donating jitted callable without being
  rebound (use-after-donate is undefined behavior on real backends).
  AST dataflow over the source tree: module-level
  ``X = jax.jit(f, donate_argnums=...)`` products and their local
  aliases are tracked; the donated argument slots poison bare-``Name``
  arguments, assignment rebinds heal them.
* ``jaxpr-host-callback-in-loop`` (LAF103) — no
  ``pure_callback``/``io_callback``/``debug_callback`` primitive inside
  a ``scan``/``while`` body of any standard target: a host round-trip
  per loop iteration serializes the device pipeline the sweep engine
  exists to keep full.
* ``jaxpr-shardmap-replication`` (LAF104) — taint analysis of every
  ``shard_map`` eqn: an output whose value still depends on a mesh axis
  (sharded inputs, ``axis_index``) must declare that axis in its
  ``out_specs``.  The plane runs ``check_vma=False`` (the pallas calls
  defeat JAX's own rep checker), so this is the replication safety net:
  a dropped ``psum`` otherwise returns shard-local counts as if global.
* ``jaxpr-packed-while-carry`` (LAF106) — no unsigned-dtype (packed
  word) array in a ``lax.while_loop`` carry of any standard target.
  The one-launch cluster program iterates label-propagation rounds
  under ``while`` with the packed slab closed over as a loop-invariant
  operand; a slab that ends up in the carry is copied (or worse,
  re-masked) every round and on a mesh invites per-round packed-word
  collectives (the LAF202 violation).  ``fori_loop`` lowers to
  ``scan``, so the sweep engine's legitimate packed accumulator is not
  flagged.
* ``jaxpr-recompile-lattice`` (LAF105) — the compile-signature lattices
  stay bounded: ``plan_sweep``'s launch shapes over any nq, the serving
  ``bucket_shape`` image over any traffic, and (dynamic, probed with
  metrics on) the ``obs.PAIRED_COUNTERS`` contract that sweep
  recompiles move 1:1 with capacity doublings.
* ``jaxpr-restore-replica`` (LAF108) — a replica restored from a
  snapshot reuses the pre-crash compile signatures: ``state_import``
  must reproduce the *capacity-shaped* device operands (including
  post-``partial_fit`` append slack), so re-running the same query
  shapes after a restore adds zero new entries to the recompile
  lattice.  A restore that trims buffers to the exact row count
  compiles a fresh signature on the very first post-recovery sweep —
  recovery time then includes a silent engine recompile.

jax imports are deferred to call time so ``--list-checks`` stays
jax-free.
"""

from __future__ import annotations

import ast
from pathlib import Path
from typing import Dict, FrozenSet, List, Optional, Tuple

from .ast_lint import (
    _call_name,
    _rel,
    filter_inline_suppressed,
    iter_py_files,
    parse_file,
)
from .registry import Finding, register

__all__ = [
    "check_donation_text",
    "check_file_donation_reuse",
    "check_jaxpr_callbacks",
    "check_jaxpr_packed_while_carry",
    "check_jaxpr_shardmaps",
    "check_restore_signatures",
    "taint_shard_map_outputs",
]

Taint = FrozenSet[str]
_EMPTY: Taint = frozenset()

_CALLBACK_PRIMS = {"pure_callback", "io_callback", "debug_callback"}
_LOOP_PRIMS = {"scan", "while"}
_AXIS_CLEARING_PRIMS = {
    "psum", "pmax", "pmin", "all_gather", "all_to_all", "reduce_scatter",
    "psum2",
}


# ---------------------------------------------------------------------------
# jaxpr plumbing
# ---------------------------------------------------------------------------


def _as_open(j):
    """ClosedJaxpr | Jaxpr -> Jaxpr."""
    return j.jaxpr if hasattr(j, "jaxpr") else j


def _param_jaxprs(eqn):
    """Every sub-jaxpr in an eqn's params, opened."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (tuple, list)) else (v,)
        for x in vs:
            if hasattr(x, "eqns") or hasattr(x, "jaxpr") and hasattr(
                _as_open(x), "eqns"
            ):
                try:
                    out.append(_as_open(x))
                except Exception:
                    pass
    return [j for j in out if hasattr(j, "eqns")]


def _walk_eqns(jaxpr, depth: int = 0):
    """Yield (eqn, loop_depth) over the whole nest."""
    for eqn in _as_open(jaxpr).eqns:
        yield eqn, depth
        bump = 1 if eqn.primitive.name in _LOOP_PRIMS else 0
        for sub in _param_jaxprs(eqn):
            yield from _walk_eqns(sub, depth + bump)


# ---------------------------------------------------------------------------
# LAF101: donation survives lowering
# ---------------------------------------------------------------------------


def check_donation_text(lowered_text: str, n_donated: int, label: str) -> List[Finding]:
    """Donation survives lowering: the module must carry one
    ``tf.aliasing_output`` attribute per donated argument."""
    aliased = lowered_text.count("tf.aliasing_output")
    if n_donated and aliased < n_donated:
        return [
            Finding(
                "jaxpr-donation-alias", label, 0,
                f"{n_donated} argument(s) are donated but only "
                f"{aliased} alias an output in the lowered module — "
                f"XLA dropped the donation silently (slab copy per "
                f"launch)",
                hint="donated operands must match an output's "
                "shape+dtype exactly; check the launch signature "
                "against its slab outputs",
            )
        ]
    return []


@register(
    "jaxpr-donation-alias", family="jaxpr", code="LAF101",
    description="every donate_argnums slot aliases an output after lowering",
)
def _check_donation_alias(ctx) -> List[Finding]:
    findings = []
    for t in ctx.targets.all():
        findings.extend(check_donation_text(t.lowered_text, t.n_donated, t.label))
    return findings


# ---------------------------------------------------------------------------
# LAF102: no use-after-donate (AST dataflow)
# ---------------------------------------------------------------------------


def _donating_defs(tree: ast.AST) -> Dict[str, Tuple[int, ...]]:
    """Module-level ``X = jax.jit(f, donate_argnums=...)`` bindings."""
    out: Dict[str, Tuple[int, ...]] = {}
    for stmt in getattr(tree, "body", []):
        if not (
            isinstance(stmt, ast.Assign)
            and len(stmt.targets) == 1
            and isinstance(stmt.targets[0], ast.Name)
            and isinstance(stmt.value, ast.Call)
            and _call_name(stmt.value) == "jit"
        ):
            continue
        for kw in stmt.value.keywords:
            if kw.arg != "donate_argnums":
                continue
            try:
                v = ast.literal_eval(kw.value)
            except (ValueError, SyntaxError):
                continue
            nums = (v,) if isinstance(v, int) else tuple(
                x for x in v if isinstance(x, int)
            )
            if nums:
                out[stmt.targets[0].id] = nums
    return out


def check_file_donation_reuse(path: Path, tree: ast.AST, rel: str) -> List[Finding]:
    donated = _donating_defs(tree)
    if not donated:
        return []
    findings: List[Finding] = []
    seen = set()

    def scan_fn(fn) -> None:
        donating = dict(donated)   # name -> donate slots (plus aliases)
        poisoned: Dict[str, int] = {}   # var -> donating call line

        def flat(stmts):
            # loop bodies twice: a donate in iteration k poisons reads
            # in iteration k+1
            out = []
            for s in stmts:
                out.append(s)
                for block in ("body", "orelse", "finalbody"):
                    sub = getattr(s, block, None)
                    if sub and not isinstance(
                        s, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ):
                        rep = 2 if isinstance(s, (ast.For, ast.While)) else 1
                        for _ in range(rep):
                            out.extend(flat(sub))
                for h in getattr(s, "handlers", []):
                    out.extend(flat(h.body))
            return out

        def scan_roots(stmt):
            # compound statements appear in flat() AND contribute their
            # nested statements separately — scanning the whole subtree
            # here would process body effects one statement early, so
            # restrict compounds to their header expressions
            if isinstance(stmt, (ast.If, ast.While)):
                return [stmt.test]
            if isinstance(stmt, ast.For):
                return [stmt.iter]
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                return [i.context_expr for i in stmt.items]
            if isinstance(stmt, (ast.Try, ast.FunctionDef, ast.AsyncFunctionDef)):
                return []
            return [stmt]

        def walk_headers(stmt):
            for root in scan_roots(stmt):
                yield from ast.walk(root)

        for stmt in flat(fn.body):
            # alias creation: `launch = _donated if cond else _plain`
            if (
                isinstance(stmt, ast.Assign)
                and len(stmt.targets) == 1
                and isinstance(stmt.targets[0], ast.Name)
                and not isinstance(stmt.value, ast.Call)
            ):
                refs = {
                    n.id
                    for n in ast.walk(stmt.value)
                    if isinstance(n, ast.Name) and n.id in donating
                }
                if refs:
                    nums: set = set()
                    for r in refs:
                        nums.update(donating[r])
                    donating[stmt.targets[0].id] = tuple(sorted(nums))

            # reads of a poisoned buffer = use-after-donate
            for node in walk_headers(stmt):
                if (
                    isinstance(node, ast.Name)
                    and isinstance(node.ctx, ast.Load)
                    and node.id in poisoned
                ):
                    key = (node.id, node.lineno)
                    if key not in seen:
                        seen.add(key)
                        findings.append(
                            Finding(
                                "jaxpr-donation-reuse", rel, node.lineno,
                                f"`{node.id}` is read after being donated "
                                f"to a donate_argnums call on line "
                                f"{poisoned[node.id]} — the buffer is "
                                f"consumed; reading it is undefined",
                                hint="rebind the variable to the call's "
                                "result, or pass a copy",
                            )
                        )

            # donating calls poison their donated bare-Name args
            for node in walk_headers(stmt):
                if not (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donating
                ):
                    continue
                nums = donating[node.func.id]
                for i, a in enumerate(node.args):
                    if isinstance(a, ast.Starred) and isinstance(
                        a.value, ast.Name
                    ):
                        if any(n >= i for n in nums):
                            poisoned[a.value.id] = node.lineno
                    elif i in nums and isinstance(a, ast.Name):
                        poisoned[a.id] = node.lineno

            # assignment rebinds heal the poison
            if isinstance(stmt, (ast.Assign, ast.AugAssign, ast.AnnAssign)):
                targets = (
                    stmt.targets if isinstance(stmt, ast.Assign) else [stmt.target]
                )
                for t in targets:
                    for n in ast.walk(t):
                        if isinstance(n, ast.Name):
                            poisoned.pop(n.id, None)

    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            scan_fn(node)
    return findings


@register(
    "jaxpr-donation-reuse", family="jaxpr", code="LAF102",
    description="no read of a buffer after donating it to a jitted call",
)
def _check_donation_reuse(ctx) -> List[Finding]:
    findings: List[Finding] = []
    for path in iter_py_files(ctx.ast_roots):
        tree, lines = parse_file(path)
        if tree is None:
            continue
        rel = _rel(path, ctx.repo_root)
        findings.extend(
            filter_inline_suppressed(
                check_file_donation_reuse(path, tree, rel), lines
            )
        )
    return findings


# ---------------------------------------------------------------------------
# LAF103: host callbacks in hot loops
# ---------------------------------------------------------------------------


def check_jaxpr_callbacks(jaxpr, label: str) -> List[Finding]:
    findings = []
    for eqn, depth in _walk_eqns(jaxpr):
        if depth > 0 and eqn.primitive.name in _CALLBACK_PRIMS:
            findings.append(
                Finding(
                    "jaxpr-host-callback-in-loop", label, 0,
                    f"`{eqn.primitive.name}` inside a loop body (depth "
                    f"{depth}) — one host round-trip per iteration "
                    f"serializes the device pipeline",
                    hint="hoist the callback out of the loop, or "
                    "accumulate on device and call back once per launch",
                )
            )
    return findings


@register(
    "jaxpr-host-callback-in-loop", family="jaxpr", code="LAF103",
    description="no host callback primitive inside a scan/while body",
)
def _check_host_callback(ctx) -> List[Finding]:
    findings = []
    for t in ctx.targets.all():
        findings.extend(check_jaxpr_callbacks(t.jaxpr, t.label))
    return findings


# ---------------------------------------------------------------------------
# LAF106: packed words stay loop-invariant in while carries
# ---------------------------------------------------------------------------


def check_jaxpr_packed_while_carry(jaxpr, label: str) -> List[Finding]:
    findings = []
    for eqn, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        for k, v in enumerate(eqn.invars[cn + bn :]):
            dtype = getattr(getattr(v, "aval", None), "dtype", None)
            if dtype is not None and dtype.kind == "u":
                findings.append(
                    Finding(
                        "jaxpr-packed-while-carry", label, 0,
                        f"while-loop carry slot {k} is {dtype.name} — a "
                        f"packed-word buffer riding the round loop is "
                        f"rebuilt/copied every iteration instead of "
                        f"staying a loop-invariant operand",
                        hint="close over the packed slab (while body "
                        "consts) and carry only the s32 label vectors; "
                        "fori_loop accumulators belong in scan",
                    )
                )
    return findings


@register(
    "jaxpr-packed-while-carry", family="jaxpr", code="LAF106",
    description="no packed (unsigned) words in a lax.while_loop carry — "
    "the slab is a loop-invariant operand of the round loop",
)
def _check_packed_while_carry(ctx) -> List[Finding]:
    findings = []
    for t in ctx.targets.all():
        findings.extend(check_jaxpr_packed_while_carry(t.jaxpr, t.label))
    return findings


# ---------------------------------------------------------------------------
# LAF107: telemetry carries are scalars / small vectors only
# ---------------------------------------------------------------------------

# a while carry slot may be 1-D up to this many elements (the label
# vector at the standard config is (2048,), the telemetry vectors are
# (64,)); anything 2-D+, or 1-D past this, is slab-sized state being
# rebuilt every round instead of riding as a loop-invariant operand
TELEMETRY_CARRY_MAX_ELEMS = 65536


def check_jaxpr_telemetry_carry(jaxpr, label: str) -> List[Finding]:
    findings = []
    for eqn, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name != "while":
            continue
        cn = eqn.params.get("cond_nconsts", 0)
        bn = eqn.params.get("body_nconsts", 0)
        for k, v in enumerate(eqn.invars[cn + bn :]):
            aval = getattr(v, "aval", None)
            shape = getattr(aval, "shape", None)
            if shape is None:
                continue
            ndim = len(shape)
            elems = 1
            for s in shape:
                elems *= int(s)
            if ndim >= 2 or elems > TELEMETRY_CARRY_MAX_ELEMS:
                findings.append(
                    Finding(
                        "jaxpr-telemetry-carry", label, 0,
                        f"while-loop carry slot {k} is "
                        f"{getattr(aval, 'dtype', '?')}{tuple(shape)} — "
                        f"telemetry/state riding the round loop must be "
                        f"s32/f32 scalars or small vectors (<= "
                        f"{TELEMETRY_CARRY_MAX_ELEMS} elems, 1-D), not a "
                        f"matrix rebuilt every iteration",
                        hint="accumulate per-round scalars into a "
                        "(max_iters,) vector via dynamic_update_slice; "
                        "O(n) slabs belong in the loop-invariant consts "
                        "(or a fori_loop/scan accumulator outside the "
                        "fixpoint)",
                    )
                )
    return findings


@register(
    "jaxpr-telemetry-carry", family="jaxpr", code="LAF107",
    description="while-loop carries are scalars or small 1-D vectors — "
    "no matrices / O(n)-per-round arrays riding the fixpoint",
)
def _check_telemetry_carry(ctx) -> List[Finding]:
    findings = []
    for t in ctx.targets.all():
        findings.extend(check_jaxpr_telemetry_carry(t.jaxpr, t.label))
    return findings


# ---------------------------------------------------------------------------
# LAF104: shard_map replication safety (taint)
# ---------------------------------------------------------------------------


def _norm_axes(v) -> Taint:
    if v is None:
        return _EMPTY
    if isinstance(v, str):
        return frozenset((v,))
    if isinstance(v, (tuple, list)):
        out = set()
        for x in v:
            if isinstance(x, str):
                out.add(x)
            elif isinstance(x, (tuple, list)):
                out.update(y for y in x if isinstance(y, str))
        return frozenset(out)
    return _EMPTY


def _spec_axes(spec) -> Taint:
    """shard_map in_specs/out_specs entry (a PartitionSpec) -> axis set."""
    out = set()
    for axes in spec:
        out.update(_norm_axes(axes))
    return frozenset(out)


def _taint_closed(closed, ins: List[Taint]) -> List[Taint]:
    jaxpr = _as_open(closed)
    if len(ins) != len(jaxpr.invars):
        # arity mismatch (transform-wrapped call): be conservative
        u = frozenset().union(*ins) if ins else _EMPTY
        return [u] * len(jaxpr.outvars)
    return _taint_jaxpr(jaxpr, ins)


def _taint_jaxpr(jaxpr, in_taints: List[Taint]) -> List[Taint]:
    env: Dict[object, Taint] = {}

    def read(v) -> Taint:
        if type(v).__name__ == "Literal":
            return _EMPTY
        return env.get(v, _EMPTY)

    for v in jaxpr.constvars:
        env[v] = _EMPTY
    for v, t in zip(jaxpr.invars, in_taints):
        env[v] = t

    for eqn in jaxpr.eqns:
        prim = eqn.primitive.name
        ins = [read(v) for v in eqn.invars]
        union = frozenset().union(*ins) if ins else _EMPTY
        if prim in _AXIS_CLEARING_PRIMS:
            cleared = _norm_axes(
                eqn.params.get("axes", eqn.params.get("axis_name"))
            )
            outs = [union - cleared] * len(eqn.outvars)
        elif prim == "axis_index":
            outs = [_norm_axes(eqn.params.get("axis_name"))]
        elif prim == "scan":
            outs = _taint_scan(eqn, ins)
        elif prim == "while":
            outs = _taint_while(eqn, ins)
        elif prim == "cond":
            outs = _taint_cond(eqn, ins)
        else:
            sub = None
            for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
                if key in eqn.params and hasattr(
                    _as_open(eqn.params[key]), "eqns"
                ):
                    sub = eqn.params[key]
                    break
            if sub is not None:
                outs = _taint_closed(sub, ins)
                if len(outs) != len(eqn.outvars):
                    outs = [union] * len(eqn.outvars)
            else:
                outs = [union] * len(eqn.outvars)
        for v, t in zip(eqn.outvars, outs):
            env[v] = t

    return [read(v) for v in jaxpr.outvars]


def _taint_scan(eqn, ins: List[Taint]) -> List[Taint]:
    closed = eqn.params["jaxpr"]
    nc, nk = eqn.params["num_consts"], eqn.params["num_carry"]
    consts, carry, xs = list(ins[:nc]), list(ins[nc : nc + nk]), list(ins[nc + nk :])
    # carry fixpoint: a psum inside the body keeps the carry clean even
    # though the conservative union would not — precision matters here
    # (the plane's count psum lives inside its pipeline scan)
    for _ in range(8):
        outs = _taint_closed(closed, consts + carry + xs)
        new = [c | o for c, o in zip(carry, outs[:nk])]
        if new == carry:
            break
        carry = new
    outs = _taint_closed(closed, consts + carry + xs)
    return list(outs[:nk]) + list(outs[nk:])


def _taint_while(eqn, ins: List[Taint]) -> List[Taint]:
    cn, bn = eqn.params["cond_nconsts"], eqn.params["body_nconsts"]
    cond, body = eqn.params["cond_jaxpr"], eqn.params["body_jaxpr"]
    cconsts, bconsts, carry = (
        list(ins[:cn]), list(ins[cn : cn + bn]), list(ins[cn + bn :]),
    )
    for _ in range(8):
        pred = _taint_closed(cond, cconsts + carry)
        pred_t = pred[0] if pred else _EMPTY
        outs = _taint_closed(body, bconsts + carry)
        new = [c | o | pred_t for c, o in zip(carry, outs)]
        if new == carry:
            break
        carry = new
    return carry


def _taint_cond(eqn, ins: List[Taint]) -> List[Taint]:
    branches = eqn.params.get("branches", ())
    idx_t, operands = ins[0] if ins else _EMPTY, ins[1:]
    n_out = len(eqn.outvars)
    outs = [idx_t] * n_out
    for br in branches:
        b_outs = _taint_closed(br, list(operands))
        if len(b_outs) == n_out:
            outs = [o | b for o, b in zip(outs, b_outs)]
        else:
            u = frozenset().union(*operands) if operands else _EMPTY
            outs = [o | u | idx_t for o in outs]
    return outs


def taint_shard_map_outputs(eqn) -> List[Tuple[Taint, Taint]]:
    """Per shard_map output: (residual_taint, declared_axes)."""
    body = _as_open(eqn.params["jaxpr"])
    ins = [_spec_axes(s) for s in eqn.params["in_specs"]]
    outs = _taint_closed(body, ins)
    result = []
    for t, spec in zip(outs, eqn.params["out_specs"]):
        declared = _spec_axes(spec)
        result.append((t - declared, declared))
    return result


def _find_shard_maps(jaxpr):
    for eqn, _ in _walk_eqns(jaxpr):
        if eqn.primitive.name == "shard_map":
            yield eqn


def check_jaxpr_shardmaps(jaxpr, label: str) -> List[Finding]:
    findings = []
    for eqn in _find_shard_maps(jaxpr):
        for k, (resid, declared) in enumerate(taint_shard_map_outputs(eqn)):
            if resid:
                findings.append(
                    Finding(
                        "jaxpr-shardmap-replication", label, 0,
                        f"shard_map output {k} still depends on mesh "
                        f"axes {sorted(resid)} but out_specs declares "
                        f"only {sorted(declared) or 'replicated'} — "
                        f"with check_vma=False each device returns its "
                        f"shard-local value as if it were global",
                        hint="psum/all_gather over the residual axes "
                        "before returning, or declare the output "
                        "sharded over them",
                    )
                )
    return findings


@register(
    "jaxpr-shardmap-replication", family="jaxpr", code="LAF104",
    description="shard_map outputs declared replicated are actually replicated",
)
def _check_shardmap_replication(ctx) -> List[Finding]:
    findings = []
    for t in ctx.targets.all():
        findings.extend(check_jaxpr_shardmaps(t.jaxpr, t.label))
    return findings


# ---------------------------------------------------------------------------
# LAF105: recompile lattice boundedness (+ the paired-counter probe)
# ---------------------------------------------------------------------------


def _lattice_static_findings() -> List[Finding]:
    from ..index.sweep import DEFAULT_CHUNKS_PER_LAUNCH, plan_sweep
    from ..stream.serve import bucket_shape

    findings = []
    sigs = {
        (p.rows_per_launch, p.chunk, p.cpl)
        for p in (plan_sweep(nq, 256) for nq in range(1, 4097))
    }
    bound = DEFAULT_CHUNKS_PER_LAUNCH + 2
    if len(sigs) > bound:
        findings.append(
            Finding(
                "jaxpr-recompile-lattice", "src/repro/index/sweep.py", 0,
                f"plan_sweep emits {len(sigs)} distinct launch signatures "
                f"over nq in [1, 4096] at chunk=256 (bound: {bound}) — "
                f"each is one engine compile",
                hint="launch shapes must quantize to the "
                "chunks_per_launch ladder; check the cpl clamp",
            )
        )
    import math

    buckets = {
        bucket_shape(nc, nb, db_tile=256, chunk=256, q_tile=128)
        for nc in range(1, 4097, 7)
        for nb in range(1, 257, 3)
    }
    b_bound = (int(math.log2(4096 // 256)) + 1) * (int(math.log2(256 // 128)) + 1)
    if len(buckets) > b_bound:
        findings.append(
            Finding(
                "jaxpr-recompile-lattice", "src/repro/stream/serve.py", 0,
                f"bucket_shape's image has {len(buckets)} shapes over "
                f"candidates<=4096, blocks<=256 (O(log n) bound: "
                f"{b_bound}) — serving compiles are not log-bounded",
                hint="bucket and chunk must both quantize to powers of "
                "two clamped to the tile bounds",
            )
        )
    return findings


def _paired_counter_findings() -> List[Finding]:
    """Dynamic probe of ``obs.PAIRED_COUNTERS``: run the steady-shape
    append workload (mirrors tests/test_obs.py) and require each pair's
    deltas to move in lockstep."""
    import numpy as np

    from .. import obs
    from ..data.synthetic import make_angular_clusters
    from ..index import RandomProjectionBackend
    from ..obs import metrics

    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=False, metrics_on=True)
    findings: List[Finding] = []
    try:
        data, _ = make_angular_clusters(
            613, 32, 8, kappa=120, noise_frac=0.3, seed=2
        )
        bk = RandomProjectionBackend(
            device=True, interpret=True, sweep=True,
            n_bits=64, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64,
        )
        bk.fit(data[:128])
        rows = np.arange(64)
        bk.query_counts(rows, 0.55)  # first sweep pays the initial compile
        names = {n for pair in obs.PAIRED_COUNTERS for n in pair}
        base = {n: metrics.counter(n).value for n in names}
        for start in range(128, 613, 97):
            bk.partial_fit(data[start : start + 97])
            bk.query_counts(rows, 0.55)
        delta = {n: metrics.counter(n).value - base[n] for n in names}
        for left, right in obs.PAIRED_COUNTERS:
            if delta[left] != delta[right]:
                findings.append(
                    Finding(
                        "jaxpr-recompile-lattice", f"<probe:{left}>", 0,
                        f"paired counters diverged over a steady-query-"
                        f"shape append workload: {left} moved "
                        f"{delta[left]}, {right} moved {delta[right]} — "
                        f"a recompile happened without (or beyond) its "
                        f"capacity doubling",
                        hint="a static arg or operand shape other than "
                        "capacity changed across appends; diff the jit "
                        "signatures",
                    )
                )
    finally:
        if was_trace or was_metrics:
            obs.enable(trace=was_trace, metrics_on=was_metrics)
        else:
            obs.disable()
    return findings


@register(
    "jaxpr-recompile-lattice", family="jaxpr", code="LAF105",
    description="compile-signature lattices are bounded; recompiles pair "
    "1:1 with capacity doublings",
)
def _check_recompile_lattice(ctx) -> List[Finding]:
    findings = _lattice_static_findings()
    if getattr(ctx, "dynamic", True):
        findings.extend(_paired_counter_findings())
    return findings


# ---------------------------------------------------------------------------
# LAF108: restored replicas reuse pre-crash compile signatures
# ---------------------------------------------------------------------------


def check_restore_signatures(pre, post, label: str) -> List[Finding]:
    """The restore contract as a pure predicate: every compile signature
    observed after a restore must already exist in the pre-crash set.

    ``pre`` / ``post`` are iterables of hashable signatures (operand
    shape tuples, or whatever the caller quantizes compiles by).  The
    corpus twins feed this directly; the dynamic probe asserts the same
    thing through the live ``sweep.recompiles`` counter.
    """
    pre_set = set(pre)
    fresh = sorted({s for s in post if s not in pre_set}, key=repr)
    if fresh:
        return [
            Finding(
                "jaxpr-restore-replica", label, 0,
                f"restore introduced {len(fresh)} compile signature(s) "
                f"absent before the crash: {fresh[:3]!r} — the restored "
                f"replica pays an engine recompile on its first query",
                hint="state_import must rebuild the capacity-shaped "
                "buffers (append slack included), not trim to the exact "
                "row count",
            )
        ]
    return []


def _restore_probe_findings() -> List[Finding]:
    """Dynamic probe: warm the sweep compile lattice on a backend with
    post-``partial_fit`` append slack, export/import its state into a
    fresh instance, re-run the same query shapes, and require zero new
    ``sweep.recompiles`` (the jitted launches are module-level, so a
    faithful restore hits the pre-crash executable cache)."""
    import numpy as np

    from .. import obs
    from ..data.synthetic import make_angular_clusters
    from ..index import RandomProjectionBackend
    from ..obs import metrics

    # geometry deliberately disjoint from the LAF105 probe / test_obs
    # workload (d=48, n_bits=128): this probe also runs in-process from
    # tier-1, and sharing operand shapes with the recompile-lattice
    # workload would pre-warm the module-level jit caches it measures
    kw = dict(
        device=True, interpret=True, sweep=True,
        n_bits=128, margin=3.0, seed=3, chunk=64, q_tile=32, db_tile=64,
    )
    was_trace, was_metrics = obs.trace_enabled(), obs.metrics_enabled()
    obs.enable(trace=False, metrics_on=True)
    findings: List[Finding] = []
    try:
        data, _ = make_angular_clusters(
            400, 48, 8, kappa=120, noise_frac=0.3, seed=5
        )
        bk = RandomProjectionBackend(**kw)
        bk.fit(data[:256])
        bk.partial_fit(data[256:])  # capacity doubles: append slack on board
        rows = np.arange(48)
        bk.query_counts(rows, 0.55)  # warm the lattice at this query shape
        bk.query_hits(rows, 0.55)
        state = bk.state_export()

        pre = metrics.counter("sweep.recompiles").value
        bk2 = RandomProjectionBackend(**kw).state_import(state)
        bk2.query_counts(rows, 0.55)
        bk2.query_hits(rows, 0.55)
        delta = metrics.counter("sweep.recompiles").value - pre
        if delta:
            findings.append(
                Finding(
                    "jaxpr-restore-replica", "src/repro/index/random_projection.py",
                    0,
                    f"restored replica compiled {delta} new sweep "
                    f"signature(s) re-running the pre-crash query shapes "
                    f"— state_import does not reproduce the capacity-"
                    f"shaped operands",
                    hint="export/import the full capacity buffers "
                    "(_data_buf/_sigs_buf), not the n-row views",
                )
            )
    finally:
        if was_trace or was_metrics:
            obs.enable(trace=was_trace, metrics_on=was_metrics)
        else:
            obs.disable()
    return findings


@register(
    "jaxpr-restore-replica", family="jaxpr", code="LAF108",
    description="snapshot restore reuses pre-crash compile signatures "
    "(recompile-free recovery)",
)
def _check_restore_replica(ctx) -> List[Finding]:
    if getattr(ctx, "dynamic", True):
        return _restore_probe_findings()
    return []

"""fluidshape — kernel shape/dtype/bounds and Mosaic-compliance rules.

Scope is the kernel layer (``ops/``, ``parallel/``): the files that build
Pallas blocks, narrow transfer buffers, and jitted entry points.  The two
most expensive bugs in this repo's history were contract violations in
exactly this layer, and both were only caught at runtime on scarce
hardware:

- the Pallas fold failing Mosaic's (8, 128) sublane/lane block rule voided
  the only TPU measurement ever taken (r05) — ``FL-KERN-BLOCK`` is that
  failure as a static invariant, blind to ``interpret=True`` (interpret
  mode accepts blocks Mosaic rejects, which is precisely how r05 shipped);
- the int16 arena-offset overflow (r13) surfaced only when a full-scale
  bench blew the bound — ``FL-KERN-NARROW`` demands every narrow-dtype
  construction be dominated by a declared bound guard.

Annotations (trailing comments on the flagged statement):

- ``# block-rule: <helper>`` — a non-literal BlockSpec/grid dim is rounded
  by ``<helper>``; the name must be a recognized rounding helper.
- ``# bound: <expr>`` — a narrow cast is covered by the named bound guard;
  the expression must reference a guard name (``i16_ok`` / ``I16_LIMIT``
  style) or a module-level definition.
- ``# bucketed-by: <helper>`` — a data-dependent shape expression was
  routed through a bucket ladder upstream of this call.
- ``# masked-by: <mask>`` — a padded plane is masked before the flagged
  reduction; the mask name must exist in the function.

A misspelled or unresolvable annotation is itself a finding — a stale
annotation must fail loudly, not silently suppress.

Known limits (deliberate, documented in the README): shape algebra more
than one helper hop away from a literal is not evaluated (annotate);
rounding helpers are recognized per module plus the shared bucket-ladder
names — a helper aliased through another module needs the annotation; the
sublane requirement uses the int32 (8, 128) tile for every plane (narrower
dtypes need larger sublane multiples — the rounding helpers in use round
to LANE, which satisfies all of them).  Static compliance does NOT replace
the interpret-mode parity tests: Mosaic alignment says a kernel CAN
compile, parity says it computes the right thing.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, Iterator, List, Optional, Set, Tuple

from .core import (Finding, ModuleContext, ProjectContext, ProjectRule,
                   Rule, register)
from .rules_concurrency import _owner_phrase, _terminal_name, _walk_pruned
from .rules_lifecycle import _functions
from .rules_trace import KERNEL_SCOPE, _entrypoint_of

SUBLANE = 8    # Mosaic second-to-last dim multiple (int32 tile)
LANE = 128     # Mosaic last dim multiple (every dtype)

#: shared bucket-ladder helpers (ops/interning.py, ops/tree_kernel.py) —
#: recognized by name in every kernel module they are imported into.
BUCKET_HELPER_NAMES = frozenset({
    "next_bucket", "next_bucket_fine", "tree_buckets",
})

BLOCK_RE = re.compile(r"block-rule:\s*(\S+)")
BOUND_RE = re.compile(r"bound:\s*(\S.*)")
BUCKET_RE = re.compile(r"bucketed-by:\s*(\S+)")
MASK_RE = re.compile(r"masked-by:\s*(\S+)")

_SIMPLE_STMT = (ast.Assign, ast.AnnAssign, ast.AugAssign, ast.Expr,
                ast.Return, ast.Assert, ast.Raise)


# -- shared shape machinery ---------------------------------------------------


def _scopes(tree: ast.Module) -> Iterator[Tuple[str, ast.AST]]:
    """(owner name, scope node) for the module plus every def; each scope
    is walked pruned, so statements belong to exactly one scope."""
    yield "<module>", tree
    for fn in _functions(tree):
        yield fn.name, fn


def _stmts(scope: ast.AST) -> List[ast.stmt]:
    """Simple statements of one scope in lexical order."""
    out = [n for n in _walk_pruned(scope) if isinstance(n, _SIMPLE_STMT)]
    out.sort(key=lambda n: n.lineno)
    return out


def _module_int_consts(tree: ast.Module) -> Dict[str, int]:
    """Module-level ``NAME = <int literal>`` bindings (DOC_BLOCK, LANE)."""
    out: Dict[str, int] = {}
    for st in tree.body:
        if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                and isinstance(st.targets[0], ast.Name) \
                and isinstance(st.value, ast.Constant) \
                and type(st.value.value) is int:
            out[st.targets[0].id] = st.value.value
    return out


def _module_names(tree: ast.Module) -> Set[str]:
    """Every module-level binding: defs, classes, assignment targets."""
    out: Set[str] = set()
    for st in tree.body:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            out.add(st.name)
        elif isinstance(st, ast.Assign):
            for t in st.targets:
                for n in ast.walk(t):
                    if isinstance(n, ast.Name):
                        out.add(n.id)
        elif isinstance(st, ast.AnnAssign) and isinstance(st.target, ast.Name):
            out.add(st.target.id)
        elif isinstance(st, (ast.Import, ast.ImportFrom)):
            for alias in st.names:
                out.add((alias.asname or alias.name).split(".")[0])
    return out


def _is_roundup(node: ast.AST) -> bool:
    """The canonical round-up shape: ``((n + m - 1) // m) * m``."""
    return (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult)
            and isinstance(node.left, ast.BinOp)
            and isinstance(node.left.op, ast.FloorDiv)
            and ast.dump(node.right) == ast.dump(node.left.right))


def _returns(fn: ast.AST) -> List[ast.Return]:
    return [n for n in _walk_pruned(fn)
            if isinstance(n, ast.Return) and n.value is not None]


def _mult_of_call(call: ast.Call, helpers: Dict[str, dict],
                  consts: Dict[str, int]) -> Optional[int]:
    """The known rounding multiple of one helper call, or None."""
    info = helpers.get(_terminal_name(call.func) or "")
    if info is None:
        return None
    if info.get("const_mult") is not None:
        return info["const_mult"]
    idx = info.get("mult_param")
    if idx is None:
        return None
    arg: Optional[ast.AST] = None
    if idx < len(call.args):
        arg = call.args[idx]
    else:
        params = info.get("params") or ()
        if idx < len(params):
            for kw in call.keywords:
                if kw.arg == params[idx]:
                    arg = kw.value
    if isinstance(arg, ast.Constant) and type(arg.value) is int:
        return arg.value
    if isinstance(arg, ast.Name):
        return consts.get(arg.id)
    return None


def _rounding_helpers(tree: ast.Module,
                      consts: Dict[str, int]) -> Dict[str, dict]:
    """name -> rounding info for every helper recognized in this module.

    Seeds: the shared bucket ladders (unknown multiple — power-of-two
    ladders bound the jit cache but prove no fixed divisor) and every def
    whose returns all match the canonical round-up shape.  Fixpoint:
    wrappers whose returns are calls (or tuples of calls) to known
    helpers, carrying the resolved multiple per tuple position —
    ``_padded_dims`` style.
    """
    helpers: Dict[str, dict] = {
        name: {"const_mult": None, "mult_param": None,
               "params": (), "tuple": None}
        for name in BUCKET_HELPER_NAMES
    }
    for fn in _functions(tree):
        rets = _returns(fn)
        if not rets or not all(_is_roundup(r.value) for r in rets):
            continue
        params = [a.arg for a in fn.args.args]
        entry = {"const_mult": None, "mult_param": None,
                 "params": tuple(params), "tuple": None}
        mult = rets[0].value.right
        if isinstance(mult, ast.Constant) and type(mult.value) is int:
            entry["const_mult"] = mult.value
        elif isinstance(mult, ast.Name):
            if mult.id in params:
                entry["mult_param"] = params.index(mult.id)
            elif mult.id in consts:
                entry["const_mult"] = consts[mult.id]
        helpers[fn.name] = entry

    changed = True
    while changed:
        changed = False
        for fn in _functions(tree):
            if fn.name in helpers:
                continue
            rets = _returns(fn)
            if not rets:
                continue
            scalar_mults: Set[Optional[int]] = set()
            tuples: List[List[Optional[int]]] = []
            ok = True
            for r in rets:
                v = r.value
                if isinstance(v, ast.Call) \
                        and (_terminal_name(v.func) or "") in helpers:
                    scalar_mults.add(_mult_of_call(v, helpers, consts))
                elif isinstance(v, ast.Tuple) and v.elts and all(
                        isinstance(e, ast.Call)
                        and (_terminal_name(e.func) or "") in helpers
                        for e in v.elts):
                    tuples.append([_mult_of_call(e, helpers, consts)
                                   for e in v.elts])
                else:
                    ok = False
                    break
            if not ok or (scalar_mults and tuples):
                continue
            entry = {"const_mult": None, "mult_param": None,
                     "params": tuple(a.arg for a in fn.args.args),
                     "tuple": None}
            if scalar_mults:
                if len(scalar_mults) == 1:
                    entry["const_mult"] = scalar_mults.pop()
            elif tuples:
                if len({len(t) for t in tuples}) != 1:
                    continue
                entry["tuple"] = [
                    t0 if all(t[i] == t0 for t in tuples) else None
                    for i, t0 in enumerate(tuples[0])
                ]
            helpers[fn.name] = entry
            changed = True
    return helpers


def _shape_env(scope: ast.AST, helpers: Dict[str, dict],
               consts: Dict[str, int]) -> Dict[str, Tuple[str, Optional[int]]]:
    """name -> ("const", value) | ("rounded", multiple or None) for the
    bindings a scope makes that the block rule can reason about.  Module
    int consts are visible in every scope; any other rebind of a tracked
    name drops it (conservative)."""
    env: Dict[str, Tuple[str, Optional[int]]] = {
        k: ("const", v) for k, v in consts.items()
    }
    for st in _stmts(scope):
        if not isinstance(st, (ast.Assign, ast.AnnAssign)):
            continue
        targets = st.targets if isinstance(st, ast.Assign) else [st.target]
        value = st.value
        if value is None or len(targets) != 1:
            continue
        tgt = targets[0]
        names = []
        if isinstance(tgt, ast.Name):
            names = [tgt.id]
        elif isinstance(tgt, ast.Tuple) and all(
                isinstance(e, ast.Name) for e in tgt.elts):
            names = [e.id for e in tgt.elts]
        for n in names:
            env.pop(n, None)
        if isinstance(tgt, ast.Name):
            if isinstance(value, ast.Constant) and type(value.value) is int:
                env[tgt.id] = ("const", value.value)
            elif isinstance(value, ast.Name) \
                    and env.get(value.id, ("", 0))[0] == "const":
                env[tgt.id] = env[value.id]
            elif isinstance(value, ast.Call) \
                    and (_terminal_name(value.func) or "") in helpers:
                env[tgt.id] = ("rounded",
                               _mult_of_call(value, helpers, consts))
        elif names and isinstance(value, ast.Call) \
                and (_terminal_name(value.func) or "") in helpers:
            tup = helpers[_terminal_name(value.func)].get("tuple")
            for i, n in enumerate(names):
                mult = tup[i] if tup and i < len(tup) else None
                env[n] = ("rounded", mult)
    return env


def _expr_text(node: ast.AST) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # pragma: no cover - unparse is py3.9+
        return "<expr>"


# -- FL-KERN-BLOCK ------------------------------------------------------------


def _dim_verdict(node: ast.AST, req: int,
                 env: Dict[str, Tuple[str, Optional[int]]]
                 ) -> Tuple[str, Optional[str]]:
    """("ok" | "bad" | "unknown", detail) for one BlockSpec dim against a
    required multiple.  "bad" is a PROVEN violation (fires even under an
    annotation); "unknown" needs a helper route or an annotation."""
    if isinstance(node, ast.Constant) and type(node.value) is int:
        if node.value % req == 0:
            return "ok", None
        return "bad", f"literal {node.value} is not a multiple of {req}"
    if isinstance(node, ast.Name):
        entry = env.get(node.id)
        if entry is None:
            return "unknown", None
        kind, val = entry
        if kind == "const":
            if val % req == 0:
                return "ok", None
            return "bad", f"'{node.id}' is {val}, not a multiple of {req}"
        if kind == "rounded":
            if val is None or val % req == 0:
                return "ok", None
            return "bad", (f"'{node.id}' is rounded to multiples of {val}, "
                           f"not of {req}")
    return "unknown", None


def _grid_clean(node: ast.AST,
                env: Dict[str, Tuple[str, Optional[int]]]) -> bool:
    """Grid extents must be built from constants and helper-rounded
    names — floordiv/mult algebra over those is fine."""
    if isinstance(node, ast.Constant) and type(node.value) is int:
        return True
    if isinstance(node, ast.Name):
        return node.id in env
    if isinstance(node, ast.BinOp) \
            and isinstance(node.op, (ast.FloorDiv, ast.Mult)):
        return _grid_clean(node.left, env) and _grid_clean(node.right, env)
    return False


@register
class KernelBlockRule(Rule):
    name = "FL-KERN-BLOCK"
    severity = "error"
    scope = KERNEL_SCOPE
    description = (
        "Pallas BlockSpec/grid dimension not provably Mosaic-aligned "
        "(the 8-sublane / 128-lane block rule) — route it through a "
        "rounding helper or annotate '# block-rule: <helper>'"
    )

    def check(self, m: ModuleContext) -> Iterable[Finding]:
        consts = _module_int_consts(m.tree)
        helpers = _rounding_helpers(m.tree, consts)
        out: List[Finding] = []
        for owner, scope in _scopes(m.tree):
            phrase = _owner_phrase(owner)
            env = _shape_env(scope, helpers, consts)
            for st in _stmts(scope):
                ann = BLOCK_RE.search(m.stmt_comment(st))
                ann_ok = bool(ann) and ann.group(1) in helpers
                if ann and not ann_ok:
                    out.append(m.finding(self, st, (
                        f"block-rule annotation names '{ann.group(1)}', "
                        f"which is no recognized rounding helper {phrase} — "
                        f"fix the name or register the helper")))
                for call in (n for n in ast.walk(st)
                             if isinstance(n, ast.Call)):
                    q = m.imports.resolve(call.func)
                    if q == "jax.experimental.pallas.BlockSpec":
                        out.extend(self._check_block(
                            m, st, call, env, phrase, ann_ok))
                    elif q == "jax.experimental.pallas.pallas_call":
                        out.extend(self._check_grid(
                            m, st, call, env, phrase, ann_ok))
        return out

    def _check_block(self, m, st, call, env, phrase, ann_ok):
        shape: Optional[ast.AST] = call.args[0] if call.args else None
        for kw in call.keywords:
            if kw.arg == "block_shape":
                shape = kw.value
        if not isinstance(shape, ast.Tuple) or not shape.elts:
            return
        dims = shape.elts
        for i, dim in enumerate(dims):
            pos = len(dims) - i          # 1 = lane dim, 2 = sublane dim
            if pos > 2:
                continue
            req = LANE if pos == 1 else SUBLANE
            verdict, detail = _dim_verdict(dim, req, env)
            if verdict == "ok" or (verdict == "unknown" and ann_ok):
                continue
            what = detail or (
                f"dim {i} {_expr_text(dim)!r} is not provably a "
                f"multiple of {req}")
            yield m.finding(self, st, (
                f"BlockSpec {what} {phrase} — Mosaic's sublane/lane "
                f"block rule rejects this at compile time on TPU even "
                f"though interpret mode accepts it; route the dim "
                f"through a rounding helper or annotate "
                f"'# block-rule: <helper>'"))

    def _check_grid(self, m, st, call, env, phrase, ann_ok):
        grid: Optional[ast.AST] = None
        for kw in call.keywords:
            if kw.arg == "grid":
                grid = kw.value
        if grid is None:
            return
        extents = grid.elts if isinstance(grid, ast.Tuple) else [grid]
        for i, ext in enumerate(extents):
            if _grid_clean(ext, env) or ann_ok:
                continue
            yield m.finding(self, st, (
                f"pallas_call grid extent {i} {_expr_text(ext)!r} "
                f"{phrase} is not built from rounded or constant dims — "
                f"an unpadded extent silently drops trailing rows; "
                f"round the dims first or annotate "
                f"'# block-rule: <helper>'"))


# -- FL-KERN-NARROW -----------------------------------------------------------


NARROW_DTYPES = {
    "numpy.int8": "int8", "numpy.int16": "int16",
    "jax.numpy.int8": "int8", "jax.numpy.int16": "int16",
}
_NARROW_STRS = {"int8", "int16"}
_CONSTRUCTORS = {
    "zeros", "ones", "empty", "full", "asarray", "ascontiguousarray",
    "array", "arange", "frombuffer", "zeros_like", "ones_like",
    "empty_like", "full_like", "int8", "int16",
}
_ACCUM_OPS = {"sum", "cumsum", "prod", "dot", "matmul", "mean", "einsum",
              "tensordot"}
GUARD_NAME_RE = re.compile(r"^(i(8|16)_ok|I(8|16)_LIMIT)$")


def _narrow_dtype_of(m: ModuleContext, node: ast.AST) -> Optional[str]:
    q = m.imports.resolve(node)
    if q in NARROW_DTYPES:
        return NARROW_DTYPES[q]
    if isinstance(node, ast.Constant) and node.value in _NARROW_STRS:
        return node.value
    return None


def _narrow_construction(m: ModuleContext,
                         call: ast.Call) -> Optional[str]:
    """The narrow dtype a call constructs into, or None."""
    operands = list(call.args) + [kw.value for kw in call.keywords]
    if isinstance(call.func, ast.Attribute) and call.func.attr == "astype":
        for arg in operands:
            dt = _narrow_dtype_of(m, arg)
            if dt:
                return dt
        return None
    q = m.imports.resolve(call.func) or ""
    if not (q.startswith("numpy.") or q.startswith("jax.numpy.")):
        return None
    tail = q.rsplit(".", 1)[-1]
    if tail not in _CONSTRUCTORS:
        return None
    if tail in _NARROW_STRS:
        return tail
    for arg in operands:
        dt = _narrow_dtype_of(m, arg)
        if dt:
            return dt
    return None


def _is_guard(m: ModuleContext, node: ast.AST) -> bool:
    """A declared bound guard: the ``i16_ok`` / ``I16_LIMIT`` pack-time
    idiom, an ``iinfo`` bounds lookup, or a dtype comparison (the buffer
    is narrow ALREADY — relayout, not narrowing)."""
    if isinstance(node, ast.Name) and GUARD_NAME_RE.match(node.id):
        return True
    if isinstance(node, ast.Attribute) and GUARD_NAME_RE.match(node.attr):
        return True
    if isinstance(node, ast.Constant) and isinstance(node.value, str) \
            and GUARD_NAME_RE.match(node.value):
        return True
    if isinstance(node, ast.Call) \
            and (_terminal_name(node.func) or "") == "iinfo":
        return True
    if isinstance(node, ast.Compare):
        for sub in ast.walk(node):
            if isinstance(sub, ast.Attribute) and sub.attr == "dtype":
                return True
    return False


def _bound_annotation_valid(expr: str, module_names: Set[str]) -> bool:
    idents = re.findall(r"[A-Za-z_]\w*", expr)
    return any(GUARD_NAME_RE.match(t) or t == "iinfo" or t in module_names
               for t in idents)


@register
class KernelNarrowRule(Rule):
    name = "FL-KERN-NARROW"
    severity = "error"
    scope = KERNEL_SCOPE
    description = (
        "narrow-dtype (int8/int16) construction or accumulation with no "
        "dominating bound guard — declare the i16_ok/I16_LIMIT pack-time "
        "check or annotate '# bound: <expr>'"
    )

    def check(self, m: ModuleContext) -> Iterable[Finding]:
        names = _module_names(m.tree)
        out: List[Finding] = []
        for owner, scope in _scopes(m.tree):
            phrase = _owner_phrase(owner)
            guard_line: Optional[int] = None
            for n in _walk_pruned(scope):
                if _is_guard(m, n):
                    line = getattr(n, "lineno", None)
                    if line is not None and (guard_line is None
                                             or line < guard_line):
                        guard_line = line
            narrow_names: Dict[str, int] = {}
            for st in _stmts(scope):
                stmt_dtype: Optional[str] = None
                for call in (n for n in ast.walk(st)
                             if isinstance(n, ast.Call)):
                    dt = _narrow_construction(m, call)
                    if dt:
                        stmt_dtype = dt
                        break
                accum = None
                if stmt_dtype is None:
                    accum = self._accumulation(st, narrow_names)
                if isinstance(st, ast.Assign):
                    for t in st.targets:
                        if isinstance(t, ast.Name):
                            if stmt_dtype:
                                narrow_names[t.id] = st.lineno
                            else:
                                narrow_names.pop(t.id, None)
                if stmt_dtype is None and accum is None:
                    continue
                if guard_line is not None and guard_line <= st.lineno:
                    continue
                ann = BOUND_RE.search(m.stmt_comment(st))
                if ann:
                    if _bound_annotation_valid(ann.group(1), names):
                        continue
                    out.append(m.finding(self, st, (
                        f"bound annotation {ann.group(1)!r} {phrase} "
                        f"references no bound guard or module name — "
                        f"fix the reference so the declared bound is "
                        f"checkable")))
                    continue
                if stmt_dtype:
                    out.append(m.finding(self, st, (
                        f"narrow {stmt_dtype} construction {phrase} has "
                        f"no dominating bound guard — values over the "
                        f"{stmt_dtype} limit wrap silently; add the "
                        f"i16_ok/I16_LIMIT pack-time check or a "
                        f"'# bound: <expr>' annotation")))
                else:
                    out.append(m.finding(self, st, (
                        f"accumulating op on narrow lanes '{accum}' "
                        f"{phrase} with no dominating bound guard — "
                        f"sums over narrow lanes overflow long before "
                        f"the inputs do; widen first or declare the "
                        f"bound")))
        return out

    @staticmethod
    def _accumulation(st: ast.stmt,
                      narrow_names: Dict[str, int]) -> Optional[str]:
        for call in (n for n in ast.walk(st) if isinstance(n, ast.Call)):
            if (_terminal_name(call.func) or "") not in _ACCUM_OPS:
                continue
            operands: List[ast.AST] = list(call.args)
            if isinstance(call.func, ast.Attribute):
                operands.append(call.func.value)
            for op in operands:
                for sub in ast.walk(op):
                    if isinstance(sub, ast.Name) and sub.id in narrow_names \
                            and narrow_names[sub.id] < st.lineno:
                        return sub.id
        return None


# -- FL-KERN-BUCKET -----------------------------------------------------------


_JIT_ENTRYPOINTS = {"jax.jit", "jax.pmap"}


def _jitted_names(m: ModuleContext) -> Tuple[Set[str], Set[str]]:
    """(jitted callables, jit factories) bound at module level: decorated
    defs, ``name = jax.jit(f)`` bindings, and defs whose every return is
    a jit application (the lru-cached factory idiom)."""
    jitted: Set[str] = set()
    factories: Set[str] = set()
    for node in ast.walk(m.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if any(_entrypoint_of(m.imports, d) in _JIT_ENTRYPOINTS
                   for d in node.decorator_list):
                jitted.add(node.name)
            else:
                rets = _returns(node)
                if rets and all(
                        isinstance(r.value, ast.Call)
                        and _entrypoint_of(m.imports, r.value)
                        in _JIT_ENTRYPOINTS for r in rets):
                    factories.add(node.name)
        elif isinstance(node, ast.Assign) and len(node.targets) == 1 \
                and isinstance(node.targets[0], ast.Name) \
                and isinstance(node.value, ast.Call) \
                and _entrypoint_of(m.imports, node.value) in _JIT_ENTRYPOINTS:
            jitted.add(node.targets[0].id)
    return jitted, factories


def _shape_tainted(node: ast.AST, dirty: Set[str],
                   helpers: Dict[str, dict]) -> bool:
    """True when an expression carries a data-dependent extent (``len``,
    ``.shape``, or a tainted name) not routed through a bucket helper."""
    if isinstance(node, ast.Call):
        if (_terminal_name(node.func) or "") in helpers:
            return False  # routed: the ladder bounds the jit cache
        if isinstance(node.func, ast.Name) and node.func.id == "len":
            return True
    if isinstance(node, ast.Attribute) and node.attr == "shape":
        return True
    if isinstance(node, ast.Name) and node.id in dirty:
        return True
    return any(_shape_tainted(c, dirty, helpers)
               for c in ast.iter_child_nodes(node))


@register
class KernelBucketRule(Rule):
    name = "FL-KERN-BUCKET"
    severity = "error"
    scope = KERNEL_SCOPE
    description = (
        "jitted entry point reached with a data-dependent shape "
        "expression not routed through a bucket-ladder helper — every "
        "distinct extent recompiles; bucket it or annotate "
        "'# bucketed-by: <helper>'"
    )

    def check(self, m: ModuleContext) -> Iterable[Finding]:
        consts = _module_int_consts(m.tree)
        helpers = _rounding_helpers(m.tree, consts)
        jitted, factories = _jitted_names(m)
        if not jitted and not factories:
            return ()
        valid_ann = set(helpers) | {
            fn.name for fn in _functions(m.tree)}
        out: List[Finding] = []
        for owner, scope in _scopes(m.tree):
            if owner in jitted:
                continue  # inside a traced body shapes are already static
            phrase = _owner_phrase(owner)
            dirty: Set[str] = set()
            for st in _stmts(scope):
                self._flag_calls(m, st, jitted, factories, dirty, helpers,
                                 valid_ann, phrase, out)
                if isinstance(st, ast.Assign):
                    tainted = _shape_tainted(st.value, dirty, helpers)
                    for t in st.targets:
                        for n in ast.walk(t):
                            if isinstance(n, ast.Name):
                                if tainted:
                                    dirty.add(n.id)
                                else:
                                    dirty.discard(n.id)
        return out

    def _flag_calls(self, m, st, jitted, factories, dirty, helpers,
                    valid_ann, phrase, out):
        ann = BUCKET_RE.search(m.stmt_comment(st))
        if ann and ann.group(1) not in valid_ann:
            out.append(m.finding(self, st, (
                f"bucketed-by annotation names '{ann.group(1)}', which "
                f"is no recognized bucket or rounding helper {phrase} — "
                f"fix the name so the routing claim is checkable")))
            ann = None
        for call in (n for n in ast.walk(st) if isinstance(n, ast.Call)):
            target = None
            if isinstance(call.func, ast.Name) and call.func.id in jitted:
                target = call.func.id
            elif isinstance(call.func, ast.Call) \
                    and (_terminal_name(call.func.func) or "") in factories:
                target = _terminal_name(call.func.func)
            if target is None:
                continue
            operands = list(call.args) + [kw.value for kw in call.keywords]
            for op in operands:
                if not _shape_tainted(op, dirty, helpers):
                    continue
                if ann:
                    break
                out.append(m.finding(self, st, (
                    f"jitted entry '{target}' called with data-dependent "
                    f"shape expression {_expr_text(op)!r} {phrase} — "
                    f"every distinct value compiles a fresh executable; "
                    f"route it through a bucket ladder or annotate "
                    f"'# bucketed-by: <helper>'")))
                break


# -- FL-KERN-PAD --------------------------------------------------------------


_REDUCERS = {"sum", "cumsum", "prod", "dot", "matmul", "mean", "einsum",
             "tensordot"}


def _is_pad_call(call: ast.Call) -> bool:
    name = _terminal_name(call.func) or ""
    return "pad" in name.lower()


def _contains_pad_call(node: ast.AST) -> bool:
    return any(isinstance(n, ast.Call) and _is_pad_call(n)
               for n in ast.walk(node))


def _mentions(node: ast.AST, name: str) -> bool:
    return any(isinstance(n, ast.Name) and n.id == name
               for n in ast.walk(node))


def _masked_expr(node: ast.AST) -> bool:
    """A mask applied in the consuming expression itself: a ``where``
    call or a mask multiply."""
    for n in ast.walk(node):
        if isinstance(n, ast.Call) \
                and "where" in (_terminal_name(n.func) or ""):
            return True
        if isinstance(n, ast.BinOp) and isinstance(n.op, ast.Mult):
            return True
    return False


@register
class KernelPadRule(Rule):
    name = "FL-KERN-PAD"
    severity = "error"
    scope = KERNEL_SCOPE
    description = (
        "plane built by a pad-producing helper reaches a "
        "reduction/digest with no mask in between — pad rows perturb "
        "the result; mask first or annotate '# masked-by: <mask>'"
    )

    def check(self, m: ModuleContext) -> Iterable[Finding]:
        out: List[Finding] = []
        for owner, scope in _scopes(m.tree):
            phrase = _owner_phrase(owner)
            local_names = {n.id for n in _walk_pruned(scope)
                           if isinstance(n, ast.Name)}
            if isinstance(scope, (ast.FunctionDef, ast.AsyncFunctionDef)):
                a = scope.args
                local_names.update(p.arg for p in (
                    a.args + a.posonlyargs + a.kwonlyargs))
            padded: Dict[str, int] = {}
            for st in _stmts(scope):
                self._flag_consumption(m, st, padded, local_names,
                                       phrase, out)
                if isinstance(st, ast.Assign):
                    is_pad = _contains_pad_call(st.value)
                    for t in st.targets:
                        if isinstance(t, ast.Name):
                            if is_pad:
                                padded[t.id] = st.lineno
                            else:
                                # any rewrite (masking included) clears
                                padded.pop(t.id, None)
        return out

    def _flag_consumption(self, m, st, padded, local_names, phrase, out):
        ann = MASK_RE.search(m.stmt_comment(st))
        if ann and ann.group(1) not in local_names:
            out.append(m.finding(self, st, (
                f"masked-by annotation names '{ann.group(1)}', which is "
                f"no name {phrase} — fix the reference so the masking "
                f"claim is checkable")))
            ann = None
        for call in (n for n in ast.walk(st) if isinstance(n, ast.Call)):
            tail = (_terminal_name(call.func) or "").lower()
            if tail not in _REDUCERS and "digest" not in tail \
                    and "hash" not in tail:
                continue
            operands: List[ast.AST] = list(call.args)
            if isinstance(call.func, ast.Attribute):
                operands.append(call.func.value)
            for op in operands:
                hit = next((name for name, line in padded.items()
                            if line < st.lineno and _mentions(op, name)),
                           None)
                if hit is None and _contains_pad_call(op):
                    hit = _expr_text(op)
                if hit is None or _masked_expr(op) or ann:
                    continue
                out.append(m.finding(self, st, (
                    f"padded plane '{hit}' reaches reduction '{tail}' "
                    f"{phrase} with no mask in between — pad rows "
                    f"contribute to the result; mask the plane or "
                    f"annotate '# masked-by: <mask>'")))


# -- FL-KERN-FAMILY -----------------------------------------------------------


_FAMILY_PATH = "fluidframework_tpu/ops/family.py"
_PIPELINE_PATH = "fluidframework_tpu/ops/pipeline.py"
_MESH_PATH = "fluidframework_tpu/parallel/shard.py"
_CANON_STAGES = ("pack", "upload", "dispatch", "device_wait", "download",
                 "extract", "fallback")
_MESH_HOOKS = ("make_pad", "pad_token", "dispatch_sharded")


@register
class KernelFamilyRule(ProjectRule):
    name = "FL-KERN-FAMILY"
    severity = "error"
    scope = KERNEL_SCOPE
    description = (
        "KernelFamily registry drift: a registered family omits a "
        "descriptor hook, serves a non-canonical stage schema, or the "
        "mesh twin lacks the single-device hooks"
    )

    def check_project(self, project: ProjectContext) -> Iterable[Finding]:
        fam_tree = project.parse(_FAMILY_PATH)
        if fam_tree is None:
            return
        fields: List[str] = []
        for node in ast.walk(fam_tree):
            if isinstance(node, ast.ClassDef) and node.name == "KernelFamily":
                fields = [st.target.id for st in node.body
                          if isinstance(st, ast.AnnAssign)
                          and isinstance(st.target, ast.Name)]
        if not fields:
            return
        for relpath in project.glob("fluidframework_tpu/**/*.py"):
            if not self.applies(relpath):
                continue
            tree = project.parse(relpath)
            if tree is None:
                continue
            for call in (n for n in ast.walk(tree)
                         if isinstance(n, ast.Call)
                         and _terminal_name(n.func) == "KernelFamily"):
                got = set(fields[:len(call.args)])
                got.update(kw.arg for kw in call.keywords if kw.arg)
                for f in fields:
                    if f not in got:
                        yield self.project_finding(relpath, call.lineno, (
                            f"KernelFamily registration omits descriptor "
                            f"hook '{f}' — every registered family must "
                            f"populate every hook so the pipeline never "
                            f"branches on family identity"))
                for kw in call.keywords:
                    if kw.arg and kw.arg not in fields:
                        yield self.project_finding(relpath, call.lineno, (
                            f"KernelFamily registration passes unknown "
                            f"hook '{kw.arg}' — registry and descriptor "
                            f"have drifted"))
                    elif kw.arg in _MESH_HOOKS \
                            and isinstance(kw.value, ast.Constant) \
                            and kw.value.value is None:
                        yield self.project_finding(relpath, call.lineno, (
                            f"KernelFamily mesh hook '{kw.arg}' is None — "
                            f"the mesh twin must register the same hooks "
                            f"as the single-device path (stage-schema "
                            f"parity)"))
        yield from self._check_stages(project)

    def _check_stages(self, project: ProjectContext) -> Iterator[Finding]:
        tree = project.parse(_PIPELINE_PATH)
        if tree is not None:
            stage_keys: Optional[Tuple] = None
            line = 1
            for st in tree.body:
                if isinstance(st, ast.Assign) and len(st.targets) == 1 \
                        and isinstance(st.targets[0], ast.Name) \
                        and st.targets[0].id == "STAGE_KEYS" \
                        and isinstance(st.value, (ast.Tuple, ast.List)):
                    line = st.lineno
                    if all(isinstance(e, ast.Constant) for e in st.value.elts):
                        stage_keys = tuple(e.value for e in st.value.elts)
            if stage_keys is not None and stage_keys != _CANON_STAGES:
                yield self.project_finding(_PIPELINE_PATH, line, (
                    f"STAGE_KEYS {stage_keys!r} diverges from the "
                    f"canonical stage schema {_CANON_STAGES!r} — every "
                    f"family's pipeline must serve the same seed_stage "
                    f"keys"))
        mesh = project.parse(_MESH_PATH)
        if mesh is not None:
            uses = any(
                (isinstance(n, ast.Name) and n.id == "seed_stage")
                or (isinstance(n, ast.Attribute) and n.attr == "seed_stage")
                for n in ast.walk(mesh))
            if not uses:
                yield self.project_finding(_MESH_PATH, 1, (
                    "the mesh twin never seeds the canonical stage "
                    "schema — sharded runs would record a different "
                    "stage shape than single-device"))

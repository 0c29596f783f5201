"""host-sync/* — tracer-leak and device-sync rules.

Inside traced code (see callgraph.py), any operation that forces a concrete
Python value out of a tracer either crashes at trace time
(ConcretizationTypeError) or — worse — silently bakes a trace-time constant
into the compiled program.  Outside traced code, per-element scalar reads
of device arrays serialize one device->host sync each (the N x B
``float(scores[i, j])`` anti-pattern).

Rules:

  host-sync/cast           float()/int()/bool() in a traced function on a
                           value not provably a static Python value.
                           Trace-time constants (static_argnames params,
                           shapes, len()) do not fire; anything param- or
                           tracer-derived does, and genuinely static sites
                           carry a suppression naming why.
  host-sync/item           .item() inside a traced function — a device
                           sync by definition.
  host-sync/asarray        numpy materialization (np.asarray/np.array/
                           np.copy) of a non-static value inside a traced
                           function.
  host-sync/traced-branch  Python if/while/assert (or for-iteration) on a
                           tracer-valued expression inside a traced
                           function: concretization error at trace time.
  host-sync/loop-readback  host code: float()/int()/.item() on a subscript
                           of a device-program result inside a for loop —
                           one device sync per element; read it back once
                           with np.asarray(...)/.tolist() instead.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Optional

from .core import Finding, SourceModule

STATIC, UNKNOWN, TRACER = 0, 1, 2

_STATIC_ATTRS = {"shape", "ndim", "dtype", "size", "aval"}
_STATIC_BUILTIN_CALLS = {
    "len", "range", "isinstance", "issubclass", "hasattr", "getattr",
    "min", "max", "sorted", "tuple", "list", "set", "dict", "zip",
    "enumerate", "abs", "sum", "str", "repr", "type", "id", "frozenset",
    "int", "float", "bool", "round",
}
_TRACER_CALL_PREFIXES = ("jax.numpy.", "jax.lax.", "jax.nn.", "jax.random.",
                         "jax.ops.", "jax.scipy.")
_NUMPY_MATERIALIZERS = {"numpy.asarray", "numpy.array", "numpy.copy",
                        "numpy.ascontiguousarray", "numpy.asanyarray"}
_CAST_BUILTINS = {"float", "int", "bool", "complex"}


class _FnEval:
    """One-pass abstract evaluation of a traced function body: every local
    name is STATIC (host Python value), TRACER (definitely a traced array),
    or UNKNOWN (could be either — parameters, untracked expressions)."""

    def __init__(self, cg, module: SourceModule, fi):
        self.cg = cg
        self.mi = cg.module_info(module)
        self.module = module
        self.fi = fi
        self.state: Dict[str, int] = {}
        args = getattr(fi.node, "args", None)
        if args is not None:
            for a in (args.posonlyargs + args.args + args.kwonlyargs
                      + ([args.vararg] if args.vararg else [])
                      + ([args.kwarg] if args.kwarg else [])):
                self.state[a.arg] = (STATIC if a.arg in fi.static_params
                                     else UNKNOWN)

    # ------------------------------------------------------------- evaluate

    def eval(self, node: ast.AST) -> int:
        if node is None:
            return STATIC
        if isinstance(node, ast.Constant):
            return STATIC
        if isinstance(node, ast.Name):
            if node.id in self.state:
                return self.state[node.id]
            # module-level constants, functions, and import aliases are
            # host values; truly unknown globals stay UNKNOWN
            if (node.id in self.mi.module_consts
                    or node.id in self.mi.functions
                    or node.id in self.mi.import_aliases
                    or node.id in self.mi.from_imports
                    or node.id in ("True", "False", "None")):
                return STATIC
            return UNKNOWN
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return STATIC
            return self.eval(node.value)
        if isinstance(node, ast.Subscript):
            return max(self.eval(node.value), self.eval(node.slice))
        if isinstance(node, (ast.Slice,)):
            vals = [v for v in (node.lower, node.upper, node.step)
                    if v is not None]
            return max([self.eval(v) for v in vals], default=STATIC)
        if isinstance(node, ast.Call):
            return self._eval_call(node)
        if isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return STATIC
            return max([self.eval(node.left)]
                       + [self.eval(c) for c in node.comparators])
        if isinstance(node, ast.BoolOp):
            return max(self.eval(v) for v in node.values)
        if isinstance(node, ast.BinOp):
            return max(self.eval(node.left), self.eval(node.right))
        if isinstance(node, ast.UnaryOp):
            return self.eval(node.operand)
        if isinstance(node, ast.IfExp):
            return max(self.eval(node.body), self.eval(node.orelse))
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return max([self.eval(e) for e in node.elts], default=STATIC)
        if isinstance(node, ast.Dict):
            parts = [v for v in list(node.keys) + list(node.values)
                     if v is not None]
            return max([self.eval(v) for v in parts], default=STATIC)
        if isinstance(node, ast.Starred):
            return self.eval(node.value)
        if isinstance(node, ast.JoinedStr):
            return STATIC
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp,
                             ast.DictComp)):
            return UNKNOWN
        if isinstance(node, ast.Lambda):
            return STATIC
        return UNKNOWN

    def _eval_call(self, node: ast.Call) -> int:
        dotted = self.cg.resolve_dotted(self.mi, node.func)
        if dotted is not None:
            if dotted in _STATIC_BUILTIN_CALLS:
                return STATIC
            if dotted.startswith(_TRACER_CALL_PREFIXES):
                return TRACER
            if dotted.startswith("numpy."):
                return STATIC
        # calls into traced kernels return tracers
        callee = self.cg._lookup_callee(self.mi, self.fi, node.func)
        if callee is not None and callee.traced:
            return TRACER
        # method calls on tracer values stay tracers (x.astype, x.at[...])
        if isinstance(node.func, ast.Attribute):
            base = self.eval(node.func.value)
            if base == TRACER:
                return TRACER
        return UNKNOWN

    # ------------------------------------------------------------ statements

    def assign(self, target: ast.AST, level: int) -> None:
        if isinstance(target, ast.Name):
            self.state[target.id] = level
        elif isinstance(target, (ast.Tuple, ast.List)):
            for e in target.elts:
                self.assign(e, level)
        elif isinstance(target, ast.Starred):
            self.assign(target.value, level)
        # attribute/subscript targets mutate containers; no name state


def _level_word(level: int) -> str:
    return {STATIC: "static", UNKNOWN: "a possible tracer",
            TRACER: "a tracer"}[level]


def _check_traced_function(cg, module: SourceModule, fi,
                           out: List[Finding]) -> None:
    ev = _FnEval(cg, module, fi)
    mi = cg.module_info(module)
    fn_node = fi.node
    body = fn_node.body if isinstance(fn_node.body, list) else [fn_node.body]

    def visit(stmts):
        for stmt in stmts:
            visit_stmt(stmt)

    def visit_stmt(stmt):
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # analyzed separately if traced
        if isinstance(stmt, (ast.Assign,)):
            scan_expr(stmt.value)
            level = ev.eval(stmt.value)
            for t in stmt.targets:
                ev.assign(t, level)
            return
        if isinstance(stmt, ast.AnnAssign):
            if stmt.value is not None:
                scan_expr(stmt.value)
                ev.assign(stmt.target, ev.eval(stmt.value))
            return
        if isinstance(stmt, ast.AugAssign):
            scan_expr(stmt.value)
            if isinstance(stmt.target, ast.Name):
                level = max(ev.eval(stmt.value),
                            ev.state.get(stmt.target.id, UNKNOWN))
                ev.state[stmt.target.id] = level
            return
        if isinstance(stmt, (ast.If, ast.While)):
            scan_expr(stmt.test)
            level = ev.eval(stmt.test)
            if level == TRACER:
                kind = "if" if isinstance(stmt, ast.If) else "while"
                out.append(Finding(
                    "host-sync/traced-branch", module.path,
                    stmt.lineno, stmt.col_offset + 1,
                    "Python `%s` on a tracer-valued expression inside "
                    "traced function `%s` — concretization at trace time; "
                    "use jnp.where/lax.cond" % (kind, fi.name)))
            visit(stmt.body)
            visit(getattr(stmt, "orelse", []) or [])
            return
        if isinstance(stmt, ast.Assert):
            scan_expr(stmt.test)
            if ev.eval(stmt.test) == TRACER:
                out.append(Finding(
                    "host-sync/traced-branch", module.path,
                    stmt.lineno, stmt.col_offset + 1,
                    "assert on a tracer inside traced function `%s` — "
                    "use checkify or move the check to the host" % fi.name))
            return
        if isinstance(stmt, (ast.For, ast.AsyncFor)):
            scan_expr(stmt.iter)
            iter_level = ev.eval(stmt.iter)
            if iter_level == TRACER:
                out.append(Finding(
                    "host-sync/traced-branch", module.path,
                    stmt.lineno, stmt.col_offset + 1,
                    "Python for-loop iterating a tracer inside traced "
                    "function `%s` — use lax.scan/fori_loop" % fi.name))
            # element of a static range/list is static; element of unknown
            # stays unknown
            ev.assign(stmt.target,
                      STATIC if iter_level == STATIC else UNKNOWN)
            visit(stmt.body)
            visit(stmt.orelse or [])
            return
        if isinstance(stmt, (ast.With, ast.AsyncWith)):
            for item in stmt.items:
                scan_expr(item.context_expr)
            visit(stmt.body)
            return
        if isinstance(stmt, ast.Try):
            visit(stmt.body)
            for h in stmt.handlers:
                visit(h.body)
            visit(stmt.orelse or [])
            visit(stmt.finalbody or [])
            return
        if isinstance(stmt, (ast.Return, ast.Expr)):
            if stmt.value is not None:
                scan_expr(stmt.value)
            return
        # everything else: scan child expressions conservatively
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.expr):
                scan_expr(child)

    def scan_expr(expr: ast.AST) -> None:
        for node in ast.walk(expr):
            if not isinstance(node, ast.Call):
                continue
            # skip calls that live inside a nested def/lambda body — they
            # are analyzed with that function (if traced)
            if module.enclosing_function(node) is not fn_node:
                continue
            dotted = cg.resolve_dotted(mi, node.func)
            if dotted in _CAST_BUILTINS and len(node.args) == 1:
                level = ev.eval(node.args[0])
                if level != STATIC:
                    out.append(Finding(
                        "host-sync/cast", module.path, node.lineno,
                        node.col_offset + 1,
                        "%s() on %s inside traced function `%s` — a host "
                        "sync (or a silent trace-time constant); if this "
                        "value is static, suppress with the reason"
                        % (dotted, _level_word(level), fi.name)))
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item" and not node.args):
                out.append(Finding(
                    "host-sync/item", module.path, node.lineno,
                    node.col_offset + 1,
                    ".item() inside traced function `%s` — device sync; "
                    "keep the value on device" % fi.name))
            elif dotted in _NUMPY_MATERIALIZERS:
                level = ev.eval(node.args[0]) if node.args else STATIC
                if level != STATIC:
                    out.append(Finding(
                        "host-sync/asarray", module.path, node.lineno,
                        node.col_offset + 1,
                        "%s on %s inside traced function `%s` — "
                        "materializes the tracer on host; use jnp"
                        % (dotted, _level_word(level), fi.name)))

    visit(body)


# --------------------------------------------------------------------------
# host-side rule: per-element device readbacks in loops


def _check_loop_readback(cg, module: SourceModule, fn_node,
                         out: List[Finding]) -> None:
    """Within a non-traced function: names assigned from device-returning
    calls (jit roots or wrappers that tail-call one) are DEVICE; attributes/
    subscripts of DEVICE stay DEVICE; np.asarray()/.tolist() launder to
    host.  float()/int()/.item() on DEVICE subscripts inside for-loops then
    flag one-sync-per-element readbacks."""
    mi = cg.module_info(module)

    def returns_device(callee) -> bool:
        if callee is None:
            return False
        if callee.traced or callee.is_root:
            return True
        # one-hop wrapper: `return _jitted(...)`
        for stmt in ast.walk(callee.node):
            if isinstance(stmt, ast.Return) and isinstance(stmt.value,
                                                           ast.Call):
                cmi = cg.module_info(callee.module)
                inner = cg._lookup_callee(cmi, callee, stmt.value.func)
                if inner is not None and (inner.traced or inner.is_root):
                    return True
        return False

    fi = cg.info_for(module, fn_node)
    if fi is None:
        return
    # flow-sensitive-enough: (lineno, is_device) events per name, so a
    # post-loop np.asarray launder does not hide a sync INSIDE the loop
    device: Dict[str, List] = {}
    _use_line = [0]

    def name_is_device(name: str, at_line: int) -> bool:
        state = False
        for lineno, is_dev in device.get(name, ()):
            if lineno > at_line:
                break
            state = is_dev
        return state

    def expr_is_device(node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return name_is_device(node.id, _use_line[0])
        if isinstance(node, ast.Attribute):
            if node.attr in _STATIC_ATTRS:
                return False  # .shape/.ndim/... are host metadata
            return expr_is_device(node.value)
        if isinstance(node, ast.Subscript):
            return expr_is_device(node.value)
        if isinstance(node, ast.Call):
            f = node.func
            # np.asarray(x) / x.tolist() launder to host
            dotted = cg.resolve_dotted(mi, f)
            if dotted in _NUMPY_MATERIALIZERS:
                return False
            if isinstance(f, ast.Attribute) and f.attr in ("tolist",
                                                           "copy_to_host_async"):
                return False
            callee = cg._lookup_callee(mi, fi, f)
            return returns_device(callee)
        return False

    assigns = [s for s in ast.walk(fn_node)
               if isinstance(s, ast.Assign)
               and module.enclosing_function(s) is fn_node]
    for stmt in sorted(assigns, key=lambda s: s.lineno):
        _use_line[0] = stmt.lineno
        is_dev = expr_is_device(stmt.value)
        for t in stmt.targets:
            if isinstance(t, ast.Name):
                device.setdefault(t.id, []).append((stmt.lineno, is_dev))

    for loop in ast.walk(fn_node):
        if not isinstance(loop, (ast.For, ast.While, ast.ListComp,
                                 ast.SetComp, ast.DictComp,
                                 ast.GeneratorExp)):
            continue
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            _use_line[0] = node.lineno
            dotted = cg.resolve_dotted(mi, node.func)
            bad = None
            if (dotted in ("float", "int") and len(node.args) == 1
                    and isinstance(node.args[0], ast.Subscript)
                    and expr_is_device(node.args[0].value)):
                bad = "%s(x[...])" % dotted
            elif (isinstance(node.func, ast.Attribute)
                    and node.func.attr == "item"
                    and expr_is_device(node.func.value)):
                bad = "x[...].item()"
            if bad:
                out.append(Finding(
                    "host-sync/loop-readback", module.path, node.lineno,
                    node.col_offset + 1,
                    "%s on a device-program result inside a loop — one "
                    "device sync per element; read the array back once "
                    "with np.asarray(...) (or .tolist()) outside the "
                    "loop" % bad))


def check(module: SourceModule, ctx) -> List[Finding]:
    cg = ctx.callgraph
    out: List[Finding] = []
    seen_traced = set()
    for fi in cg.traced_functions(module):
        if isinstance(fi.node, ast.Lambda):
            continue  # lambda bodies are tiny; covered via enclosing checks
        seen_traced.add(id(fi.node))
        _check_traced_function(cg, module, fi, out)
    mi = cg.module_info(module)
    for fi in mi.by_node.values():
        if fi.traced or isinstance(fi.node, ast.Lambda):
            continue
        _check_loop_readback(cg, module, fi.node, out)
    return out

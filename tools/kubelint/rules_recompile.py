"""recompile/* — recompilation-hazard rules.

Every XLA compile of a serving-shape program costs tens of seconds (see
utils/compilation.py), so the codebase's contract is: jit objects are
created ONCE (decorators / module level), static arguments are hashable,
and shape-like static values are bucketed through
``utils.intern.pow2_bucket`` so growth recompiles only at doublings.

Rules:

  recompile/jit-in-body       jax.jit()/jax.pmap() called inside a
                              function or loop body (or on a fresh lambda)
                              — a new jit object per call means a new
                              tracing cache per call: 100% miss rate.
  recompile/nonhashable-static  a static_argnums/static_argnames parameter
                              with a mutable (list/dict/set) default, or a
                              call site passing a list/dict/set literal
                              for a known static parameter — jit raises
                              (or, for exotic types, silently retraces).
  recompile/unbucketed-static  a call site passing a shape-derived value
                              (len(...) / .shape[...] arithmetic) for a
                              known static parameter without wrapping it
                              in pow2_bucket(...) — every new size
                              compiles a fresh program instead of hitting
                              the pow2 bucket (utils/intern.py contract).
                              Checked through the interprocedural
                              provenance engine (tools/kubeclose/
                              engine.py): a bare name is resolved to its
                              defining expressions across assignments,
                              parameters and call sites, so laundering a
                              len(...) through a local or a helper
                              parameter no longer hides it.
  recompile/shape-branch      an if/while test inside a traced function
                              comparing .shape[...] against a call result
                              — a shape-dependent Python branch whose
                              bound is itself dynamic splits the compile
                              cache unboundedly.
"""

from __future__ import annotations

import ast
from typing import List, Optional, Set

from .core import Finding, SourceModule

_JIT_LIKE = {"jax.jit", "jax.pmap"}


def _engine(ctx):
    """The shared interprocedural provenance engine (tools/kubeclose),
    built lazily once per lint run over the run's modules/callgraph.
    Import is deferred: kubeclose depends on kubelint's callgraph, so a
    module-level import here would be circular."""
    eng = getattr(ctx, "_provenance_engine", None)
    if eng is None:
        from tools.kubeclose.engine import ProvenanceEngine
        eng = ProvenanceEngine(ctx.modules, callgraph=ctx.callgraph)
        ctx._provenance_engine = eng
    return eng


def _resolved_shape_leak(ctx, cg, mi, caller, v):
    """Interprocedural unbucketed-shape check for a bare-name argument:
    resolve the name to its defining expressions (through assignments,
    parameters, call sites) and apply the same syntactic test to each.
    Returns the offending (module, expr) or None."""
    if not isinstance(v, ast.Name):
        return None
    for dmi, _dfi, dexpr in _engine(ctx).resolve_name_exprs(
            mi, caller, v.id):
        if (_contains_shape_or_len(cg, dmi, dexpr)
                and not _is_pow2_bucketed(cg, dmi, dexpr)):
            return dmi, dexpr
    return None


def _static_params_of(callee) -> Set[str]:
    return callee.static_params if callee is not None else set()


def _positional_params_of(callee) -> List[str]:
    args = getattr(callee.node, "args", None)
    if args is None:
        return []
    return [a.arg for a in args.posonlyargs + args.args]


def _contains_shape_or_len(cg, mi, expr: ast.AST) -> bool:
    for node in ast.walk(expr):
        if isinstance(node, ast.Attribute) and node.attr == "shape":
            return True
        if isinstance(node, ast.Call):
            if cg.resolve_dotted(mi, node.func) == "len":
                return True
    return False


def _is_pow2_bucketed(cg, mi, expr: ast.AST) -> bool:
    """True when every shape-derived component of ``expr`` flows through a
    pow2_bucket(...) call (checked at the top level: the expression IS a
    pow2_bucket call, possibly through trivial arithmetic)."""
    if isinstance(node := expr, ast.Call):
        dotted = cg.resolve_dotted(mi, node.func) or ""
        if dotted.split(".")[-1] == "pow2_bucket":
            return True
    if isinstance(expr, ast.BinOp):
        return (_is_pow2_bucketed(cg, mi, expr.left)
                and _is_pow2_bucketed(cg, mi, expr.right))
    # leaves without shape/len content are fine
    return not _contains_shape_or_len(cg, mi, expr)


def check(module: SourceModule, ctx) -> List[Finding]:
    cg = ctx.callgraph
    mi = cg.module_info(module)
    out: List[Finding] = []

    for node in ast.walk(module.tree):
        if not isinstance(node, ast.Call):
            continue

        dotted = cg.resolve_dotted(mi, node.func)

        # ---- jit object created per call -------------------------------
        target = None
        if dotted in _JIT_LIKE:
            target = node
        elif dotted in ("functools.partial", "partial") and node.args:
            if cg.resolve_dotted(mi, node.args[0]) in _JIT_LIKE:
                target = node
        if target is not None:
            parent = module.parent(node)
            is_decorator = any(
                node in getattr(a, "decorator_list", [])
                for a in [parent] if parent is not None)
            in_function = module.enclosing_function(node) is not None
            fresh_lambda = any(isinstance(a, ast.Lambda)
                               for a in node.args[:1])
            if in_function and not is_decorator:
                out.append(Finding(
                    "recompile/jit-in-body", module.path, node.lineno,
                    node.col_offset + 1,
                    "jax.jit called inside a function/loop body%s — a "
                    "fresh jit object never hits its own tracing cache; "
                    "hoist to a decorator or module level"
                    % (" on a fresh lambda" if fresh_lambda else "")))

        # ---- static-arg hygiene at call sites --------------------------
        callee = None
        enc = module.enclosing_function(node)
        caller = cg.info_for(module, enc) if enc is not None else None
        if caller is not None:
            callee = cg._lookup_callee(mi, caller, node.func)
        else:
            callee = cg._lookup_callee(
                mi, _ModuleScope(mi), node.func)  # module-level call
        statics = _static_params_of(callee)
        if statics:
            # keyword AND positional spellings both reach static params
            passed = [(kw.arg, kw.value) for kw in node.keywords]
            params = _positional_params_of(callee)
            passed += [(params[i], a) for i, a in enumerate(node.args)
                       if i < len(params)]
            for name, v in passed:
                if name not in statics:
                    continue
                if isinstance(v, (ast.List, ast.Dict, ast.Set, ast.ListComp,
                                  ast.SetComp, ast.DictComp)):
                    out.append(Finding(
                        "recompile/nonhashable-static", module.path,
                        v.lineno, v.col_offset + 1,
                        "list/dict/set passed for static parameter `%s` of "
                        "jitted `%s` — static args must be hashable "
                        "(use a tuple)" % (name, callee.name)))
                elif (_contains_shape_or_len(cg, mi, v)
                        and not _is_pow2_bucketed(cg, mi, v)):
                    out.append(Finding(
                        "recompile/unbucketed-static", module.path,
                        v.lineno, v.col_offset + 1,
                        "shape-derived value passed for static parameter "
                        "`%s` of jitted `%s` without pow2_bucket(...) — "
                        "every new size compiles a fresh program "
                        "(utils/intern.py bucketing contract)"
                        % (name, callee.name)))
                else:
                    leak = _resolved_shape_leak(ctx, cg, mi, caller, v)
                    if leak is not None:
                        dmi, dexpr = leak
                        out.append(Finding(
                            "recompile/unbucketed-static", module.path,
                            v.lineno, v.col_offset + 1,
                            "`%s` reaches static parameter `%s` of jitted "
                            "`%s` carrying a shape-derived value without "
                            "pow2_bucket(...) (defined at %s:%d, resolved "
                            "interprocedurally) — every new size compiles "
                            "a fresh program"
                            % (v.id, name, callee.name,
                               dmi.module.name,
                               getattr(dexpr, "lineno", 0))))

    # ---- mutable defaults on static params -----------------------------
    for mi_fi in mi.by_node.values():
        if not mi_fi.static_params:
            continue
        args = getattr(mi_fi.node, "args", None)
        if args is None:
            continue
        pos = args.posonlyargs + args.args
        defaults = args.defaults
        offset = len(pos) - len(defaults)
        pairs = [(pos[offset + i].arg, d) for i, d in enumerate(defaults)]
        pairs += [(a.arg, d) for a, d in zip(args.kwonlyargs,
                                             args.kw_defaults)
                  if d is not None]
        for name, default in pairs:
            if name in mi_fi.static_params and isinstance(
                    default, (ast.List, ast.Dict, ast.Set)):
                out.append(Finding(
                    "recompile/nonhashable-static", module.path,
                    default.lineno, default.col_offset + 1,
                    "static parameter `%s` of jitted `%s` has a mutable "
                    "default — unhashable; use a tuple or None"
                    % (name, mi_fi.name)))

    # ---- shape-dependent branches with dynamic bounds ------------------
    for fi in cg.traced_functions(module):
        if isinstance(fi.node, ast.Lambda):
            continue
        for stmt in ast.walk(fi.node):
            if not isinstance(stmt, (ast.If, ast.While)):
                continue
            if module.enclosing_function(stmt) is not fi.node:
                continue
            test = stmt.test
            if not isinstance(test, ast.Compare):
                continue
            sides = [test.left] + list(test.comparators)
            has_shape = any(
                isinstance(n, ast.Attribute) and n.attr == "shape"
                for s in sides for n in ast.walk(s))
            has_call = any(
                isinstance(n, ast.Call)
                and (cg.resolve_dotted(mi, n.func) or "").split(".")[-1]
                not in ("len", "pow2_bucket", "min", "max")
                for s in sides for n in ast.walk(s))
            if has_shape and has_call:
                out.append(Finding(
                    "recompile/shape-branch", module.path, stmt.lineno,
                    stmt.col_offset + 1,
                    "shape-dependent branch against a dynamic bound inside "
                    "traced `%s` — splits the compile cache per size; "
                    "bucket the bound (pow2_bucket) or lift the branch out "
                    "of the trace" % fi.name))
    return out


class _ModuleScope:
    """Minimal caller stand-in for module-level call resolution."""

    def __init__(self, mi):
        self.node = mi.module.tree

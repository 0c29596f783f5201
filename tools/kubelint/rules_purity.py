"""purity/* — kernel-purity rules.

Jitted programs are traced once per shape bucket and replayed from the
compile cache; any environment read or module-global mutation inside a
kernel module is therefore either (a) frozen at trace time and silently
stale forever after, or (b) host-side hidden state that makes the
"placements bit-match the reference" contract unreproducible.  Kernel
modules (anything under an ops/ or models/ package, plus any module
defining a jit root) must be pure: inputs in, arrays out.

Rules:

  purity/env-access     os.environ / os.getenv read or write inside a
                        kernel module.  Configuration belongs in
                        ProgramConfig / KubeSchedulerConfiguration, where
                        it participates in the jit static key.
  purity/global-mutate  `global` declaration, or mutation of a
                        module-level name (aug-assign, .append/.update/
                        .add/.extend/[...]=) from inside a kernel-module
                        function — hidden state across traces.
"""

from __future__ import annotations

import ast
from typing import List, Set

from .core import Finding, SourceModule

_MUTATORS = {"append", "extend", "add", "update", "insert", "setdefault",
             "pop", "remove", "clear", "__setitem__"}

def _env_access(cg, mi, node: ast.AST) -> bool:
    if isinstance(node, ast.Attribute):
        dotted = cg.resolve_dotted(mi, node)
        return dotted in ("os.environ",)
    if isinstance(node, ast.Call):
        dotted = cg.resolve_dotted(mi, node.func)
        return dotted in ("os.getenv", "os.putenv", "os.environ.get")
    return False


def check(module: SourceModule, ctx) -> List[Finding]:
    cg = ctx.callgraph
    if not cg.is_kernel_module(module):
        return []
    mi = cg.module_info(module)
    out: List[Finding] = []

    module_names: Set[str] = set(mi.module_consts) | set(mi.functions)

    for node in ast.walk(module.tree):
        # ---- environment access --------------------------------------
        if _env_access(cg, mi, node):
            out.append(Finding(
                "purity/env-access", module.path, node.lineno,
                node.col_offset + 1,
                "environment access inside a kernel module — frozen at "
                "trace time and invisible to the jit cache key; route "
                "through ProgramConfig instead"))

        # ---- global mutation -----------------------------------------
        if isinstance(node, ast.Global):
            out.append(Finding(
                "purity/global-mutate", module.path, node.lineno,
                node.col_offset + 1,
                "`global %s` inside a kernel-module function — hidden "
                "state across traces; pass state explicitly"
                % ", ".join(node.names)))
        fn = module.enclosing_function(node)
        if fn is None:
            continue
        if isinstance(node, ast.AugAssign) and isinstance(node.target,
                                                          ast.Name):
            if node.target.id in module_names and not _shadowed(
                    module, fn, node.target.id):
                out.append(Finding(
                    "purity/global-mutate", module.path, node.lineno,
                    node.col_offset + 1,
                    "module-level `%s` mutated inside a kernel-module "
                    "function" % node.target.id))
        if isinstance(node, ast.Call) and isinstance(node.func,
                                                     ast.Attribute):
            if (node.func.attr in _MUTATORS
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id in module_names
                    and node.func.value.id not in mi.functions
                    and node.func.value.id not in mi.import_aliases
                    and not _shadowed(module, fn, node.func.value.id)):
                out.append(Finding(
                    "purity/global-mutate", module.path, node.lineno,
                    node.col_offset + 1,
                    "module-level container `%s` mutated (.%s) inside a "
                    "kernel-module function — hidden state across traces"
                    % (node.func.value.id, node.func.attr)))
        if isinstance(node, ast.Assign):
            for t in node.targets:
                if (isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Name)
                        and t.value.id in module_names
                        and t.value.id not in mi.functions
                        and not _shadowed(module, fn, t.value.id)):
                    out.append(Finding(
                        "purity/global-mutate", module.path, node.lineno,
                        node.col_offset + 1,
                        "module-level container `%s` written by subscript "
                        "inside a kernel-module function" % t.value.id))
    # deduplicate env-access findings that landed twice on one site
    seen = set()
    deduped = []
    for f in out:
        key = (f.rule, f.line, f.col)
        if key not in seen:
            seen.add(key)
            deduped.append(f)
    return deduped


def _shadowed(module: SourceModule, fn: ast.AST, name: str) -> bool:
    """True when ``name`` is a parameter or local assignment of ``fn`` (or
    an enclosing function) — then it is not the module-level binding."""
    node = fn
    while node is not None:
        args = getattr(node, "args", None)
        if args is not None:
            params = [a.arg for a in args.posonlyargs + args.args
                      + args.kwonlyargs]
            if args.vararg:
                params.append(args.vararg.arg)
            if args.kwarg:
                params.append(args.kwarg.arg)
            if name in params:
                return True
        for stmt in ast.walk(node):
            if isinstance(stmt, ast.Assign):
                for t in stmt.targets:
                    if isinstance(t, ast.Name) and t.id == name:
                        return True
            if isinstance(stmt, (ast.For, ast.AsyncFor)) and isinstance(
                    stmt.target, ast.Name) and stmt.target.id == name:
                return True
        node = module.enclosing_function(node)
    return False

"""Collective-surface census: every cross-device communication op in a
traced program, with operand bytes per ladder rung.

Unlike the prover (absint.py), this pass needs no value analysis — it is
a plain recursive walk over the jaxpr collecting (op, axis names, dtype,
reduce kind, operand shape, operand bytes) rows.  The rows are committed
into EXACT_MANIFEST.json per rung of the pow2 ladder, giving CI a
two-directional drift gate over the collective surface (a new psum or a
vanished all_gather is a diff, not a silent lowering change) and giving
kubecensus cost rows the per-collective DCN byte attribution.
"""

from __future__ import annotations

from typing import List

from .absint import COLLECTIVES, _REDUCE_KIND

_ITEMSIZE = {"bool": 1, "int8": 1, "uint8": 1, "bfloat16": 2,
             "float16": 2, "int16": 2, "uint16": 2,
             "float32": 4, "int32": 4, "uint32": 4,
             "float64": 8, "int64": 8, "uint64": 8}


def _sub_jaxprs(params: dict):
    """Every jaxpr reachable from an eqn's params — ClosedJaxpr (pjit,
    scan, cond branches) AND plain Jaxpr (shard_map bodies store theirs
    unclosed)."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else [v]
        for u in items:
            if hasattr(u, "eqns"):
                yield u
            elif hasattr(u, "jaxpr") and hasattr(u.jaxpr, "eqns"):
                yield u.jaxpr


def _axes_of(eqn) -> tuple:
    axes = eqn.params.get("axes")
    if axes is None:
        axes = eqn.params.get("axis_name")
    if axes is None:
        return ()
    if not isinstance(axes, (tuple, list)):
        axes = (axes,)
    return tuple(str(a) for a in axes)


def collect_collectives(closed_jaxpr) -> List[dict]:
    """All collective eqns in the program, in deterministic eqn order."""
    rows: List[dict] = []

    def visit(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in COLLECTIVES:
                aval = eqn.invars[0].aval
                dtype = aval.dtype.name
                n = 1
                for d in aval.shape:
                    n *= int(d)
                rows.append({
                    "op": eqn.primitive.name,
                    "kind": _REDUCE_KIND.get(eqn.primitive.name,
                                             eqn.primitive.name),
                    "axes": list(_axes_of(eqn)),
                    "dtype": dtype,
                    "shape": [int(d) for d in aval.shape],
                    "bytes": n * _ITEMSIZE.get(dtype, 4),
                })
            for sub in _sub_jaxprs(eqn.params):
                visit(sub)

    visit(closed_jaxpr.jaxpr)
    return rows

"""The exactness lattice: an abstract interpreter over jaxprs.

Proves the exact-reduction invariant for every cross-shard collective
in a traced program: a float reduction is exact iff it is a max/min
(exactly associative in IEEE754) or a sum of integer-valued terms whose
value-range bound stays below 2**24 (f32 integers are exact up to that
magnitude, so any association order yields the same bits).

Each jaxpr variable carries an ``AbsVal``:

  int_valued   the value is mathematically an integer (bools and int
               dtypes trivially; float values via the transfer rules —
               comparisons, floor, products/sums of integer-valued terms)
  lo/hi        symbolic interval endpoints (bounds.Expr) over named dim
               symbols, so one probe-rung trace yields bounds evaluable
               at the north-star shape
  lastsum      per-row bound on the sum over the LAST axis — the load-
               bearing component: a plain interval bounds the DPS zone
               count by N*P (hopeless), while "each pod lands on exactly
               one node" gives row sums <= P via the one-hot dot rule
  lastsum_global  True when the bound was derived OUTSIDE the shard_map
               body, i.e. it bounds the GLOBAL row sum; summing a
               value across disjoint shards is then bounded by the
               single global bound instead of shards x local
  random       PRNG taint (threefry/random_bits and everything computed
               from them) — the gumbel-decomposition witness for the
               tie-broken argmax rule
  iota/varies  enough structure to recognize ``x[:, None] == iota`` as a
               one-hot row pattern (lastsum == hi) without special-casing
               the helper that builds it
  parts        per-slice components of a ``concatenate`` (jnp.stack of
               score planes), so a static plane index recovers the
               plane's own facts — the gumbel plane stays distinguishable
               from the integer count planes it is stacked with
  sharded      dim -> mesh-axis (from shard_map in_names) tiling marks
  tile_total   "summing this value over all tiles of axis k is <= Expr":
               produced when a dot contracts a tiled dim using a global
               lastsum, consumed by psum bounds

Unknown primitives default to TOP (sound; precision recovers at the next
comparison, which is bool-valued regardless of its inputs).  While-loop
carries are widened to a field-wise post-fixpoint (see _stabilize): each
fact survives only if the body re-establishes it every round, so the
score-plane bundle keeps its PRNG taint and per-plane facts across the
auction round loop.  Scan carries are widened to TOP in one shot.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Tuple

from tools.kubecensus.rules import Finding

from .bounds import BOT, INF, ONE, TOP, ZERO, Expr

__all__ = ["AbsVal", "Interp", "Reduction", "Finding", "COLLECTIVES"]

COLLECTIVES = ("psum", "pmax", "pmin", "all_gather", "ppermute",
               "all_to_all", "reduce_scatter")

_REDUCE_KIND = {"psum": "sum", "pmax": "max", "pmin": "min",
                "all_gather": "gather", "ppermute": "permute",
                "all_to_all": "all_to_all", "reduce_scatter": "sum"}


def _dtype_kind(dtype) -> str:
    name = getattr(dtype, "name", str(dtype))
    if name.startswith("bool"):
        return "bool"
    if name.startswith(("int", "uint")):
        return "int"
    if name.startswith(("float", "bfloat")):
        return "float"
    return "other"


@dataclasses.dataclass
class AbsVal:
    shape: Tuple[int, ...]
    kind: str                  # "bool" | "int" | "float" | "other"
    int_valued: bool
    lo: Expr
    hi: Expr
    lastsum: Optional[Expr] = None
    lastsum_global: bool = False
    random: bool = False
    iota_dim: Optional[int] = None
    varies: Optional[frozenset] = None      # None = may vary everywhere
    parts: Optional[Tuple[Tuple[int, int, "AbsVal"], ...]] = None
    parts_axis: int = 0
    sharded: Optional[Dict[object, int]] = None   # key -> dim
    tile_total: Optional[Dict[object, Tuple[Expr, bool]]] = None

    # ---- helpers ------------------------------------------------------
    @property
    def nonneg(self) -> bool:
        c = self.lo._const()
        return c is not None and c >= 0.0

    def varies_on(self, dim: int) -> bool:
        return self.varies is None or dim in self.varies

    def replace(self, **kw) -> "AbsVal":
        return dataclasses.replace(self, **kw)

    def drop_structure(self, **kw) -> "AbsVal":
        """Interval/int/random survive; positional structure does not."""
        base = dataclasses.replace(
            self, lastsum=None, lastsum_global=False, iota_dim=None,
            varies=None, parts=None, sharded=None, tile_total=None)
        return dataclasses.replace(base, **kw) if kw else base


def _top(aval) -> AbsVal:
    kind = _dtype_kind(aval.dtype)
    if kind == "bool":
        return AbsVal(tuple(aval.shape), kind, True, ZERO, ONE)
    return AbsVal(tuple(aval.shape), kind, kind == "int", BOT, TOP)


def _is_zero(v: AbsVal) -> bool:
    return (v.varies == frozenset() and v.lo._const() == 0.0
            and v.hi._const() == 0.0)


def _join(a: AbsVal, b: AbsVal, shape=None) -> AbsVal:
    # joining with a constant zero (the ubiquitous where(mask, x, 0))
    # only relaxes lo toward 0 — every structural fact of x survives,
    # including the load-bearing global row-sum bound
    for p, q in ((a, b), (b, a)):
        if _is_zero(q) and not _is_zero(p):
            out = p.replace(
                shape=tuple(shape) if shape is not None else p.shape,
                lo=p.lo.emin(ZERO), parts=None)
            if not p.nonneg:
                out.lastsum, out.lastsum_global = None, False
            return out
    nonneg = a.nonneg and b.nonneg
    lastsum = None
    if nonneg and a.lastsum is not None and b.lastsum is not None:
        lastsum = a.lastsum.emax(b.lastsum)
    tt = None
    if a.tile_total and b.tile_total:
        tt = {}
        for k in a.tile_total:
            if k in b.tile_total:
                (ea, ga), (eb, gb) = a.tile_total[k], b.tile_total[k]
                tt[k] = (ea.emax(eb), ga and gb)
        tt = tt or None
    sharded = None
    if a.sharded and b.sharded:
        sharded = {k: d for k, d in a.sharded.items()
                   if b.sharded.get(k) == d} or None
    return AbsVal(
        shape=tuple(shape) if shape is not None else a.shape,
        kind=a.kind if a.kind == b.kind else "other",
        int_valued=a.int_valued and b.int_valued,
        lo=a.lo.emin(b.lo), hi=a.hi.emax(b.hi),
        lastsum=lastsum,
        lastsum_global=(lastsum is not None and a.lastsum_global
                        and b.lastsum_global),
        random=a.random or b.random,
        iota_dim=a.iota_dim if a.iota_dim == b.iota_dim else None,
        varies=(a.varies | b.varies
                if a.varies is not None and b.varies is not None else None),
        sharded=sharded, tile_total=tt)


def _bool01(shape) -> AbsVal:
    return AbsVal(tuple(shape), "bool", True, ZERO, ONE)


@dataclasses.dataclass
class Reduction:
    """One cross-shard collective."""
    op: str                    # psum | pmax | ...
    kind: str                  # sum | max | min | gather | ...
    axes: Tuple[str, ...]      # mesh axis names
    dtype: str
    shape: Tuple[int, ...]     # operand shape at the probe rung
    int_dtype: bool
    int_valued: bool
    lo: Expr
    hi: Expr
    note: str = ""


class Interp:
    """One abstract interpretation of a closed jaxpr.

    ``sizes``: dim-size -> tuple of candidate symbol names (bounds.
    sym_table).  Findings that need the north-star environment (sum
    bounds) are NOT emitted here —
    reductions are recorded with symbolic bounds and judged by the
    driver, where entry exemptions apply."""

    def __init__(self, sizes: Dict[int, Tuple[str, ...]],
                 program: str = ""):
        self.sizes = dict(sizes or {})
        self.program = program
        self.reductions: List[Reduction] = []
        self.findings: List[Finding] = []
        self.in_shardmap = 0

    # ---- symbols ------------------------------------------------------
    def size_expr(self, n: int) -> Expr:
        names = self.sizes.get(int(n))
        return Expr.sym(names) if names else Expr.const(n)

    def mesh_sym(self, axis: str) -> Expr:
        return Expr.sym("MESH:%s" % axis)

    def _outside_body(self) -> bool:
        return self.in_shardmap == 0

    def _finding(self, rule: str, message: str) -> None:
        self.findings.append(Finding(rule=rule, program=self.program,
                                     message=message))

    # ---- entry point --------------------------------------------------
    def run(self, closed_jaxpr, invals: List[AbsVal]) -> List[AbsVal]:
        jaxpr = closed_jaxpr.jaxpr
        consts = [self._literal_val_abs(c) for c in closed_jaxpr.consts]
        return self._frame(jaxpr, consts, invals)

    # ---- frame interpretation -----------------------------------------
    def _frame(self, jaxpr, consts: List[AbsVal],
               invals: List[AbsVal]) -> List[AbsVal]:
        env: Dict[object, AbsVal] = {}

        for var, v in zip(jaxpr.constvars, consts):
            env[var] = v
        for var, v in zip(jaxpr.invars, invals):
            env[var] = v if v is not None else _top(var.aval)

        def read(atom) -> AbsVal:
            if hasattr(atom, "val"):          # core.Literal
                return self._literal(atom)
            got = env.get(atom)
            return got if got is not None else _top(atom.aval)

        for eqn in jaxpr.eqns:
            ins = [read(a) for a in eqn.invars]
            fn = _TRANSFER.get(eqn.primitive.name)
            if fn is not None:
                outs = fn(self, eqn, ins)
            else:
                outs = self._default(eqn, ins)
            for var, v in zip(eqn.outvars, outs):
                if type(var).__name__ == "DropVar":
                    continue
                env[var] = v
        return [read(v) for v in jaxpr.outvars]

    # ---- literals / defaults ------------------------------------------
    def _literal(self, lit) -> AbsVal:
        return self._literal_val_abs(lit.val)

    def _literal_val_abs(self, val) -> AbsVal:
        import numpy as np
        try:
            arr = np.asarray(val)
        except Exception:
            return AbsVal((), "other", False, BOT, TOP)
        kind = _dtype_kind(arr.dtype)
        if arr.size == 0 or kind == "other":
            return AbsVal(tuple(arr.shape), kind, kind in ("bool", "int"),
                          BOT, TOP)
        if kind == "bool":
            return _bool01(arr.shape)
        lo, hi = float(arr.min()), float(arr.max())
        if not (np.isfinite(lo) and np.isfinite(hi)):
            return AbsVal(tuple(arr.shape), kind, kind == "int", BOT, TOP)
        int_valued = (kind == "int"
                      or bool(np.all(arr == np.floor(arr))))
        v = AbsVal(tuple(arr.shape), kind, int_valued,
                   Expr.const(lo), Expr.const(hi))
        if arr.ndim == 0 or (lo == hi):
            v.varies = frozenset()
        return v

    def _default(self, eqn, ins: List[AbsVal]) -> List[AbsVal]:
        """Sound fallback: TOP values, union PRNG taint; descend into any
        sub-jaxprs so collectives inside unmodeled primitives are still
        seen (with TOP operands)."""
        rnd = any(v.random for v in ins)
        if eqn.primitive.name.startswith("random_") or \
                eqn.primitive.name.startswith("threefry"):
            rnd = True
        for sub in _sub_jaxprs(eqn.params):
            n = len(sub.jaxpr.invars)
            self.run(sub, [None] * n)
        return [_top(v.aval).replace(random=rnd) for v in eqn.outvars]


# ======================================================================
# transfer functions
# ======================================================================

_TRANSFER: Dict[str, Callable] = {}


def _reg(*names):
    def deco(fn):
        for n in names:
            _TRANSFER[n] = fn
        return fn
    return deco


def _sub_jaxprs(params: dict):
    """Every ClosedJaxpr reachable from an eqn's params (generic)."""
    out = []
    for v in params.values():
        vs = v if isinstance(v, (list, tuple)) else [v]
        for u in vs:
            if hasattr(u, "jaxpr") and hasattr(u, "consts"):
                out.append(u)
    return out


def _mag(v: AbsVal) -> Expr:
    return v.lo.neg().emax(v.hi)


def _taint(ins: List[AbsVal]) -> bool:
    return any(v.random for v in ins)


def _shape(eqn, i=0):
    return tuple(eqn.outvars[i].aval.shape)


def _kind(eqn, i=0):
    return _dtype_kind(eqn.outvars[i].aval.dtype)


# ---- comparisons / logicals: bool01 regardless of inputs --------------

@_reg("lt", "le", "gt", "ge", "ne", "and", "or", "xor", "not",
      "is_finite", "reduce_and", "reduce_or")
def _t_bool(interp, eqn, ins):
    if _kind(eqn) != "bool":
        # and/or/xor/not are bitwise on int dtypes — not 0/1 valued
        v = _top(eqn.outvars[0].aval)
    else:
        v = _bool01(_shape(eqn))
    v.random = _taint(ins)
    return [v]


@_reg("eq")
def _t_eq(interp, eqn, ins):
    v = _bool01(_shape(eqn))
    v.random = _taint(ins)
    a, b = ins
    shape = v.shape
    if shape:
        last = len(shape) - 1
        # x[:, None] == iota  (either side): rows along the last axis hold
        # at most one True -> one-hot row, lastsum == 1.  Global iff
        # derived outside a shard_map body (a local iota only
        # enumerates the local tile).
        for p, q in ((a, b), (b, a)):
            if p.iota_dim == last and not q.varies_on(last):
                v.lastsum = ONE
                v.lastsum_global = interp._outside_body()
    return [v]


# ---- structure --------------------------------------------------------

@_reg("iota")
def _t_iota(interp, eqn, ins):
    d = eqn.params["dimension"]
    shape = _shape(eqn)
    v = AbsVal(shape, _kind(eqn), True, ZERO,
               Expr.const(max(shape[d] - 1, 0)))
    v.iota_dim = d
    v.varies = frozenset((d,))
    return [v]


@_reg("broadcast_in_dim")
def _t_broadcast(interp, eqn, ins):
    (a,) = ins
    shape = _shape(eqn)
    bdims = tuple(eqn.params["broadcast_dimensions"])
    out = a.replace(shape=shape, parts=None, sharded=None,
                    tile_total=None)
    # varies: only images of (possibly-varying) operand dims vary
    src_varies = (a.varies if a.varies is not None
                  else frozenset(range(len(a.shape))))
    out.varies = frozenset(bdims[d] for d in src_varies
                           if d < len(bdims) and a.shape[d] == shape[bdims[d]])
    out.iota_dim = (bdims[a.iota_dim]
                    if a.iota_dim is not None and a.iota_dim < len(bdims)
                    else None)
    last = len(shape) - 1
    if last >= 0:
        if bdims and bdims[-1] == last and len(a.shape) >= 1 \
                and a.shape[-1] == shape[last]:
            pass                                   # last axis preserved
        else:
            # last axis is new/broadcast: row sum = size * value
            out.lastsum = None
            out.lastsum_global = False
    if a.parts is not None and a.parts_axis < len(bdims) \
            and bdims[a.parts_axis] is not None:
        out.parts = a.parts
        out.parts_axis = bdims[a.parts_axis]
    return [out]


@_reg("convert_element_type")
def _t_convert(interp, eqn, ins):
    (a,) = ins
    kind = _kind(eqn)
    out = a.replace(shape=_shape(eqn), kind=kind)
    name = eqn.outvars[0].aval.dtype.name
    if kind == "int":
        out.int_valued = True
    elif kind == "float":
        if name == "bfloat16":
            # bf16 has an 8-bit mantissa: integer values stay exact only
            # below 2**8 (the one-hot/mask casts the MXU path feeds)
            hi_c = _mag(a)._const()
            out.int_valued = (a.int_valued and hi_c is not None
                              and hi_c <= 256.0)
        else:
            out.int_valued = a.int_valued
    return [out]


@_reg("reshape")
def _t_reshape(interp, eqn, ins):
    (a,) = ins
    shape = _shape(eqn)
    out = a.drop_structure().replace(shape=shape)
    # a row-major reshape that keeps the last-dim size keeps the rows
    # themselves (jnp.stack's expand_dims included) — the row-sum bound
    # survives
    if a.shape and shape and a.shape[-1] == shape[-1] and a.nonneg:
        out.lastsum, out.lastsum_global = a.lastsum, a.lastsum_global
    return [out]


@_reg("transpose")
def _t_transpose(interp, eqn, ins):
    (a,) = ins
    perm = tuple(eqn.params["permutation"])
    shape = _shape(eqn)
    out = a.replace(shape=shape, parts=None)
    inv = {old: new for new, old in enumerate(perm)}
    out.iota_dim = inv.get(a.iota_dim) if a.iota_dim is not None else None
    out.varies = (frozenset(inv[d] for d in a.varies)
                  if a.varies is not None else None)
    out.sharded = ({k: inv[d] for k, d in a.sharded.items()}
                   if a.sharded else None)
    if perm and perm[-1] != len(perm) - 1:
        out.lastsum, out.lastsum_global = None, False
    if a.parts is not None:
        out.parts, out.parts_axis = a.parts, inv[a.parts_axis]
    return [out]


@_reg("squeeze")
def _t_squeeze(interp, eqn, ins):
    (a,) = ins
    dims = set(eqn.params["dimensions"])
    shape = _shape(eqn)
    keep = [d for d in range(len(a.shape)) if d not in dims]
    remap = {old: new for new, old in enumerate(keep)}
    out = a.replace(shape=shape, parts=None)
    out.iota_dim = remap.get(a.iota_dim) if a.iota_dim is not None else None
    out.varies = (frozenset(remap[d] for d in a.varies if d in remap)
                  if a.varies is not None else None)
    out.sharded = ({k: remap[d] for k, d in a.sharded.items() if d in remap}
                   if a.sharded else None)
    if keep and keep[-1] != len(a.shape) - 1:
        out.lastsum, out.lastsum_global = None, False
    if a.parts is not None and a.parts_axis in remap:
        out.parts, out.parts_axis = a.parts, remap[a.parts_axis]
    return [out]


@_reg("concatenate")
def _t_concat(interp, eqn, ins):
    d = eqn.params["dimension"]
    shape = _shape(eqn)
    out = ins[0]
    for v in ins[1:]:
        out = _join(out, v, shape=shape)
    parts, off = [], 0
    for a, v in zip(eqn.invars, ins):
        n = a.aval.shape[d]
        parts.append((off, off + n, v))
        off += n
    out.parts, out.parts_axis = tuple(parts), d
    if d == len(shape) - 1:
        # concatenating along the last axis adds row sums
        ls = None
        if all(v.nonneg for v in ins):
            ls = ZERO
            for a, v in zip(eqn.invars, ins):
                term = (v.lastsum if v.lastsum is not None
                        else Expr.const(a.aval.shape[d]) * v.hi)
                ls = ls + term
        out.lastsum = ls
        out.lastsum_global = (ls is not None
                              and all(v.lastsum_global or v.lastsum is None
                                      for v in ins)
                              and interp._outside_body())
    return [out]


def _part_lookup(a: AbsVal, axis: int, start: int, stop: int):
    if a.parts is None or a.parts_axis != axis:
        return None
    for p0, p1, v in a.parts:
        if start >= p0 and stop <= p1:
            return v
    return None


@_reg("slice")
def _t_slice(interp, eqn, ins):
    (a,) = ins
    shape = _shape(eqn)
    starts = tuple(eqn.params["start_indices"])
    limits = tuple(eqn.params["limit_indices"])
    hit = None
    if a.parts is not None:
        ax = a.parts_axis
        full_elsewhere = all(
            starts[d] == 0 and limits[d] == a.shape[d]
            for d in range(len(a.shape)) if d != ax)
        if full_elsewhere:
            hit = _part_lookup(a, ax, starts[ax], limits[ax])
    base = hit if hit is not None else a
    out = base.replace(shape=shape, parts=None)
    out.iota_dim = None        # offsets shift iota values
    out.varies = None
    if not base.nonneg and len(shape) > 0 \
            and (starts[-1] != 0 or limits[-1] != a.shape[-1]):
        # last-axis subset sums only shrink for nonnegative values
        out.lastsum, out.lastsum_global = None, False
    return [out]


@_reg("dynamic_slice")
def _t_dynslice(interp, eqn, ins):
    a = ins[0]
    shape = _shape(eqn)
    out = a.replace(shape=shape, parts=None, iota_dim=None, varies=None)
    if not a.nonneg and len(shape) > 0 and shape[-1] != a.shape[-1]:
        out.lastsum, out.lastsum_global = None, False
    return [out]


@_reg("rev", "sort")
def _t_perm(interp, eqn, ins):
    # permutations along an axis: per-element bounds and (for sort) the
    # axis sum are preserved; positional structure is not
    return [v.drop_structure().replace(
        shape=tuple(o.aval.shape),
        lastsum=v.lastsum if v.nonneg else None,
        lastsum_global=v.lastsum_global if v.nonneg else False,
        random=_taint(ins))
        for v, o in zip(ins[:len(eqn.outvars)], eqn.outvars)]


@_reg("gather")
def _t_gather(interp, eqn, ins):
    a = ins[0]
    shape = _shape(eqn)
    out = a.drop_structure().replace(shape=shape, random=_taint(ins))
    dnums = eqn.params.get("dimension_numbers")
    slice_sizes = eqn.params.get("slice_sizes")
    if dnums is None or slice_sizes is None:
        return [out]
    # operand dims passed through WHOLE (full slice, not collapsed) map
    # to output dims via offset_dims in order.  A row selection on the
    # OTHER dims (jnp.take of live pods out of the plane stack) keeps
    # the plane decomposition and the per-row sums on the full dims —
    # selecting (possibly duplicated) rows never grows a row's own sum.
    collapsed = set(getattr(dnums, "collapsed_slice_dims", ()))
    kept = [d for d in range(len(a.shape)) if d not in collapsed]
    full = {}
    for od, ad in zip(tuple(getattr(dnums, "offset_dims", ())), kept):
        if int(slice_sizes[ad]) == int(a.shape[ad]):
            full[ad] = od
    if a.parts is not None and a.parts_axis in full:
        out.parts, out.parts_axis = a.parts, full[a.parts_axis]
    last_a, last_o = len(a.shape) - 1, len(shape) - 1
    if a.nonneg and a.lastsum is not None and full.get(last_a) == last_o:
        out.lastsum, out.lastsum_global = a.lastsum, a.lastsum_global
    if a.sharded:
        sh = {k: full[d] for k, d in a.sharded.items() if d in full}
        out.sharded = sh or None
    return [out]


@_reg("pad")
def _t_pad(interp, eqn, ins):
    a, pval = ins

    def padded(v, shape=None):
        j = _join(v, pval, shape=shape)
        out = j.drop_structure()
        if _is_zero(pval):
            # zero padding adds nothing to any row sum
            out.lastsum, out.lastsum_global = j.lastsum, j.lastsum_global
        return out

    return [padded(a, _shape(eqn))]


# ---- arithmetic -------------------------------------------------------

def _const_like(v: AbsVal) -> bool:
    return v.varies == frozenset() or v.shape == ()


@_reg("add", "sub")
def _t_addsub(interp, eqn, ins):
    a, b = ins
    sub = eqn.primitive.name == "sub"
    lo = a.lo + (b.hi.neg() if sub else b.lo)
    hi = a.hi + (b.lo.neg() if sub else b.hi)
    out = AbsVal(_shape(eqn), _kind(eqn),
                 a.int_valued and b.int_valued, lo, hi,
                 random=_taint(ins))
    if not sub and a.nonneg and b.nonneg and out.shape:
        la = a.lastsum if a.lastsum is not None else \
            Expr.const(out.shape[-1]) * a.hi
        lb = b.lastsum if b.lastsum is not None else \
            Expr.const(out.shape[-1]) * b.hi
        if a.lastsum is not None or b.lastsum is not None:
            out.lastsum = la + lb
            out.lastsum_global = a.lastsum_global and b.lastsum_global
    out.sharded = a.sharded if a.sharded else b.sharded
    return [out]


@_reg("mul")
def _t_mul(interp, eqn, ins):
    a, b = ins
    if a.nonneg and b.nonneg:
        lo, hi = ZERO, a.hi * b.hi
    else:
        m = _mag(a) * _mag(b)
        lo, hi = m.neg(), m
    out = AbsVal(_shape(eqn), _kind(eqn),
                 a.int_valued and b.int_valued, lo, hi,
                 random=_taint(ins))
    if a.nonneg and b.nonneg:
        for p, q in ((a, b), (b, a)):
            if p.lastsum is not None and _const_like(q):
                out.lastsum = p.lastsum * q.hi
                out.lastsum_global = p.lastsum_global
                break
    out.sharded = a.sharded if a.sharded else b.sharded
    return [out]


@_reg("div")
def _t_div(interp, eqn, ins):
    a, b = ins
    out = AbsVal(_shape(eqn), _kind(eqn), _kind(eqn) == "int",
                 BOT, TOP, random=_taint(ins))
    if a.nonneg and b.lo._const() is not None and b.lo._const() >= 1.0:
        out.lo, out.hi = ZERO, a.hi
    return [out]


@_reg("floor", "round", "ceil")
def _t_floor(interp, eqn, ins):
    (a,) = ins
    return [a.drop_structure(random=a.random).replace(
        shape=_shape(eqn), int_valued=True,
        lo=a.lo + Expr.const(-1.0), hi=a.hi + Expr.const(1.0))]


@_reg("neg")
def _t_neg(interp, eqn, ins):
    (a,) = ins
    return [AbsVal(_shape(eqn), _kind(eqn), a.int_valued,
                   a.hi.neg(), a.lo.neg(), random=a.random)]


@_reg("abs")
def _t_abs(interp, eqn, ins):
    (a,) = ins
    return [AbsVal(_shape(eqn), _kind(eqn), a.int_valued, ZERO, _mag(a),
                   random=a.random)]


@_reg("max", "min")
def _t_maxmin(interp, eqn, ins):
    a, b = ins
    mx = eqn.primitive.name == "max"
    lo = a.lo.emax(b.lo) if mx else a.lo.emin(b.lo)
    hi = a.hi.emax(b.hi) if mx else a.hi.emin(b.hi)
    out = AbsVal(_shape(eqn), _kind(eqn),
                 a.int_valued and b.int_valued, lo, hi,
                 random=_taint(ins))
    out.sharded = a.sharded if a.sharded else b.sharded
    return [out]


@_reg("clamp")
def _t_clamp(interp, eqn, ins):
    lo_v, x, hi_v = ins
    return [AbsVal(_shape(eqn), _kind(eqn),
                   x.int_valued and lo_v.int_valued and hi_v.int_valued,
                   x.lo.emax(lo_v.lo), x.hi.emin(hi_v.hi),
                   random=_taint(ins))]


@_reg("select_n")
def _t_select(interp, eqn, ins):
    pred, cases = ins[0], ins[1:]
    out = cases[0]
    for c in cases[1:]:
        out = _join(out, c, shape=_shape(eqn))
    # value taint comes from the selected branches; a random predicate
    # choosing between non-random values does not make them gumbel
    out = out.replace(shape=_shape(eqn))
    return [out]


@_reg("sign")
def _t_sign(interp, eqn, ins):
    (a,) = ins
    return [AbsVal(_shape(eqn), _kind(eqn), True, Expr.const(-1.0), ONE,
                   random=a.random)]


@_reg("integer_pow")
def _t_ipow(interp, eqn, ins):
    (a,) = ins
    y = eqn.params["y"]
    int_valued = a.int_valued and y >= 0
    if a.nonneg and y >= 0:
        hi = ONE
        for _ in range(min(int(y), 8)):
            hi = hi * a.hi
        if y > 8:
            hi = TOP
        return [AbsVal(_shape(eqn), _kind(eqn), int_valued, ZERO, hi,
                       random=a.random)]
    return [AbsVal(_shape(eqn), _kind(eqn), int_valued, BOT, TOP,
                   random=a.random)]


@_reg("copy", "stop_gradient", "reduce_precision", "real", "imag",
      "device_put")
def _t_copy(interp, eqn, ins):
    a = ins[0]
    return [a.replace(shape=_shape(eqn))]


@_reg("exp", "log", "log1p", "expm1", "tanh", "logistic", "rsqrt",
      "sqrt", "sin", "cos", "erf", "erf_inv", "pow",
      "nextafter", "rem", "shift_right_logical",
      "shift_left", "bitcast_convert_type", "population_count")
def _t_float_misc(interp, eqn, ins):
    outs = []
    for o in eqn.outvars:
        v = _top(o.aval)
        v.random = _taint(ins)
        outs.append(v)
    return outs


# ---- reductions (local) ----------------------------------------------

@_reg("reduce_sum")
def _t_reduce_sum(interp, eqn, ins):
    (a,) = ins
    axes = tuple(eqn.params["axes"])
    count = 1
    for d in axes:
        count *= a.shape[d]
    cexpr = interp.size_expr(count) if len(axes) == 1 else Expr.const(count)
    if a.nonneg:
        hi = cexpr * a.hi
        if axes == (len(a.shape) - 1,) and a.lastsum is not None:
            hi = hi.emin(a.lastsum)
        lo = ZERO
    else:
        hi = cexpr * _mag(a)
        lo = hi.neg()
    out = AbsVal(_shape(eqn), _kind(eqn), a.int_valued, lo, hi,
                 random=a.random)
    # summing over a device-sharded dim: the total across shards is the
    # global sum -> bound for a following psum over that mesh axis
    if a.sharded:
        tt = {}
        for key, dim in a.sharded.items():
            if dim in axes:
                if a.lastsum is not None and a.lastsum_global \
                        and axes == (len(a.shape) - 1,):
                    tt[key] = (a.lastsum, True)
                else:
                    tt[key] = (hi * interp.mesh_sym(key), False)
        if tt:
            out.tile_total = tt
    return [out]


@_reg("reduce_max", "reduce_min", "cummax", "cummin", "argsort")
def _t_reduce_minmax(interp, eqn, ins):
    a = ins[0]
    return [a.drop_structure(random=_taint(ins)).replace(
        shape=_shape(eqn),
        sharded=None if eqn.primitive.name.startswith("cum") else None)]


@_reg("cumsum")
def _t_cumsum(interp, eqn, ins):
    (a,) = ins
    d = eqn.params.get("axis", 0)
    n = a.shape[d] if a.shape else 1
    if a.nonneg:
        lo, hi = ZERO, Expr.const(n) * a.hi
    else:
        hi = Expr.const(n) * _mag(a)
        lo = hi.neg()
    return [AbsVal(_shape(eqn), _kind(eqn), a.int_valued, lo, hi,
                   random=a.random)]


@_reg("argmax", "argmin")
def _t_argmax(interp, eqn, ins):
    (a,) = ins
    # the tie-break discipline: a float argmax is deterministic only
    # through the gumbel decomposition (argmax over where(tie, gumbel,
    # -2**62) == categorical); bool/int operands are the blessed
    # first-true-index / counting idioms
    if a.kind == "float" and not a.random:
        interp._finding(
            "exact/raw-tie-argmax",
            "argmax over a float operand with no PRNG taint: tie-broken "
            "selections must route through the gumbel decomposition "
            "(ops/kernels.py gumbel_tiebreak_argmax) so ties replay "
            "selectHost bit-for-bit")
    axes = tuple(eqn.params["axes"])
    hi = max((a.shape[d] for d in axes), default=1)
    return [AbsVal(_shape(eqn), _kind(eqn), True, ZERO,
                   Expr.const(max(hi - 1, 0)))]


@_reg("scatter", "scatter-add", "scatter-max", "scatter-min", "scatter-mul")
def _t_scatter(interp, eqn, ins):
    op, _, upd = ins[0], ins[1], ins[2]
    name = eqn.primitive.name
    int_valued = op.int_valued and upd.int_valued
    if name == "scatter-add":
        n = 1
        for d in upd.shape:
            n *= d
        hi = op.hi + Expr.const(n) * upd.hi.emax(ZERO)
        lo = op.lo + Expr.const(n) * upd.lo.emin(ZERO)
    else:
        j = _join(op, upd, shape=_shape(eqn))
        lo, hi, int_valued = j.lo, j.hi, j.int_valued
    return [AbsVal(_shape(eqn), _kind(eqn), int_valued, lo, hi,
                   random=_taint(ins))]


@_reg("dynamic_update_slice")
def _t_dus(interp, eqn, ins):
    a, b = ins[0], ins[1]
    return [_join(a, b, shape=_shape(eqn)).drop_structure()]


# ---- dot_general: the load-bearing rule -------------------------------

@_reg("dot_general")
def _t_dot(interp, eqn, ins):
    a, b = ins
    (lc, rc), (lb, rb) = eqn.params["dimension_numbers"]
    K = 1
    for d in lc:
        K *= a.shape[d]
    kexpr = interp.size_expr(K) if len(lc) == 1 else Expr.const(K)
    int_valued = a.int_valued and b.int_valued
    nonneg = a.nonneg and b.nonneg
    if nonneg:
        hi, lo = kexpr * a.hi * b.hi, ZERO
    else:
        hi = kexpr * _mag(a) * _mag(b)
        lo = hi.neg()
    out = AbsVal(_shape(eqn), _kind(eqn), int_valued, lo, hi,
                 random=_taint(ins))
    # the exact-count refinement (2D matmul contracting A's last axis,
    # against B's first — or B's last, the transposed-rhs form):
    # out[s, z] = sum_p A[s, p] * B[p, z]       (or B[z, p])
    #   per-element   <= rowsum(A) * max(B)        (one-hot dot rule)
    #   per-row sum   <= rowsum(A) * rowsum(B)     (counts stay counts;
    #                    B-first form only — B's row sums run over z there)
    #   over tiles of a sharded p-dim: global rowsum(A) bounds the TOTAL
    if (nonneg and len(a.shape) == 2 and len(b.shape) == 2
            and lc == (1,) and rc in ((0,), (1,)) and not lb and not rb):
        # effective row-sum bounds: explicit if derived (one-hot rows),
        # else the implicit size*max bound of the current (local) shape
        la = a.lastsum if a.lastsum is not None else kexpr * a.hi
        ga = a.lastsum_global if a.lastsum is not None \
            else interp._outside_body()
        if la.is_finite:
            out.hi = out.hi.emin(la * b.hi)
        if rc == (0,):
            lbnd = (b.lastsum if b.lastsum is not None
                    else interp.size_expr(b.shape[-1]) * b.hi)
            gb = b.lastsum_global if b.lastsum is not None \
                else interp._outside_body()
            if la.is_finite and lbnd.is_finite:
                out.lastsum = la * lbnd
                out.lastsum_global = ga and gb
        if a.sharded and ga and la.is_finite:
            tt = {}
            for key, dim in a.sharded.items():
                if dim == 1:
                    tt[key] = (la * b.hi, True)
            if tt:
                out.tile_total = tt
    return [out]


# ---- PRNG -------------------------------------------------------------

@_reg("random_bits", "random_fold_in", "random_wrap", "random_unwrap",
      "random_seed", "random_split", "random_gamma", "threefry2x32")
def _t_random(interp, eqn, ins):
    outs = []
    for o in eqn.outvars:
        v = _top(o.aval)
        v.random = True
        outs.append(v)
    return outs


# ---- control flow -----------------------------------------------------

@_reg("jit", "closed_call", "core_call", "remat", "checkpoint",
      "custom_jvp_call", "custom_vjp_call", "custom_vjp_call_jaxpr")
def _t_call(interp, eqn, ins):
    for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
        sub = eqn.params.get(key)
        if sub is not None and hasattr(sub, "jaxpr"):
            outs = interp.run(sub, list(ins))
            return outs[:len(eqn.outvars)] + [
                _top(o.aval) for o in eqn.outvars[len(outs):]]
    return interp._default(eqn, ins)


def _stabilize(prev: AbsVal, out: AbsVal) -> AbsVal:
    """Field-wise widening for loop carries: keep a fact only while the
    body's output still supports it.  Every field can only degrade (to
    its own TOP) and never recover, so iterating ``w = stabilize(w,
    body(w))`` reaches a post-fixpoint in a handful of rounds; at the
    fixpoint ``body(w) <= w`` holds field-wise, making ``w`` a sound
    invariant for every loop iteration."""
    if prev == out:
        return prev
    ls_ok = (prev.lastsum == out.lastsum
             and prev.lastsum_global == out.lastsum_global)
    return AbsVal(
        shape=prev.shape,
        kind=prev.kind if prev.kind == out.kind else "other",
        int_valued=prev.int_valued and out.int_valued,
        lo=prev.lo if prev.lo == out.lo else BOT,
        hi=prev.hi if prev.hi == out.hi else TOP,
        lastsum=prev.lastsum if ls_ok else None,
        lastsum_global=prev.lastsum_global if ls_ok else False,
        # taint is a must-property (PRNG-derived on EVERY path), so it
        # survives only if the body re-derives it each round
        random=prev.random and out.random,
        iota_dim=prev.iota_dim if prev.iota_dim == out.iota_dim else None,
        varies=prev.varies if prev.varies == out.varies else None,
        parts=prev.parts if (prev.parts == out.parts
                             and prev.parts_axis == out.parts_axis) else None,
        parts_axis=prev.parts_axis,
        sharded=prev.sharded if prev.sharded == out.sharded else None,
        tile_total=(prev.tile_total
                    if prev.tile_total == out.tile_total else None),
    )


@_reg("while")
def _t_while(interp, eqn, ins):
    cn = eqn.params["cond_nconsts"]
    bn = eqn.params["body_nconsts"]
    cond_consts = ins[:cn]
    body_consts = ins[cn:cn + bn]
    carry = ins[cn + bn:]
    body = eqn.params["body_jaxpr"]
    cond = eqn.params["cond_jaxpr"]
    # fixpoint widening: seed the carries with their initial facts and
    # stabilize against the body until nothing degrades further.  This
    # is what lets the round loop carry the score-plane bundle (gumbel
    # taint, per-plane decomposition, one-hot row sums) into the body
    # without collapsing it to TOP.
    carry_vars = body.jaxpr.invars[bn:]
    w = [v.replace(shape=tuple(var.aval.shape))
         for v, var in zip(carry, carry_vars)]
    w += [_top(var.aval) for var in carry_vars[len(w):]]
    # fixpoint-search passes are muted: reductions/findings are recorded
    # only on the final pass under the converged invariant
    saved = interp.reductions, interp.findings
    interp.reductions, interp.findings = [], []
    try:
        for _ in range(4):
            outs = interp.run(body, body_consts + w)
            new_w = [_stabilize(p, o) for p, o in zip(w, outs)]
            if new_w == w:
                break
            w = new_w
        else:
            # no convergence (should not happen: fields only degrade) —
            # fall back to the sound TOP widening
            w = [_top(var.aval) for var in carry_vars]
    finally:
        interp.reductions, interp.findings = saved
    interp.run(cond, cond_consts + list(w[:len(cond.jaxpr.invars) - cn]))
    outs = interp.run(body, body_consts + w)
    return [_stabilize(p, o).replace(shape=tuple(o_var.aval.shape))
            for p, o, o_var in zip(w, outs, eqn.outvars)]


@_reg("scan")
def _t_scan(interp, eqn, ins):
    num_consts = eqn.params["num_consts"]
    num_carry = eqn.params["num_carry"]
    body = eqn.params["jaxpr"]
    consts = ins[:num_consts]
    xs = ins[num_consts + num_carry:]
    carry = [_top(v.aval)
             for v in body.jaxpr.invars[num_consts:num_consts + num_carry]]
    sliced = []
    for v, var in zip(xs, body.jaxpr.invars[num_consts + num_carry:]):
        sliced.append(v.drop_structure().replace(
            shape=tuple(var.aval.shape),
            lastsum=v.lastsum if v.nonneg else None,
            lastsum_global=v.lastsum_global if v.nonneg else False))
    interp.run(body, consts + carry + sliced)
    return [_top(o.aval) for o in eqn.outvars]


@_reg("cond")
def _t_cond(interp, eqn, ins):
    index, ops = ins[0], ins[1:]
    branches = eqn.params["branches"]
    outs_per = []
    for br in branches:
        outs_per.append(interp.run(br, list(ops)))
    joined = []
    for i, o in enumerate(eqn.outvars):
        vals = [outs[i] for outs in outs_per if i < len(outs)]
        if not vals:
            joined.append(_top(o.aval))
            continue
        j = vals[0]
        for v in vals[1:]:
            j = _join(j, v, shape=tuple(o.aval.shape))
        joined.append(j.replace(shape=tuple(o.aval.shape)))
    return joined


# ---- shard_map + collectives ------------------------------------------

@_reg("shard_map")
def _t_shard_map(interp, eqn, ins):
    body = eqn.params["jaxpr"]          # plain Jaxpr
    body_ins = []
    for v, spec in zip(ins, eqn.params["in_specs"]):
        sharded = dict(v.sharded or {})
        for dim, axes in enumerate(spec):
            if axes is None:
                continue
            for ax in (axes if isinstance(axes, tuple) else (axes,)):
                sharded[ax] = dim
        body_ins.append(v.replace(sharded=sharded or None))
    interp.in_shardmap += 1
    try:
        outs = interp._frame(body, [], body_ins)
    finally:
        interp.in_shardmap -= 1
    result = []
    for o, v in zip(eqn.outvars, outs):
        result.append(v.drop_structure().replace(shape=tuple(o.aval.shape)))
    return result


def _record_collective(interp, eqn, v: AbsVal, axes, lo, hi, note=""):
    aval = eqn.invars[0].aval
    interp.reductions.append(Reduction(
        op=eqn.primitive.name,
        kind=_REDUCE_KIND.get(eqn.primitive.name, eqn.primitive.name),
        axes=tuple(str(a) for a in axes),
        dtype=aval.dtype.name,
        shape=tuple(aval.shape),
        int_dtype=_dtype_kind(aval.dtype) in ("int", "bool"),
        int_valued=v.int_valued,
        lo=lo, hi=hi, note=note))


@_reg("psum")
def _t_psum(interp, eqn, ins):
    axes = tuple(eqn.params["axes"])
    outs = []
    for v, o in zip(ins, eqn.outvars):
        lo, hi = v.lo, v.hi
        notes = []
        for ax in axes:
            tt = (v.tile_total or {}).get(ax)
            if tt is not None:
                hi = tt[0]
                lo = ZERO if v.nonneg else hi.neg()
                notes.append("disjoint-tile total over '%s'" % ax)
            else:
                fan = interp.mesh_sym(ax)
                hi = fan * hi
                lo = fan * lo if v.nonneg else (fan * _mag(v)).neg()
        _record_collective(interp, eqn, v, axes, lo, hi,
                           note="; ".join(notes))
        outs.append(AbsVal(tuple(o.aval.shape), v.kind, v.int_valued,
                           lo, hi, random=v.random))
    return outs


@_reg("pmax", "pmin")
def _t_pminmax(interp, eqn, ins):
    axes = tuple(eqn.params["axes"])
    outs = []
    for v, o in zip(ins, eqn.outvars):
        _record_collective(interp, eqn, v, axes, v.lo, v.hi)
        outs.append(v.drop_structure().replace(shape=tuple(o.aval.shape)))
    return outs


@_reg("all_gather")
def _t_all_gather(interp, eqn, ins):
    (v,) = ins
    axes = eqn.params["axis_name"]
    axes = axes if isinstance(axes, tuple) else (axes,)
    _record_collective(interp, eqn, v, axes, v.lo, v.hi)
    if len(v.shape) >= 2:
        interp._finding(
            "exact/shardmap-row-gather",
            "all_gather of a rank-%d operand %s inside a shard_map body: "
            "the gather-free discipline moves per-shard REDUCED vectors "
            "(winner indices, scalars), never tiles/rows — reduce before "
            "you gather" % (len(v.shape), "x".join(map(str, v.shape))))
    out = v.drop_structure().replace(shape=_shape(eqn))
    if v.nonneg and v.lastsum is not None \
            and eqn.params.get("all_gather_dimension", 0) != len(v.shape) - 1:
        out.lastsum, out.lastsum_global = v.lastsum, v.lastsum_global
    return [out]


@_reg("axis_index")
def _t_axis_index(interp, eqn, ins):
    return [AbsVal((), "int", True, ZERO, TOP)]

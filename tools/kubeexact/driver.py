"""kubeexact driver: per-entry proving, judging, and exemption audit.

For every registry entry with ``exact=True`` this module

  1. traces the program at its largest ladder rung (the probe rung) and
     runs the exactness lattice (absint.Interp) over the jaxpr, seeding
     input facts the builders guarantee (entry.exact_facts);
  2. judges every recorded cross-shard reduction against the
     committed north-star environment: float max/min and integer-dtype
     sums are exact by construction; float sums must be integer-valued
     with a finite symbolic bound that evaluates below 2**24;
  3. walks the collective surface at every ladder rung (operand bytes
     per rung — the DCN cost attribution kubecensus joins);
  4. applies the entry's audited (rule, reason) exemptions, flagging
     stale ones exactly like kubecensus.

``prove_callable`` is the public seam the bad-snippet tests drive,
mirroring kubecensus.audit_callable.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Tuple

from tools.kubecensus.registry import ENTRIES, Entry, Rung, build_world
from tools.kubecensus.rules import Finding

from . import northstar, surface
from .absint import AbsVal, Interp, Reduction
from .bounds import INT_EXACT_LIMIT, ONE, ZERO, sym_table


# ---------------------------------------------------------------- facts

def _fact_onehot_rows(aval) -> AbsVal:
    """Rows along the last axis are one-hot: values in {0, 1} and each
    row sums to exactly 1 — a GLOBAL bound (it holds for the full array,
    not just a shard's tile)."""
    from .absint import _dtype_kind
    return AbsVal(tuple(aval.shape), _dtype_kind(aval.dtype), True,
                  ZERO, ONE, lastsum=ONE, lastsum_global=True)


_FACTS = {"onehot_rows": _fact_onehot_rows}


# ---------------------------------------------------------------- tracing

def _flat_call(fn, args, kwargs, static_argnames, static_argnums):
    """(positional-only callable, flat concrete args) via the census
    closure — the SAME seam kubecensus traces through, so the jaxpr the
    prover sees is the jaxpr the compile census commits."""
    from tools.kubecensus import census

    kwargs = kwargs or {}
    dyn_kw, static_kw = census._split_kwargs(kwargs, static_argnames)
    call = census._closure(fn, args, static_argnums, list(dyn_kw),
                           static_kw)
    stat = set(static_argnums)
    flat = [a for i, a in enumerate(args) if i not in stat]
    flat += [dyn_kw[k] for k in dyn_kw]
    return call, tuple(flat)


def _input_absvals(flat_args, jaxpr_invars,
                   facts: Tuple[Tuple[str, str], ...]) -> List[Optional[AbsVal]]:
    """Default every input to TOP; seed fact-matched leaves.  Facts match
    by substring against the leaf's pytree path (keystr), so a fact names
    a builder field (\"zone_hot\"), not a flatten position."""
    import jax

    leaves, _ = jax.tree_util.tree_flatten_with_path(tuple(flat_args))
    invals: List[Optional[AbsVal]] = []
    for (path, leaf), var in zip(leaves, jaxpr_invars):
        v = None
        ps = jax.tree_util.keystr(path)
        for substr, factname in facts:
            if substr in ps and factname in _FACTS:
                v = _FACTS[factname](var.aval)
        invals.append(v)
    return invals


# ---------------------------------------------------------------- judging

def _judge_reduction(red: Reduction, env: Dict[str, float]) -> dict:
    """One manifest proof row for a recorded reduction."""
    row = {
        "op": red.op, "kind": red.kind, "axes": list(red.axes),
        "dtype": red.dtype, "shape": list(red.shape),
        "int_valued": bool(red.int_valued), "note": red.note,
    }
    if red.kind in ("max", "min", "gather", "permute", "all_to_all"):
        row.update(status="exact", why="order-free reduction")
        return row
    if red.int_dtype:
        row.update(status="exact", why="integer dtype (modular, exact in "
                                        "any association order)")
        return row
    # a float sum: needs integer-valuedness + a bound below 2**24
    if not red.int_valued:
        row.update(status="violation", rule="exact/nonexact-psum",
                   why="float sum of values not proven integer-valued — "
                       "association order changes the bits")
        return row
    bound_expr = red.lo.neg().emax(red.hi)
    row["bound"] = bound_expr.render()
    try:
        bound = bound_expr.eval(env)
    except KeyError as e:
        row.update(status="violation", rule="exact/sum-overflow",
                   why="bound references a symbol outside the committed "
                       "north-star environment: %s" % e)
        return row
    row["bound_northstar"] = bound
    if bound >= INT_EXACT_LIMIT:
        row.update(status="violation", rule="exact/sum-overflow",
                   why="integer-valued sum bound %.6g >= 2**24 at the "
                       "north-star shapes — partial sums leave the exact "
                       "f32 integer range" % bound)
        return row
    margin = INT_EXACT_LIMIT / bound if bound > 0 else float("inf")
    row.update(status="exact", margin=round(margin, 4),
               why="integer-valued sum, bound %.6g < 2**24" % bound)
    return row


# ---------------------------------------------------------------- proving

@dataclasses.dataclass
class ProofResult:
    program: str
    proofs: List[dict]
    findings: List[Finding]          # unsuppressed
    suppressed: List[Finding]
    surface: Dict[str, List[dict]]   # rung name -> collective rows
    facts: Tuple[Tuple[str, str], ...] = ()

    @property
    def clean(self) -> bool:
        return not self.findings


def prove_callable(program: str, fn, args: tuple, kwargs: dict = None,
                   static_argnames: Tuple[str, ...] = (),
                   static_argnums: Tuple[int, ...] = (),
                   facts: Tuple[Tuple[str, str], ...] = (),
                   sizes: Optional[Dict[str, int]] = None,
                   env: Optional[Dict[str, float]] = None,
                   ) -> Tuple[List[dict], List[Finding]]:
    """Prove one callable at one concrete input signature.  Returns
    (proof rows, findings) with NO exemptions applied — the public seam
    the bad-snippet tests drive."""
    import jax

    closed = None
    call, flat = _flat_call(fn, args, kwargs, static_argnames,
                            static_argnums)
    closed = jax.make_jaxpr(call)(*flat)
    invals = _input_absvals(flat, closed.jaxpr.invars, tuple(facts))
    interp = Interp(sym_table({k: int(v) for k, v in (sizes or {}).items()}),
                    program=program)
    interp.run(closed, invals)
    env = dict(northstar.NORTHSTAR_ENV if env is None else env)
    proofs: List[dict] = []
    findings: List[Finding] = list(interp.findings)
    for red in interp.reductions:
        row = _judge_reduction(red, env)
        proofs.append(row)
        if row["status"] == "violation":
            findings.append(Finding(
                rule=row["rule"], program=program,
                message="%s %s %s %s: %s" % (
                    red.op, red.kind, "x".join(map(str, red.shape)),
                    red.dtype, row["why"])))
    return proofs, findings


def _entry_sizes(w) -> Dict[str, int]:
    return {"B": w.B, "N": w.N, "P": w.P, "R": w.R,
            "Z": int(w.cluster.zone_hot.shape[-1])}


def prove_entry(entry: Entry) -> ProofResult:
    """Prove one registry entry at its largest ladder rung, census the
    collective surface at every rung, and apply its audited exemptions."""
    import jax

    rung = entry.ladder[-1]
    w = build_world(rung)
    fn, args, kwargs = entry.build(w)
    proofs, raw = prove_callable(
        entry.key, fn, args, kwargs,
        static_argnames=entry.static_argnames,
        static_argnums=entry.static_argnums,
        facts=entry.exact_facts,
        sizes=_entry_sizes(w))

    surf: Dict[str, List[dict]] = {}
    for r in entry.ladder:
        wr = build_world(r)
        fr, ar, kr = entry.build(wr)
        call, flat = _flat_call(fr, ar, kr, entry.static_argnames,
                                entry.static_argnums)
        surf[r.name] = surface.collect_collectives(
            jax.make_jaxpr(call)(*flat))

    exempt = dict(entry.exact_exempt)
    used = set()
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for f in raw:
        reason = exempt.get(f.rule, "")
        if reason:
            f.suppressed, f.reason = True, reason
            used.add(f.rule)
            suppressed.append(f)
        else:
            findings.append(f)
    for p in proofs:
        if p["status"] == "violation" and p.get("rule") in exempt:
            p["status"] = "exempt"
            p["reason"] = exempt[p["rule"]]
    for rule, reason in exempt.items():
        if rule not in used:
            findings.append(Finding(
                "exact/unused-exemption", entry.key,
                "exemption for %s matches no finding — remove the stale "
                "entry (reason was: %s)" % (rule, reason)))
    return ProofResult(program=entry.key, proofs=proofs,
                       findings=findings, suppressed=suppressed,
                       surface=surf, facts=entry.exact_facts)


# ---------------------------------------------------------------- headroom

def headroom(results: List[ProofResult]) -> Tuple[dict, List[Finding]]:
    """The committed 2**24 margin: the minimum across every proved float
    sum, with the dominating term named.  Margin below the floor is a
    finding — the gate that keeps \"grow the deployment target\" an
    explicit reviewed change."""
    min_margin = float("inf")
    dominating = ""
    for r in results:
        for p in r.proofs:
            m = p.get("margin")
            if m is not None and m < min_margin:
                min_margin = m
                dominating = "%s: %s %s bound %s = %.6g" % (
                    r.program, p["op"], p["kind"], p.get("bound", "?"),
                    p.get("bound_northstar", float("nan")))
    row = {
        "floor": northstar.MARGIN_FLOOR,
        "min_margin": (None if min_margin == float("inf")
                       else round(min_margin, 4)),
        "dominating": dominating,
        "int_exact_limit": INT_EXACT_LIMIT,
    }
    findings: List[Finding] = []
    if min_margin != float("inf") and min_margin < northstar.MARGIN_FLOOR:
        findings.append(Finding(
            "exact/headroom", "<northstar>",
            "minimum 2**24 margin %.4gx is below the %gx floor — "
            "dominating term: %s" % (min_margin, northstar.MARGIN_FLOOR,
                                     dominating)))
    return row, findings


# ---------------------------------------------------------------- running

@dataclasses.dataclass
class ExactResult:
    results: List[ProofResult]
    headroom: dict
    findings: List[Finding]          # global, unsuppressed (incl. headroom)
    suppressed: List[Finding]

    @property
    def clean(self) -> bool:
        return not self.findings


def exact_entries(entries: Optional[List[Entry]] = None) -> List[Entry]:
    return [e for e in (ENTRIES if entries is None else entries)
            if e.exact]


def run_exact(entries: Optional[List[Entry]] = None) -> ExactResult:
    results = [prove_entry(e) for e in exact_entries(entries)]
    hr, hr_findings = headroom(results)
    findings: List[Finding] = list(hr_findings)
    suppressed: List[Finding] = []
    for r in results:
        findings.extend(r.findings)
        suppressed.extend(r.suppressed)
    return ExactResult(results=results, headroom=hr, findings=findings,
                       suppressed=suppressed)

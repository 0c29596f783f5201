"""Census tracer: abstract interpretation of every registered jit root.

For each (entry x ladder rung [x mesh]) variant this module abstractifies
the registry-built inputs to ShapeDtypeStructs, runs ``jit(...).lower()``
(tracing + StableHLO lowering, no device execution, no compile), and
derives the manifest row: flattened in/out avals, the donation aliasing
XLA honored, a stable sha256 of the closed jaxpr, and XLA cost-analysis
FLOPs/bytes.  The jaxpr-level rule family (rules.py) runs once per entry
on the smallest rung — the rules are shape-independent, the ladder rows
are not.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Dict, List, Optional, Tuple

from . import rules
from .registry import ENTRIES, Entry, Rung, build_world
from .rules import Finding

__all__ = ["Finding", "CensusResult", "run_census", "audit_entry",
           "audit_callable", "trace_variant"]


def _is_array(x) -> bool:
    import numpy as np
    import jax
    return isinstance(x, (np.ndarray, jax.Array))


def _abstract(tree, keep_sharding: bool = False):
    """Arrays -> ShapeDtypeStruct (optionally keeping committed
    NamedShardings); everything else passes through untouched."""
    import jax

    def leaf(x):
        if _is_array(x):
            sh = None
            if keep_sharding and isinstance(x, jax.Array):
                s = x.sharding
                if type(s).__name__ == "NamedSharding":
                    sh = s
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return x
    return jax.tree.map(leaf, tree)


def _split_kwargs(kwargs: dict,
                  static_names: Tuple[str, ...]) -> Tuple[dict, dict]:
    """(dynamic traced kwargs, static kwargs).  Statics are exactly the
    names the jit's static_argnames declares (registry Entry mirrors the
    decorator); everything else — arrays AND Python scalars — is traced
    and contributes an aval to the compiled signature."""
    dyn, static = {}, {}
    for k, v in kwargs.items():
        (static if k in static_names else dyn)[k] = v
    return dyn, static


def aval_strs(tree) -> List[str]:
    """Flattened 'dtype[d0,d1]' signatures, matching the spelling of
    jax's own compile-log ShapedArray repr (what the compile watchdog,
    utils/sanitize.py, records).  Python
    scalars are traced as weak-typed rank-0 avals of the default dtype —
    record them the way the log will report them."""
    import jax
    out = []
    for leaf in jax.tree_util.tree_leaves(tree):
        if hasattr(leaf, "shape") and hasattr(leaf, "dtype"):
            dims = ",".join(str(d) for d in leaf.shape)
            out.append("%s[%s]" % (leaf.dtype.name, dims))
        elif isinstance(leaf, bool):
            out.append("bool[]")
        elif isinstance(leaf, int):
            out.append("int32[]")
        elif isinstance(leaf, float):
            out.append("float32[]")
        else:
            out.append(repr(leaf))
    return out


def _lowering_hash(text: str) -> str:
    """sha256 of the lowered StableHLO module text — the traced jaxpr's
    canonical serialization.  NOT the pretty-printed jaxpr: jax's jaxpr
    printer shares repeated sub-jaxprs through a process-wide name
    counter (_where17 vs _where18), so str(jaxpr) depends on what else
    the process traced first; the MLIR module is self-contained and —
    together with the cold-cache lowering in trace_variant — stable
    across processes for a fixed jax version."""
    return hashlib.sha256(text.encode()).hexdigest()


def _static_sig(static_kw: dict) -> str:
    """Short stable digest of the static argument values (ProgramConfig
    etc.) so manifest rows distinguish static variants without embedding
    pages of repr."""
    r = repr(sorted((k, repr(v)) for k, v in static_kw.items()))
    return hashlib.sha256(r.encode()).hexdigest()[:16]


def _closure(fn, args, static_argnums: Tuple[int, ...],
             dyn_names: List[str], static_kw: dict):
    """A positional-only callable over (dynamic pos args + dynamic
    kwargs), with every static (positional or keyword) closed over —
    what make_jaxpr / eval_shape can trace.  ``args`` supplies the
    static positions' values; dynamic positions are replaced from the
    call's flat inputs."""
    stat = set(static_argnums)
    dyn_idx = [i for i in range(len(args)) if i not in stat]

    def call(*flat):
        full = list(args)
        for j, i in enumerate(dyn_idx):
            full[i] = flat[j]
        dkw = dict(zip(dyn_names, flat[len(dyn_idx):]))
        return fn(*full, **dkw, **static_kw)
    return call


@dataclasses.dataclass
class Variant:
    """One traced (entry, rung[, mesh]) combination."""
    row: dict
    lowered: object
    entry: Entry


def trace_variant(entry: Entry, rung: Rung, mesh: bool = False) -> Variant:
    import jax

    world = build_world(rung)
    fn, args, kwargs = entry.build(world)
    dyn_kw, static_kw = _split_kwargs(kwargs, entry.static_argnames)
    if mesh:
        args, dyn_kw = _mesh_place(entry, args, dyn_kw)
    # keep committed NamedShardings either for the @mesh twin (inputs
    # placed above) or for entries whose builders already commit them
    # (the shard_map family) — stripping them would lower a module the
    # serving path never dispatches
    keep = mesh or entry.keep_sharding
    stat_idx = set(entry.static_argnums)
    abs_args = tuple(a if i in stat_idx
                     else _abstract(a, keep_sharding=keep)
                     for i, a in enumerate(args))
    abs_dyn = _abstract(dyn_kw, keep_sharding=keep)
    dyn_pos = [a for i, a in enumerate(abs_args) if i not in stat_idx]
    # Cold-cache lowering: jax dedups repeated sub-jaxprs (_where/_take/
    # clip helpers) into shared private funcs through trace caches that
    # outlive a single lower() — a warm cache from UNRELATED earlier work
    # changes which helpers dedup, adding/removing a private func and
    # renumbering every symbol after it, so the module text (and its
    # sha256) would depend on process history.  Clearing right before
    # the lower pins every variant to the one canonical cold-cache
    # module; the manifest is regenerated under the same discipline.
    jax.clear_caches()
    lowered = _lower(entry, fn, abs_args, abs_dyn, static_kw, mesh)
    out_avals = _out_avals(lowered, fn, abs_args, entry.static_argnums,
                           abs_dyn, static_kw)
    cost = _cost(lowered)
    xb = _collective_bytes(entry, rung)
    if xb is not None:
        cost = dict(cost or {})
        cost["collective_bytes"] = xb
    n_donated = 0
    if entry.donate_argnums:
        n_donated = sum(
            len(jax.tree_util.tree_leaves(_abstract(args[i])))
            for i in entry.donate_argnums if i < len(args))
    text = lowered.as_text()   # multi-MB for the big programs: once
    aliased = text.count("tf.aliasing_output")
    variant_name = rung.name + ("@mesh" if mesh else "")
    in_avals = aval_strs((dyn_pos, abs_dyn))
    statics = dict(static_kw)
    statics.update({"arg%d" % i: args[i] for i in stat_idx})
    row = {
        "program": entry.program,
        "tag": entry.tag,
        "qualname": entry.qualname,
        "variant": variant_name,
        "in_avals": in_avals,
        "compiled_in_avals": _compiled_in_avals(lowered, in_avals),
        "out_avals": aval_strs(out_avals),
        "lowering_sha256": _lowering_hash(text),
        "static_sig": _static_sig(statics),
        "donation": {"argnums": list(entry.donate_argnums),
                     "donated_leaves": n_donated,
                     "aliased_outputs": aliased},
        "sharding": "pods=1,nodes=1" if mesh else None,
        "cost": cost,
    }
    return Variant(row=row, lowered=lowered, entry=entry)


def _compiled_in_avals(lowered, fallback: List[str]) -> List[str]:
    """The POST-PRUNING input avals — what XLA actually compiles and
    what jax's compile log reports (jit drops args the program never
    reads, e.g. batch term tables a cfg without those filters ignores).
    Read from the lowering's compile args; fall back to the full call
    signature on jax versions that don't expose them."""
    try:
        avals = lowered._lowering.compile_args["global_in_avals"]
    except Exception:
        return list(fallback)
    return ["%s[%s]" % (a.dtype.name, ",".join(str(d) for d in a.shape))
            for a in avals]


def _lower(entry, fn, abs_args, abs_dyn, static_kw, mesh):
    if mesh:
        from kubetpu.parallel import mesh as pmesh
        m = pmesh.make_mesh((1, 1))
        with pmesh.ambient_mesh(m):
            return fn.lower(*abs_args, **abs_dyn, **static_kw)
    return fn.lower(*abs_args, **abs_dyn, **static_kw)


def _mesh_place(entry, args, dyn_kw):
    """Commit the variant's inputs to a (1, 1) mesh the way the serving
    path does (mesh.shard_cluster/shard_batch semantics), so the lowered
    module carries the NamedShardings of the sharded program family."""
    import jax

    from kubetpu.parallel import mesh as pmesh
    from kubetpu.state.tensors import ClusterTensors
    m = pmesh.make_mesh((1, 1))

    def place(x):
        if isinstance(x, ClusterTensors):
            return pmesh.shard_cluster(x, m)
        if _is_array(x):
            return pmesh.replicate(x, m)
        if hasattr(x, "_fields"):     # PodBatch / overlay NamedTuples
            return pmesh.shard_batch(x, m)
        return x
    stat = set(entry.static_argnums)
    return (tuple(a if i in stat else place(a)
                  for i, a in enumerate(args)),
            {k: place(v) for k, v in dyn_kw.items()})


def _out_avals(lowered, fn, abs_args, static_argnums, abs_dyn, static_kw):
    import jax
    out = getattr(lowered, "out_info", None)
    if out is not None:
        return out
    stat = set(static_argnums)
    dyn_pos = [a for i, a in enumerate(abs_args) if i not in stat]
    return jax.eval_shape(
        _closure(fn, abs_args, static_argnums, list(abs_dyn), static_kw),
        *(tuple(dyn_pos) + tuple(abs_dyn.values())))


_exact_surface_cache: Optional[dict] = None


def _collective_bytes(entry: Entry, rung: Rung) -> Optional[dict]:
    """Per-collective DCN byte attribution for this variant, joined from
    the committed exactness surface (EXACT_MANIFEST.json, written by
    ``python -m tools.kubeexact --write``).  Lets a reader split a
    program's roofline into arithmetic vs cross-device transfer.
    Programs outside the exactness registry (or a missing manifest)
    contribute nothing — never an error."""
    global _exact_surface_cache
    if _exact_surface_cache is None:
        try:
            from tools.kubeexact.manifest import load_manifest
            _exact_surface_cache = load_manifest() or {}
        except Exception:
            _exact_surface_cache = {}
    key = entry.program + (":" + entry.tag if entry.tag else "")
    prog = (_exact_surface_cache.get("programs") or {}).get(key)
    if prog is None:
        return None
    rows = (prog.get("surface") or {}).get(rung.name)
    if rows is None:
        return None
    by_op: Dict[str, int] = {}
    for r in rows:
        by_op[r["op"]] = by_op.get(r["op"], 0) + int(r.get("bytes", 0))
    return {"total_bytes": sum(by_op.values()), "ops": len(rows),
            "by_op": by_op}


def _cost(lowered) -> Optional[dict]:
    try:
        ca = lowered.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return None
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out or None


# ---------------------------------------------------------------- rules


def audit_callable(program: str, fn, args: tuple, kwargs: dict = None,
                   donate_argnums: Tuple[int, ...] = (),
                   static_argnames: Tuple[str, ...] = (),
                   static_argnums: Tuple[int, ...] = (),
                   const_threshold: int = rules.CONST_CAPTURE_THRESHOLD,
                   ) -> List[Finding]:
    """Run every jaxpr-level rule on one callable at one input signature.
    ``fn`` may be a jit object or a plain traceable; statics ride in
    kwargs (static_argnames) or positionally (static_argnums).  This is
    the public seam the bad-snippet tests drive."""
    import jax

    kwargs = kwargs or {}
    dyn_kw, static_kw = _split_kwargs(kwargs, static_argnames)
    stat_idx = set(static_argnums)
    abs_args = tuple(a if i in stat_idx else _abstract(a)
                     for i, a in enumerate(args))
    abs_dyn = _abstract(dyn_kw)
    dyn_pos = [a for i, a in enumerate(abs_args) if i not in stat_idx]
    call = _closure(fn, abs_args, static_argnums, list(abs_dyn), static_kw)
    flat = tuple(dyn_pos) + tuple(abs_dyn.values())
    findings: List[Finding] = []
    closed = jax.make_jaxpr(call)(*flat)
    findings += rules.check_host_callbacks(program, closed)
    findings += rules.check_constant_capture(program, closed,
                                             threshold=const_threshold)
    findings += rules.check_f64(program, call, flat)
    findings += rules.check_rank_promotion(program, call, flat)
    if donate_argnums and hasattr(fn, "lower"):
        lowered = fn.lower(*abs_args, **abs_dyn, **static_kw)
        n_donated = sum(len(jax.tree_util.tree_leaves(abs_args[i]))
                        for i in donate_argnums if i < len(abs_args))
        findings += rules.check_donation(program, lowered, donate_argnums,
                                         n_donated)
    return findings


def audit_entry(entry: Entry, rung: Optional[Rung] = None) -> List[Finding]:
    """Rules for one registry entry (smallest ladder rung by default),
    with the entry's audited exemptions applied."""
    rung = rung or entry.ladder[0]
    world = build_world(rung)
    fn, args, kwargs = entry.build(world)
    raw = audit_callable(entry.key, fn, args, kwargs,
                         donate_argnums=entry.donate_argnums,
                         static_argnames=entry.static_argnames,
                         static_argnums=entry.static_argnums)
    exempt = dict(entry.exempt)
    used = set()
    out: List[Finding] = []
    for f in raw:
        reason = exempt.get(f.rule, "")
        if reason:
            f.suppressed, f.reason = True, reason
            used.add(f.rule)
        out.append(f)
    for rule, reason in exempt.items():
        if rule not in used:
            out.append(Finding(
                "census/unused-exemption", entry.key,
                "exemption for %s matches no finding — remove the stale "
                "entry (reason was: %s)" % (rule, reason)))
    return out


# ----------------------------------------------------------- whole census


@dataclasses.dataclass
class CensusResult:
    rows: List[dict]
    findings: List[Finding]          # unsuppressed
    suppressed: List[Finding]

    @property
    def clean(self) -> bool:
        return not self.findings


def run_census(entries: Optional[List[Entry]] = None,
               with_mesh: bool = True,
               with_rules: bool = True) -> CensusResult:
    """Trace every registered variant across its ladder (plus the mesh
    twin for meshable entries) and run the rule family once per entry.
    Rows come back sorted by (program, tag, variant) so the manifest
    serialization is order-independent of the registry."""
    from .discover import unregistered_roots

    entries = ENTRIES if entries is None else entries
    rows: List[dict] = []
    findings: List[Finding] = []
    suppressed: List[Finding] = []
    for e in entries:
        for rung in e.ladder:
            rows.append(trace_variant(e, rung).row)
        if e.meshable:
            rows.append(trace_variant(e, e.ladder[0], mesh=True).row)
        if with_rules:
            for f in audit_entry(e):
                (suppressed if f.suppressed else findings).append(f)
    if with_rules:
        findings.extend(unregistered_roots({e.qualname for e in entries}))
    rows.sort(key=lambda r: (r["program"], r["tag"], r["variant"]))
    return CensusResult(rows=rows, findings=findings, suppressed=suppressed)

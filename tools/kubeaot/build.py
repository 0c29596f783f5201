"""Build-side AOT pipeline: compile, serialize, prune, and gate.

Four operations, all over one artifact directory (kubetpu/utils/aot.py
AotStore layout — ``*.aotx`` payloads + ``index.json``):

* ``build_census``: walk the kubecensus registry and, for every
  COMPILE_MANIFEST variant of the seamed serving programs, run
  ``jit(...).lower().compile()`` (no execution — the same builders and
  cold-cache discipline the census uses, so the capture's lowering
  sha256 must EQUAL the manifest row's; a mismatch means the build did
  not compile what the census audited and fails the build).  Index rows
  are keyed by manifest row id (family "census") so ci_lint.sh can
  compare the two key sets.
* ``build_shape``: deploy-shaped capture.  Builds the warm-restart world
  at the target (nodes x wave) shape, arms a capture-mode runtime, and
  runs ``Scheduler.prewarm`` — every seamed dispatch of the dry-run
  ladder is lowered, compiled, serialized, and indexed (family
  "serving") with byte-identical call forms to a real restart of that
  shape, which is what makes the serve-time signature lookup hit.
* ``prune``: drop ladder buckets the flight recorder never saw serve
  (the exported trace's per-cycle ``pod_bucket`` meta), census rows
  whose manifest row no longer exists (census "removed" drift = dead
  rung), and — the proof join — census rows whose registry rung the
  committed compile-surface closure (CLOSURE_MANIFEST.json,
  tools/kubeclose) no longer proves reachable: observation says what WAS
  served, the closure says what CAN be dispatched, and an artifact
  outside both is dead weight.  Artifacts are deleted, the index
  rewritten.
* ``check_index``: the pure-JSON CI gate — the committed AOT_INDEX.json
  census rows and COMPILE_MANIFEST.json must share the same row keys in
  both directions (an artifact with no manifest row, or a manifest row
  with no artifact at census rungs, fails), and the index must agree
  with the committed closure (an artifact rung the closure proves
  unreachable, or a closure-reachable rung with no artifact, is a
  prune/closure disagreement).  Runs without jax.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional, Set

from kubetpu.utils.compilation import cache_disabled


# the seamed serving programs (kubetpu/utils/aot.py dispatch seams in
# models/gang.py, models/sequential.py, models/programs.py, and the
# mesh twins in parallel/shardmap.py) — the only jit roots a
# deserialized executable can ever be dispatched for.  Legacy gspmd
# @mesh variants are excluded: that family calls jit under an ambient
# mesh and does not route through the seams; the shard_map programs DO
# (schedule_gang_mesh / schedule_sequential_mesh).  HONEST COVERAGE
# NOTE: artifacts capture at the census (1, 1)-mesh rung, and the mesh
# key is part of the signature — a (2, 4) fleet's dispatches sign
# differently and fall back per key to the trace path, so today the
# mesh rows pin the build-time sha oracle (lowering == manifest) and
# make arming safe, NOT a production mesh warm start.  Deploy-shaped
# mesh capture needs build_shape to run under the fleet's mesh config
# on a same-topology build host — the ROADMAP item 1 residual.
AOT_PROGRAMS = ("_schedule_gang", "_schedule_sequential",
                "_materialize_assigned", "_explain_verdicts",
                "_shardmap_gang", "_shardmap_sequential")

_REPO_ROOT = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
DEFAULT_OUT = os.path.join(_REPO_ROOT, "artifacts", "aot")
INDEX_COMMIT_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                 "AOT_INDEX.json")
CLOSURE_PATH = os.path.join(_REPO_ROOT, "CLOSURE_MANIFEST.json")


def closure_reachable_keys(closure_path: str = CLOSURE_PATH
                           ) -> Optional[Set[str]]:
    """Registry entry keys ("program" or "program:tag") the committed
    compile-surface closure proves reachable: the union of
    ``registry:<key>`` coverage pointers over every enumerated combo of
    CLOSURE_MANIFEST.json.  None when no closure is committed or the
    file is unreadable — prune/check then skip the proof join instead of
    treating every rung as dead."""
    try:
        with open(closure_path) as f:
            doc = json.load(f)
    except (OSError, ValueError):
        return None
    keys: Set[str] = set()
    for prog in (doc.get("programs") or {}).values():
        for combo in (prog.get("combos") or {}).values():
            cov = combo.get("coverage") or ""
            if cov.startswith("registry:"):
                keys.add(cov.split(":", 1)[1])
    return keys


def aot_manifest_ids(rows: Optional[List[dict]]) -> Optional[Set[str]]:
    """Manifest row ids the AOT pipeline is responsible for: the seamed
    serving programs at census rungs, mesh twins excluded."""
    if rows is None:
        return None
    from tools.kubecensus.manifest import row_id
    return {row_id(r) for r in rows
            if r["program"] in AOT_PROGRAMS
            and not r["variant"].endswith("@mesh")}


def build_census(out_dir: str = DEFAULT_OUT,
                 commit_index: Optional[str] = INDEX_COMMIT_PATH,
                 programs=AOT_PROGRAMS) -> List[dict]:
    """Compile + serialize every manifest variant of ``programs`` (one
    report dict per variant: row / seconds / bytes / ok / sha_match).
    ``commit_index`` additionally writes the version-controlled index
    copy ci_lint.sh gates against."""
    import jax

    from kubetpu.utils import aot
    from tools.kubecensus.manifest import load_manifest, row_id
    from tools.kubecensus.registry import ENTRIES, build_world

    rt = aot.AotRuntime(aot.AotStore(out_dir), mode="capture",
                        family="census")
    manifest = {row_id(r): r for r in (load_manifest() or [])}
    report: List[dict] = []
    with cache_disabled():
        for e in ENTRIES:
            if e.program not in programs:
                continue
            for rung in e.ladder:
                rid = "%s%s@%s" % (e.program, ":" + e.tag if e.tag else "",
                                   rung.name)
                w = build_world(rung)
                fn, args, kwargs = e.build(w)
                # cold-cache discipline (census.trace_variant): warm trace
                # caches change sub-jaxpr dedup and renumber the module, so
                # the sha would drift from the manifest's canonical hash
                jax.clear_caches()
                t0 = time.time()
                row = rt.capture_call(e.program, fn, args, kwargs,
                                      static_argnums=e.static_argnums,
                                      static_argnames=e.static_argnames,
                                      row_name=rid, variant=rung.name)
                mrow = manifest.get(rid)
                report.append({
                    "row": rid,
                    "seconds": round(time.time() - t0, 2),
                    "bytes": row.get("bytes") if row else None,
                    "ok": row is not None,
                    # the bit-identity oracle: same lowering hash == same
                    # StableHLO == same placements as the traced path
                    "sha_match": bool(row and mrow
                                      and row["lowering_sha256"]
                                      == mrow["lowering_sha256"]),
                })
    rt.flush_index(extra_path=commit_index, replace_family="census")
    return report


def build_shape(out_dir: str, n_nodes: int, wave: int, ladder: int = 2,
                existing_per_node: int = 2) -> dict:
    """Deploy-shaped capture: the deterministic warm-restart world and
    wave (hollow.restart_world / restart_wave — the SAME builders a
    restart of that shape uses, so the store insertion order, label
    vocab, and topology-term mix are identical by construction), a
    capture-armed
    ``Scheduler.prewarm``, and then a REAL drained wave.  The drain is
    what makes the serve-time lookup hit: prewarm's synthetic dry-run
    batch differs from a live wave in exactly the statics a signature
    cannot paper over (active_topo_keys in the static cfg, the term-table
    bucket of the batch), so the live cycle's call forms must themselves
    be captured — every seamed dispatch of the drain is lowered,
    compiled, serialized, and indexed (family "serving")."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    from kubetpu.harness import hollow
    from kubetpu.scheduler import Scheduler
    from kubetpu.utils import aot

    rt = aot.arm(aot.AotRuntime(aot.AotStore(out_dir), mode="capture",
                                family="serving"))
    try:
        with cache_disabled():
            store = hollow.restart_world(
                n_nodes, existing_per_node=existing_per_node)
            sched = Scheduler(store, config=KubeSchedulerConfiguration(
                profiles=[KubeSchedulerProfile()], batch_size=wave,
                mode="gang", chain_cycles=True), async_binding=False)
            t0 = time.time()
            sched.prewarm(ladder_steps=ladder)
            for p in hollow.restart_wave(wave):
                store.add(p)
            scheduled = 0
            while True:
                got = sched.schedule_pending(timeout=1.0)
                if not got:
                    break
                scheduled += sum(1 for o in got if o.node)
            seconds = time.time() - t0
            sched.close()
        rt.flush_index()
        return {"rows": len(rt.rows()), "seconds": round(seconds, 2),
                "scheduled": scheduled, "out": out_dir,
                "stats": rt.stats()}
    finally:
        aot.disarm()


def trace_buckets(doc: dict) -> Set[int]:
    """Pod-axis buckets a flight-recorder export actually served: the
    per-cycle ``pod_bucket`` meta of a to_pipeline_doc() document (or a
    /debug/flightz dump) — prewarm records carry no bucket and scheduling
    records always do, so this is exactly the recorder's bucket-hit set."""
    buckets: Set[int] = set()
    for rec in doc.get("cycle_meta") or []:
        b = (rec.get("meta") or {}).get("pod_bucket")
        if b:
            buckets.add(int(b))
    return buckets


def prune(out_dir: str, trace_path: Optional[str] = None,
          manifest_rows: Optional[List[dict]] = None,
          closure_path: str = CLOSURE_PATH) -> dict:
    """Drop dead artifacts: serving rows whose pod bucket the recorder
    never saw (no trace data = no serving-row pruning), census rows
    whose manifest row is gone (the census drift gate's "removed"
    class), and census rows whose registry rung falls outside the
    committed compile-surface closure — proof-driven pruning: the
    closure enumerates every signature the serving seams can reach, so
    an artifact for a rung no enumerated combo covers can never be
    dispatched and is deleted even while its manifest row lingers.
    Deletes the ``.aotx`` payloads and rewrites the index in place."""
    from kubetpu.utils.aot import AotStore
    from tools.kubecensus.manifest import load_manifest

    store = AotStore(out_dir)
    doc = store.read_index()
    if doc is None:
        return {"error": "no index at %s" % store.index_path}
    buckets: Set[int] = set()
    if trace_path:
        with open(trace_path) as f:
            buckets = trace_buckets(json.load(f))
    ids = aot_manifest_ids(load_manifest() if manifest_rows is None
                           else manifest_rows)
    reach = closure_reachable_keys(closure_path)
    kept, dropped, unproved = [], [], []
    for r in doc.get("rows", []):
        fam = r.get("family")
        rid = r.get("row") or ""
        dead = (fam == "serving" and buckets and r.get("pod_bucket")
                and int(r["pod_bucket"]) not in buckets)
        dead = dead or (fam == "census" and ids is not None
                        and rid not in ids)
        if (not dead and fam == "census" and reach is not None
                and rid.partition("@")[0] not in reach):
            unproved.append(rid)
            dead = True
        if dead:
            dropped.append(rid)
            if r.get("artifact"):
                store.remove(r["artifact"])
        else:
            kept.append(r)
    store.write_index(doc.get("env") or {}, kept)
    return {"kept": len(kept), "dropped": sorted(dropped),
            "unproved": sorted(unproved), "buckets": sorted(buckets)}


def check_index(index_path: str = INDEX_COMMIT_PATH,
                manifest_path: Optional[str] = None,
                closure_path: str = CLOSURE_PATH) -> List[str]:
    """The CI gate (pure JSON, no jax): committed-index census rows and
    COMPILE_MANIFEST.json must share the same row keys for the seamed
    programs at census rungs, in both directions — and the index must
    agree with the committed compile-surface closure: an artifact rung
    the closure proves unreachable should have been pruned, and a
    closure-reachable rung of an AOT program with no artifact means the
    prune/build pipeline and the proof disagree.  Returns the failure
    list (empty = pass)."""
    from tools.kubecensus.manifest import load_manifest

    try:
        with open(index_path) as f:
            doc = json.load(f)
    except (OSError, ValueError) as e:
        return ["unreadable AOT index at %s (%s) — run: make aot"
                % (index_path, e)]
    rows = load_manifest(manifest_path) if manifest_path else load_manifest()
    want = aot_manifest_ids(rows)
    if want is None:
        return ["no COMPILE_MANIFEST.json — run: make census"]
    have = {r.get("row") for r in doc.get("rows", [])
            if r.get("family") == "census"}
    failures = []
    for rid in sorted(want - have):
        failures.append("manifest row with no artifact: %s" % rid)
    for rid in sorted(have - want):
        failures.append("artifact with no manifest row: %s" % rid)
    reach = closure_reachable_keys(closure_path)
    if reach is not None:
        have_keys = {rid.partition("@")[0] for rid in have if rid}
        for k in sorted(have_keys - reach):
            failures.append("artifact rung outside the proved closure "
                            "(prune/closure disagreement — run: python "
                            "-m tools.kubeaot --prune): %s" % k)
        aotable = {k for k in reach
                   if k.partition(":")[0] in AOT_PROGRAMS}
        for k in sorted(aotable - have_keys):
            failures.append("closure-reachable rung with no artifact "
                            "(prune/closure disagreement — run: make "
                            "aot): %s" % k)
    return failures

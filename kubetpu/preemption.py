"""Preemption: wave-batched what-if victim selection.

reference: pkg/scheduler/core/generic_scheduler.go — Preempt :252,
podEligibleToPreemptOthers :1063, nodesWherePreemptionMightHelp :1041,
selectNodesForPreemption :858, selectVictimsOnNode :949 (clone + remove
lower-priority pods + re-run filters + reprieve by PDB then priority),
processPreemptionWithExtenders :317, pickOneNodeForPreemption :729
(6-criteria lexicographic tie-break); invoked from scheduler.go:391 preempt.

TPU shape of the what-if: the reference clones one NodeInfo per candidate
and serially re-runs all filter plugins per victim add-back — an
O(candidates x victims) host loop, run once per failed pod.  Here BOTH
loops are batched:

  * the candidate axis is vmapped — every candidate's what-if state is the
    shared cycle snapshot plus a per-candidate delta, and one device pass
    answers "does the pod now fit" for ALL candidates at once; the
    reprieve loop is a lax.scan over add-back depth (PDB-violating first,
    then by descending priority — :1004-1037), so device passes per
    preemption = reprieve depth + 1, independent of the candidate count;

  * the FAILED-POD axis is batched too (preempt_wave): every
    preemption-eligible FitError of a scheduling cycle is served by ONE
    [B, C, K] what-if program (models/programs.py whatif_wave) built from
    vectorized numpy victim tensors (CycleContext.victim_index), instead
    of one candidates pass + one what-if dispatch per pod.  Cross-pod
    contention — two preemptors claiming one node — resolves host-side in
    ranked commit order: the higher pick_one_node_for_preemption rank wins
    the node, losers fall back to their next-ranked candidate, and pods
    left without a fresh candidate are re-waved against the updated
    overlay for a small fixed number of rounds (like the gang auction).
    Winners' victim deletions and nominations land on the shared
    CycleContext commit overlay (note_evict / the queue nominator), so
    later rounds see earlier evictions without re-tensorizing — a
    deviation from the reference's one-pod-per-cycle snapshot reuse that
    only ever AVOIDS needless double-eviction (no victim is ever deleted
    twice).

Pods whose what-if can perturb topology verdicts (own spread constraints
or affinity terms, or any existing-pod filter term in the cluster) keep
the exact per-pod reprieve (_whatif_reprieve, pod_valid masking included);
term-free pods — the common preemption workload — take the resource-only
wave kernel, whose non-fit filter verdicts are provably constant across
victim removal (whatif_static_ok).

The cycle's snapshot tensors are reused (reference Preempt reuses the
Schedule call's nodeInfoSnapshot); nothing is re-tensorized per failed pod.

Host-filter deviation: see README.md "Preemption" — volume-type (host)
filters are validated against the final victim-adjusted NodeInfo instead
of inside every reprieve step.
"""

from __future__ import annotations

import functools
import time
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import jax
import numpy as np

from .api import types as api
from .framework.interface import CycleState
from .framework.types import NodeInfo, PodInfo
from .models import programs
from .models.batch import PodBatchBuilder
from .state.tensors import (MIB, CH_PODS, SnapshotBuilder,
                            resource_to_channels)
from .utils.intern import pow2_bucket
from .utils.trace import flight_span


class Victims:
    __slots__ = ("pods", "num_pdb_violations")

    def __init__(self, pods: List[api.Pod], num_pdb_violations: int):
        self.pods = pods
        self.num_pdb_violations = num_pdb_violations


def _pod_channels(pi: PodInfo, table, R: int) -> np.ndarray:
    """A pod's resource request as cluster channels (CH_PODS = 1).  Unknown
    scalar resources resolve to channel -1 and are skipped — a victim may
    carry an extended resource no node ever registered."""
    vec = resource_to_channels(pi.resource, table, R, intern_new=False)
    vec[CH_PODS] = 1.0
    return vec


class _NodeVictims(NamedTuple):
    """One node's evictable-pod index, priority-descending (stable order —
    the reprieve order of :1004-1037 before PDB partitioning)."""
    prios: np.ndarray   # [V] i32, descending
    snap_pos: np.ndarray  # [V] i32 — position in ni.pods snapshot order
                          # (the PDB disruption budget consumes in THIS
                          # order, filterPodsWithPDBViolation :1118)
    rows: np.ndarray    # [V] i32 existing-pod tensor rows (-1 unknown)
    req: np.ndarray     # [V, R] f32 request channels (CH_PODS = 1)
    nz: np.ndarray      # [V, 2] f32 (non-zero cpu milli, mem MiB)
    ts: np.ndarray      # [V] f64 creation timestamps
    pis: tuple          # PodInfo per victim, same order
    uids: tuple         # pod uid per victim, same order


class CycleContext:
    """Per-cycle tensors the scheduler shares with preemption (reference:
    Preempt runs against the same g.nodeInfoSnapshot as Schedule).  Also
    caches per-pod feasibility rows so N failed pods cost ONE candidates
    pass, not N."""

    def __init__(self, builder: SnapshotBuilder, cluster, cfg,
                 node_infos: Sequence[NodeInfo], batch=None,
                 row_of: Optional[Dict[str, int]] = None,
                 feasible=None, unresolvable=None):
        self.builder = builder
        self.cluster = cluster
        self.cfg = cfg
        self.node_infos = node_infos
        self.batch = batch           # the cycle's PodBatch (all live pods)
        self.row_of = row_of or {}   # pod uid -> batch row
        self.feasible = feasible     # [B, N] np.ndarray or None
        self.unresolvable = unresolvable
        # same-cycle committed placements, overlaid before any what-if: the
        # reference's reused nodeInfoSnapshot serves exactly ONE pod per
        # cycle; with B pods per cycle a pod failing late in the batch must
        # see the capacity already claimed by earlier commits or preemption
        # overestimates free space and deletes victims for nothing
        self.commit_req = None       # [N, R] np — committed request channels
        self.commit_nz = None        # [N, 2] np
        self.commit_ports = None     # [N, P] np bool — committed host ports
        self.commits = 0
        self._verdict_commits = 0
        self._cluster_cache = None   # (commits, overlaid cluster)
        self._lazy = None            # (feasible_dev, unresolvable_dev)
        self.pod_uids = None         # the cluster's existing-pod rows in
                                     # row order, uid or None a row (set
                                     # by the scheduler; required when the
                                     # cluster is delta-resident or
                                     # CHAINED and rows no longer follow
                                     # node_infos order)
        self.pod_rows = None         # uid -> existing-pod tensor row, from
                                     # pod_uids when first asked for
        self._has_filter_terms = None  # lazy: any valid existing
                                       # anti-affinity term in the cluster
        # node row -> _NodeVictims (lazy, one host pass per cycle)
        self._victim_index = None
        # wave results by pod uid (nominated node name or None) — the
        # PostFilter per-pod path short-circuits on these
        self.wave_nominated: Dict[str, Optional[str]] = {}
        # victims evicted THIS cycle, shared by every wave/preempt call
        # against this context: the victim_index is a cycle-lifetime cache,
        # so a later attempt must not re-select (and re-subtract) a victim
        # an earlier wave already deleted
        self.evicted_uids: set = set()

    def has_filter_terms(self) -> bool:
        """Does the cluster carry ANY valid existing-pod required
        anti-affinity term?  (One tiny readback, cached per cycle.)  When
        False, removing victims cannot change the InterPodAffinity verdict
        of a term-less preemptor, so the what-if may drop that filter."""
        if self._has_filter_terms is None:
            self._has_filter_terms = bool(
                np.asarray(self.cluster.filter_terms.valid).any())
        return self._has_filter_terms

    def set_lazy_verdicts(self, feasible_dev, unresolvable_dev) -> None:
        """Share DEVICE verdict arrays without forcing a transfer: they
        materialize only if a preemption attempt actually reads them with
        no commits in between (otherwise a refresh supersedes them and the
        multi-MB device->host copy never happens)."""
        self._lazy = (feasible_dev, unresolvable_dev)

    def _ensure_overlay(self) -> None:
        if self.commit_req is None:
            shape = self.cluster.requested.shape
            self.commit_req = np.zeros(shape, np.float32)
            self.commit_nz = np.zeros((shape[0], 2), np.float32)
            self.commit_ports = np.zeros(
                (shape[0], self.cluster.ports.shape[1]), bool)

    def note_commit(self, row: int, node_row: int) -> None:
        """Record a committed batch placement (batch row -> node row)."""
        if self.batch is None:
            return
        self._ensure_overlay()
        self.commit_req[node_row] += np.asarray(self.batch.req[row])
        self.commit_nz[node_row] += np.asarray(self.batch.nonzero_req[row])
        self.commit_ports[node_row] |= (
            np.asarray(self.batch.ports_asnode_hot[row]) > 0.5)
        self.commits += 1

    def note_commits(self, rows: Sequence[int],
                     node_rows: Sequence[int]) -> None:
        """``note_commit`` for each (batch row, node row) pair, in order,
        as ONE scatter an overlay (the commit loop notes a run of pods at
        once: Scheduler._commit_run).  Unbuffered (``ufunc.at``): two
        pods of a run may share a node, and their requests add up in the
        order given, as a ``+=`` a pod adds them."""
        if self.batch is None or not len(rows):
            return
        self._ensure_overlay()
        rows = np.asarray(rows, np.intp)
        node_rows = np.asarray(node_rows, np.intp)
        batch = self.batch
        np.add.at(self.commit_req, node_rows, np.asarray(batch.req)[rows])
        np.add.at(self.commit_nz, node_rows,
                  np.asarray(batch.nonzero_req)[rows])
        np.logical_or.at(self.commit_ports, node_rows,
                         np.asarray(batch.ports_asnode_hot)[rows] > 0.5)
        self.commits += len(rows)

    def note_evict(self, node_row: int, req_vec: np.ndarray,
                   nz_vec: np.ndarray) -> None:
        """Record a deleted preemption victim so later wave rounds (and
        later preemption attempts this cycle) see the freed capacity
        without re-tensorizing.  Ports are NOT restored — matching the
        serial what-if, which never adjusted them either (conservative:
        a victim's host ports stay blocked until the next snapshot)."""
        self._ensure_overlay()
        self.commit_req[node_row] -= req_vec
        self.commit_nz[node_row] -= nz_vec
        self.commits += 1

    def cluster_now(self):
        """The cycle's cluster tensors with committed placements overlaid
        (resource/pod-count channels and host ports; committed pods'
        topology terms are not overlaid — a bounded deviation, matching the
        nominated-pods overlay's scope in the reference,
        generic_scheduler.go:541-545)."""
        if self.commits == 0:
            return self.cluster
        if (self._cluster_cache is not None
                and self._cluster_cache[0] == self.commits):
            return self._cluster_cache[1]
        import jax.numpy as jnp
        cl = self.cluster._replace(
            requested=self.cluster.requested + jnp.asarray(self.commit_req),
            nonzero_requested=(self.cluster.nonzero_requested
                               + jnp.asarray(self.commit_nz)),
            ports=self.cluster.ports | jnp.asarray(self.commit_ports))
        self._cluster_cache = (self.commits, cl)
        return cl

    def pod_verdicts(self, pod_uid: str):
        """(feasible_row, unresolvable_row) for a cycle pod, computing the
        whole-batch filter pass lazily on first use (one device call shared
        by every preemption attempt this cycle).  Verdicts taken before the
        latest commit are STALE — a gang-mode pod that lost purely to
        intra-batch contention has round-0 feasibility on nodes that are now
        full, which would exclude exactly the cheapest preemption
        candidates; returning None routes the caller to its single-pod
        [1, N] pass against cluster_now(), far cheaper than re-running the
        whole [B, N] batch per failing pod."""
        row = self.row_of.get(pod_uid)
        if row is None:
            return None
        self._materialize_lazy()
        if self.feasible is not None and self._verdict_commits != self.commits:
            return None
        if self.feasible is None:
            if self.batch is None:
                return None
            self.refresh_verdicts()
        return self.feasible[row], self.unresolvable[row]

    def _materialize_lazy(self) -> None:
        """Pull the auction's device verdict arrays to host IF they are
        still current (no commits since) and nothing fresher exists."""
        if self.feasible is None and self._lazy is not None \
                and self.commits == 0:
            self.feasible = np.asarray(self._lazy[0])
            self.unresolvable = np.asarray(self._lazy[1])

    def refresh_verdicts(self) -> None:
        """One whole-batch filter pass against the CURRENT committed state,
        shared by every preemption attempt that follows.  The scheduler
        calls this once after the commit loop so N failed pods cost one
        [B, N] pass, not N single-pod passes."""
        feasible, unresolvable = programs.filter_verdicts(
            self.cluster_now(), self.batch, self.cfg)
        self.feasible = np.asarray(feasible)
        self.unresolvable = np.asarray(unresolvable)
        self._verdict_commits = self.commits

    def min_pod_priority(self):
        """Lowest priority among all existing pods (lazily computed once
        per cycle), or None when the cluster has no pods.  A preemptor
        whose priority is <= this can never find a victim, so preemption
        short-circuits without any device pass (the reference reaches the
        same conclusion inside selectVictimsOnNode, one candidate at a
        time)."""
        if not hasattr(self, "_min_prio"):
            prios = [pi.pod.priority() for ni in self.node_infos
                     for pi in ni.pods]
            self._min_prio = min(prios) if prios else None
        return self._min_prio

    def pod_row_map(self) -> Dict[str, int]:
        """pod uid -> existing-pod tensor row (cached for the cycle, like
        victim_index which consumes it).  The scheduler's clusters carry
        their rows explicitly (``pod_uids``: they diverge from build
        order); otherwise it is the build order of state/tensors.py
        SnapshotBuilder.build."""
        if self.pod_rows is None:
            if self.pod_uids is not None:
                self.pod_rows = {uid: row for row, uid
                                 in enumerate(self.pod_uids) if uid}
            else:
                self.pod_rows = {pi.pod.uid: row for row, pi in enumerate(
                    pi for ni in self.node_infos for pi in ni.pods)}
        return self.pod_rows

    def victim_index(self) -> Dict[int, _NodeVictims]:
        """node row -> priority-ordered victim arrays, built in ONE host
        pass over the snapshot and shared by every wave round and every
        preemptor this cycle.  Replaces the per-(pod, candidate) Python
        loops that re-walked ni.pods and re-assembled resource vectors for
        every failed pod."""
        if self._victim_index is None:
            table = self.builder.table
            R = int(self.cluster.requested.shape[1])
            pod_rows = self.pod_row_map()
            out: Dict[int, _NodeVictims] = {}
            for j, ni in enumerate(self.node_infos):
                if not ni.pods:
                    continue
                prios = np.fromiter((pi.pod.priority() for pi in ni.pods),
                                    np.int64, len(ni.pods))
                order = np.argsort(-prios, kind="stable")
                pis = [ni.pods[int(k)] for k in order]
                out[j] = _NodeVictims(
                    prios=prios[order].astype(np.int32),
                    snap_pos=order.astype(np.int32),
                    rows=np.fromiter(
                        (pod_rows.get(pi.pod.uid, -1) for pi in pis),
                        np.int32, len(pis)),
                    req=np.stack([_pod_channels(pi, table, R)
                                  for pi in pis]),
                    nz=np.array([[pi.non_zero_cpu, pi.non_zero_mem / MIB]
                                 for pi in pis], np.float32),
                    ts=np.fromiter(
                        (pi.pod.metadata.creation_timestamp or 0.0
                         for pi in pis), np.float64, len(pis)),  # kubelint: ignore[numeric/f64] host-only pickOne tie-break; f32 quantizes unix seconds to ~256 s and never reaches the device
                    pis=tuple(pis),
                    uids=tuple(pi.pod.uid for pi in pis))
            self._victim_index = out
        return self._victim_index


@functools.partial(jax.jit, static_argnames=("cfg",))
def _whatif_reprieve(cluster, batch1, cfg, cand_rows, rm_valid, rm_req,
                     rm_nz, vic_row, vic_req, vic_nz):
    """Batched selectVictimsOnNode (generic_scheduler.go:949) for ONE pod
    whose what-if needs pod_valid masking (topology terms in play); the
    term-free wave path runs models/programs.py whatif_wave instead.

    cand_rows [C]        candidate node rows
    rm_valid  [C, P]     pod_valid with ALL of each candidate's lower-priority
                         pods masked out
    rm_req    [C, R]     summed resources of those pods (per own node row)
    rm_nz     [C, 2]     their non-zero-request sums
    vic_row   [C, K]     victim pod rows in reprieve order (-1 pad)
    vic_req   [C, K, R]  per-victim resources
    vic_nz    [C, K, 2]

    Returns (fits0 [C] — pod fits with all victims removed,
             reprieved [K, C] — victim k stayed on the node)."""
    import jax.numpy as jnp

    from .models.batch import densify_for
    batch1 = densify_for(cluster, batch1)
    C = cand_rows.shape[0]
    K = vic_row.shape[1]
    base_req = cluster.requested
    base_nz = cluster.nonzero_requested

    def one(pod_valid, dreq, dnz, row):
        cl = cluster._replace(
            pod_valid=pod_valid,
            requested=base_req.at[row].add(-dreq),
            nonzero_requested=base_nz.at[row].add(-dnz))
        feas, _, _ = programs.run_filters(cl, batch1, cfg)
        return feas[0]  # [N]

    vfilter = jax.vmap(one, in_axes=(0, 0, 0, 0))

    def verdicts(pod_valid, dreq, dnz):
        feas = vfilter(pod_valid, dreq, dnz, cand_rows)       # [C, N]
        return jnp.take_along_axis(feas, cand_rows[:, None], 1)[:, 0]

    fits0 = verdicts(rm_valid, rm_req, rm_nz)

    def step(carry, k):
        pod_valid, dreq, dnz, ok = carry
        row = vic_row[:, k]                                   # [C]
        exists = (row >= 0) & ok
        e = exists.astype(jnp.float32)
        try_valid = pod_valid.at[jnp.arange(C), jnp.clip(row, 0)].max(exists)
        try_dreq = dreq - vic_req[:, k] * e[:, None]
        try_dnz = dnz - vic_nz[:, k] * e[:, None]
        fit = verdicts(try_valid, try_dreq, try_dnz) & exists
        keep = fit[:, None]
        pod_valid = jnp.where(keep, try_valid, pod_valid)
        dreq = jnp.where(keep, try_dreq, dreq)
        dnz = jnp.where(keep, try_dnz, dnz)
        return (pod_valid, dreq, dnz, ok), fit

    (_, _, _, _), reprieved = jax.lax.scan(
        step, (rm_valid, rm_req, rm_nz, fits0), jnp.arange(K))
    return fits0, reprieved


class Preemptor:
    def __init__(self, scheduler, max_candidates: int = 2048,
                 wave_rounds: int = 4):
        self.sched = scheduler
        # memory bound on the vmapped candidate axis, NOT the reference's
        # behavior — when exceeded, candidates are pre-ranked and trimmed
        self.max_candidates = max_candidates
        # contention-resolution rounds per wave: pods left without a fresh
        # candidate after losing a node re-enter the next round's what-if
        # against the updated eviction/nomination overlay; leftovers after
        # the cap fail cleanly (requeue + retry next cycle)
        self.wave_rounds = wave_rounds
        # element budget for one [B, C, K, R] wave tensor set — beyond it
        # the wave splits along the pod axis (keeps HBM bounded at
        # pathological candidate x victim fan-out)
        self.max_wave_elements = 1 << 26

    # ------------------------------------------------------------------ entry

    def preempt(self, fwk, state: CycleState, pod: api.Pod,
                cycle: Optional[CycleContext] = None) -> Optional[str]:
        """reference: scheduler.go:391 + generic_scheduler.go:252 Preempt.
        Returns the nominated node name, or None.  A thin wrapper over a
        1-pod wave; when the scheduler already served this pod in the
        cycle's batched wave, the recorded verdict is returned as-is."""
        if cycle is not None and pod.uid in cycle.wave_nominated:
            return cycle.wave_nominated[pod.uid]
        return self.preempt_wave(fwk, cycle, [pod]).get(pod.uid)

    def preempt_wave(self, fwk, cycle: Optional[CycleContext],
                     pods: Sequence[api.Pod]) -> Dict[str, Optional[str]]:
        """Serve every preemption-eligible failed pod of a cycle with ONE
        batched what-if per contention round.  Returns pod uid -> nominated
        node name (None = no preemption).  Victim deletions and nominations
        are committed in ranked order as part of the wave; results are also
        recorded on the CycleContext so the per-pod PostFilter path
        short-circuits."""
        sched = self.sched
        results: Dict[str, Optional[str]] = {}
        alias: Dict[str, str] = {}   # caller uid -> store-refreshed uid
        fresh: List[api.Pod] = []
        for pod in pods:
            p = sched.store.get_pod(pod.namespace, pod.metadata.name) or pod
            results[p.uid] = None
            if p.uid != pod.uid:
                alias[pod.uid] = p.uid
            # reference: podEligibleToPreemptOthers runs before any
            # candidates work — an ineligible pod must not cost a snapshot
            # tensorization on the cycle-less direct path
            if self._eligible(p):
                fresh.append(p)
        if fresh and sched.metrics is not None:
            # reference: metrics.PreemptionAttempts.Inc() per Preempt call
            sched.metrics.preemption_attempts.inc(amount=len(fresh))
        if fresh and cycle is None:
            cycle = self._build_cycle(fwk, fresh)
        try:
            if fresh and cycle.node_infos:
                self._run_wave(fwk, cycle, fresh, results)
        except BaseException:
            # record only COMMITTED winners: their victims are gone and a
            # re-attempt must not double-preempt — but unserved pods must
            # stay eligible for the scheduler's per-pod fallback
            if cycle is not None:
                cycle.wave_nominated.update(
                    {uid: n for uid, n in results.items() if n})
            raise
        for orig, ref in alias.items():
            results[orig] = results[ref]
        if cycle is not None:
            cycle.wave_nominated.update(results)
        return results

    def _run_wave(self, fwk, cycle: CycleContext, pods: List[api.Pod],
                  results: Dict[str, Optional[str]]) -> None:
        sched = self.sched
        min_prio = cycle.min_pod_priority()
        if min_prio is None:
            return
        # nothing anywhere is evictable by a pod at/below the cluster's
        # minimum priority — skip the whole candidates/what-if machinery
        # (eligibility was already filtered by preempt_wave)
        live = [p for p in pods if p.priority() > min_prio]
        if not live:
            return
        # ranked commit order: priority-descending, queue order within ties
        # (the reference's serial drain pops by priority too)
        live.sort(key=lambda p: -p.priority())
        pdbs = sched.store.list("PodDisruptionBudget")
        node_row = {ni.node_name: j
                    for j, ni in enumerate(cycle.node_infos)}
        # cycle-scoped, not wave-scoped: a later preempt call against this
        # same context (extender path, wave-failure fallback) must see the
        # victims this wave deletes, or the stale victim_index would hand
        # them out — and note_evict would subtract them — twice
        deleted = cycle.evicted_uids
        pending = live
        has_preempt_ext = any(e.supports_preemption()
                              for e in sched.extenders)
        for _ in range(self.wave_rounds):
            fastw, slow_entries = self._wave_round(fwk, cycle, pending,
                                                   pdbs, deleted)
            claimed: set = set()
            next_pending: List[api.Pod] = []
            for pod in pending:
                b = fastw.index.get(pod.uid) if fastw is not None else None
                if b is not None and not has_preempt_ext:
                    # lazy lexicographic resolution: only the WINNER's
                    # victim list materializes (a full node_victims dict
                    # per pod re-created the per-pod host loops this wave
                    # exists to kill)
                    best, victims, had_claimed = fastw.resolve(
                        fwk, self, pod, b, claimed)
                else:
                    nv = (slow_entries.get(pod.uid)
                          if pod.uid in slow_entries
                          else (fastw.entries_dict(fwk, self, pod, b)
                                if b is not None else {}))
                    had_claimed = any(n in claimed for n in nv)
                    if had_claimed:
                        # a higher-ranked preemptor won this node in THIS
                        # round; its entry predates that claim — fall back
                        # to the next-ranked candidates, or re-wave
                        nv = {n: v for n, v in nv.items()
                              if n not in claimed}
                    nv = self._process_with_extenders(pod, nv)
                    best = pick_one_node_for_preemption(nv) if nv else None
                    victims = nv.get(best) if best is not None else None
                if best is None:
                    if had_claimed:
                        next_pending.append(pod)
                    continue
                self._commit_victims(fwk, pod, best, victims, cycle,
                                     node_row[best])
                deleted.update(p.uid for p in victims.pods)
                claimed.add(best)
                results[pod.uid] = best
            pending = next_pending
            if not pending:
                break

    def _commit_victims(self, fwk, pod: api.Pod, best: str,
                        victims: Victims, cycle: CycleContext,
                        node_row: int) -> None:
        """Delete the chosen victims and nominate the preemptor
        (reference: scheduler.go:403-415), recording the evictions on the
        cycle overlay so later wave rounds see the freed capacity."""
        sched = self.sched
        table = cycle.builder.table
        R = int(cycle.cluster.requested.shape[1])
        if victims.pods and sched.metrics is not None:
            # reference: metrics.PreemptionVictims.Observe per preemptor
            sched.metrics.preemption_victims.observe(len(victims.pods))
        for victim in victims.pods:
            try:
                sched.store.delete(victim)
            except Exception:
                # already gone (raced external delete): nothing was freed,
                # so neither the event nor the overlay subtraction applies
                continue
            if sched.recorder:
                sched.recorder.event(victim, "Normal", "Preempted",
                                     f"by {pod.namespace}/{pod.metadata.name} "
                                     f"on node {best}")
            pi = PodInfo(victim)
            cycle.note_evict(node_row, _pod_channels(pi, table, R),
                             np.asarray([pi.non_zero_cpu,
                                         pi.non_zero_mem / MIB], np.float32))
        # reject lower-priority waiting (Permit) pods on the node
        def maybe_reject(wp):
            if (wp.pod.priority() < pod.priority()):
                wp.reject("preempted")
        fwk.iterate_over_waiting_pods(maybe_reject)
        # clear nomination of lower-priority pods nominated to this node
        for np_ in sched.queue.nominated_pods_for_node(best):
            if np_.priority() < pod.priority():
                sched.queue.delete_nominated_pod_if_exists(np_)
        sched.queue.add_nominated_pod(pod, best)

    def _eligible(self, pod: api.Pod) -> bool:
        """reference: generic_scheduler.go:1063 podEligibleToPreemptOthers —
        if the pod already nominated a node and a lower-priority pod there
        is terminating, wait instead of preempting again."""
        nominated = pod.status.nominated_node_name
        if not nominated:
            return True
        ni = self.sched.snapshot.get(nominated)
        if ni is None:
            return True
        for pi in ni.pods:
            if (pi.pod.metadata.deletion_timestamp is not None
                    and pi.pod.priority() < pod.priority()):
                return False
        return True

    # ------------------------------------------------------------ cycle state

    def _build_cycle(self, fwk, pods: Sequence[api.Pod]) -> CycleContext:
        """Fallback when no cycle tensors were handed over (direct calls,
        extender path)."""
        sched = self.sched
        sched.cache.update_snapshot(sched.snapshot)
        node_infos = list(sched.snapshot.node_info_list)
        builder = SnapshotBuilder(
            hard_pod_affinity_weight=fwk.hard_pod_affinity_weight)
        builder.intern_pending([PodInfo(p) for p in pods])
        cluster = builder.build(node_infos).to_device()
        cfg = programs.ProgramConfig(
            filters=fwk.tensor_filters, scores=fwk.tensor_scores,
            hostname_topokey=max(
                builder.table.topokey.get(api.LABEL_HOSTNAME), 0),
            plugin_args=fwk.tensor_plugin_args(builder.table))
        return CycleContext(builder=builder, cluster=cluster, cfg=cfg,
                            node_infos=node_infos)

    def _pods_batch(self, pods: Sequence[api.Pod], cycle: CycleContext):
        import jax
        pb = PodBatchBuilder(cycle.builder.table)
        sels = [self.sched.store.default_spread_selector(p) for p in pods]
        return jax.tree.map(np.asarray,
                            pb.build([PodInfo(p) for p in pods],
                                     spread_selectors=sels))

    def _pod_batch1(self, pod: api.Pod, cycle: CycleContext):
        return self._pods_batch([pod], cycle)

    def _cluster_with_nominated(self, pod: api.Pod, cycle: CycleContext):
        """cluster_now plus equal/higher-priority nominated pods' resources
        on their nominated rows — the preemption simulation must respect
        capacity other preemptors already reserved (reference:
        addNominatedPods inside fitsOnNode, generic_scheduler.go:594).
        Wave winners are visible here too: their nominations land in the
        queue nominator at commit time, before the next round's entries."""
        import jax.numpy as jnp
        from .models.batch import build_nominated
        cl = cycle.cluster_now()
        prio = pod.priority()
        node_row = {ni.node_name: j
                    for j, ni in enumerate(cycle.node_infos)}
        entries = []
        for p, nn in self.sched.queue.all_nominated():
            if p.uid == pod.uid or p.priority() < prio:
                continue
            row = node_row.get(nn)
            if row is None:
                continue
            entries.append((PodInfo(p), row))
        if not entries:
            return cl
        nom = build_nominated(entries, cycle.builder.table)
        add = np.zeros(cl.requested.shape, np.float32)
        keep = nom.valid & (nom.node >= 0)
        np.add.at(add, nom.node[keep], nom.req[keep])
        return cl._replace(requested=cl.requested + jnp.asarray(add))

    # ------------------------------------------------------- candidate nodes

    def _wave_candidates(self, fwk, cycle: CycleContext,
                         pods: Sequence[api.Pod]) -> Dict[str, List[int]]:
        """reference: generic_scheduler.go:1041 nodesWherePreemptionMightHelp
        for the whole wave — every failed node that is not
        UnschedulableAndUnresolvable.  In-batch pods share ONE [B, N]
        verdict refresh; out-of-batch pods (direct/extender calls) share
        one grouped pass.  Host-filter failures count as resolvable
        failures too, so host verdicts are ANDed into feasibility here."""
        node_infos = cycle.node_infos
        n = len(node_infos)
        verd: Dict[str, tuple] = {}
        need_pass: List[api.Pod] = []
        for pod in pods:
            v = cycle.pod_verdicts(pod.uid)
            if v is None:
                # missing or stale (commits/evictions landed since): the
                # grouped wave-sized [Bw, N] pass below is never bigger
                # than a whole-batch refresh, and a 1-pod fallback wave
                # keeps its cheap [1, N]-bucket pass (pod_verdicts'
                # documented routing)
                need_pass.append(pod)
            else:
                verd[pod.uid] = v
        if need_pass:
            batch = self._pods_batch(need_pass, cycle)
            feas, unres = programs.filter_verdicts(cycle.cluster_now(),
                                                   batch, cycle.cfg)
            feas = np.asarray(feas)
            unres = np.asarray(unres)
            for i, pod in enumerate(need_pass):
                verd[pod.uid] = (feas[i], unres[i])
        out: Dict[str, List[int]] = {}
        for pod in pods:
            feasible, unresolvable = verd[pod.uid]
            feasible = np.array(feasible[:n])
            unresolvable = np.asarray(unresolvable[:n])
            if fwk.has_relevant_host_filters(pod):
                state = CycleState()
                for j, ni in enumerate(node_infos):
                    if feasible[j]:
                        st = fwk.run_filter_plugins(state, pod, ni)
                        if not st.is_success():
                            feasible[j] = False
            out[pod.uid] = [j for j, (f, u) in
                            enumerate(zip(feasible.tolist(),
                                          unresolvable.tolist()))
                            if not f and not u]
        return out

    # -------------------------------------------------------- victim search

    def _wave_round(self, fwk, cycle: CycleContext,
                    pods: Sequence[api.Pod], pdbs, deleted: set):
        """One contention round's what-if for every pending pod:
        candidates -> (fast wave | per-pod topology reprieve).  Returns
        (_FastWave or None, {slow pod uid: {node: Victims}})."""
        from .framework.types import pod_with_affinity

        cand = self._wave_candidates(fwk, cycle, pods)
        has_terms = cycle.has_filter_terms()
        fast: List[api.Pod] = []
        slow: List[api.Pod] = []
        for pod in pods:
            if not cand.get(pod.uid):
                continue
            # the wave kernel's static-verdict split is only sound when the
            # what-if provably cannot move a topology verdict (see
            # whatif_static_ok); term-carrying pods keep the exact per-pod
            # reprieve with pod_valid masking
            if (pod.spec.topology_spread_constraints
                    or pod_with_affinity(pod) or has_terms):
                slow.append(pod)
            else:
                fast.append(pod)
        fastw = self._fast_wave(cycle, fast, cand, pdbs, deleted) \
            if fast else None
        slow_entries = {}
        for pod in slow:
            cands = [(j, cycle.node_infos[j]) for j in cand[pod.uid]]
            slow_entries[pod.uid] = self._select_nodes_for_preemption(
                fwk, pod, cands, pdbs, cycle, deleted)
        return fastw, slow_entries

    def _prio_victim_prep(self, cycle: CycleContext, prio: int, pdbs,
                          deleted: set) -> Dict[int, Tuple[np.ndarray, int]]:
        """node row -> (victim index positions in reprieve order,
        n_pdb_violating) for a preemptor of priority `prio`.  Shared by
        every same-priority pod in the wave: the victim ORDER
        (PDB-violating first, then descending priority, :1004-1037)
        depends only on (priority, node), never on the preemptor's
        identity."""
        vi = cycle.victim_index()
        prep: Dict[int, Tuple[np.ndarray, int]] = {}
        for j, nv in vi.items():
            # prios is descending; evictable pods (< prio) are a suffix
            start = int(np.searchsorted(-nv.prios, -prio, side="right"))
            if start >= len(nv.prios):
                continue
            sel = np.arange(start, len(nv.prios))
            if deleted:
                keep = [int(k) for k in sel if nv.uids[k] not in deleted]
                if not keep:
                    continue
                sel = np.asarray(keep, np.int64)
            n_viol = 0
            if pdbs:
                # the per-PDB disruption budget consumes in SNAPSHOT order
                # (the serial path feeds ni.pods order, :1118) — feeding
                # the priority-sorted list would mark different victims as
                # violating and break wave == serial victim selection
                raw = sorted((int(k) for k in sel),
                             key=lambda k: int(nv.snap_pos[k]))
                violating, _ = filter_pods_with_pdb_violation(
                    [nv.pis[k].pod for k in raw], pdbs)
                vset = {p.uid for p in violating}
                lv = [int(k) for k in sel if nv.uids[k] in vset]
                lnv = [int(k) for k in sel if nv.uids[k] not in vset]
                sel = np.asarray(lv + lnv, np.int64)
                n_viol = len(lv)
            prep[j] = (sel, n_viol)
        return prep

    def _fast_wave(self, cycle: CycleContext, pods: List[api.Pod],
                   cand: Dict[str, List[int]], pdbs,
                   deleted: set) -> "_FastWave":
        """The wave kernel path: ONE [B, C, K] what-if for every term-free
        pending pod.  Host work is vectorized numpy — a compact
        per-(priority, node) victim table plus per-pod index rows; the
        [B, C, K, R] expansion happens on device (whatif_wave)."""
        import jax.numpy as jnp

        vi = cycle.victim_index()
        preps = {prio: self._prio_victim_prep(cycle, prio, pdbs, deleted)
                 for prio in {p.priority() for p in pods}}

        # per-pod candidate rows that actually carry victims, trimmed to
        # max_candidates by pickOneNode-style stats (cheapest kept)
        cand_lists: List[List[int]] = []
        for pod in pods:
            prep = preps[pod.priority()]
            rows = [j for j in cand[pod.uid] if j in prep]
            if len(rows) > self.max_candidates:
                def rank(j):
                    pr = vi[j].prios[prep[j][0]]
                    return (int(pr.max()), int(pr.sum()), len(pr))
                rows = sorted(rows, key=rank)[: self.max_candidates]
            cand_lists.append(rows)
        max_c = max((len(r) for r in cand_lists), default=0)
        if max_c == 0:
            return _FastWave.empty(pods)
        used = {(pod.priority(), j)
                for pod, rows in zip(pods, cand_lists) for j in rows}
        K = pow2_bucket(max(len(preps[prio][j][0]) for prio, j in used), 1)
        C = pow2_bucket(max_c, 1)
        R = int(cycle.cluster.requested.shape[1])

        # split along the pod axis if the device-side [B, C, K, R] gather
        # would blow the HBM budget (pathological candidate x victim
        # fan-out); chunks stay individually pow2-bucketed
        max_pods = max(1, self.max_wave_elements // max(C * K * R, 1))
        if len(pods) > max_pods:
            return _WaveUnion([
                self._fast_wave(cycle, pods[i:i + max_pods], cand, pdbs,
                                deleted)
                for i in range(0, len(pods), max_pods)])

        # compact victim table: one row per used (priority, node) — the
        # device gathers it out to [B, C, K, R], so the upload stays
        # O(S * K) however many same-priority preemptors share it
        order = sorted(used)
        S = pow2_bucket(len(order), 1)
        pos = {key: i for i, key in enumerate(order)}
        tab_req = np.zeros((S, K, R), np.float32)
        tab_valid = np.zeros((S, K), bool)
        tab_prio = np.full((S, K), -2**31, np.int64)
        tab_ts = np.zeros((S, K), np.float64)  # kubelint: ignore[numeric/f64] host-only pickOne tie-break timestamps; never device-bound
        tab_viol = np.zeros((S, K), bool)
        for (prio, j), i in pos.items():
            sel, n_viol = preps[prio][j]
            tab_req[i, :len(sel)] = vi[j].req[sel]
            tab_valid[i, :len(sel)] = True
            tab_prio[i, :len(sel)] = vi[j].prios[sel]
            tab_ts[i, :len(sel)] = vi[j].ts[sel]
            tab_viol[i, :n_viol] = True

        batch = self._pods_batch(pods, cycle)
        B = int(batch.valid.shape[0])     # pow2 pod-axis bucket
        cand_rows = np.full((B, C), -1, np.int32)
        cand_valid = np.zeros((B, C), bool)
        cand_idx = np.zeros((B, C), np.int32)
        for b, (pod, rows) in enumerate(zip(pods, cand_lists)):
            if not rows:
                continue
            nc = len(rows)
            prio = pod.priority()
            cand_rows[b, :nc] = np.asarray(rows, np.int32)
            cand_valid[b, :nc] = True
            cand_idx[b, :nc] = np.asarray([pos[(prio, j)] for j in rows],
                                          np.int32)

        # nominated-pod reservations per (pod, candidate): equal-or-greater
        # priority, self excluded (addNominatedPods, :594) — wave winners
        # of earlier rounds are in the queue nominator already
        nom_add = None
        node_row = {ni.node_name: j
                    for j, ni in enumerate(cycle.node_infos)}
        table = cycle.builder.table
        for p, nn in self.sched.queue.all_nominated():
            row = node_row.get(nn)
            if row is None:
                continue
            vec = _pod_channels(PodInfo(p), table, R)
            hit = cand_rows == row                       # [B, C]
            for b, pod in enumerate(pods):
                if p.uid == pod.uid or p.priority() < pod.priority():
                    continue
                if nom_add is None:
                    nom_add = np.zeros((B, C, R), np.float32)
                nom_add[b][hit[b]] += vec
        # jnp.zeros allocates device-side — the no-nominations common case
        # uploads nothing and keeps the jit signature stable
        nom_dev = (jnp.zeros((B, C, R), jnp.float32) if nom_add is None
                   else jnp.asarray(nom_add))

        # the droppable topology filters are gone for every fast pod by
        # construction (that is what made them fast)
        cfg_w = cycle.cfg._replace(filters=tuple(
            f for f in cycle.cfg.filters
            if f not in ("PodTopologySpread", "InterPodAffinity")))
        cluster = cycle.cluster_now()
        static_ok = programs.whatif_static_ok(cluster, batch, cfg_w)
        # flight_span attaches under the scheduler's open preemption-wave
        # span (utils/trace.py) — no-op when the recorder is disarmed
        with flight_span("whatif-readback", pods=B) as sp:
            # perf_counter, not time.time(): the wait is a DURATION, and
            # an NTP step mid-wave used to corrupt it (negative or wildly
            # inflated device_wait_s in the span args)
            t_dev = time.perf_counter()
            packed = np.asarray(programs.whatif_wave(
                cluster, static_ok, jnp.asarray(np.asarray(batch.req)),
                jnp.asarray(cand_rows), jnp.asarray(cand_valid), nom_dev,
                jnp.asarray(tab_req), jnp.asarray(tab_valid),
                jnp.asarray(cand_idx)))   # ONE readback for the whole wave
            if sp is not None:
                # wave device-wait attribution (the what-if dispatch +
                # transfer is the wave's only device sync)
                sp.args["device_wait_s"] = round(
                    time.perf_counter() - t_dev, 6)

        # pickOneNode metrics, vectorized over the whole [B, C, K] block
        # (generic_scheduler.go:729 criteria 1-5; criterion 6 = first in
        # candidate order, the argmin tie-break in _FastWave.resolve)
        evicted = (tab_valid[cand_idx] & cand_valid[:, :, None]
                   & ~packed[:, :, 1:])                      # [B, C, K]
        prio_g = tab_prio[cand_idx]
        fits = packed[:, :, 0] & cand_valid
        m1 = (evicted & tab_viol[cand_idx]).sum(axis=2)
        m2 = np.where(evicted, prio_g, -2**31).max(axis=2)
        m3 = np.where(evicted, prio_g, 0).sum(axis=2)
        m4 = evicted.sum(axis=2)
        # latest start time of the highest-priority victim: argmax takes
        # the FIRST max like the serial max() — matching reprieve order
        top = np.argmax(np.where(evicted, prio_g, -2**31), axis=2)
        m5 = -np.take_along_axis(tab_ts[cand_idx], top[:, :, None],
                                 axis=2)[:, :, 0]
        m5 = np.where(m4 > 0, m5, 0.0)
        return _FastWave(cycle=cycle, pods=pods, cand_lists=cand_lists,
                         preps=preps, vi=vi, evicted=evicted, fits=fits,
                         metrics=(m1, m2, m3, m4, m5))


    def _select_nodes_for_preemption(self, fwk, pod: api.Pod,
                                     candidates, pdbs,
                                     cycle: CycleContext,
                                     deleted: set = frozenset()
                                     ) -> Dict[str, Victims]:
        """reference: generic_scheduler.go:858 selectNodesForPreemption —
        the parallel what-if for ONE topology-term-carrying pod, batched
        over every candidate (see _whatif_reprieve).

        The what-if's cfg drops topology filters the preemptor provably
        cannot trip: PodTopologySpread constrains only pods WITH
        constraints, and InterPodAffinity is droppable when the pod has no
        affinity terms AND no existing pod carries a filter term (removing
        victims can then never change the verdict).  Without this, every
        candidate paid the [1, P] x [P, N] same-pair matmuls — at
        5000 nodes x 20k pods the 2048-candidate what-if cost seconds per
        preemptor for workloads with no topology terms at all."""
        import jax.numpy as jnp
        from .framework.types import pod_with_affinity

        cfg_w = cycle.cfg
        drop = []
        if not pod.spec.topology_spread_constraints:
            drop.append("PodTopologySpread")
        if not pod_with_affinity(pod) and not cycle.has_filter_terms():
            drop.append("InterPodAffinity")
        if drop:
            cfg_w = cfg_w._replace(filters=tuple(
                f for f in cfg_w.filters if f not in drop))

        prio = pod.priority()
        table = cycle.builder.table
        R = cycle.cluster.requested.shape[1]
        P = cycle.cluster.pod_valid.shape[0]

        # per-candidate victim lists in reprieve order: PDB-violating first,
        # each group by descending priority (:1004-1037)
        entries = []  # (row, ordered victims [PodInfo], n_violating)
        pod_rows = cycle.pod_row_map()
        for row, ni in candidates:
            lower = [pi for pi in ni.pods
                     if pi.pod.priority() < prio
                     and pi.pod.uid not in deleted]
            if not lower:
                continue
            violating, non_violating = filter_pods_with_pdb_violation(
                [pi.pod for pi in lower], pdbs)
            vset = {p.uid for p in violating}
            lv = sorted((pi for pi in lower if pi.pod.uid in vset),
                        key=lambda pi: -pi.pod.priority())
            lnv = sorted((pi for pi in lower if pi.pod.uid not in vset),
                         key=lambda pi: -pi.pod.priority())
            entries.append((row, lv + lnv, len(lv)))
        if not entries:
            return {}
        if len(entries) > self.max_candidates:
            # memory cap: keep the candidates cheapest by pickOneNode-style
            # stats (lowest max victim priority, then sum, then count)
            def rank(e):
                vs = e[1]
                return (max(pi.pod.priority() for pi in vs),
                        sum(pi.pod.priority() for pi in vs), len(vs))
            entries = sorted(entries, key=rank)[: self.max_candidates]

        C = pow2_bucket(len(entries), 1)
        K = pow2_bucket(max(len(e[1]) for e in entries), 1)
        cand_rows = np.zeros((C,), np.int32)
        rm_valid = np.broadcast_to(
            np.asarray(cycle.cluster.pod_valid), (C, P)).copy()
        rm_req = np.zeros((C, R), np.float32)
        rm_nz = np.zeros((C, 2), np.float32)
        vic_row = np.full((C, K), -1, np.int32)
        vic_req = np.zeros((C, K, R), np.float32)
        vic_nz = np.zeros((C, K, 2), np.float32)
        for c, (row, victims, _nv) in enumerate(entries):
            cand_rows[c] = row
            for k, pi in enumerate(victims):
                prow = pod_rows.get(pi.pod.uid, -1)
                if prow >= 0:
                    rm_valid[c, prow] = False
                vic_row[c, k] = prow
                vr = _pod_channels(pi, table, R)
                vic_req[c, k] = vr
                vic_nz[c, k, 0] = pi.non_zero_cpu
                vic_nz[c, k, 1] = pi.non_zero_mem / MIB
                rm_req[c] += vr
                rm_nz[c] += vic_nz[c, k]
        # pad rows: candidate 0's row with no removals (fits0 false unless
        # genuinely feasible; padded candidates are dropped below)
        for c in range(len(entries), C):
            cand_rows[c] = entries[0][0]

        batch1 = self._pod_batch1(pod, cycle)
        fits0, reprieved = _whatif_reprieve(
            self._cluster_with_nominated(pod, cycle), batch1, cfg_w,
            jnp.asarray(cand_rows), jnp.asarray(rm_valid),
            jnp.asarray(rm_req), jnp.asarray(rm_nz), jnp.asarray(vic_row),
            jnp.asarray(vic_req), jnp.asarray(vic_nz))
        fits0 = np.asarray(fits0)
        reprieved = np.asarray(reprieved)  # [K, C]

        out: Dict[str, Victims] = {}
        for c, (row, victims, n_violating) in enumerate(entries):
            if not fits0[c]:
                continue
            final = [victims[k].pod for k in range(len(victims))
                     if not reprieved[k, c]]
            num_viol = sum(1 for k in range(min(n_violating, len(victims)))
                           if not reprieved[k, c])
            ni = cycle.node_infos[row]
            if not self._host_filters_pass(fwk, pod, ni,
                                           {p.uid for p in final}):
                continue
            out[ni.node_name] = Victims(pods=final,
                                        num_pdb_violations=num_viol)
        return out

    def _host_filters_pass(self, fwk, pod: api.Pod, ni: NodeInfo,
                           removed_uids: set) -> bool:
        if not fwk.has_relevant_host_filters(pod):
            return True
        sim_ni = ni.clone()
        for pi in list(sim_ni.pods):
            if pi.pod.uid in removed_uids:
                sim_ni.remove_pod(pi.pod)
        st = fwk.run_filter_plugins(CycleState(), pod, sim_ni)
        return st.is_success()

    # ------------------------------------------------------------- extenders

    def _process_with_extenders(self, pod: api.Pod,
                                node_victims: Dict[str, Victims]
                                ) -> Dict[str, Victims]:
        """reference: generic_scheduler.go:317 processPreemptionWithExtenders
        + core/extender.go:317 ProcessPreemption."""
        if not node_victims:
            return node_victims
        for ext in self.sched.extenders:
            if not (ext.supports_preemption() and ext.is_interested(pod)):
                continue
            try:
                node_victims = ext.process_preemption(pod, node_victims)
            except Exception:
                if getattr(ext, "ignorable", False):
                    continue
                return {}
            if not node_victims:
                return {}
        return node_victims


class _FastWave:
    """One round's wave what-if results plus lazy contention resolution.

    resolve() reproduces pick_one_node_for_preemption's lexicographic
    tie-break over vectorized numpy metric arrays — criteria 1-5 as
    argmin filters, criterion 6 (first remaining) as candidate order —
    and materializes a Victims list only for the winner.  Host-filter
    validation runs on the winner and, on failure, bans the node and
    re-resolves (equivalent to the eager path's pre-pick entry drop)."""

    def __init__(self, cycle, pods, cand_lists, preps, vi, evicted, fits,
                 metrics):
        self.cycle = cycle
        self.pods = pods
        self.cand_lists = cand_lists
        self.preps = preps
        self.vi = vi
        self.evicted = evicted          # [B, C, K] bool
        self.fits = fits                # [B, C] bool
        self.metrics = metrics          # 5 x [B, C]
        self.index = {pod.uid: b for b, pod in enumerate(pods)}
        self.names = [[cycle.node_infos[j].node_name for j in rows]
                      for rows in cand_lists]

    @classmethod
    def empty(cls, pods):
        z = np.zeros((len(pods), 0), np.int64)
        return cls(cycle=None, pods=pods, cand_lists=[[] for _ in pods],
                   preps={}, vi={}, evicted=np.zeros((len(pods), 0, 0),
                                                     bool),
                   fits=z.astype(bool), metrics=(z, z, z, z, z))

    def _victims(self, pod, b: int, c: int) -> Victims:
        j = self.cand_lists[b][c]
        sel, n_viol = self.preps[pod.priority()][j]
        ev = self.evicted[b, c, :len(sel)].tolist()
        final = [self.vi[j].pis[int(k)].pod
                 for t, k in enumerate(sel) if ev[t]]
        num_viol = sum(1 for t in range(min(n_viol, len(sel))) if ev[t])
        return Victims(pods=final, num_pdb_violations=num_viol)

    def _pick(self, b: int, skip: set) -> Optional[int]:
        names = self.names[b]
        nc = len(names)
        if nc == 0:
            return None
        ok = self.fits[b, :nc].copy()
        if skip:
            ok &= np.fromiter((n not in skip for n in names), bool, nc)
        idx = np.flatnonzero(ok)
        for m in self.metrics:
            if idx.size <= 1:
                break
            vals = m[b, idx]
            idx = idx[vals == vals.min()]
        return int(idx[0]) if idx.size else None

    def resolve(self, fwk, preemptor, pod, b: int, claimed: set):
        """(node, victims, had_claimed) — had_claimed: some feasible entry
        was lost to a same-round claim (the re-wave trigger)."""
        names = self.names[b]
        had_claimed = bool(claimed) and any(
            n in claimed for n, f in zip(names, self.fits[b].tolist()) if f)
        banned = set(claimed)
        while True:
            c = self._pick(b, banned)
            if c is None:
                return None, None, had_claimed
            victims = self._victims(pod, b, c)
            j = self.cand_lists[b][c]
            if preemptor._host_filters_pass(
                    fwk, pod, self.cycle.node_infos[j],
                    {p.uid for p in victims.pods}):
                return names[c], victims, had_claimed
            banned.add(names[c])

    def entries_dict(self, fwk, preemptor, pod,
                     b: int) -> Dict[str, Victims]:
        """Eager node_victims dict (extender path only — extenders inspect
        the full map, reference ProcessPreemption)."""
        out: Dict[str, Victims] = {}
        for c, name in enumerate(self.names[b]):
            if not self.fits[b, c]:
                continue
            victims = self._victims(pod, b, c)
            j = self.cand_lists[b][c]
            if not preemptor._host_filters_pass(
                    fwk, pod, self.cycle.node_infos[j],
                    {p.uid for p in victims.pods}):
                continue
            out[name] = victims
        return out


class _WaveUnion:
    """Routes per-pod wave handles across HBM-budget chunks of one round
    (the opaque b handle becomes (chunk, b))."""

    def __init__(self, waves):
        self.waves = waves
        self.index = {uid: (w, b) for w in waves
                      for uid, b in w.index.items()}

    def resolve(self, fwk, preemptor, pod, key, claimed):
        w, b = key
        return w.resolve(fwk, preemptor, pod, b, claimed)

    def entries_dict(self, fwk, preemptor, pod, key):
        w, b = key
        return w.entries_dict(fwk, preemptor, pod, b)


# ---------------------------------------------------------------------------
# pure functions (host)


def filter_pods_with_pdb_violation(pods: List[api.Pod],
                                   pdbs) -> Tuple[List[api.Pod], List[api.Pod]]:
    """reference: generic_scheduler.go:1118 filterPodsWithPDBViolation."""
    violating, non_violating = [], []
    remaining = {id(pdb): pdb.disruptions_allowed for pdb in pdbs}
    for p in pods:
        hit = False
        for pdb in pdbs:
            if pdb.metadata.namespace != p.namespace:
                continue
            if pdb.selector is not None and pdb.selector.matches(
                    p.metadata.labels):
                if remaining[id(pdb)] <= 0:
                    hit = True
                else:
                    remaining[id(pdb)] -= 1
        (violating if hit else non_violating).append(p)
    return violating, non_violating


def pick_one_node_for_preemption(node_victims: Dict[str, Victims]) -> Optional[str]:
    """reference: generic_scheduler.go:729 — lexicographic tie-break:
    1. fewest PDB violations
    2. lowest highest-victim-priority
    3. lowest sum of victim priorities
    4. fewest victims
    5. latest earliest start time of highest-priority victim
    6. first in iteration order (reference returns the first remaining)."""
    if not node_victims:
        return None
    nodes = list(node_victims)

    def metric(fns):
        nonlocal nodes
        vals = {n: fns(node_victims[n]) for n in nodes}
        best = min(vals.values())
        nodes = [n for n in nodes if vals[n] == best]

    metric(lambda v: v.num_pdb_violations)
    if len(nodes) == 1:
        return nodes[0]
    metric(lambda v: max((p.priority() for p in v.pods), default=-2**31))
    if len(nodes) == 1:
        return nodes[0]
    metric(lambda v: sum(p.priority() for p in v.pods))
    if len(nodes) == 1:
        return nodes[0]
    metric(lambda v: len(v.pods))
    if len(nodes) == 1:
        return nodes[0]
    # latest start time of the highest-priority victim (max => min of -ts)
    def neg_latest_start(v: Victims):
        if not v.pods:
            return 0.0
        top = max(v.pods, key=lambda p: p.priority())
        return -top.metadata.creation_timestamp
    metric(neg_latest_start)
    return nodes[0]

"""Plain host reference for the default scheduler profile (v1.19 plugins).

Imports nothing of the program: nodes and pods arrive as the plain
records of ``perfbench/lib/world.py`` (built from the configuration
file), the client's event log as tuples.  It decides ``correct``:

(a) ``replay``: the client's own event log, in the order the client
    observed it, held to every guarantee the configuration states:
    no node over allocatable cpu / memory / pod count, no pod bound
    twice or to an unknown node, no required (anti-)affinity term
    violated at the moment of the bind, every bind the client saw read
    back from the store, no pod left unschedulable that this reference
    can place.

(b) ``gang_misses``: one cycle of the program's gang auction, over a
    cluster this reference knows exactly, checked against the auction's
    own semantics (``kubetpu/models/gang.py``'s docstring, restated):
    in every round each pod still unassigned proposes to ONE node of its
    feasible, score-maximal set, scored against the state at the START
    of the round; proposals are admitted in pod order while the node
    still has room and no required term breaks; the rest propose again.
    So every placement must lie in the tie set of some round's start
    state.  The rounds are not visible from outside, so the check
    explains the placements greedily, round by round (``gang_misses``);
    whatever no round explains is a miss.  Scores are the default
    plugins' integer scores in upstream's arithmetic (int64 division for
    NodeResourcesLeastAllocated, a truncated float64 product for
    NodeResourcesBalancedAllocation) and their weighted sum.

    For the pod templates this reference accepts (resource requests,
    labels, required hostname/zone anti-affinity and affinity on a
    one-label selector) seven of the nine default score plugins are the
    same on every feasible node, so they are constants here, each with
    the upstream rule that makes it so:

      ImageLocality 0 (no node reports an image), InterPodAffinity 0 (no
      preferred term, no required *affinity* on an existing pod: hard
      anti-affinity is not scored), NodeAffinity 0, NodePreferAvoidPods
      100 (weight 10000), PodTopologySpread 0 (no constraint, weight 2),
      DefaultPodTopologySpread 100 (no service/controller selects the
      pod, so every count is 0), TaintToleration 100 (no taint).

    A record that holds anything outside that list (a preferred term, a
    spread constraint, a node-affinity term, a required term on a
    selector of several labels) raises, whether it is the incoming pod's
    or an existing pod's, and whatever the template called it: a
    configuration that needs more brings a reference file of its own.

``auction_schedule`` is that auction written plainly, with this
reference's own scores: in float64 it places the check's resident pods;
with every arithmetic result rounded to bfloat16 (``lowprec=True``) or
with the batch's own pods left out of the term filter
(``blind_batch=True``) it is a control: put in the program's place it
must FAIL (b).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

MAX_NODE_SCORE = 100
HOSTNAME = "kubernetes.io/hostname"
# weight * normalized score of the seven plugins that do not vary here
CONSTANT_SCORE = 10000 * MAX_NODE_SCORE + MAX_NODE_SCORE + MAX_NODE_SCORE
# what a record (lib/world.py PodRec) may hold besides its required terms,
# none of which this reference models
UNMODELLED = (("anti_preferred", "a preferred anti-affinity term"),
              ("aff_preferred", "a preferred affinity term"),
              ("spread", "a topology spread constraint"),
              ("node_affinity_in", "a node-affinity term"))


def bf16(x):
    """Round to the nearest bfloat16 (ties to even), kept as float32."""
    a = np.asarray(x, np.float32)
    bits = a.view(np.uint32)
    bits = (bits + (((bits >> 16) & 1) + 0x7FFF)) & np.uint32(0xFFFF0000)
    return bits.view(np.float32)


def _one_pair(match_labels) -> Tuple[str, str]:
    items = tuple(match_labels)
    if len(items) != 1:
        raise NotImplementedError(
            f"reference default_plugins: selector {dict(items)} is not a "
            "one-label match")
    return items[0]


class Cluster:
    """Mutable plain cluster state: per-node sums and label counts."""

    def __init__(self, nodes: Sequence[Any]):
        self.names = [n.name for n in nodes]
        self.row = {n.name: i for i, n in enumerate(nodes)}
        n = len(nodes)
        self.alloc_cpu = np.array([x.cpu_milli for x in nodes], np.int64)
        self.alloc_mem = np.array([x.mem_bytes for x in nodes], np.int64)
        self.alloc_pods = np.array([x.pods for x in nodes], np.int64)
        self.req_cpu = np.zeros(n, np.int64)
        self.req_mem = np.zeros(n, np.int64)
        self.count = np.zeros(n, np.int64)
        # topology key -> per-node domain id
        self.domain: Dict[str, np.ndarray] = {}
        keys = {k for x in nodes for k in x.labels}
        for key in keys:
            ids: Dict[str, int] = {}
            self.domain[key] = np.array(
                [ids.setdefault(x.labels[key], len(ids))
                 if key in x.labels else -1 for x in nodes], np.int64)
        # (label key, value) -> per-node count of pods carrying it
        self.label_count: Dict[Tuple[str, str], np.ndarray] = {}
        # (label key, value) -> topology key -> per-node count of pods
        # carrying a required anti-affinity term of that shape
        self.anti_count: Dict[Tuple[str, str],
                              Dict[str, np.ndarray]] = {}
        self.where: Dict[str, int] = {}      # bound pod name -> node row

    # -- state ----------------------------------------------------------

    def _bump(self, pod, r: int, d: int) -> None:
        _check_record(pod)
        self.req_cpu[r] += d * pod.cpu_milli
        self.req_mem[r] += d * pod.mem_bytes
        self.count[r] += d
        n = len(self.names)
        for kv in pod.labels.items():
            arr = self.label_count.get(kv)
            if arr is None:
                arr = self.label_count[kv] = np.zeros(n, np.int64)
            arr[r] += d
        for topo, sel in pod.anti_required:
            by_topo = self.anti_count.setdefault(_one_pair(sel), {})
            arr = by_topo.get(topo)
            if arr is None:
                arr = by_topo[topo] = np.zeros(n, np.int64)
            arr[r] += d

    def add(self, pod, node: str) -> None:
        r = self.row[node]
        self._bump(pod, r, +1)
        self.where[pod.name] = r

    def remove(self, pod) -> None:
        self._bump(pod, self.where.pop(pod.name), -1)

    # -- filters --------------------------------------------------------

    def _in_domain(self, topo: str, per_node: np.ndarray,
                   row: Optional[int] = None):
        """Per node (or for one row): does its ``topo`` domain hold any of
        ``per_node``?"""
        if row is not None:
            if topo == HOSTNAME:
                return bool(per_node[row] > 0)
            dom = self.domain.get(topo)
            if dom is None or dom[row] < 0:
                return False
            return bool(per_node[dom == dom[row]].sum() > 0)
        if topo == HOSTNAME:
            return per_node > 0
        dom = self.domain.get(topo)
        if dom is None:
            return np.zeros(len(self.names), bool)
        ok = dom >= 0
        sums = np.bincount(dom[ok], weights=per_node[ok],
                           minlength=int(dom.max()) + 1)
        out = np.zeros(len(self.names), bool)
        out[ok] = sums[dom[ok]] > 0
        return out

    def fits(self, pod, row: Optional[int] = None):
        """NodeResourcesFit, per node or for one row."""
        r = slice(None) if row is None else row
        return ((self.req_cpu[r] + pod.cpu_milli <= self.alloc_cpu[r])
                & (self.req_mem[r] + pod.mem_bytes <= self.alloc_mem[r])
                & (self.count[r] + 1 <= self.alloc_pods[r]))

    def terms_ok(self, pod, row: Optional[int] = None):
        """InterPodAffinity's filter, per node or for one row."""
        _check_record(pod)
        ok = np.ones(len(self.names), bool) if row is None else True
        zeros = np.zeros(len(self.names), np.int64)
        for topo, sel in pod.anti_required:
            ok &= np.logical_not(self._in_domain(
                topo, self.label_count.get(_one_pair(sel), zeros), row))
        # existing pods' anti-affinity against this pod's labels
        for kv in pod.labels.items():
            for topo, arr in self.anti_count.get(kv, {}).items():
                ok &= np.logical_not(self._in_domain(topo, arr, row))
        for topo, sel in pod.aff_required:
            kv = _one_pair(sel)
            have = self.label_count.get(kv, zeros)
            if have.sum() == 0 and pod.labels.get(kv[0]) == kv[1]:
                continue   # upstream's rule for the first pod of a group
            ok &= self._in_domain(topo, have, row)
        return ok

    def feasible(self, pod) -> np.ndarray:
        return self.fits(pod) & self.terms_ok(pod)

    # -- scores ---------------------------------------------------------

    def scores(self, pod, lowprec: bool = False) -> np.ndarray:
        """Weighted sum of the default score plugins per node."""
        cpu = self.req_cpu + max(pod.cpu_milli, 0)
        mem = self.req_mem + max(pod.mem_bytes, 0)
        if not lowprec:
            def least(req, cap):
                s = (cap - req) * MAX_NODE_SCORE // np.maximum(cap, 1)
                return np.where((cap == 0) | (req > cap), 0, s)
            la = (least(cpu, self.alloc_cpu)
                  + least(mem, self.alloc_mem)) // 2
            fc = np.where(self.alloc_cpu == 0, 1.0,
                          cpu / np.maximum(self.alloc_cpu, 1))
            fm = np.where(self.alloc_mem == 0, 1.0,
                          mem / np.maximum(self.alloc_mem, 1))
            ba = np.where((fc >= 1) | (fm >= 1), 0,
                          ((1.0 - np.abs(fc - fm))
                           * float(MAX_NODE_SCORE)).astype(np.int64))
            return (la + ba + CONSTANT_SCORE).astype(np.float64)
        r = bf16

        def least(req, cap):
            req, cap = r(req), r(cap)
            s = np.floor(r(r(r(cap - req) * r(MAX_NODE_SCORE))
                           / np.maximum(cap, 1)))
            return np.where((cap == 0) | (req > cap), 0, s)
        la = np.floor(r(r(least(cpu, self.alloc_cpu)
                          + least(mem, self.alloc_mem)) / 2))
        fc = r(r(cpu) / np.maximum(r(self.alloc_cpu), 1))
        fm = r(r(mem) / np.maximum(r(self.alloc_mem), 1))
        ba = np.where((fc >= 1) | (fm >= 1), 0,
                      np.floor(r(r(1.0 - np.abs(r(fc - fm)))
                                 * r(MAX_NODE_SCORE))))
        return r(r(r(la + ba) + r(10000 * MAX_NODE_SCORE))
                 + r(2 * MAX_NODE_SCORE)).astype(np.float64)

    def tie_set(self, pod) -> np.ndarray:
        """Rows of the feasible nodes with the maximal score."""
        ok = self.feasible(pod)
        if not ok.any():
            return np.zeros(0, np.int64)
        s = np.where(ok, self.scores(pod), -np.inf)
        return np.flatnonzero(s == s.max())


def _check_record(pod) -> None:
    """Refuse what this reference does not model, by what the record
    HOLDS: ignoring a term would pass a placement upstream forbids, or
    score a node upstream scores otherwise."""
    for attr, what in UNMODELLED:
        if getattr(pod, attr, ()):
            raise NotImplementedError(
                f"reference default_plugins does not model {what} "
                f"(pod {pod.name}: {attr} {getattr(pod, attr)})")
    for _, sel in tuple(pod.anti_required) + tuple(pod.aff_required):
        if len(tuple(sel)) != 1:
            raise NotImplementedError(
                f"reference default_plugins does not model a required term "
                f"whose selector {dict(sel)} is not a one-label match "
                f"(pod {pod.name})")


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     lowprec: bool = False,
                     blind_batch: bool = False) -> Dict[str, str]:
    """The propose-and-admit auction, plainly.  Each round every pod
    still unassigned proposes to one of its best feasible nodes, picked
    at random, all judged against the state at the start of the round;
    proposals are admitted in pod order while the node has room and the
    pod's terms still hold there; the rest go to the next round.
    ``blind_batch`` (a control) admits without looking at the terms of
    the pods admitted before it in the same round.  Mutates ``cluster``.
    Returns {pod name: node name or ""}."""
    out = {pod.name: "" for pod in pods}
    left = list(pods)
    while left:
        proposals = []
        for pod in left:
            ok = cluster.feasible(pod)
            if not ok.any():
                continue
            s = np.where(ok, cluster.scores(pod, lowprec=lowprec), -np.inf)
            best = np.flatnonzero(s == s.max())
            proposals.append((pod, int(best[rng.integers(len(best))])))
        admitted = set()
        for pod, r in proposals:
            if cluster.fits(pod, r) and (blind_batch
                                         or cluster.terms_ok(pod, r)):
                cluster.add(pod, cluster.names[r])
                out[pod.name] = cluster.names[r]
                admitted.add(pod.name)
        if not admitted:
            break
        left = [pod for pod in left if pod.name not in admitted]
    return out


def gang_misses(cluster: Cluster, pods: Sequence[Any],
                placements: Dict[str, str]) -> List[str]:
    """Check (b).  ``pods`` in the batch's order; ``placements`` maps
    each to the node one auction cycle under test gave it ("" = left
    pending).  Round by round: every placement not yet explained whose
    node lies in its pod's tie set at the round's start is admitted, in
    pod order, if it still fits and its terms still hold there (else it
    waits for a later round); a round that explains nothing ends it.
    Mutates ``cluster`` along the placements it explains."""
    out = []
    waiting = []
    for pod in pods:
        node = placements.get(pod.name, "")
        if node and node not in cluster.row:
            out.append(f"{pod.name}: placed on unknown node {node}")
        elif node:
            waiting.append((pod, cluster.row[node]))
    while waiting:
        proposed = [(pod, r) for pod, r in waiting
                    if r in cluster.tie_set(pod)]
        admitted = set()
        for pod, r in proposed:
            if cluster.fits(pod, r) and cluster.terms_ok(pod, r):
                cluster.add(pod, cluster.names[r])
                admitted.add(pod.name)
        if not admitted:
            break
        waiting = [(pod, r) for pod, r in waiting
                   if pod.name not in admitted]
    for pod, r in waiting:
        ties = cluster.tie_set(pod)
        ok = bool(cluster.fits(pod, r) and cluster.terms_ok(pod, r))
        s = cluster.scores(pod)
        why = ("infeasible" if not ok else
               f"score {s[r]:.0f} < best {s[ties[0]]:.0f}"
               if len(ties) else "no feasible node")
        out.append(f"{pod.name}: {cluster.names[r]} outside every round's "
                   f"tie set ({why})")
    for pod in pods:
        if not placements.get(pod.name, "") and len(cluster.tie_set(pod)):
            out.append(f"{pod.name}: left pending, the reference can "
                       f"place it")
    return out


def _node_violations(cluster: Cluster, pod, r: int) -> List[str]:
    """Guarantees broken on row ``r`` right after ``pod`` landed there."""
    out = []
    name = cluster.names[r]
    for what, used, cap in (
            ("cpu", cluster.req_cpu[r], cluster.alloc_cpu[r]),
            ("memory", cluster.req_mem[r], cluster.alloc_mem[r]),
            ("pods", cluster.count[r], cluster.alloc_pods[r])):
        if used > cap:
            out.append(f"node {name} over allocatable {what}: "
                       f"{used} > {cap} after {pod.name}")
    return out


def replay(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
           pods: Dict[str, Any], log: Sequence[tuple],
           readback: Dict[str, Optional[str]],
           stuck: Sequence[str] = ()) -> List[str]:
    """Check (a).  ``init``: (pod record, node name) bound before the
    run.  ``pods``: every pod record the client ever offered, by name.
    ``log``: the client's events in observed order -- ("add", name, t),
    ("bind", name, node, t), ("delete", name, t).  ``readback``: pod name
    -> node name as the store holds it after the run (None = gone).
    ``stuck``: pods the client gave up on as never bound."""
    out: List[str] = []
    cluster = Cluster(nodes)
    for pod, node in init:
        cluster.add(pod, node)
        out.extend(_node_violations(cluster, pod, cluster.row[node]))
    bound: Dict[str, str] = {}
    deleted = set()
    for ev in log:
        kind, name = ev[0], ev[1]
        pod = pods.get(name)
        if kind == "bind":
            node = ev[2]
            if pod is None:
                out.append(f"bind of a pod never offered: {name}")
            elif name in bound:
                out.append(f"pod {name} bound twice: {bound[name]}, {node}")
            elif node not in cluster.row:
                out.append(f"pod {name} bound to unknown node {node}")
            elif name in deleted:
                out.append(f"pod {name} bound after its delete")
            else:
                if not cluster.terms_ok(pod, cluster.row[node]):
                    out.append(f"required (anti-)affinity violated: "
                               f"{name} on {node}")
                cluster.add(pod, node)
                out.extend(_node_violations(cluster, pod,
                                            cluster.row[node]))
                bound[name] = node
        elif kind == "delete":
            deleted.add(name)
            if name in cluster.where:
                cluster.remove(pod)
    for name, node in bound.items():
        want = None if name in deleted else node
        got = readback.get(name)
        if got != want:
            out.append(f"read-back: {name} bound to {node}, store holds "
                       f"{got!r}, expected {want!r}")
    for name in stuck:
        pod = pods[name]
        if name not in bound and cluster.feasible(pod).any():
            out.append(f"{name} left unschedulable; the reference can "
                       f"place it")
    return out

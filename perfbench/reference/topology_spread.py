"""Plain host reference for the default profile WITH PodTopologySpread's
filter modelled (v1.19 ``podtopologyspread/filtering.go``), for rows
whose pods carry ``DoNotSchedule`` topology spread constraints.

Imports nothing of the program.  The resource arithmetic
(NodeResourcesFit, LeastAllocated, BalancedAllocation, the constant
plugins), the auction and the tie-set check are ``default_plugins``',
imported; what this file adds is the hard constraint.

Filter (``filtering.go``: ``calPreFilterState`` and ``Filter``), for an
incoming pod with constraints c = (maxSkew, topologyKey, selector):

  eligible nodes   those that carry EVERY constraint's topology key (and
                   pass the pod's node selector / affinity: a record
                   that holds one is refused, so every node passes);
  registered pairs each (key, value) an eligible node carries; its
                   matchNum is the number of pods on the ELIGIBLE nodes
                   of that pair which the selector selects, counted
                   within the incoming pod's namespace, terminating pods
                   left out (``countPodsMatchSelector``);
  minMatchNum      the least matchNum over the key's registered pairs
                   (``TpKeyToCriticalPaths``): a value no eligible node
                   carries is not in it;
  selfMatchNum     1 when the selector selects the pod's own labels;
  a node passes    when it carries the key and
                   matchNum(its pair) + selfMatchNum - minMatchNum
                   <= maxSkew, for every constraint; a pair that is not
                   registered counts 0 pods;
  no eligible node at all: the pre-filter state is empty and every node
                   passes (``len(s.TpPairToMatchNum) == 0``).

``Cluster.add`` / ``remove`` keep the counts, so ``feasible`` and
``terms_ok`` see every pod placed before: in ``gang_misses`` that is
every pod admitted earlier in the same explained round, which is what
holds a gang cycle to the JOINT constraint (``default_plugins.gang_misses``
admits in pod order only what still ``fits`` and is still ``terms_ok``).

Score: v1.19's PodTopologySpread scores only ``ScheduleAnyway``
constraints, and DefaultPodTopologySpread skips a pod that carries
explicit constraints: both are the same on every node for the records
this file accepts, so ``default_plugins``' constants stand.

Departures from upstream, each with why it cannot show here:

  * a record carries no namespace and no deletion timestamp
    (``lib/world.py``): a record without a ``namespace`` attribute is in
    ``default``, one without ``terminating`` is not terminating;
  * selectors are ``match_labels`` conjunctions, no ``matchExpressions``;
  * a node without a constraint's key is "unschedulable and
    unresolvable" upstream and plainly infeasible here: the reference
    has no preemption to tell the two apart.

REFUSED, by what a record holds, incoming or existing: a
``ScheduleAnyway`` constraint (it is scored, not filtered), a
node-affinity term (it narrows the eligible nodes) and every inter-pod
term, required or preferred (``interpod_terms.py`` models those; a row
that mixes the two kinds brings a reference that joins them).

Controls, as switches of ``auction_schedule``: ``lowprec`` is
``default_plugins``'; ``blind_batch`` is restated here: the batch's own
pods are left out of the COUNT for the whole auction (the program's
``intra_batch_topology`` off evaluates the filter once, against the
cluster before the batch), not only out of the admission.

Check (a), ``replay``.  The client's log cannot hold the constraint
exactly, for two reasons:

  * a delete lowers the global minimum, and the client's deletes race
    the scheduler's snapshot (``lib/check.py``): whether the cycle that
    decided a bind had seen a delete logged shortly before it is not
    known;
  * the bind lane binds a cycle's pods in BATCH order while the auction
    decided them in ROUND order: inside one cycle's binds the log's
    order is not the order of decision, and where one cycle's binds end
    and the next one's begin the log does not say.

So the spread line is SOUND first: it flags a bind only when no
resolution of the two races can excuse it.  At the bind of a constrained
pod, the i-th of the log, into pair z:

    low_z + selfMatchNum - high_min > maxSkew      is a violation

  low_z     z's matching pods bound more than ``burst`` - 1 binds before
            it (a cycle logs at most ``burst`` binds, back to back, so
            these were decided in an earlier cycle and its snapshot held
            them) and not deleted by any delete logged before this bind
            (a delete the snapshot had not seen only makes the true
            count larger);
  high_min  the least, over the registered pairs, of the matching pods
            bound up to ``burst`` - 1 binds AFTER it (every bind of its
            own cycle, whichever round decided it) with only the
            deletes logged before the pod's own ``add`` applied (the
            store delivers events in the caller's thread, so the cycle
            that popped the pod had seen those; a later delete it may
            not have seen, and leaving it out only makes the count
            larger).  Where the adds are not in bind order the earliest
            add of any later bind stands for the pod's own.

The true count of z at the decision is at least low_z and the true
minimum at most high_min, so a sound auction never trips the line.
What it can still see depends on the traffic: with as many residents as
a batch holds nearly every pod bound more than a batch earlier is
deleted by now, and with four batches pending four batches of deletes
lie between a pod's add and its bind: low_z reads a few tens and
high_min some 2,000 over a whole run of the saturated mix (PERF.md has
the distribution), so the line sees nothing there; a cluster that holds
several batches' worth of matching pods gives it teeth.  The exact guard
of the constraint is check (b).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench.reference import default_plugins as _base

DEFAULT_NAMESPACE = "default"
DO_NOT_SCHEDULE = "DoNotSchedule"
# the most binds one cycle logs: the rows' batch size
BURST = 1024
# what a record may hold that this reference does not model
UNMODELLED = (("node_affinity_in", "a node-affinity term"),
              ("anti_required", "a required anti-affinity term"),
              ("aff_required", "a required affinity term"),
              ("anti_preferred", "a preferred anti-affinity term"),
              ("aff_preferred", "a preferred affinity term"))

Selector = Tuple[Tuple[str, str], ...]


def namespace(pod) -> str:
    return getattr(pod, "namespace", DEFAULT_NAMESPACE)


def selects(sel: Selector, ns: str, pod) -> bool:
    """Does a constraint of a pod in ``ns`` count ``pod``: same
    namespace, every ``match_labels`` pair on it, not terminating."""
    return (ns == namespace(pod) and not getattr(pod, "terminating", False)
            and all(pod.labels.get(k) == v for k, v in sel))


def _check_record(pod) -> None:
    for attr, what in UNMODELLED:
        if getattr(pod, attr, ()):
            raise NotImplementedError(
                f"reference topology_spread does not model {what} "
                f"(pod {pod.name}: {attr} {getattr(pod, attr)})")
    for c in pod.spread:
        if c[2] != DO_NOT_SCHEDULE:
            raise NotImplementedError(
                f"reference topology_spread does not model a {c[2]} "
                f"constraint: it is scored, not filtered "
                f"(pod {pod.name}: spread {pod.spread})")


def _shape(pod) -> tuple:
    """What the filter reads of a pod: pods of one shape share a verdict."""
    return (namespace(pod), tuple(sorted(pod.labels.items())),
            tuple(pod.spread))


class Cluster(_base.Cluster):
    """``default_plugins.Cluster``'s resource state plus, per distinct
    (namespace, selector), the per-node count of the bound pods it
    selects."""

    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.bound: Dict[str, Tuple[Any, int]] = {}   # name -> (pod, row)
        self.selected: Dict[Tuple[str, Selector], np.ndarray] = {}
        # the control: pods added while it is set are left out of the count
        self.blind_batch = False
        self._version = 0
        self._memo: Dict[tuple, tuple] = {}

    # -- state ----------------------------------------------------------

    def _bump(self, pod, r: int, d: int) -> None:
        _check_record(pod)
        self._version += 1
        self.req_cpu[r] += d * pod.cpu_milli
        self.req_mem[r] += d * pod.mem_bytes
        self.count[r] += d
        if not self.blind_batch:
            for (ns, sel), arr in self.selected.items():
                if selects(sel, ns, pod):
                    arr[r] += d

    def add(self, pod, node: str) -> None:
        super().add(pod, node)
        self.bound[pod.name] = (pod, self.row[node])

    def remove(self, pod) -> None:
        super().remove(pod)
        del self.bound[pod.name]

    def recount(self) -> None:
        """Drop the counts; they are made again from the bound pods when
        next asked for."""
        self.selected.clear()
        self._version += 1

    def _selected(self, ns: str, sel: Selector) -> np.ndarray:
        arr = self.selected.get((ns, sel))
        if arr is None:
            arr = np.zeros(len(self.names), np.int64)
            for pod, r in self.bound.values():
                if selects(sel, ns, pod):
                    arr[r] += 1
            self.selected[(ns, sel)] = arr
        return arr

    def _memoized(self, what: str, key: tuple, make):
        hit = self._memo.get((what,) + key)
        if hit is None or hit[0] != self._version:
            hit = self._memo[(what,) + key] = (self._version, make())
        return hit[1]

    # -- the constraint -------------------------------------------------

    def eligible(self, pod) -> np.ndarray:
        """Per node: does it carry every constraint's topology key."""
        ok = np.ones(len(self.names), bool)
        for _, topo, _, _ in pod.spread:
            dom = self.domain.get(topo)
            ok &= (dom >= 0) if dom is not None else False
        return ok

    def pair_counts(self, pod, constraint) -> Tuple[np.ndarray, np.ndarray]:
        """(matchNum per pair id of the constraint's key, which pair ids
        are registered) for ``pod``: counted over its eligible nodes."""
        _, topo, _, sel = constraint
        dom = self.domain[topo]
        elig = self.eligible(pod)
        size = int(dom.max()) + 1
        per_node = self._selected(namespace(pod), tuple(sel))
        sums = np.bincount(dom[elig], weights=per_node[elig],
                           minlength=size)
        registered = np.bincount(dom[elig], minlength=size) > 0
        return np.rint(sums).astype(np.int64), registered

    def _spread_ok(self, pod) -> np.ndarray:
        n = len(self.names)
        ok = np.ones(n, bool)
        if not pod.spread or not self.eligible(pod).any():
            return ok             # no constraint, or an empty state
        ns = namespace(pod)
        for c in pod.spread:
            max_skew, topo, _, sel = c
            dom = self.domain[topo]
            has_key = dom >= 0
            sums, registered = self.pair_counts(pod, c)
            safe = np.where(has_key, dom, 0)
            match_num = np.where(has_key & registered[safe], sums[safe], 0)
            self_num = 1 if selects(tuple(sel), ns, pod) else 0
            skew = match_num + self_num - int(sums[registered].min())
            ok &= has_key & (skew <= int(max_skew))
        return ok

    def terms_ok(self, pod, row: Optional[int] = None):
        """PodTopologySpread's filter, per node or for one row."""
        _check_record(pod)
        ok = self._memoized("spread", _shape(pod),
                            lambda: self._spread_ok(pod))
        return ok if row is None else bool(ok[row])

    def feasible(self, pod) -> np.ndarray:
        return self.fits(pod) & self.terms_ok(pod)

    def scores(self, pod, lowprec: bool = False) -> np.ndarray:
        return self._memoized(
            "scores", (pod.cpu_milli, pod.mem_bytes, bool(lowprec)),
            lambda: _base.Cluster.scores(self, pod, lowprec=lowprec))

    def tie_set(self, pod) -> np.ndarray:
        return self._memoized(
            "ties", (pod.cpu_milli, pod.mem_bytes) + _shape(pod),
            lambda: _base.Cluster.tie_set(self, pod))


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     blind_batch: bool = False,
                     **base_controls) -> Dict[str, str]:
    """``default_plugins.auction_schedule`` over this cluster: the
    propose-and-admit auction, each round judged against the state at its
    start, the constraint recounted at every round and at every admission
    of a round.  ``blind_batch`` (a control) leaves the batch's own pods
    out of the count for the whole auction and admits without looking at
    the constraint.  Mutates ``cluster``.  Returns {pod name: node name
    or ""}."""
    cluster.blind_batch = bool(blind_batch)
    try:
        return _base.auction_schedule(cluster, pods, rng,
                                      blind_batch=blind_batch,
                                      **base_controls)
    finally:
        if cluster.blind_batch:
            cluster.blind_batch = False
            cluster.recount()


def gang_misses(cluster: Cluster, pods: Sequence[Any],
                placements: Dict[str, str]) -> List[str]:
    """Check (b), as ``default_plugins.gang_misses`` states it: round by
    round, every placement not yet explained whose node lies in its pod's
    tie set at the round's start is admitted in pod order if it still
    fits and its constraints still hold there; a round that explains
    nothing ends it.  One thing is other than in ``default_plugins``: a
    placement whose node lay in the tie set at the start of SOME explained
    round stays proposed, and is admitted in whichever later round its
    constraints hold at its turn.  Under this constraint a round admits
    many pods a zone and several of them can share a node, all judged
    against the score at the round's start; the explanation's rounds are
    not the auction's (a pod the auction placed in its second round can
    take a zone's room in the explanation's first), so the pod of such a
    node that finds its zone full at its turn would come back to a node
    one pod fuller and no longer maximal: a miss that no auction made.
    The feasibility half stays exact: nothing is admitted where the
    count, with every pod explained before it, breaks maxSkew.  Mutates
    ``cluster`` along the placements it explains."""
    out = []
    waiting = []
    for pod in pods:
        node = placements.get(pod.name, "")
        if node and node not in cluster.row:
            out.append(f"{pod.name}: placed on unknown node {node}")
        elif node:
            waiting.append((pod, cluster.row[node]))
    proposed = set()
    while waiting:
        proposed.update(pod.name for pod, r in waiting
                        if r in cluster.tie_set(pod))
        admitted = set()
        for pod, r in waiting:
            if (pod.name in proposed and cluster.fits(pod, r)
                    and cluster.terms_ok(pod, r)):
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


# ------------------------------------------------------------- check (a)

class _Side:
    """One side of the spread line (``low`` or ``high``): per constraint
    shape (namespace, key, selector) the matching pods of each pair of
    the key, over the init pods, the first ``n`` binds of the log and
    less the first ``d`` deletes; both only ever advance."""

    def __init__(self, cluster: Cluster, shapes, init, pods, binds, deletes):
        self.cluster, self.pods = cluster, pods
        self.binds, self.deletes = binds, deletes
        self.counts = {s: np.zeros(int(cluster.domain[s[1]].max()) + 1,
                                   np.int64)
                       for s in shapes if s[1] in cluster.domain}
        self.where: Dict[str, int] = {}     # counted pod -> node row
        self.gone: set = set()              # deleted, counted or not
        self.n = self.d = 0
        for pod, node in init:
            self._count(pod.name, cluster.row[node])

    def _push(self, name: str, d: int) -> None:
        pod, r = self.pods[name], self.where[name]
        for (ns, topo, sel), arr in self.counts.items():
            p = int(self.cluster.domain[topo][r])
            if p >= 0 and selects(sel, ns, pod):
                arr[p] += d

    def _count(self, name: str, r: int) -> None:
        if name not in self.gone and name not in self.where:
            self.where[name] = r
            self._push(name, +1)

    def advance(self, n_binds: int, deletes_before: int) -> None:
        """Count the binds up to the ``n_binds``-th and apply the deletes
        logged before log index ``deletes_before``."""
        while self.n < min(n_binds, len(self.binds)):
            _, ev = self.binds[self.n]
            self.n += 1
            self._count(ev[1], self.cluster.row[ev[2]])
        while (self.d < len(self.deletes)
               and self.deletes[self.d][0] < deletes_before):
            name = self.deletes[self.d][1]
            self.d += 1
            self.gone.add(name)
            if name in self.where:
                self._push(name, -1)
                del self.where[name]


def spread_violations(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
                      pods: Dict[str, Any], log: Sequence[tuple],
                      burst: Optional[int] = None,
                      slack: Optional[List[tuple]] = None) -> List[str]:
    """The spread line of check (a), as the module docstring states it;
    ``burst``: the most binds one cycle logs (``BURST`` where not given).
    ``slack``, where given, receives (low_z, selfMatchNum, high_min,
    maxSkew) of every constrained bind: how far each was from the line."""
    burst = BURST if burst is None else int(burst)
    cluster = Cluster(nodes)
    binds = [(n, ev) for n, ev in enumerate(log)
             if ev[0] == "bind" and ev[1] in pods and ev[2] in cluster.row]
    add_at = {ev[1]: n for n, ev in enumerate(log) if ev[0] == "add"}
    deletes = [(n, ev[1]) for n, ev in enumerate(log) if ev[0] == "delete"]
    all_pods = dict(pods, **{p.name: p for p, _ in init})
    shapes = {(namespace(p), c[1], tuple(c[3]))
              for p in all_pods.values() for c in p.spread}
    if not shapes or not binds:
        return []
    low = _Side(cluster, shapes, init, all_pods, binds, deletes)
    high = _Side(cluster, shapes, init, all_pods, binds, deletes)
    # the log index whose earlier deletes the i-th bind's cycle had seen:
    # the pod's own add, or an earlier one of a later bind
    seen = [add_at.get(ev[1], -1) for _, ev in binds]
    for i in range(len(seen) - 2, -1, -1):
        seen[i] = min(seen[i], seen[i + 1])
    registered: Dict[tuple, Dict[str, np.ndarray]] = {}   # by pod.spread
    out: List[str] = []
    for i, (at, ev) in enumerate(binds):
        low.advance(i - (burst - 1), at)
        high.advance(i + burst, seen[i])
        pod, r = pods[ev[1]], cluster.row[ev[2]]
        if not pod.spread:
            continue
        if pod.spread not in registered:
            elig = cluster.eligible(pod)
            registered[pod.spread] = {
                c[1]: np.unique(cluster.domain[c[1]][elig])
                for c in pod.spread if elig.any()}
        ns = namespace(pod)
        for max_skew, topo, _, sel in pod.spread:
            if topo not in registered[pod.spread]:
                continue          # no eligible node: an empty state
            p = int(cluster.domain[topo][r])
            if p < 0:
                out.append(f"topology spread: {pod.name} bound to "
                           f"{ev[2]}, which has no {topo}")
                continue
            key = (ns, topo, tuple(sel))
            self_num = 1 if selects(tuple(sel), ns, pod) else 0
            low_z = int(low.counts[key][p])
            high_min = int(high.counts[key][
                registered[pod.spread][topo]].min())
            if slack is not None:
                slack.append((low_z, self_num, high_min, int(max_skew)))
            if low_z + self_num - high_min > int(max_skew):
                out.append(
                    f"topology spread violated: {pod.name} on {ev[2]}: "
                    f"at least {low_z} matching pods in its {topo}, at "
                    f"most {high_min} in the least, maxSkew {max_skew}")
    return out


def replay(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
           pods: Dict[str, Any], log: Sequence[tuple],
           readback: Dict[str, Optional[str]],
           stuck: Sequence[str] = ()) -> List[str]:
    """Check (a), as ``default_plugins.replay`` states it (capacity,
    double binds, unknown nodes, read-back, nothing left unschedulable
    that the reference can place), with the spread line of the module
    docstring in the place of the required-term test."""
    out: List[str] = []
    cluster = Cluster(nodes)
    for pod, node in init:
        cluster.add(pod, node)
        out.extend(_base._node_violations(cluster, pod, cluster.row[node]))
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
                cluster.add(pod, node)
                out.extend(_base._node_violations(cluster, pod,
                                                  cluster.row[node]))
                bound[name] = node
        elif kind == "delete":
            deleted.add(name)
            if name in cluster.where:
                cluster.remove(pod)
    out.extend(spread_violations(nodes, init, pods, log))
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

"""Plain host reference for the default profile WITH PodTopologySpread's
score modelled (``podtopologyspread/scoring.go``: PreScore, Score,
NormalizeScore), for rows whose pods carry ``ScheduleAnyway`` topology
spread constraints.

Imports nothing of the program.  The resource arithmetic
(NodeResourcesFit, LeastAllocated, BalancedAllocation, the constant
plugins), the auction, the strict tie-set check and the replay are
``default_plugins``', imported; what this file adds is the soft
constraint's score, in float64 and int64 as upstream has them.

Score, for an incoming pod whose ``ScheduleAnyway`` constraints are
c = (maxSkew, topologyKey, selector), over the FILTERED nodes (those
that pass the filters: here NodeResourcesFit alone):

  ignored nodes    filtered nodes that lack any constraint's topology
                   key: they score 0 and take no part in the minimum
                   and maximum (``initPreScoreState``);
  registered pairs each (key, value) a filtered, not ignored node
                   carries, for the keys other than the hostname;
                   topoSize = their number, a constraint; for the
                   hostname key the size is the number of filtered, not
                   ignored nodes;
  the weight       ``math.log(size + 2)`` (``topologyNormalizingWeight``);
  pair counts      over ALL nodes that carry every constraint's key (and
                   pass the pod's node selector / affinity: a record that
                   holds one is refused, so every node passes), filtered
                   or not: the pods of the node that the selector selects,
                   counted within the incoming pod's namespace,
                   terminating pods left out
                   (``countPodsMatchSelector``), added to the node's
                   pair IF it is registered; a hostname constraint reads
                   the node's own count at Score;
  the raw score    sum over the constraints whose key the node carries of
                   float64(adjusted count) * weight, then ``int64()`` of
                   the float64 sum; adjusted: a count below maxSkew reads
                   maxSkew - 1 (the max-skew adjustment of the snapshot
                   of ``scoring.go`` that ``kubetpu/ops/kernels.py``
                   ``spread_soft_score`` cites; v1.19.0 as released adds
                   maxSkew - 1 to every count instead: the
                   configuration's ``assumed`` says which this follows);
  NormalizeScore   min (from MaxInt64) and max (from 0) over the
                   filtered, not ignored nodes; max == 0: every such
                   node MaxNodeScore; else the integer quotient
                   ``100 * (max + min - s) / max``; ignored nodes 0.
  A pod with no ``ScheduleAnyway`` constraint scores MaxNodeScore on
  every filtered node (Score adds nothing, max == 0).  The plugin's
  weight in the default profile is 2.

There is no filter here: a ``ScheduleAnyway`` constraint forbids
nothing, so ``terms_ok`` is true everywhere, NO skew is bounded, and
``default_plugins.gang_misses`` (strict: a placement is admitted in the
round whose tie set holds it, if it still fits) is check (b) as it
stands.  ``Cluster.add`` / ``remove`` keep the per-node counts of
matching pods, so a round of ``gang_misses`` scores against every pod
admitted in the rounds before it: a gang cycle is held to the score
RECOUNTED at every round.

Departures from upstream, each with why it cannot show here:

  * a record carries no namespace and no deletion timestamp
    (``lib/world.py``): a record without a ``namespace`` attribute is in
    ``default``, one without ``terminating`` is not terminating;
  * selectors are ``match_labels`` conjunctions, no ``matchExpressions``;
  * ``math.log`` is the platform's libm, upstream's ``math.Log`` is Go's
    own port of FreeBSD's ``e_log.c``: both are within one unit of the
    last place of the true logarithm and could part by one; for three
    zones both read ``log(5)`` = 1.6094379124341003 (a test pins it);
  * no cluster-wide default constraints (``defaultConstraints``): v1.19
    ships none, and ``lib/world.py`` can state no Service or controller
    for the system defaults of later versions to select by.

REFUSED, by what a record holds, incoming or existing: a
``DoNotSchedule`` constraint (it is filtered, not scored:
``topology_spread.py`` models it), a node-affinity term (it narrows the
counted nodes), every inter-pod term, required or preferred
(``interpod_terms.py``), and a pod with two ``ScheduleAnyway``
constraints on ONE topology key (upstream, as recalled, keeps one
counter a (key, value) pair and would sum both selectors' pods into it;
no row states such a pod and the repo holds no copy of the source to
settle it).

Controls, as switches of ``auction_schedule``: ``lowprec`` is
``default_plugins``' (the summed scores, this file's part included,
rounded to bfloat16); ``no_soft_spread`` is this file's: the plugin's
weight 0, so the resource plugins alone decide; ``f32_product`` makes the
raw score's product and sum in float32 with numpy's float32 logarithm,
the nearest precision below upstream's float64.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench.reference import default_plugins as _base
# one rule for both spread rows: a constraint counts a pod of its owner's
# namespace that carries every match_labels pair and is not terminating;
# BURST is the most pods one cycle holds (the rows' batch size); UNMODELLED
# what a record may hold that neither reference models
from perfbench.reference.topology_spread import (BURST, UNMODELLED,
                                                 namespace, selects)

MAX_NODE_SCORE = _base.MAX_NODE_SCORE
HOSTNAME = _base.HOSTNAME
SPREAD_WEIGHT = 2                     # the plugin's weight, default profile
SCHEDULE_ANYWAY = "ScheduleAnyway"
Selector = Tuple[Tuple[str, str], ...]


def soft_constraints(pod) -> tuple:
    """The pod's ``ScheduleAnyway`` constraints, in its own order."""
    return tuple(c for c in pod.spread if c[2] == SCHEDULE_ANYWAY)


def _check_record(pod) -> None:
    for attr, what in UNMODELLED:
        if getattr(pod, attr, ()):
            raise NotImplementedError(
                f"reference topology_spread_soft does not model {what} "
                f"(pod {pod.name}: {attr} {getattr(pod, attr)})")
    for c in pod.spread:
        if c[2] != SCHEDULE_ANYWAY:
            raise NotImplementedError(
                f"reference topology_spread_soft does not model a {c[2]} "
                f"constraint: it is filtered, not scored "
                f"(pod {pod.name}: spread {pod.spread})")
    keys = [c[1] for c in pod.spread]
    if len(set(keys)) != len(keys):
        raise NotImplementedError(
            f"reference topology_spread_soft does not model two "
            f"{SCHEDULE_ANYWAY} constraints on one topology key "
            f"(pod {pod.name}: spread {pod.spread})")


def adjust_for_max_skew(cnt: np.ndarray, max_skew: int) -> np.ndarray:
    """Counts below maxSkew read maxSkew - 1: skews the constraint
    tolerates score alike."""
    return np.where(cnt < int(max_skew), int(max_skew) - 1, cnt)


def normalizing_weight(size: int, dtype=np.float64):
    if dtype is np.float64:
        return math.log(float(size + 2))
    return np.log(dtype(size + 2))


class Cluster(_base.Cluster):
    """``default_plugins.Cluster``'s resource state plus, per distinct
    (namespace, selector), the per-node count of the bound pods it
    selects."""

    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.bound: Dict[str, Tuple[Any, int]] = {}   # name -> (pod, row)
        self.selected: Dict[Tuple[str, Selector], np.ndarray] = {}
        # the controls: the plugin's weight, 0 when it is switched off;
        # the type the raw score's product and sum are made in
        self.spread_weight = SPREAD_WEIGHT
        self.product_dtype = np.float64
        # pods of one shape score alike until the next add or remove
        self._version = 0
        self._memo: Dict[tuple, Tuple[int, np.ndarray]] = {}

    # -- state ----------------------------------------------------------

    def _bump(self, pod, r: int, d: int) -> None:
        _check_record(pod)
        self._version += 1
        self.req_cpu[r] += d * pod.cpu_milli
        self.req_mem[r] += d * pod.mem_bytes
        self.count[r] += d
        for (ns, sel), arr in self.selected.items():
            if selects(sel, ns, pod):
                arr[r] += d

    def add(self, pod, node: str) -> None:
        super().add(pod, node)
        self.bound[pod.name] = (pod, self.row[node])

    def remove(self, pod) -> None:
        super().remove(pod)
        del self.bound[pod.name]

    def _selected(self, ns: str, sel: Selector) -> np.ndarray:
        arr = self.selected.get((ns, sel))
        if arr is None:
            arr = np.zeros(len(self.names), np.int64)
            for pod, r in self.bound.values():
                if selects(sel, ns, pod):
                    arr[r] += 1
            self.selected[(ns, sel)] = arr
        return arr

    # -- filter: a soft constraint forbids nothing ----------------------

    def terms_ok(self, pod, row=None):
        _check_record(pod)
        return np.ones(len(self.names), bool) if row is None else True

    def feasible(self, pod) -> np.ndarray:
        _check_record(pod)
        return self.fits(pod)

    # -- score ----------------------------------------------------------

    def _has_keys(self, constraints) -> np.ndarray:
        """Per node: does it carry every constraint's topology key."""
        ok = np.ones(len(self.names), bool)
        for _, topo, _, _ in constraints:
            dom = self.domain.get(topo)
            ok &= (dom >= 0) if dom is not None else False
        return ok

    def zone_counts(self, pod, constraint, filtered: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
        """(matching pods per pair id of the constraint's key, which pair
        ids are registered) as PreScore leaves them for ``pod`` over the
        ``filtered`` nodes."""
        _, topo, _, sel = constraint
        dom = self.domain[topo]
        has_all = self._has_keys(soft_constraints(pod))
        size = int(dom.max()) + 1
        registered = np.bincount(dom[filtered & has_all],
                                 minlength=size) > 0
        per_node = self._selected(namespace(pod), tuple(sel))
        sums = np.rint(np.bincount(dom[has_all], weights=per_node[has_all],
                                   minlength=size)).astype(np.int64)
        return np.where(registered, sums, 0), registered

    def spread_raw(self, pod, filtered: np.ndarray) -> np.ndarray:
        """Score's int64 per node, before NormalizeScore (0 on a node the
        plugin ignores or never scores)."""
        constraints = soft_constraints(pod)
        has_all = self._has_keys(constraints)
        scored = filtered & has_all
        dtype = self.product_dtype
        total = np.zeros(len(self.names), dtype)
        for c in constraints:
            max_skew, topo, _, sel = c
            if topo == HOSTNAME:
                size = int(scored.sum())
                cnt = self._selected(namespace(pod), tuple(sel))
            else:
                sums, registered = self.zone_counts(pod, c, filtered)
                size = int(registered.sum())
                cnt = sums[np.where(has_all, self.domain[topo], 0)]
            total += (adjust_for_max_skew(cnt, max_skew).astype(dtype)
                      * normalizing_weight(size, dtype))
        return np.where(scored, total.astype(np.int64), 0)

    def spread_score(self, pod, filtered: np.ndarray) -> np.ndarray:
        """PodTopologySpread's normalised score per node (0 outside the
        filtered set, which upstream never scores)."""
        out = np.zeros(len(self.names), np.int64)
        constraints = soft_constraints(pod)
        if not constraints:
            out[filtered] = MAX_NODE_SCORE
            return out
        scored = filtered & self._has_keys(constraints)
        if not scored.any():
            return out
        raw = self.spread_raw(pod, filtered)
        lo = int(raw[scored].min())
        hi = max(int(raw[scored].max()), 0)
        if hi == 0:
            out[scored] = MAX_NODE_SCORE
        else:
            out[scored] = MAX_NODE_SCORE * (hi + lo - raw[scored]) // hi
        return out

    def scores(self, pod, lowprec: bool = False) -> np.ndarray:
        """Weighted sum of the default score plugins per node; the
        PodTopologySpread part is normalised over the filtered nodes."""
        _check_record(pod)
        key = (pod.cpu_milli, pod.mem_bytes, namespace(pod),
               tuple(pod.spread), bool(lowprec), self.spread_weight,
               self.product_dtype)
        hit = self._memo.get(key)
        if hit is None or hit[0] != self._version:
            total = (super().scores(pod, lowprec=lowprec)
                     + self.spread_weight
                     * self.spread_score(pod, self.fits(pod)))
            if lowprec:
                total = _base.bf16(total).astype(np.float64)
            hit = self._memo[key] = (self._version, total)
        return hit[1]


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     no_soft_spread: bool = False,
                     f32_product: bool = False,
                     **base_controls) -> Dict[str, str]:
    """``default_plugins.auction_schedule`` over this cluster, a cycle at
    a time: the propose-and-admit auction, each round judged against the
    state at its start, PodTopologySpread's score included and recounted
    at every round.  The pods are auctioned ``BURST`` at a time, in their
    order, as a scheduler with that batch size places them: a soft
    constraint lets a whole round follow the round's first scores, so a
    population bound in cycles of a batch is as uneven as a batch (the
    cycle's pods all go to the zone that was least), where ONE auction
    over all of it from an empty cluster, every zone tied under maxSkew,
    would leave the zones level, a state no window of this traffic
    starts a cycle from and on which check (b) cannot tell a working
    score from none (PERF.md, section 6, PR 42).  ``no_soft_spread`` (a
    control) gives the plugin weight 0, ``f32_product`` (another) makes
    the raw score in float32; ``default_plugins``' own controls pass
    through.  Mutates ``cluster``.  Returns {pod name: node name or
    ""}."""
    cluster.spread_weight = 0 if no_soft_spread else SPREAD_WEIGHT
    cluster.product_dtype = np.float32 if f32_product else np.float64
    out: Dict[str, str] = {}
    try:
        for at in range(0, len(pods), BURST):
            out.update(_base.auction_schedule(
                cluster, pods[at:at + BURST], rng, **base_controls))
    finally:
        cluster.spread_weight = SPREAD_WEIGHT
        cluster.product_dtype = np.float64
    return out


def serial_schedule(cluster: Cluster, pods: Sequence[Any], rng
                    ) -> Dict[str, str]:
    """Upstream's own loop: one pod at a time, each scored against every
    pod placed before it.  What a user of a serial scheduler sees; the
    gang cycle's herd (PERF.md, Open questions) is read against it."""
    out = {}
    for pod in pods:
        ties = cluster.tie_set(pod)
        out[pod.name] = ""
        if len(ties):
            node = cluster.names[int(ties[rng.integers(len(ties))])]
            cluster.add(pod, node)
            out[pod.name] = node
    return out


def zone_skew(cluster: Cluster, pod) -> int:
    """The most less the least of the pods ``pod``'s first soft
    constraint selects, over the pairs its key has on the nodes."""
    c = soft_constraints(pod)[0]
    sums, registered = cluster.zone_counts(
        pod, c, np.ones(len(cluster.names), bool))
    return int(sums[registered].max() - sums[registered].min())


# check (b): explains one gang cycle's placements round by round against
# ``Cluster.tie_set`` / ``fits`` / ``terms_ok`` above
gang_misses = _base.gang_misses


def replay(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
           pods: Dict[str, Any], log: Sequence[tuple],
           readback: Dict[str, Optional[str]],
           stuck: Sequence[str] = ()) -> List[str]:
    """Check (a) is ``default_plugins.replay`` itself: capacity, double
    binds, unknown nodes, read-back, nothing left unschedulable that the
    reference can place.  A ``ScheduleAnyway`` constraint adds no line
    to it (it bounds no skew and makes no node infeasible), so the
    records this file accepts are handed on WITHOUT their constraints,
    which ``default_plugins`` would refuse to look at."""
    def plain(pod):
        _check_record(pod)
        return dataclasses.replace(pod, spread=()) if pod.spread else pod
    named = {ev[1] for ev in log} | set(stuck)
    return _base.replay(
        nodes, [(plain(pod), node) for pod, node in init],
        {name: plain(pod) for name, pod in pods.items() if name in named},
        log, readback, stuck)

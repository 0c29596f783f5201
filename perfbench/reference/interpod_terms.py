"""Plain host reference for the default profile WITH InterPodAffinity's
terms modelled (v1.19 ``interpodaffinity/filtering.go`` and
``scoring.go``), for rows whose existing or incoming pods carry required
or preferred pod (anti-)affinity terms.

Imports nothing of the program.  The resource arithmetic
(NodeResourcesFit, LeastAllocated, BalancedAllocation, the constant
plugins), the auction and the tie-set check are ``default_plugins``',
imported; what this file adds is the term model, for incoming AND
existing pods, evaluated against the pod in hand every time: no sum is
taken to be zero because of what a template happens to carry.

Filter (``filtering.go``), a node passes when all three hold:

  existing pods' required anti-affinity   for every bound pod's required
      anti-affinity term that selects the incoming pod (namespace and
      every ``match_labels`` pair), the node does not share the owner's
      node's value of the term's topology key (an owner whose node lacks
      the key pins nothing);
  the pod's required anti-affinity        for every term, no bound pod it
      selects lives in the node's domain of the term's key;
  the pod's required affinity             the node carries every term's
      key and, for every term, its domain holds a bound pod that matches
      ALL of the pod's affinity terms (1.19 counts a pod only if it
      matches every term); or, upstream's bootstrap rule: no such pod
      exists on any key-carrying node, the pod matches all its own terms,
      and the node carries every key.

Score (``scoring.go``), per (topology key, value) and read back per
node through the node's own labels:

  + weight   for each preferred affinity term of the incoming pod and
             each bound pod it selects, at the bound pod's node's value;
  - weight   the same for its preferred anti-affinity terms;
  + weight / - weight   for each bound pod's preferred (anti-)affinity
             term that selects the incoming pod, at the owner's node's
             value;
  + hardPodAffinityWeight (1, the default)   for each bound pod's
             REQUIRED affinity term that selects the incoming pod;
  NormalizeScore over the feasible nodes with min and max starting at 0:
  ``int64(100 * (float64(score - min) / float64(max - min)))``, 0 when
  max == min, and skipped altogether (every node 0) when nothing was
  counted.  The plugin's weight in the default profile is 1.

Departures from upstream, each with why it cannot show here:

  * a term carries no namespace list (``lib/world.py`` templates state
    none), so it selects within its owner's namespace, which is
    upstream's rule for an empty list; a record without a ``namespace``
    attribute is in ``default``;
  * selectors are ``match_labels`` conjunctions, no ``matchExpressions``;
  * identical terms are kept as one row with a per-node owner count, so
    a term's selector is evaluated once per distinct term and pod, not
    once per owner: the sums are the same;
  * the float64 product of NormalizeScore is kept as upstream computes
    it, so 29/50, 29/100, 57/100 and 58/100 of the range read one point
    under the exact quotient, as they do upstream.

Topology-spread constraints and node-affinity terms it REFUSES, by what
a record holds, incoming or existing: the rows that need them bring
their own reference.

Controls, as switches of ``auction_schedule``: ``lowprec`` and
``blind_batch`` are ``default_plugins``' (the summed scores, this file's
part included, rounded to bfloat16; the batch's own pods left out of the
term filter); ``no_terms_match`` is this file's: no existing pod's term
is taken to select any incoming pod (the incoming pod's own terms still
count the existing pods).
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench.reference import default_plugins as _base

MAX_NODE_SCORE = _base.MAX_NODE_SCORE
HARD_POD_AFFINITY_WEIGHT = 1          # InterPodAffinityArgs' default
INTERPOD_WEIGHT = 1                   # the plugin's weight, default profile
DEFAULT_NAMESPACE = "default"
# what a record may hold that this reference does not model
UNMODELLED = (("spread", "a topology spread constraint"),
              ("node_affinity_in", "a node-affinity term"))
# kinds of term an existing pod owns, with the sign of its score weight
# (None: a filter term, not scored)
_OWNED = (("anti_required", None), ("aff_required", +1),
          ("anti_preferred", -1), ("aff_preferred", +1))

Selector = Tuple[Tuple[str, str], ...]


def namespace(pod) -> str:
    return getattr(pod, "namespace", DEFAULT_NAMESPACE)


def selects(sel: Selector, ns: str, pod) -> bool:
    """Does a term of an owner in ``ns`` select ``pod``: same namespace,
    every ``match_labels`` pair on the pod."""
    return ns == namespace(pod) and all(
        pod.labels.get(k) == v for k, v in sel)


def _check_record(pod) -> None:
    for attr, what in UNMODELLED:
        if getattr(pod, attr, ()):
            raise NotImplementedError(
                f"reference interpod_terms does not model {what} "
                f"(pod {pod.name}: {attr} {getattr(pod, attr)})")


def _owned_terms(pod):
    """(kind, topology key, selector, weight) of every term ``pod`` owns;
    a required term's weight is 1."""
    for kind, _ in _OWNED:
        for term in getattr(pod, kind, ()):
            if len(term) == 3:
                weight, topo, sel = term
            else:
                (topo, sel), weight = term, 1
            yield kind, str(topo), tuple(sel), int(weight)


class Cluster(_base.Cluster):
    """``default_plugins.Cluster``'s resource state plus the term tables:
    per distinct selector the per-node count of bound pods it selects,
    per distinct owned term the per-node count of its owners."""

    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.bound: Dict[str, Tuple[Any, int]] = {}   # name -> (pod, row)
        # (namespace, selector) -> per-node count of the bound pods of
        # that namespace the selector selects; made when first asked for
        self.selected: Dict[Tuple[str, Selector], np.ndarray] = {}
        # (kind, namespace, topology key, selector, weight) -> per-node
        # count of the bound pods owning such a term
        self.owners: Dict[tuple, np.ndarray] = {}
        # the control: no existing term selects any incoming pod
        self.no_terms_match = False
        self._version = 0
        self._memo: Optional[tuple] = None

    # -- state ----------------------------------------------------------

    def _bump(self, pod, r: int, d: int) -> None:
        _check_record(pod)
        self._version += 1
        self.req_cpu[r] += d * pod.cpu_milli
        self.req_mem[r] += d * pod.mem_bytes
        self.count[r] += d
        ns = namespace(pod)
        for (sel_ns, sel), arr in self.selected.items():
            if selects(sel, sel_ns, pod):
                arr[r] += d
        for kind, topo, sel, weight in _owned_terms(pod):
            key = (kind, ns, topo, sel, weight)
            arr = self.owners.get(key)
            if arr is None:
                arr = self.owners[key] = np.zeros(len(self.names), np.int64)
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

    # -- topology -------------------------------------------------------

    def _domain_sum(self, topo: str, per_node: np.ndarray) -> np.ndarray:
        """Per node: the sum of ``per_node`` over the nodes that share
        its value of ``topo``; 0 on a node without the key, and a count
        on a node without the key reaches nobody."""
        dom = self.domain.get(topo)
        out = np.zeros(len(self.names), np.int64)
        if dom is None:
            return out
        ok = dom >= 0
        sums = np.bincount(dom[ok], weights=per_node[ok],
                           minlength=int(dom.max()) + 1)
        out[ok] = np.rint(sums[dom[ok]]).astype(np.int64)
        return out

    def _has_key(self, topo: str) -> np.ndarray:
        dom = self.domain.get(topo)
        return (np.zeros(len(self.names), bool) if dom is None
                else dom >= 0)

    def _existing(self, pod, kinds):
        """(kind, topology key, weight, per-node owner count) of every
        distinct existing term of those kinds that selects ``pod``."""
        for (kind, ns, topo, sel, weight), arr in self.owners.items():
            if (kind in kinds and not self.no_terms_match
                    and selects(sel, ns, pod)):
                yield kind, topo, weight, arr

    # -- filter ---------------------------------------------------------

    def terms_ok(self, pod, row: Optional[int] = None):
        """InterPodAffinity's filter, per node or for one row."""
        _check_record(pod)
        n = len(self.names)
        ns = namespace(pod)
        ok = np.ones(n, bool)
        for _, topo, _, owners in self._existing(pod, ("anti_required",)):
            ok &= self._domain_sum(topo, owners) == 0
        for topo, sel in pod.anti_required:
            ok &= self._domain_sum(topo, self._selected(ns, tuple(sel))) == 0
        if pod.aff_required:
            # 1.19: an existing pod counts only if it matches ALL terms
            every = tuple(kv for _, sel in pod.aff_required for kv in sel)
            matched = self._selected(ns, every)
            have = np.ones(n, bool)
            keys = np.ones(n, bool)
            anywhere = 0
            for topo, _ in pod.aff_required:
                has_key = self._has_key(topo)
                keys &= has_key
                have &= self._domain_sum(topo, matched) > 0
                anywhere += int(matched[has_key].sum())
            bootstrap = anywhere == 0 and selects(every, ns, pod)
            ok &= keys & (have | bootstrap)
        return ok if row is None else bool(ok[row])

    def feasible(self, pod) -> np.ndarray:
        ok = self.fits(pod) & self.terms_ok(pod)
        self._memo = (pod.name, self._version, ok)
        return ok

    # -- score ----------------------------------------------------------

    def interpod_raw(self, pod) -> Tuple[np.ndarray, bool]:
        """(per-node sum of the (key, value) weights the node's own
        labels read back, whether anything was counted at all)."""
        ns = namespace(pod)
        raw = np.zeros(len(self.names), np.int64)
        counted = False

        def count(topo, weight, per_node):
            nonlocal raw, counted
            if weight and per_node[self._has_key(topo)].any():
                counted = True
                raw = raw + weight * self._domain_sum(topo, per_node)

        for weight, topo, sel in pod.aff_preferred:
            count(topo, int(weight), self._selected(ns, tuple(sel)))
        for weight, topo, sel in pod.anti_preferred:
            count(topo, -int(weight), self._selected(ns, tuple(sel)))
        signs = dict(_OWNED)
        for kind, topo, weight, owners in self._existing(
                pod, ("aff_required", "aff_preferred", "anti_preferred")):
            if kind == "aff_required":
                weight = HARD_POD_AFFINITY_WEIGHT
            count(topo, signs[kind] * weight, owners)
        return raw, counted

    def interpod_score(self, pod, feasible: np.ndarray) -> np.ndarray:
        """InterPodAffinity's normalised score per node (0 outside the
        feasible set, which upstream never scores)."""
        raw, counted = self.interpod_raw(pod)
        out = np.zeros(len(self.names), np.int64)
        if not counted or not feasible.any():
            return out
        lo = min(int(raw[feasible].min()), 0)
        hi = max(int(raw[feasible].max()), 0)
        if hi > lo:
            f = float(MAX_NODE_SCORE) * (
                (raw - lo).astype(np.float64) / float(hi - lo))
            out[feasible] = f.astype(np.int64)[feasible]
        return out

    def scores(self, pod, lowprec: bool = False) -> np.ndarray:
        """Weighted sum of the default score plugins per node; the
        InterPodAffinity part is normalised over the feasible nodes."""
        memo = self._memo
        feasible = (memo[2] if memo is not None
                    and memo[:2] == (pod.name, self._version)
                    else self.fits(pod) & self.terms_ok(pod))
        total = (super().scores(pod, lowprec=lowprec)
                 + INTERPOD_WEIGHT * self.interpod_score(pod, feasible))
        return _base.bf16(total).astype(np.float64) if lowprec else total


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     no_terms_match: bool = False,
                     **base_controls) -> Dict[str, str]:
    """``default_plugins.auction_schedule`` over this cluster: the
    propose-and-admit auction, each round judged against the state at its
    start, InterPodAffinity's score included.  ``no_terms_match`` (a
    control) takes no existing term to select any incoming pod;
    ``default_plugins``' own controls (``lowprec``, ``blind_batch``) pass
    through.  Mutates ``cluster``.  Returns {pod name: node name or ""}."""
    cluster.no_terms_match = bool(no_terms_match)
    try:
        return _base.auction_schedule(cluster, pods, rng, **base_controls)
    finally:
        cluster.no_terms_match = False


# check (b): explains one gang cycle's placements round by round against
# ``Cluster.tie_set`` / ``fits`` / ``terms_ok`` above
gang_misses = _base.gang_misses


def replay(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
           pods: Dict[str, Any], log: Sequence[tuple],
           readback: Dict[str, Optional[str]],
           stuck: Sequence[str] = ()) -> List[str]:
    """Check (a), as ``default_plugins.replay`` states it, with this
    file's terms: at every bind the pod's own required terms and every
    bound pod's required anti-affinity, init pods included, must hold on
    the node it was bound to."""
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
                if not cluster.terms_ok(pod, cluster.row[node]):
                    out.append(f"required (anti-)affinity violated: "
                               f"{name} on {node}")
                cluster.add(pod, node)
                out.extend(_base._node_violations(cluster, pod,
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

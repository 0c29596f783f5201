"""Plain host reference for the default profile WITH NodeAffinity's
filter modelled (v1.19 ``nodeaffinity/node_affinity.go:54`` Filter,
``plugins/helper/node_affinity.go`` PodMatchesNodeSelectorAndAffinityTerms,
``v1helper.MatchNodeSelectorTerms``), for rows whose pods carry a
REQUIRED node-affinity term: upstream's SchedulingNodeAffinity.

Imports nothing of the program.  The resource arithmetic
(NodeResourcesFit, LeastAllocated, BalancedAllocation, the constant
plugins), the required one-label pod (anti-)affinity filter, the auction
and the tie-set check are ``default_plugins``', by import and unedited;
what this file adds is the nodes' labels, kept, and one filter.

Filter.  A record states its required node affinity as
``node_affinity_in``: ``(key, values)`` requirements, all of ONE node
selector term (``lib/world.py`` builds one term with one ``In``
expression from it; several pairs would be one term's several
expressions, ANDed).  A node passes when, for every ``(key, values)``,
it CARRIES ``key`` and its value is one of ``values``
(``labels.Selector`` ``In``: ``ls.Has(key) && set.Has(ls.Get(key))``);
a node that lacks the key is refused; a value no node carries matches
nothing and harms nothing.  A pod without the field passes every node
(``affinity == nil``).  The verdict does not depend on what is bound:
it is computed once a requirement and kept.

Score.  NodeAffinity's score sums the weights of the PREFERRED terms a
node matches; a record has no key for one, so the raw score is 0 on
every node and NormalizeScore (DefaultNormalizeScore: max 0 -> all 0)
leaves it 0: the constant ``default_plugins`` already counts.  The
scores are the parent's, unchanged.

Departures from upstream, each with why it cannot show here:

  * one term of ``In`` expressions only: no ``NotIn`` / ``Exists`` /
    ``DoesNotExist`` / ``Gt`` / ``Lt``, no ``matchFields``, no second
    term (terms are ORed upstream), no ``spec.nodeSelector``:
    ``lib/world.py`` has no key for any of them, so no record holds one;
  * a refused node is refused: upstream words it
    UnschedulableAndUnresolvable (preemption cannot help), which a
    placement cannot show; ``tests/test_node_affinity_zones.py`` reads
    the program's own mask for it;
  * ``replay`` is ``default_plugins.replay`` written out again over THIS
    ``Cluster`` (that one builds its cluster from its own module's
    globals): a copy, as ``interpod_terms.replay`` is (ROADMAP C12 (ii));
    it words a refused bind "required node affinity violated".

Any other term a record may hold that ``default_plugins`` does not
model (a preferred pod term, a spread constraint, a several-label
selector) still raises, incoming or existing.

Controls, as switches of ``auction_schedule``: ``no_node_affinity``
(every node passes the node-affinity filter), the counterpart of
``perfbench/controls/no-node-affinity.py``, and ``in_needs_every_value``
(``In`` read as "the node carries EVERY listed value", which no node can
of two values under one key), the counterpart of
``perfbench/controls/in-needs-every-value.py``; ``default_plugins``' own
(``lowprec``, ``blind_batch``) pass through.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from perfbench.reference import default_plugins as _base


def _without_node_affinity(pod):
    """The record as ``default_plugins`` may see it: the node-affinity
    term, which this file judges, taken off; everything else stays and
    is refused there if unmodelled."""
    if getattr(pod, "node_affinity_in", ()):
        return dataclasses.replace(pod, node_affinity_in=())
    return pod


class Cluster(_base.Cluster):
    """``default_plugins.Cluster`` with the nodes' labels kept."""

    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.labels = [dict(n.labels) for n in nodes]
        # (key, values) -> per-node verdict; node labels never change
        self._in: Dict[Tuple[str, Tuple[str, ...]], np.ndarray] = {}
        # the controls: every node passes the node-affinity filter; an
        # ``In`` requirement asks for every listed value at once
        self.no_node_affinity = False
        self.in_needs_every_value = False

    def _bump(self, pod, r: int, d: int) -> None:
        super()._bump(_without_node_affinity(pod), r, d)

    def _matches_in(self, key: str, values: Tuple[str, ...]) -> np.ndarray:
        hit = self._in.get((key, values))
        if hit is None:
            allowed = set(values)
            hit = self._in[(key, values)] = np.array(
                [key in lab and lab[key] in allowed for lab in self.labels],
                bool)
        if self.in_needs_every_value and len(set(values)) > 1:
            return np.zeros(len(self.names), bool)   # one value a key
        return hit

    def node_affinity_ok(self, pod, row: Optional[int] = None):
        """NodeAffinity's filter, per node or for one row."""
        ok = np.ones(len(self.names), bool)
        if not self.no_node_affinity:
            for key, values in getattr(pod, "node_affinity_in", ()):
                ok = ok & self._matches_in(str(key), tuple(values))
        return ok if row is None else bool(ok[row])

    def terms_ok(self, pod, row: Optional[int] = None):
        """InterPodAffinity's filter as ``default_plugins`` has it AND
        NodeAffinity's, per node or for one row."""
        ok = super().terms_ok(_without_node_affinity(pod), row)
        return ok & self.node_affinity_ok(pod, row)


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     no_node_affinity: bool = False,
                     in_needs_every_value: bool = False,
                     **base_controls) -> Dict[str, str]:
    """``default_plugins.auction_schedule`` over this cluster.  Controls:
    with ``no_node_affinity`` no node is refused for a required
    node-affinity term; with ``in_needs_every_value`` a node passes an
    ``In`` requirement only if it carries every listed value.  Mutates
    ``cluster``."""
    cluster.no_node_affinity = bool(no_node_affinity)
    cluster.in_needs_every_value = bool(in_needs_every_value)
    try:
        return _base.auction_schedule(cluster, pods, rng, **base_controls)
    finally:
        cluster.no_node_affinity = cluster.in_needs_every_value = False


# check (b): explains one gang cycle's placements round by round against
# ``Cluster.tie_set`` / ``fits`` / ``terms_ok`` above
gang_misses = _base.gang_misses


def replay(nodes: Sequence[Any], init: Sequence[Tuple[Any, str]],
           pods: Dict[str, Any], log: Sequence[tuple],
           readback: Dict[str, Optional[str]],
           stuck: Sequence[str] = ()) -> List[str]:
    """Check (a), as ``default_plugins.replay`` states it, over this
    file's cluster: at every bind the node must also match the pod's
    required node-affinity term; init pods are held to theirs too."""
    out: List[str] = []
    cluster = Cluster(nodes)
    for pod, node in init:
        if node in cluster.row and not cluster.node_affinity_ok(
                pod, cluster.row[node]):
            out.append(f"required node affinity violated: {pod.name} on "
                       f"{node}")
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
                r = cluster.row[node]
                if not cluster.node_affinity_ok(pod, r):
                    out.append(f"required node affinity violated: "
                               f"{name} on {node}")
                if not _base.Cluster.terms_ok(
                        cluster, _without_node_affinity(pod), r):
                    out.append(f"required (anti-)affinity violated: "
                               f"{name} on {node}")
                cluster.add(pod, node)
                out.extend(_base._node_violations(cluster, pod, r))
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

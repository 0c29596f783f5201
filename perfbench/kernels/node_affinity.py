"""Operations and bytes the pods' REQUIRED node-affinity terms add to one
gang-auction cycle, from shapes.  With ``auction.py``'s count for ONE
round it is the yardstick of ``auction_nodeaffinity_roofline``.

A batch whose every pod carries a required node selector term
(upstream's SchedulingNodeAffinity row: one term a pod, one ``In``
expression over the zone key listing two values) asks of ANY
implementation of NodeAffinity's filter (``nodeaffinity/node_affinity.go:54``,
``v1helper.MatchNodeSelectorTerms``), over the terms and values the
configuration states, never the buckets they are padded to:

  the match     for each (pod, node) and each value an expression
                lists, one compare of the node's label under the
                expression's key with the value;
  the OR / AND  the ORs that join an expression's values (one fewer than
                its values), the ANDs that join a term's expressions
                (one fewer than its expressions), and one AND of the
                verdict into the pod's feasibility row.

  = 2 x (listed values of the term) operations a (pod, node): 4 for
  upstream's template.

ONCE A CYCLE, whatever the program's round count: a node's labels do
not change inside a cycle and no placement changes the verdict, so a
program that needs more rounds for the same placements is doing more
than the row asks.  The plain auction is asked for ONE round too
(``auction.ops(batch, nodes, 1)``), so the share reads the same work
whatever implements it.  A program that matches each DISTINCT term once
and gathers does less than this count; the share then reads higher and
still cannot pass 100%, because the one round beside it is 27
operations a pair and the term's part 4.

Bytes, once a cycle: one label id a node for each key a term names, and
each pod's term (its key and its listed values, one word each), beside
the plain auction's one round.  No [pods, nodes] plane need leave the
chip.

Everything comes from the configuration file and the cycle's own pod
count, never from the program's shapes or counters, so it works on a
program that says nothing about node affinity.  A file of its own beside
``auction.py`` and the other rows': the PR that adds a row edits no file
of the benchmark.
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction


def ops(batch: int, nodes: int, values_per_pod: float) -> float:
    """Operations the required node-affinity terms add to one cycle.
    ``values_per_pod``: the values one pod's term lists, summed over its
    expressions."""
    return float(batch) * nodes * 2.0 * values_per_pod


def bytes_moved(batch: int, nodes: int, keys_per_pod: float,
                values_per_pod: float) -> float:
    if not values_per_pod:
        return 0.0
    return 4.0 * (nodes * keys_per_pod
                  + batch * (keys_per_pod + values_per_pod))


def shapes_of(config: Dict[str, Any], world) -> Dict[str, float]:
    """From the configuration alone, for ONE pod of the measured
    template: the expressions (keys) its required term holds and the
    values they list.  ``world`` is ``perfbench.lib.world``."""
    measured = world.measured_record(config, "measured", 0)
    term = measured.node_affinity_in
    return {"keys_per_pod": float(len(term)),
            "values_per_pod": float(sum(len(v) for _k, v in term))}


def least_seconds(batch: int, nodes: int, flops_per_s: float,
                  bytes_per_s: float, shapes: Dict[str, float]
                  ) -> Dict[str, float]:
    """The least time the chip could take for ONE cycle's auction WITH
    the required node-affinity terms, and which bound sets it.
    ``shapes``: ``shapes_of`` here."""
    term_ops = ops(batch, nodes, shapes["values_per_pod"])
    n_ops = auction.ops(batch, nodes, 1) + term_ops
    n_bytes = (auction.bytes_moved(batch, nodes, 1)
               + bytes_moved(batch, nodes, **shapes))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "node_affinity_ops": term_ops}

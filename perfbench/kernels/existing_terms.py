"""Operations and bytes the EXISTING pods' (anti-)affinity terms add to
one gang-auction cycle, from shapes.  With ``auction.py``'s count it is
the yardstick of ``auction_terms_roofline``.

``auction.py`` counts the plain auction and the incoming pods' own
required anti-affinity terms.  A cluster whose bound pods carry terms
(upstream's MixedSchedulingBasePod row: 8,000 such rows) adds what ANY
implementation of InterPodAffinity has to do for them:

  once a cycle      the match of every existing term against every pod
                    of the batch: one compare for each label the
                    selector names, one for the namespace, one ``and``
                    for each compare after the first.  A term row of
                    upstream's templates names one label: 3 operations a
                    (term, pod) pair, over the E valid rows, not the
                    bucket they are padded to.
  once a round      for the (term, pod) pairs that MATCHED, and only
                    those, one add (or one ``or``, for a required
                    anti-affinity term) for each node that shares the
                    owner's node's value of the term's topology key.  A
                    pair that did not match touches no node: an
                    implementation can skip it, so it is not counted.

With plain measured pods (a template that states no label) no existing
term of a non-empty selector can select a pod of the batch: the second
part is ZERO, and what is left is E x B x 3 a cycle.

Bytes, once a cycle: the term rows (selector label and value id,
namespace id, topology key, owner's pod row, weight: 6 words) and the
owners' node rows (1 word) read; per matched pair and round one word of
the node's running sum read and written.

Nothing is counted twice and nothing that an implementation could skip,
so the share cannot pass 100%.
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction

MATCH_OPS_PER_LABEL = 2         # the label's compare and its ``and``
MATCH_OPS_NAMESPACE = 1
TERM_ROW_WORDS = 7
# the fields of a pod record (lib/world.py PodRec) that hold terms an
# EXISTING pod is matched by, as (topology key, selector) after the
# weight is dropped
OWNED = ("anti_required", "aff_required", "anti_preferred", "aff_preferred")


def ops(batch: int, term_rows: int, labels_per_term: float = 1.0,
        matched_node_adds: float = 0.0, rounds: float = 1.0) -> float:
    """Operations the existing terms add to one cycle.  ``term_rows``:
    valid rows of both tables; ``matched_node_adds``: over the matched
    (term, pod) pairs, the nodes that share the owner's topology pair."""
    per_pair = MATCH_OPS_PER_LABEL * labels_per_term + MATCH_OPS_NAMESPACE
    return (float(term_rows) * batch * per_pair
            + float(matched_node_adds) * rounds)


def bytes_moved(term_rows: int, matched_node_adds: float = 0.0,
                rounds: float = 1.0) -> float:
    return 4.0 * (TERM_ROW_WORDS * term_rows
                  + 2 * matched_node_adds * rounds)


def _terms_of(rec):
    for field in OWNED:
        for term in getattr(rec, field, ()):
            yield term[-2], tuple(term[-1])       # (topology key, selector)


def shapes_of(config: Dict[str, Any], n_nodes: int, resident_bound: int,
              world) -> Dict[str, float]:
    """From the configuration alone: the valid existing-term rows a cycle
    runs over (the init pods' and ``resident_bound`` measured pods'), the
    mean labels a term's selector names, and, for ONE pod of the measured
    template, the nodes its matched terms reach (``matched_node_adds`` a
    pod).  ``world`` is ``perfbench.lib.world``."""
    groups = list(world.init_groups(config)) + [
        (config["measured_pods"]["template"], int(resident_bound))]
    measured = world.measured_record(config, "measured", 0)
    values = world.node_label_values(config)
    rows = labels = adds = 0.0
    for template, count in groups:
        for topo, sel in _terms_of(world.pod_record(config, template,
                                                    "init", 0)):
            rows += count
            labels += count * len(sel)
            if all(measured.labels.get(k) == v for k, v in sel):
                domain = (1.0 if topo == world.HOSTNAME else
                          n_nodes / len(values[topo]) if topo in values
                          else 0.0)
                adds += count * domain
    return {"term_rows": rows,
            "labels_per_term": labels / rows if rows else 0.0,
            "matched_node_adds_per_pod": adds}


def least_seconds(batch: int, nodes: int, rounds: float, flops_per_s: float,
                  bytes_per_s: float, resident_pods: int,
                  incoming_terms: bool, term_rows: int,
                  labels_per_term: float = 1.0,
                  matched_node_adds: float = 0.0) -> Dict[str, float]:
    """The least time the chip could take for the auction WITH the
    existing terms, and which bound sets it."""
    term_ops = ops(batch, term_rows, labels_per_term, matched_node_adds,
                   rounds)
    n_ops = term_ops + auction.ops(batch, nodes, rounds, resident_pods,
                                   incoming_terms)
    n_bytes = (auction.bytes_moved(batch, nodes, rounds, resident_pods,
                                   incoming_terms)
               + bytes_moved(term_rows, matched_node_adds, rounds))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "term_ops": term_ops}

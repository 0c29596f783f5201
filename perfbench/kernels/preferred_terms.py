"""Operations and bytes the INCOMING pods' preferred pod (anti-)affinity
terms add to one gang-auction cycle, from shapes.  With ``auction.py``'s
count for the rounds and ``existing_terms.py``'s for the bound pods' own
terms it is the yardstick of ``auction_prefscore_roofline``.

``auction.py`` counts the plain auction, ``existing_terms.py`` what the
bound pods' terms cost against the batch.  A batch whose pods carry
PREFERRED terms (upstream's SchedulingPreferredPodAffinity row: one
hostname term a pod, selecting every pod of the row) adds what ANY
implementation of InterPodAffinity's score has to do for them
(``scoring.go`` processExistingPod, the incoming pod's half), over valid
terms and bound pods, never the buckets they are padded to:

  once a cycle      the match of each pod's each preferred term against
                    each bound pod: one compare and one ``and`` for each
                    label the selector names, one compare for the
                    namespace: 3 operations a (term, bound pod) pair for
                    upstream's one-label selector.
  once a round      for each still-unassigned pod, each of its terms and
                    each bound pod the term MATCHED, and only those, one
                    add of the weight for each node of the bound pod's
                    domain of the term's key: ONE node for the hostname
                    key.  A pair that did not match touches no node.

"Still unassigned" is counted as ``spread.py`` counts it, the LEAST any
run of R rounds over B pods can have, B + R(R-1)/2, with R the
program's own round count (meta ``auction_rounds``): the share compares
runs only at equal rounds, beside ``auction_rounds_per_cycle.sat``.  The
pods the batch admits in earlier rounds become bound pods for the later
ones; they are left out (a floor: at most B more bound pods among
thousands).  NormalizeScore's maximum, minimum and quotient over the
feasible nodes are 4 operations a (pod, node) and round: counted, since
no implementation can skip them once anything was counted.

Bytes, once a cycle: the bound pods' label ids and node rows (3 words a
pod, as ``auction.py`` has them for a term) and the term rows (label and
value id, namespace id, topology key, weight: 5 words a term).  The
per-node sums need not leave the chip, on either side: of
``existing_terms.py``'s bytes this file takes the term rows alone and
not its two words a matched pair and round, which in a row where EVERY
pair matches (6,024 x 1,024 a round) would be 98 MB that no
implementation has to move.

Everything comes from the configuration file, the traffic's resident
bound and the cycle's round count, nothing from the program's shapes;
nothing is counted twice (``auction.py`` is asked for the plain auction,
WITHOUT its own term count, which is this file's first line) and nothing
an implementation could skip, so the share cannot pass 100%.  A fourth
file beside ``auction.py``, ``existing_terms.py`` and ``spread.py`` for
the reason they are three: ``kernels/auction.py`` may not be edited by
the PR that adds a row (PERF.md, section 7 (iii)).
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction, existing_terms, spread

MATCH_OPS_PER_LABEL = existing_terms.MATCH_OPS_PER_LABEL
MATCH_OPS_NAMESPACE = existing_terms.MATCH_OPS_NAMESPACE
NORMALIZE_OPS_PER_NODE = 4      # running max, running min, subtract, divide
POD_ROW_WORDS = 3
TERM_ROW_WORDS = 5
PREFERRED = ("aff_preferred", "anti_preferred")


def ops(batch: int, nodes: int, rounds: float, bound_pods: int,
        terms_per_pod: float, labels_per_term: float = 1.0,
        matched_node_adds_per_pod: float = 0.0) -> float:
    """Operations the incoming pods' preferred terms add to one cycle.
    ``matched_node_adds_per_pod``: for ONE pod of the batch, over its
    terms and the bound pods each matched, the nodes of the bound pod's
    domain."""
    if not terms_per_pod:
        return 0.0
    per_pair = MATCH_OPS_PER_LABEL * labels_per_term + MATCH_OPS_NAMESPACE
    proposing = spread.pod_rounds(batch, rounds)
    return (float(batch) * terms_per_pod * bound_pods * per_pair
            + proposing * matched_node_adds_per_pod
            + proposing * nodes * NORMALIZE_OPS_PER_NODE)


def bytes_moved(batch: int, bound_pods: int, terms_per_pod: float) -> float:
    if not terms_per_pod:
        return 0.0
    return 4.0 * (POD_ROW_WORDS * bound_pods
                  + TERM_ROW_WORDS * batch * terms_per_pod)


def shapes_of(config: Dict[str, Any], n_nodes: int, resident_bound: int,
              world) -> Dict[str, float]:
    """From the configuration alone, for ONE pod of the measured
    template: its preferred terms, the mean labels a term names, and the
    nodes its matched (term, bound pod) pairs reach, the bound pods being
    the init pods and ``resident_bound`` measured ones.  ``world`` is
    ``perfbench.lib.world``."""
    measured = world.measured_record(config, "measured", 0)
    terms = [(topo, tuple(sel)) for field in PREFERRED
             for _w, topo, sel in getattr(measured, field)]
    groups = list(world.init_groups(config)) + [
        (config["measured_pods"]["template"], int(resident_bound))]
    values = world.node_label_values(config)
    adds = 0.0
    for template, count in groups:
        bound = world.pod_record(config, template, "init", 0)
        for topo, sel in terms:
            if all(bound.labels.get(k) == v for k, v in sel):
                adds += count * (1.0 if topo == world.HOSTNAME else
                                 n_nodes / len(values[topo])
                                 if topo in values else 0.0)
    return {"terms_per_pod": float(len(terms)),
            "labels_per_term": (sum(len(sel) for _t, sel in terms)
                                / len(terms) if terms else 0.0),
            "matched_node_adds_per_pod": adds}


def least_seconds(batch: int, nodes: int, rounds: float, flops_per_s: float,
                  bytes_per_s: float, bound_pods: int,
                  preferred: Dict[str, float],
                  existing: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip could take for the auction WITH the
    incoming pods' preferred terms and the bound pods' own terms, and
    which bound sets it.  ``preferred``: ``shapes_of`` here;
    ``existing``: ``existing_terms.shapes_of``."""
    pref_ops = ops(batch, nodes, rounds, bound_pods, **preferred)
    exist_adds = batch * existing["matched_node_adds_per_pod"]
    n_ops = (auction.ops(batch, nodes, rounds) + pref_ops
             + existing_terms.ops(batch, int(existing["term_rows"]),
                                  existing["labels_per_term"], exist_adds,
                                  rounds))
    n_bytes = (auction.bytes_moved(batch, nodes, rounds)
               + bytes_moved(batch, bound_pods, preferred["terms_per_pod"])
               + existing_terms.bytes_moved(int(existing["term_rows"])))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "preferred_ops": pref_ops}

"""Operations and bytes the INCOMING pods' REQUIRED pod-affinity terms,
and the bound pods' own required terms as score rows, add to one
gang-auction cycle, from shapes.  With ``auction.py``'s count for ONE
round it is the yardstick of ``auction_reqaffinity_roofline``.

A batch whose every pod carries a required affinity term (upstream's
SchedulingPodAffinity row: one zone-keyed term a pod, selecting every
pod of the row) asks of ANY implementation of InterPodAffinity
(``filtering.go`` satisfyPodAffinity, ``scoring.go`` processExistingPod),
over valid terms and bound pods, never the buckets they are padded to:

  the match        each pod's each required term against each bound
                   pod: one compare and one ``and`` for each label the
                   selector names, one compare for the namespace: 3
                   operations a (term, bound pod) pair for upstream's
                   one-label selector;
  the count        for each (term, bound pod) pair that MATCHED, one add
                   into the bound pod's node's (key, value) pair;
  the verdict      one read of that pair's count and its compare with
                   zero for each (pod, term, node): 1 operation;
  the score rows   every bound pod's required term is a score row at
                   ``hardPodAffinityWeight``: its match against each pod
                   of the batch (3 operations a pair), one add of the
                   weight into the owner's pair for each pair that
                   matched, and NormalizeScore's maximum, minimum,
                   subtraction and quotient, 4 a (pod, node).

ALL OF IT ONCE A CYCLE, whatever the program's round count: the matches
do not change inside a cycle, a placement only ever adds to a pair's
count, and a program that needs more rounds for the same placements is
doing more than the row asks.  So the plain auction is asked for ONE
round too (``auction.ops(batch, nodes, 1)``), and the share reads the
same work whatever implements it: a change that halves the rounds
doubles no yardstick (``auction_spread_roofline``'s per-round count is
what the driver's note at PR 43 is about).  The pods the batch admits
become bound pods for the later ones of the same cycle; they are left
out (a floor: at most B more among thousands).

Bytes, once a cycle: the bound pods' label ids and node rows (3 words a
pod), the incoming term rows (5 words) and the bound pods' score rows (7
words, as ``existing_terms.py`` has them), and the plain auction's one
round.  No [pods, nodes] plane need leave the chip.

Everything comes from the configuration file and the cycle's own pod
count; nothing is counted twice and nothing an implementation could
skip, so the share cannot pass 100%.  A file of its own beside
``auction.py``, ``existing_terms.py`` and ``preferred_terms.py``: the PR
that adds a row edits no file of the benchmark.
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction, existing_terms, preferred_terms

MATCH_OPS_PER_LABEL = existing_terms.MATCH_OPS_PER_LABEL
MATCH_OPS_NAMESPACE = existing_terms.MATCH_OPS_NAMESPACE
VERDICT_OPS_PER_NODE = 1
NORMALIZE_OPS_PER_NODE = preferred_terms.NORMALIZE_OPS_PER_NODE
POD_ROW_WORDS = preferred_terms.POD_ROW_WORDS
TERM_ROW_WORDS = preferred_terms.TERM_ROW_WORDS
SCORE_ROW_WORDS = existing_terms.TERM_ROW_WORDS


def ops(batch: int, nodes: int, bound_pods: int, terms_per_pod: float,
        labels_per_term: float = 1.0, matched_per_term: float = 0.0,
        score_rows: float = 0.0, score_labels_per_row: float = 1.0,
        score_rows_matched: float = 0.0) -> float:
    """Operations the required affinity terms add to one cycle.
    ``matched_per_term``: the bound pods ONE incoming term matches;
    ``score_rows``: the bound pods' required terms; ``score_rows_matched``:
    those of them that select one incoming pod."""
    if not terms_per_pod:
        return 0.0
    terms = float(batch) * terms_per_pod
    per_pair = MATCH_OPS_PER_LABEL * labels_per_term + MATCH_OPS_NAMESPACE
    per_row = (MATCH_OPS_PER_LABEL * score_labels_per_row
               + MATCH_OPS_NAMESPACE)
    scored = (float(batch) * (score_rows * per_row + score_rows_matched
                              + nodes * NORMALIZE_OPS_PER_NODE)
              if score_rows else 0.0)
    return (terms * bound_pods * per_pair + terms * matched_per_term
            + terms * nodes * VERDICT_OPS_PER_NODE + scored)


def bytes_moved(batch: int, bound_pods: int, terms_per_pod: float,
                score_rows: float = 0.0) -> float:
    if not terms_per_pod:
        return 0.0
    return 4.0 * (POD_ROW_WORDS * bound_pods
                  + TERM_ROW_WORDS * batch * terms_per_pod
                  + SCORE_ROW_WORDS * score_rows)


def shapes_of(config: Dict[str, Any], resident_bound: int,
              world) -> Dict[str, float]:
    """From the configuration alone, for ONE pod of the measured
    template: its required affinity terms, the mean labels a term names,
    the bound pods one term matches (the init pods and ``resident_bound``
    measured ones), and the bound pods' own required affinity terms with
    how many of them select the pod.  ``world`` is
    ``perfbench.lib.world``."""
    measured = world.measured_record(config, "measured", 0)
    terms = [tuple(sel) for _topo, sel in measured.aff_required]
    groups = list(world.init_groups(config)) + [
        (config["measured_pods"]["template"], int(resident_bound))]
    matched = rows = row_labels = rows_matched = 0.0
    for template, count in groups:
        bound = world.pod_record(config, template, "init", 0)
        for sel in terms:
            if all(bound.labels.get(k) == v for k, v in sel):
                matched += count
        for _topo, sel in bound.aff_required:
            rows += count
            row_labels += count * len(sel)
            if all(measured.labels.get(k) == v for k, v in sel):
                rows_matched += count
    return {"terms_per_pod": float(len(terms)),
            "labels_per_term": (sum(len(sel) for sel in terms) / len(terms)
                                if terms else 0.0),
            "matched_per_term": matched / len(terms) if terms else 0.0,
            "score_rows": rows,
            "score_labels_per_row": row_labels / rows if rows else 0.0,
            "score_rows_matched": rows_matched}


def least_seconds(batch: int, nodes: int, flops_per_s: float,
                  bytes_per_s: float, bound_pods: int,
                  shapes: Dict[str, float]) -> Dict[str, float]:
    """The least time the chip could take for ONE cycle's auction WITH
    the required affinity terms, and which bound sets it.  ``shapes``:
    ``shapes_of`` here."""
    term_ops = ops(batch, nodes, bound_pods, **shapes)
    n_ops = auction.ops(batch, nodes, 1) + term_ops
    n_bytes = (auction.bytes_moved(batch, nodes, 1)
               + bytes_moved(batch, bound_pods, shapes["terms_per_pod"],
                             shapes["score_rows"]))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "required_ops": term_ops}

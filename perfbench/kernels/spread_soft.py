"""Operations and bytes a ``ScheduleAnyway`` topology spread constraint
adds to one gang-auction cycle, from shapes.  With ``auction.py``'s count
it is the yardstick of ``auction_softspread_roofline``.

``auction.py`` counts the plain auction, ``spread.py`` what a
``DoNotSchedule`` constraint's filter costs.  A batch whose pods carry
``ScheduleAnyway`` constraints (upstream's PreferredTopologySpreading
row: one zone constraint a pod) adds what ANY implementation of
PodTopologySpread's score (``scoring.go``: PreScore, Score,
NormalizeScore) has to do for them, over VALID rows and never the
buckets they are padded to:

  once a cycle      the match of each valid constraint's selector and
                    namespace against each countable pod row (the bound
                    pods and the batch's own, which are counted once
                    admitted): one compare and one ``and`` for each
                    label the selector names, one compare for the
                    namespace: 3 operations a (constraint, pod) pair for
                    upstream's one-label selector (as ``spread.py``);
                    for each newly admitted pod and constraint, one add
                    onto its pair's count.
  once a round      for each still-unassigned pod and valid constraint:
                    the topology size (one add a pair of the key that a
                    filtered node carries) and its logarithm; for each
                    node the pair's count read and held against maxSkew
                    (one compare), multiplied by the weight and added to
                    the node's sum: 3 operations a (pod, constraint,
                    node); for each (pod, node): the truncation and
                    NormalizeScore's running maximum, running minimum,
                    max + min - s and the quotient: 5.

"Still unassigned" is counted as ``spread.py`` counts it, the LEAST any
run of R rounds over B pods can have, B + R(R-1)/2, with R the program's
own round count (meta ``auction_rounds``): the share compares runs only
at equal rounds, beside ``auction_rounds_per_cycle.sat``.

Bytes, once a cycle: the countable pods' label ids and node rows (3
words a pod), the constraint rows (label and value id, namespace id,
topology key, maxSkew: 5 words) and the nodes' pair ids (1 word a node
and key); per round the pairs' counts read and written (2 words a pair
and key).  The per-node sums and scores need not leave the chip.

Everything comes from the configuration file, the traffic's resident
bound and the cycle's round count, nothing from the program's shapes;
nothing is counted twice and nothing an implementation could skip, so
the share cannot pass 100%.  A fifth file beside ``auction.py``,
``existing_terms.py``, ``spread.py`` and ``preferred_terms.py`` for the
reason they are four: ``kernels/auction.py`` may not be edited by the
PR that adds a row (PERF.md, section 7 (iii)).
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction, spread

MATCH_OPS_PER_LABEL = spread.MATCH_OPS_PER_LABEL
MATCH_OPS_NAMESPACE = spread.MATCH_OPS_NAMESPACE
SCORE_OPS_PER_NODE = 3          # against maxSkew, times the weight, add
NORMALIZE_OPS_PER_NODE = 5      # truncate, max, min, max + min - s, divide
POD_ROW_WORDS = spread.POD_ROW_WORDS
CONSTRAINT_ROW_WORDS = spread.CONSTRAINT_ROW_WORDS
SCHEDULE_ANYWAY = "ScheduleAnyway"


def ops(batch: int, nodes: int, rounds: float, countable_pods: int,
        constraints_per_pod: float, labels_per_selector: float = 1.0,
        pairs: float = 1.0) -> float:
    """Operations the soft constraints add to one cycle."""
    c = float(constraints_per_pod)
    if not c:
        return 0.0
    per_pair = MATCH_OPS_PER_LABEL * labels_per_selector + MATCH_OPS_NAMESPACE
    once = float(batch) * c * (countable_pods * per_pair + 1.0)
    proposing = spread.pod_rounds(batch, rounds)
    per_round = proposing * (c * (SCORE_OPS_PER_NODE * nodes + pairs + 1.0)
                             + NORMALIZE_OPS_PER_NODE * nodes)
    return once + per_round


def bytes_moved(batch: int, nodes: int, rounds: float, countable_pods: int,
                constraints_per_pod: float, pairs: float = 1.0,
                keys: float = 1.0) -> float:
    c = float(constraints_per_pod)
    if not c:
        return 0.0
    return 4.0 * (POD_ROW_WORDS * countable_pods
                  + CONSTRAINT_ROW_WORDS * batch * c + nodes * keys
                  + 2.0 * pairs * keys * float(rounds))


def shapes_of(config: Dict[str, Any], world) -> Dict[str, float]:
    """From the configuration alone: the ``ScheduleAnyway`` constraints
    one measured pod carries, the mean labels a selector names, the
    distinct topology keys and the mean pairs (label values) a key has on
    the nodes.  ``world`` is ``perfbench.lib.world``."""
    measured = world.measured_record(config, "measured", 0)
    soft = [c for c in measured.spread if c[2] == SCHEDULE_ANYWAY]
    values = world.node_label_values(config)
    keys = sorted({c[1] for c in soft})
    n = len(soft)
    return {"constraints_per_pod": float(n),
            "labels_per_selector": (sum(len(c[3]) for c in soft) / n
                                    if n else 0.0),
            "keys": float(len(keys)),
            "pairs": (sum(len(values.get(k, ())) for k in keys) / len(keys)
                      if keys else 0.0)}


def least_seconds(batch: int, nodes: int, rounds: float, flops_per_s: float,
                  bytes_per_s: float, resident_pods: int,
                  constraints_per_pod: float,
                  labels_per_selector: float = 1.0, pairs: float = 1.0,
                  keys: float = 1.0) -> Dict[str, float]:
    """The least time the chip could take for the auction WITH the soft
    constraints' score, and which bound sets it."""
    countable = int(resident_pods) + int(batch)
    soft_ops = ops(batch, nodes, rounds, countable, constraints_per_pod,
                   labels_per_selector, pairs)
    n_ops = soft_ops + auction.ops(batch, nodes, rounds)
    n_bytes = (auction.bytes_moved(batch, nodes, rounds)
               + bytes_moved(batch, nodes, rounds, countable,
                             constraints_per_pod, pairs, keys))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "soft_spread_ops": soft_ops}

"""Operations and bytes a hard topology spread constraint adds to one
gang-auction cycle, from shapes.  With ``auction.py``'s count it is the
yardstick of ``auction_spread_roofline``.

``auction.py`` counts the plain auction (and an incoming pod's required
anti-affinity term).  A batch whose pods carry ``DoNotSchedule``
constraints (upstream's TopologySpreading row: one zone constraint a
pod) adds what ANY implementation of PodTopologySpread's filter has to
do for them, over VALID rows and never the buckets they are padded to:

  once a cycle      the match of each valid constraint's selector and
                    namespace against each countable pod row (the bound
                    pods and the batch's own, which are counted once
                    admitted): one compare and one ``and`` for each
                    label the selector names, one compare for the
                    namespace.  Upstream's template names one label: 3
                    operations a (constraint, pod) pair.
  once a round      for each still-unassigned pod and valid constraint:
                    the minimum over the key's registered pairs (pairs -
                    1 compares), and for each node the skew test: the
                    count of the node's pair read, the self match
                    added, the minimum subtracted, the compare with
                    maxSkew and the ``and`` into the pod's feasible set:
                    5 operations a (pod, constraint, node);
                    for each newly admitted pod and constraint shape it
                    matches, one add onto its pair's count.

"Still unassigned" is counted as the LEAST any run of R rounds over B
pods can have: a round admits at least one pod, so the pods proposing in
the rounds sum to at least B + R(R-1)/2 (everything admitted in the
first round but one pod for each later round).  A run that admits a few
pods every round, as this row's does, evaluates about B(R+1)/2: the
count is a floor, not this program's profile.

R is the PROGRAM'S OWN round count (meta ``auction_rounds``), as in
``auction.py``: a program that admits more pods a round runs fewer
rounds and its least time falls with them.  So the share compares runs
only at equal rounds, and ``auction_rounds_per_cycle.sat`` is read
beside it.

Bytes, once a cycle: the countable pods' label ids and node rows (3
words a pod, as ``auction.py`` has them for a term), the constraint rows
(label and value id, namespace id, topology key, maxSkew: 5 words) and
the nodes' pair ids (1 word a node and key); per round the pairs' counts
read and written (2 words a pair and constraint shape).

Nothing is counted twice and nothing that an implementation could skip,
so the share cannot pass 100%.  A third file beside ``auction.py`` and
``existing_terms.py`` only because ``kernels/auction.py`` may not be
edited by the PR that adds a row (PERF.md, section 7 (iii), asks a
``benchmark`` issue to fold the three).
"""

from __future__ import annotations

from typing import Any, Dict

from . import auction

MATCH_OPS_PER_LABEL = 2         # the label's compare and its ``and``
MATCH_OPS_NAMESPACE = 1
SKEW_OPS_PER_NODE = 5           # read, + self, - min, compare, and
POD_ROW_WORDS = 3
CONSTRAINT_ROW_WORDS = 5
DO_NOT_SCHEDULE = "DoNotSchedule"


def pod_rounds(batch: int, rounds: float) -> float:
    """The least sum, over ``rounds`` rounds, of the pods still
    unassigned at each round's start, ``batch`` pods in all."""
    r = max(float(rounds), 1.0)
    return float(batch) + r * (r - 1.0) / 2.0


def ops(batch: int, nodes: int, rounds: float, countable_pods: int,
        constraints_per_pod: float, labels_per_selector: float = 1.0,
        pairs: float = 1.0) -> float:
    """Operations the hard constraints add to one cycle."""
    c = float(constraints_per_pod)
    per_pair = MATCH_OPS_PER_LABEL * labels_per_selector + MATCH_OPS_NAMESPACE
    once = float(batch) * c * countable_pods * per_pair
    per_round = pod_rounds(batch, rounds) * c * (
        SKEW_OPS_PER_NODE * nodes + max(pairs - 1.0, 0.0))
    return once + per_round + float(batch) * c


def bytes_moved(batch: int, nodes: int, rounds: float, countable_pods: int,
                constraints_per_pod: float, pairs: float = 1.0,
                keys: float = 1.0) -> float:
    c = float(constraints_per_pod)
    return 4.0 * (POD_ROW_WORDS * countable_pods
                  + CONSTRAINT_ROW_WORDS * batch * c + nodes * keys
                  + 2.0 * pairs * keys * float(rounds))


def shapes_of(config: Dict[str, Any], world) -> Dict[str, float]:
    """From the configuration alone: the valid ``DoNotSchedule``
    constraints one measured pod carries, the mean labels a selector
    names, the distinct topology keys and the mean pairs (label values) a
    key has on the nodes.  ``world`` is ``perfbench.lib.world``."""
    measured = world.measured_record(config, "measured", 0)
    hard = [c for c in measured.spread if c[2] == DO_NOT_SCHEDULE]
    values = world.node_label_values(config)
    keys = sorted({c[1] for c in hard})
    n = len(hard)
    return {"constraints_per_pod": float(n),
            "labels_per_selector": (sum(len(c[3]) for c in hard) / n
                                    if n else 0.0),
            "keys": float(len(keys)),
            "pairs": (sum(len(values.get(k, ())) for k in keys) / len(keys)
                      if keys else 0.0)}


def least_seconds(batch: int, nodes: int, rounds: float, flops_per_s: float,
                  bytes_per_s: float, resident_pods: int,
                  constraints_per_pod: float,
                  labels_per_selector: float = 1.0, pairs: float = 1.0,
                  keys: float = 1.0) -> Dict[str, float]:
    """The least time the chip could take for the auction WITH the hard
    constraints, and which bound sets it."""
    countable = int(resident_pods) + int(batch)
    spread_ops = ops(batch, nodes, rounds, countable, constraints_per_pod,
                     labels_per_selector, pairs)
    n_ops = spread_ops + auction.ops(batch, nodes, rounds)
    n_bytes = (auction.bytes_moved(batch, nodes, rounds)
               + bytes_moved(batch, nodes, rounds, countable,
                             constraints_per_pod, pairs, keys))
    t_ops, t_bytes = n_ops / flops_per_s, n_bytes / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes,
            "spread_ops": spread_ops}

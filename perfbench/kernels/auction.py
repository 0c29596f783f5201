"""Operations and bytes one gang-auction cycle must touch, from shapes
and the number of rounds run.  The yardstick of ``auction_roofline``.

The auction (kubetpu/models/gang.py) places a batch of B pending pods on
N nodes in rounds: every round each still-unplaced pod proposes to its
best feasible node, every node admits the proposals that fit, and the
placed pods' requests are added to the nodes.  What ANY implementation of
that algorithm has to do, counted per (pod, node) pair and round, with R
resource channels (cpu, memory, ephemeral storage, pod count = 4 for the
templates here):

  NodeResourcesFit          R compares of request + used against
                            allocatable and R-1 ands            2R - 1
  NodeResourcesLeastAllocated  per resource (cpu, memory): subtract,
                            multiply by 100, divide; then add and halve
                                                                 8
  NodeResourcesBalancedAllocation  two divisions, subtract, abs,
                            subtract from 1, multiply, truncate  7
  weighted sum of the two varying scores and the mask            3
  running maximum with its index (the proposal)                  2

  = 2R + 19 operations; the seven other default score plugins are the
  same on every node for these templates and cost nothing per pair.

A batch whose pods carry a required anti-affinity term adds, ONCE per
cycle, the match of each pod's selector against the P resident pods'
labels (compare, and, accumulate onto the pod's node: 3 operations per
pod pair) and, per round, the same against the B pods of the batch
placed so far (3 per pair).

Bytes per round: the node tables that change between rounds have to be
read again (used and allocatable, N x R float32 each), the pods' requests
(B x R) read, and one proposal per pod (node index, score, flag)
written.  The B x N feasibility and score planes need not leave the
chip.  Once per cycle with terms: the P resident pods' label ids
(P x 2 int32: two labels a pod) and node rows (P int32).

Nothing is counted twice and nothing that an implementation could skip,
so the share of the roofline cannot pass 100%.  It says which bound
holds; on a v5e it is the operations (the model's arithmetic intensity
is far above 197e12 / 819e9 = 240 operations a byte).
"""

from __future__ import annotations

from typing import Dict

R_CHANNELS = 4
OPS_PER_PAIR = 2 * R_CHANNELS + 19
TERM_OPS_PER_PAIR = 3


def ops(batch: int, nodes: int, rounds: int, resident_pods: int = 0,
        terms: bool = False) -> float:
    """Operations one cycle must perform."""
    n = float(batch) * nodes * OPS_PER_PAIR * rounds
    if terms:
        n += float(batch) * resident_pods * TERM_OPS_PER_PAIR
        n += float(batch) * batch * TERM_OPS_PER_PAIR * rounds
    return n


def bytes_moved(batch: int, nodes: int, rounds: int, resident_pods: int = 0,
                terms: bool = False) -> float:
    """Bytes one cycle must move to and from device memory."""
    per_round = 4.0 * (2 * nodes * R_CHANNELS + batch * R_CHANNELS
                       + 3 * batch)
    n = per_round * rounds
    if terms:
        n += 4.0 * 3 * resident_pods
    return n


def least_seconds(batch: int, nodes: int, rounds: int, flops_per_s: float,
                  bytes_per_s: float, resident_pods: int = 0,
                  terms: bool = False) -> Dict[str, float]:
    """The least time the chip could take, and which bound sets it."""
    t_ops = ops(batch, nodes, rounds, resident_pods, terms) / flops_per_s
    t_bytes = bytes_moved(batch, nodes, rounds, resident_pods,
                          terms) / bytes_per_s
    return {"seconds": max(t_ops, t_bytes),
            "bound": "operations" if t_ops >= t_bytes else "bytes",
            "ops_seconds": t_ops, "bytes_seconds": t_bytes}

"""Bytes the cluster delta of one cycle must move, from the rows that
changed.  The yardstick of ``delta_apply_roofline``.

Between two cycles the resident cluster tensors are brought up to date
by one scatter program (kubetpu/models/programs.py
``_apply_cluster_delta``) over the rows of the cycle's dirty nodes and of
the pods on them (cycle meta ``delta_rows`` = node rows + pod rows; the
``delta-build`` span's ``node_rows_dirty`` says how many are node rows).
What ANY way of keeping the cluster resident has to move for a row that
changed, in (read from the update) and out (written to the resident
tensors), in 4-byte words:

  a pod row     its label ids (one word a label the template states),
                the row of its node (1 word) and its two flags (valid,
                terminating: 1 word together)
  a node row    its R resource channels requested (cpu, memory,
                ephemeral storage, pod count = 4 for the templates here)
                and its label ids (one word a label)

and one compare-and-select a word written.  Not counted, because an
implementation could skip it: the dense ``[rows, L]`` label one-hots the
program densifies the ids into, the key masks, namespaces, taints, ports,
images, zones and allocatable of a row (a pod's coming or going moves
none of them), every row of a dirty node that did not itself change, the
pads up to the pow2 bucket, and the resident tensors' untouched rows.

A program that does not say how ``delta_rows`` splits is counted as if
every row were of the cheaper kind.  Nothing is counted twice and
nothing that could be skipped, so the share cannot pass 100%; it is bound
by bytes (one operation a word is far under 197e12 / 819e9 = 240 a
byte).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

# the scatter program's name in a capture's "XLA Modules" line
# (kubetpu/models/programs.py: jit(_apply_cluster_delta))
DELTA_PROGRAM = "apply_cluster_delta"
R_CHANNELS = 4
WORD = 4
POD_ROW_FIXED_WORDS = 2          # node row; valid + terminating
OPS_PER_WORD = 1                 # the compare-and-select of the write


def pod_row_bytes(labels_per_pod: int) -> int:
    """Bytes in and out for one pod row."""
    return 2 * WORD * (int(labels_per_pod) + POD_ROW_FIXED_WORDS)


def node_row_bytes(labels_per_node: int) -> int:
    """Bytes in and out for one node row."""
    return 2 * WORD * (R_CHANNELS + int(labels_per_node))


def bytes_moved(delta_rows: float, labels_per_pod: int,
                labels_per_node: int,
                node_rows: Optional[float] = None) -> float:
    """Bytes one cycle's delta must move.  ``node_rows``: how many of
    ``delta_rows`` are node rows; None counts every row as the cheaper
    kind."""
    pod_b, node_b = pod_row_bytes(labels_per_pod), node_row_bytes(
        labels_per_node)
    if node_rows is None:
        return float(delta_rows) * min(pod_b, node_b)
    node_rows = min(max(float(node_rows), 0.0), float(delta_rows))
    return node_rows * node_b + (float(delta_rows) - node_rows) * pod_b


def least_seconds(delta_rows: float, labels_per_pod: int,
                  labels_per_node: int, flops_per_s: float,
                  bytes_per_s: float,
                  node_rows: Optional[float] = None) -> Dict[str, Any]:
    b = bytes_moved(delta_rows, labels_per_pod, labels_per_node, node_rows)
    n = OPS_PER_WORD * b / (2 * WORD)        # one a word written
    by_ops, by_bytes = n / flops_per_s, b / bytes_per_s
    return {"ops": n, "bytes": b, "seconds": max(by_ops, by_bytes),
            "bound": "operations" if by_ops >= by_bytes else "bytes"}


def shapes_of(config: Dict[str, Any], world) -> Dict[str, int]:
    """``labels_per_pod`` / ``labels_per_node`` of a configuration file's
    measured template and nodes, through ``lib/world.py``'s own records
    (so the shorthand's two labels and a literal template's are counted
    the same way)."""
    pod = world.measured_record(config, "measured", 0)
    node = world.node_records(dict(config, cluster=dict(
        config["cluster"], nodes=1)))[0]
    return {"labels_per_pod": len(pod.labels),
            "labels_per_node": len(node.labels)}

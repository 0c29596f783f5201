"""``interpod_terms`` with one more control: ``no_batch_score_terms``, the
reference's counterpart of ``perfbench/controls/no-batch-score-terms.py``.

Under it a pod admitted INSIDE the auction is added to the cluster with
its labels and its requests but without its score-side terms (preferred
affinity and anti-affinity, required affinity): a later round's pod
still counts it through its own terms and is not counted by the admitted
pod's, 2c + k where upstream's serial loop reads 2c + 2k.  Required
anti-affinity terms, which filter, stay.  Everything else is
``interpod_terms``', by import; a cluster built for the check
(``lib/check.check_cluster`` with this module as the reference) places
its residents with the switch off, as ``interpod_terms`` does.

Not a configuration's reference: ``perfbench/tools/batch_terms_control.py``
runs it, judged by the configuration's own ``gang_misses``.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Sequence

from perfbench.reference import interpod_terms as _terms

SCORE_SIDE = ("aff_preferred", "anti_preferred", "aff_required")


class Cluster(_terms.Cluster):
    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.batch_blind = False

    def add(self, pod, node: str) -> None:
        if self.batch_blind:
            pod = dataclasses.replace(pod, **{f: () for f in SCORE_SIDE})
        super().add(pod, node)


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     no_batch_score_terms: bool = False,
                     **controls) -> Dict[str, str]:
    """``interpod_terms.auction_schedule``; with ``no_batch_score_terms``
    the pods it admits score nobody.  Mutates ``cluster``."""
    cluster.batch_blind = bool(no_batch_score_terms)
    try:
        return _terms.auction_schedule(cluster, pods, rng, **controls)
    finally:
        cluster.batch_blind = False


gang_misses = _terms.gang_misses
replay = _terms.replay

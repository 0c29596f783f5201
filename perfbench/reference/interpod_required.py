"""``interpod_terms`` with one more control: ``no_required_affinity``, the
reference's counterpart of ``perfbench/controls/no-required-affinity.py``.

Under it every node satisfies the INCOMING pod's required pod-affinity
terms (``filtering.go`` satisfyPodAffinity taken to be true, the
bootstrap rule with it): the pod's required anti-affinity, the existing
pods' required anti-affinity and every score, the existing pods'
required affinity terms at ``hardPodAffinityWeight`` included, stay as
they are.  It is the scheduler as it would be if the incoming side of
InterPodAffinity's affinity filter admitted everything.

Everything else is ``interpod_terms``', by import and unedited (the PR
that adds a row edits no file of the benchmark); with the switch off
this file IS ``interpod_terms``, so ``sp-podaffinity-5000`` names it as
its ``reference`` and the row's check (b), ``tools/control.py`` and
``tools/cell_controls.py`` find the switch where they look for it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence

from perfbench.reference import interpod_terms as _terms


class Cluster(_terms.Cluster):
    def __init__(self, nodes: Sequence[Any]):
        super().__init__(nodes)
        self.no_required_affinity = False

    def terms_ok(self, pod, row: Optional[int] = None):
        if self.no_required_affinity and pod.aff_required:
            pod = dataclasses.replace(pod, aff_required=())
        return super().terms_ok(pod, row)


def auction_schedule(cluster: Cluster, pods: Sequence[Any], rng,
                     no_required_affinity: bool = False,
                     **controls) -> Dict[str, str]:
    """``interpod_terms.auction_schedule``; with ``no_required_affinity``
    no node is refused for an incoming pod's required affinity term.
    Mutates ``cluster``."""
    cluster.no_required_affinity = bool(no_required_affinity)
    try:
        return _terms.auction_schedule(cluster, pods, rng, **controls)
    finally:
        cluster.no_required_affinity = False


gang_misses = _terms.gang_misses
# check (a) builds its own ``interpod_terms`` cluster: the switch is
# never on there
replay = _terms.replay

"""What a gang cycle says about which of its batch's term sets are live
(PR 37): the cycle's meta ``term_sets_live`` and the ``batch-build``
span's argument of the same name list exactly the sets with a valid row —
the sets whose existing-pod products the auction's kernels do not gate off
(kubetpu/ops/kernels.py ``_if_live``; the host's reading of the same
predicates is kubetpu/models/batch.py ``live_term_sets``)."""

import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.harness import hollow
from kubetpu.models.batch import live_term_sets
from tests.test_spread_trace_meta import _cycle_of


def _pod(i, **labels):
    return hollow.make_pod(f"p-{i}", labels=labels or {"color": "blue"})


def _preferring(p):
    p.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
        preferred_during_scheduling_ignored_during_execution=[
            api.WeightedPodAffinityTerm(
                weight=3, pod_affinity_term=api.PodAffinityTerm(
                    label_selector=api.LabelSelector(
                        match_labels={"color": "blue"}),
                    topology_key=api.LABEL_ZONE))]))
    return p


BLUE_SVC = api.Service(metadata=api.ObjectMeta(name="blue",
                                               namespace="default"),
                       selector={"color": "blue"})


@pytest.mark.parametrize("what,pods,services,want", [
    ("a plain batch", lambda: [_pod(i) for i in range(6)], (), []),
    ("hard zone constraints",
     lambda: [hollow.with_spread(_pod(i), api.LABEL_ZONE, max_skew=5)
              for i in range(6)], (), ["spread"]),
    ("soft constraints are their own set",
     lambda: [hollow.with_spread(_pod(i), api.LABEL_ZONE, max_skew=5,
                                 when="ScheduleAnyway") for i in range(6)],
     (), ["spread_soft"]),
    ("one pod of six with required anti-affinity",
     lambda: [hollow.with_anti_affinity(_pod(0, app="solo"))]
     + [_pod(i) for i in range(1, 6)], (), ["raa"]),
    ("required affinity that the pod's own labels bootstrap",
     lambda: [hollow.with_affinity(_pod(i)) for i in range(6)], (), ["ra"]),
    ("preferred terms", lambda: [_preferring(_pod(i)) for i in range(6)],
     (), ["pref"]),
    ("a Service selects the pods: the controller selectors count",
     lambda: [_pod(i) for i in range(6)], (BLUE_SVC,), ["default_spread"]),
    # six pods in a batch of eight: a padding row skips nothing, and has
    # no selector either
    ("pods with explicit constraints skip the controller selectors",
     lambda: [hollow.with_spread(_pod(i), api.LABEL_ZONE, max_skew=5)
              for i in range(6)], (BLUE_SVC,), ["spread"]),
    ("... the one pod of the batch without them does not",
     lambda: [hollow.with_spread(_pod(i), api.LABEL_ZONE, max_skew=5)
              for i in range(5)] + [_pod(5)], (BLUE_SVC,),
     ["spread", "default_spread"]),
    ("rows of three sets in one batch",
     lambda: [hollow.with_anti_affinity(_pod(0, app="solo")),
              hollow.with_spread(_pod(1), api.LABEL_ZONE, max_skew=5),
              _preferring(_pod(2)), _pod(3)], (),
     ["raa", "pref", "spread"])])
def test_a_gang_cycle_names_exactly_its_live_term_sets(what, pods, services,
                                                       want):
    rec = _cycle_of(pods(), services)
    assert rec["meta"]["term_sets_live"] == want
    build = [s for s in rec["spans"] if s["name"] == "batch-build"]
    assert len(build) == 1 and build[0]["args"]["term_sets_live"] == want


def test_live_term_sets_reads_the_predicates_the_kernels_gate_on():
    """Set by set on a built batch: a set is named iff its ``valid`` has a
    true row; the controller selectors iff some pod has a non-nil one and
    does not skip them."""
    from tests.test_kernels import GATE_SETS, _gate_world, _mask_set
    _, batch, _ = _gate_world()
    assert live_term_sets(batch) == list(GATE_SETS[:5])   # every pod skips
    for name in GATE_SETS:
        for rows, live in (("none", False), ("one", True), ("all", True)):
            got = live_term_sets(_mask_set(batch, name, rows))
            assert (name in got) == live, (name, rows)
            others = [s for s in GATE_SETS[:5] if s != name]
            assert [s for s in got if s in others] == others
    nil = batch._replace(
        spread_skip=np.zeros_like(batch.spread_skip),
        spread_selector=batch.spread_selector._replace(
            sel_valid=np.zeros_like(batch.spread_selector.sel_valid)))
    assert "default_spread" not in live_term_sets(nil)

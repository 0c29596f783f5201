"""Kernel golden tests: expectations derived from the reference plugins'
documented algorithms and unit-test tables (values computed independently
with integer arithmetic)."""
import jax.numpy as jnp
import numpy as np
import pytest

from kubetpu.api import types as api
from tests.harness import run_cluster
from tests.test_tensors import mknode, mkpod


def cpu_mem_pod(name, cpu, mem, **kw):
    return mkpod(name, cpu=cpu, mem=mem, **kw)


FIT_ONLY = ["NodeResourcesFit"]
LEAST = [("NodeResourcesLeastAllocated", 1)]
BALANCED = [("NodeResourcesBalancedAllocation", 1)]


class TestFit:
    def test_exact_fit_boundary(self):
        nodes = [mknode("n1", cpu="1", mem="1Gi")]
        existing = {"n1": [cpu_mem_pod("e1", "600m", "512Mi")]}
        r = run_cluster(nodes, existing, [cpu_mem_pod("p", "400m", "512Mi")],
                        filters=FIT_ONLY, scores=[])
        assert r.feasible[0, 0]  # exactly fits
        r = run_cluster(nodes, existing, [cpu_mem_pod("p", "401m", "512Mi")],
                        filters=FIT_ONLY, scores=[])
        assert not r.feasible[0, 0]

    def test_pod_count(self):
        nodes = [mknode("n1", pods="1")]
        existing = {"n1": [cpu_mem_pod("e1", "1m", "1Mi")]}
        r = run_cluster(nodes, existing, [cpu_mem_pod("p", "1m", "1Mi")],
                        filters=FIT_ONLY, scores=[])
        assert not r.feasible[0, 0]  # too many pods

    def test_zero_request_always_fits(self):
        nodes = [mknode("n1", cpu="1", mem="1Gi")]
        # node already over-full on cpu
        existing = {"n1": [cpu_mem_pod("e1", "2", "512Mi")]}
        r = run_cluster(nodes, existing, [mkpod("p", cpu=None)],
                        filters=FIT_ONLY, scores=[])
        assert r.feasible[0, 0]

    def test_extended_resource(self):
        n = mknode("n1")
        n.status.allocatable["example.com/gpu"] = "2"
        nodes = [n]
        gpu_pod = mkpod("p")
        gpu_pod.spec.containers[0].resources.requests["example.com/gpu"] = "3"
        r = run_cluster(nodes, {}, [gpu_pod], filters=FIT_ONLY, scores=[])
        assert not r.feasible[0, 0]
        gpu_pod2 = mkpod("p2")
        gpu_pod2.spec.containers[0].resources.requests["example.com/gpu"] = "2"
        r = run_cluster(nodes, {}, [gpu_pod2], filters=FIT_ONLY, scores=[])
        assert r.feasible[0, 0]


class TestResourceScores:
    def test_least_allocated_formula(self):
        # node: 4000m cpu, 10000Mi mem; existing 2500m/5000Mi; pod 1000m/2000Mi
        # cpu: (4000-3500)*100/4000 = 12 (int div); mem: (10000-7000)*100/10000 = 30
        # score = (12+30)/2 = 21
        nodes = [mknode("n1", cpu="4", mem="10000Mi")]
        existing = {"n1": [cpu_mem_pod("e", "2500m", "5000Mi")]}
        r = run_cluster(nodes, existing, [cpu_mem_pod("p", "1", "2000Mi")],
                        filters=FIT_ONLY, scores=LEAST)
        assert r.scores[0, 0] == 21

    def test_balanced_allocation_formula(self):
        # cpu frac 3500/4000 = 0.875, mem frac 7000/10000 = 0.7
        # score = floor((1-0.175)*100) = 82
        nodes = [mknode("n1", cpu="4", mem="10000Mi")]
        existing = {"n1": [cpu_mem_pod("e", "2500m", "5000Mi")]}
        r = run_cluster(nodes, existing, [cpu_mem_pod("p", "1", "2000Mi")],
                        filters=FIT_ONLY, scores=BALANCED)
        assert r.scores[0, 0] == pytest.approx(82)

    def test_balanced_overcommit_zero(self):
        nodes = [mknode("n1", cpu="1", mem="10000Mi")]
        r = run_cluster(nodes, {}, [cpu_mem_pod("p", "2", "100Mi")],
                        filters=[], scores=BALANCED)
        assert r.scores[0, 0] == 0

    def test_nonzero_defaults_in_scoring(self):
        # pod with no requests counts as 100m/200MB in Least/Balanced
        # cpu: (1000-100)*100/1000 = 90; mem: (1000-200)*100/1000 = 80 -> 85
        nodes = [mknode("n1", cpu="1", mem=str(1000 * 1024 * 1024))]
        r = run_cluster(nodes, {}, [mkpod("p", cpu=None)],
                        filters=FIT_ONLY, scores=LEAST)
        assert r.scores[0, 0] == 85


class TestNodeFilters:
    def test_node_name(self):
        nodes = [mknode("n1"), mknode("n2")]
        r = run_cluster(nodes, {}, [mkpod("p", node_name="n2")],
                        filters=["NodeName"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True])
        assert r.unresolvable[0, 0]

    def test_unschedulable(self):
        nodes = [mknode("n1", unschedulable=True), mknode("n2")]
        r = run_cluster(nodes, {}, [mkpod("p")],
                        filters=["NodeUnschedulable"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True])
        tol = api.Toleration(key="node.kubernetes.io/unschedulable",
                             operator="Exists", effect="NoSchedule")
        r = run_cluster(nodes, {}, [mkpod("p2", tolerations=[tol])],
                        filters=["NodeUnschedulable"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, True])

    def test_taints(self):
        t = api.Taint(key="k", value="v", effect="NoSchedule")
        prefer = api.Taint(key="p", value="", effect="PreferNoSchedule")
        nodes = [mknode("n1", taints=[t]), mknode("n2", taints=[prefer]), mknode("n3")]
        r = run_cluster(nodes, {}, [mkpod("p")],
                        filters=["TaintToleration"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True, True])
        tol = api.Toleration(key="k", operator="Equal", value="v", effect="NoSchedule")
        r = run_cluster(nodes, {}, [mkpod("p2", tolerations=[tol])],
                        filters=["TaintToleration"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, True, True])

    def test_taint_score(self):
        prefer = api.Taint(key="p", value="", effect="PreferNoSchedule")
        nodes = [mknode("n1", taints=[prefer]), mknode("n2")]
        r = run_cluster(nodes, {}, [mkpod("p")], filters=[],
                        scores=[("TaintToleration", 1)])
        # n1 has 1 intolerable prefer taint -> reverse-normalized: n1=0, n2=100
        np.testing.assert_array_equal(r.scores[0], [0, 100])

    def test_ports(self):
        used = mkpod("e1")
        used.spec.containers[0].ports = [api.ContainerPort(host_port=8080)]
        nodes = [mknode("n1"), mknode("n2")]
        want = mkpod("p")
        want.spec.containers[0].ports = [api.ContainerPort(host_port=8080)]
        r = run_cluster(nodes, {"n1": [used]}, [want],
                        filters=["NodePorts"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True])

    def test_ports_wildcard_semantics(self):
        used = mkpod("e1")
        used.spec.containers[0].ports = [
            api.ContainerPort(host_port=8080, host_ip="1.2.3.4")]
        nodes = [mknode("n1")]
        # different specific ip, same port: no conflict
        p = mkpod("p")
        p.spec.containers[0].ports = [
            api.ContainerPort(host_port=8080, host_ip="5.6.7.8")]
        r = run_cluster(nodes, {"n1": [used]}, [p], filters=["NodePorts"], scores=[])
        assert r.feasible[0, 0]
        # wildcard ip, same port: conflict
        p2 = mkpod("p2")
        p2.spec.containers[0].ports = [api.ContainerPort(host_port=8080)]
        r = run_cluster(nodes, {"n1": [used]}, [p2], filters=["NodePorts"], scores=[])
        assert not r.feasible[0, 0]

    def test_node_selector_and_affinity(self):
        nodes = [mknode("n1", labels={"disk": "ssd"}), mknode("n2")]
        r = run_cluster(nodes, {}, [mkpod("p", node_selector={"disk": "ssd"})],
                        filters=["NodeAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, False])
        aff = api.Affinity(node_affinity=api.NodeAffinity(
            required_during_scheduling_ignored_during_execution=api.NodeSelector([
                api.NodeSelectorTerm(match_expressions=[
                    api.NodeSelectorRequirement("disk", "In", ["ssd", "nvme"])])])))
        r = run_cluster(nodes, {}, [mkpod("p2", affinity=aff)],
                        filters=["NodeAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, False])

    def test_preferred_node_affinity_score(self):
        nodes = [mknode("n1", labels={"disk": "ssd"}), mknode("n2")]
        aff = api.Affinity(node_affinity=api.NodeAffinity(
            preferred_during_scheduling_ignored_during_execution=[
                api.PreferredSchedulingTerm(weight=80, preference=api.NodeSelectorTerm(
                    match_expressions=[api.NodeSelectorRequirement("disk", "In", ["ssd"])]))]))
        r = run_cluster(nodes, {}, [mkpod("p", affinity=aff)],
                        filters=[], scores=[("NodeAffinity", 1)])
        np.testing.assert_array_equal(r.scores[0], [100, 0])


class TestSpread:
    def zone_nodes(self):
        return [mknode("a1", labels={api.LABEL_ZONE: "zoneA", api.LABEL_HOSTNAME: "a1"}),
                mknode("a2", labels={api.LABEL_ZONE: "zoneA", api.LABEL_HOSTNAME: "a2"}),
                mknode("b1", labels={api.LABEL_ZONE: "zoneB", api.LABEL_HOSTNAME: "b1"})]

    def spread_pod(self, name, max_skew=1, key=api.LABEL_ZONE, labels=None):
        return mkpod(name, labels=labels or {"app": "web"},
                     topology_spread_constraints=[api.TopologySpreadConstraint(
                         max_skew=max_skew, topology_key=key,
                         when_unsatisfiable="DoNotSchedule",
                         label_selector=api.LabelSelector(match_labels={"app": "web"}))])

    def test_hard_spread_filter(self):
        nodes = self.zone_nodes()
        # zoneA has 2 matching pods, zoneB has 0 -> skew: placing in A = 3-0 > 1
        existing = {"a1": [mkpod("e1", labels={"app": "web"})],
                    "a2": [mkpod("e2", labels={"app": "web"})]}
        r = run_cluster(nodes, existing, [self.spread_pod("p")],
                        filters=["PodTopologySpread"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, False, True])

    def test_hard_spread_satisfiable(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("e1", labels={"app": "web"})]}
        # zoneA=1, zoneB=0; placing in A: 2-0=2 > 1 fail; B: 1-1=0 ok... wait
        # minMatch with B=0: A->1+1-0=2>1 fail, B->0+1-0=1<=1 ok
        r = run_cluster(nodes, existing, [self.spread_pod("p")],
                        filters=["PodTopologySpread"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, False, True])

    def test_spread_missing_key_fails(self):
        nodes = self.zone_nodes() + [mknode("c1", labels={api.LABEL_HOSTNAME: "c1"})]
        r = run_cluster(nodes, {}, [self.spread_pod("p")],
                        filters=["PodTopologySpread"], scores=[])
        # c1 lacks the zone label -> fails constraint
        np.testing.assert_array_equal(r.feasible[0], [True, True, True, False])

    def test_nonmatching_selector_pod_ignored(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("e1", labels={"app": "other"})] * 3}
        r = run_cluster(nodes, existing, [self.spread_pod("p")],
                        filters=["PodTopologySpread"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, True, True])

    def test_soft_spread_score_prefers_low_count_zone(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("e1", labels={"app": "web"})],
                    "a2": [mkpod("e2", labels={"app": "web"})]}
        pod = mkpod("p", labels={"app": "web"},
                    topology_spread_constraints=[api.TopologySpreadConstraint(
                        max_skew=1, topology_key=api.LABEL_ZONE,
                        when_unsatisfiable="ScheduleAnyway",
                        label_selector=api.LabelSelector(match_labels={"app": "web"}))])
        r = run_cluster(nodes, existing, [pod], filters=[],
                        scores=[("PodTopologySpread", 2)])
        s = r.scores[0]
        assert s[2] > s[0] and s[2] > s[1]


class TestInterPodAffinity:
    def zone_nodes(self):
        return [mknode("a1", labels={api.LABEL_ZONE: "zoneA"}),
                mknode("b1", labels={api.LABEL_ZONE: "zoneB"})]

    def affinity_pod(self, name, anti=False, labels=None, sel=None,
                     key=api.LABEL_ZONE):
        term = api.PodAffinityTerm(
            label_selector=api.LabelSelector(match_labels=sel or {"app": "db"}),
            topology_key=key)
        if anti:
            aff = api.Affinity(pod_anti_affinity=api.PodAntiAffinity(
                required_during_scheduling_ignored_during_execution=[term]))
        else:
            aff = api.Affinity(pod_affinity=api.PodAffinity(
                required_during_scheduling_ignored_during_execution=[term]))
        return mkpod(name, labels=labels or {}, affinity=aff)

    def test_required_affinity(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("db", labels={"app": "db"})]}
        r = run_cluster(nodes, existing, [self.affinity_pod("p")],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, False])
        assert r.unresolvable[0, 1]  # affinity failure is unresolvable

    def test_required_affinity_no_match_anywhere(self):
        nodes = self.zone_nodes()
        r = run_cluster(nodes, {}, [self.affinity_pod("p")],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, False])

    def test_bootstrap_self_match(self):
        # pod matches its own affinity term -> schedulable anywhere with the key
        nodes = self.zone_nodes()
        r = run_cluster(nodes, {},
                        [self.affinity_pod("p", labels={"app": "db"})],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, True])

    def test_required_anti_affinity(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("db", labels={"app": "db"})]}
        r = run_cluster(nodes, existing, [self.affinity_pod("p", anti=True)],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True])

    def test_existing_pod_anti_affinity(self):
        # existing pod repels incoming pods labeled app=web zone-wide
        nodes = self.zone_nodes()
        repeller = self.affinity_pod("r", anti=True, sel={"app": "web"})
        existing = {"a1": [repeller]}
        r = run_cluster(nodes, existing, [mkpod("p", labels={"app": "web"})],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [False, True])
        r = run_cluster(nodes, existing, [mkpod("p2", labels={"app": "other"})],
                        filters=["InterPodAffinity"], scores=[])
        np.testing.assert_array_equal(r.feasible[0], [True, True])

    def test_preferred_affinity_score(self):
        nodes = self.zone_nodes()
        existing = {"a1": [mkpod("db", labels={"app": "db"})]}
        term = api.WeightedPodAffinityTerm(weight=50, pod_affinity_term=api.PodAffinityTerm(
            label_selector=api.LabelSelector(match_labels={"app": "db"}),
            topology_key=api.LABEL_ZONE))
        pod = mkpod("p", affinity=api.Affinity(pod_affinity=api.PodAffinity(
            preferred_during_scheduling_ignored_during_execution=[term])))
        r = run_cluster(nodes, existing, [pod], filters=[],
                        scores=[("InterPodAffinity", 1)])
        np.testing.assert_array_equal(r.scores[0], [100, 0])


class TestOtherScores:
    def test_image_locality(self):
        n1 = mknode("n1")
        n1.status.images = [api.ContainerImage(names=["img:1"], size_bytes=270 * 1024 * 1024)]
        nodes = [n1, mknode("n2")]
        r = run_cluster(nodes, {}, [mkpod("p")], filters=[],
                        scores=[("ImageLocality", 1)])
        # scaled = 270MB * (1/2 nodes) = 135MB; (135-23)/(1000-23)*100 = 11
        assert r.scores[0, 0] == pytest.approx(11)
        assert r.scores[0, 1] == 0

    def test_prefer_avoid(self):
        import json
        n1 = mknode("n1")
        n1.metadata.annotations[api.PREFER_AVOID_PODS_ANNOTATION_KEY] = json.dumps({
            "preferAvoidPods": [{"podSignature": {"podController": {
                "kind": "ReplicaSet", "uid": "rs-1"}}}]})
        nodes = [n1, mknode("n2")]
        pod = mkpod("p")
        pod.metadata.owner_references = [api.OwnerReference(
            kind="ReplicaSet", uid="rs-1", controller=True)]
        r = run_cluster(nodes, {}, [pod], filters=[],
                        scores=[("NodePreferAvoidPods", 1)])
        np.testing.assert_array_equal(r.scores[0], [0, 100])
        free = mkpod("free")
        r = run_cluster(nodes, {}, [free], filters=[],
                        scores=[("NodePreferAvoidPods", 1)])
        np.testing.assert_array_equal(r.scores[0], [100, 100])

    def test_default_spread(self):
        nodes = [mknode("n1", labels={api.LABEL_ZONE_LEGACY: "zA"}),
                 mknode("n2", labels={api.LABEL_ZONE_LEGACY: "zB"})]
        existing = {"n1": [mkpod("e1", labels={"app": "svc"})]}
        sel = api.LabelSelector(match_labels={"app": "svc"})
        r = run_cluster(nodes, existing, [mkpod("p", labels={"app": "svc"})],
                        filters=[], scores=[("DefaultPodTopologySpread", 1)],
                        spread_selectors=[sel])
        # n1 hosts 1 matching pod; zone A count 1; n2: 0/0
        # node score n1: 100*(1-1)/1=0; zone n1: 100*(1-1)/1=0 -> 0
        # n2: node 100, zone 100 -> 100
        np.testing.assert_array_equal(r.scores[0], [0, 100])


class TestSelect:
    def test_picks_max_and_breaks_ties(self):
        nodes = [mknode("n1", cpu="4"), mknode("n2", cpu="8"), mknode("n3", cpu="8")]
        r = run_cluster(nodes, {}, [cpu_mem_pod("p", "1", "1Gi")],
                        filters=FIT_ONLY, scores=LEAST)
        assert r.chosen[0] in (1, 2)
        assert r.scores[0, 1] == r.scores[0, 2] > r.scores[0, 0]


# ---------------------------------------------------------------------------
# the runtime gate of the six term sets (ops/kernels.py _if_live, PR 37):
# everything a topology kernel does along the existing-pod axis for one set
# runs only when the batch has a valid row in it, and what it returns
# otherwise is what the live code computes for an all-invalid set.

GATE_SETS = ("ra", "raa", "pref", "spread", "spread_soft", "default_spread")


def _gate_world():
    """Twelve nodes in three zones, 64 pod rows (40 resident pods, some with
    anti-affinity and preferred terms of their own), and a batch of eight
    pods that EACH carry a term of every set, with selectors the residents'
    labels hit: masking a set's ``valid`` leaves real selectors behind the
    invalid rows, as a padded slot never does."""
    import random

    import jax

    from kubetpu.framework.types import NodeInfo, PodInfo
    from kubetpu.harness import hollow
    from kubetpu.models import programs
    from kubetpu.models.batch import PodBatchBuilder
    from kubetpu.state.tensors import SnapshotBuilder

    rng = random.Random(37)
    nodes = [mknode(f"n{i}", labels={api.LABEL_HOSTNAME: f"n{i}",
                                     api.LABEL_ZONE: f"z{i % 3}"})
             for i in range(12)]
    infos = []
    k = 0
    for n in nodes:
        ni = NodeInfo(n)
        for _ in range(rng.randint(2, 5)):
            k += 1
            p = mkpod(f"e{k}", labels={"app": rng.choice(["web", "db"]),
                                       "color": rng.choice(["blue", "red"])})
            if k % 7 == 0:
                hollow.with_anti_affinity(p, match={"app": "web"})
            if k % 5 == 0:
                p.spec.affinity = p.spec.affinity or api.Affinity()
                p.spec.affinity.pod_affinity = api.PodAffinity(
                    preferred_during_scheduling_ignored_during_execution=[
                        api.WeightedPodAffinityTerm(
                            weight=7, pod_affinity_term=api.PodAffinityTerm(
                                label_selector=api.LabelSelector(
                                    match_labels={"color": "blue"}),
                                topology_key=api.LABEL_ZONE))])
            p.spec.node_name = n.name
            ni.add_pod(p)
        infos.append(ni)
    pending = []
    for i in range(8):
        p = mkpod(f"p{i}", labels={"app": "web", "color": "blue"})
        hollow.with_affinity(p, api.LABEL_ZONE, match={"app": "db"})
        hollow.with_anti_affinity(p, api.LABEL_HOSTNAME,
                                  match={"color": "red"})
        p.spec.affinity.pod_anti_affinity \
            .preferred_during_scheduling_ignored_during_execution.append(
                api.WeightedPodAffinityTerm(
                    weight=3 + i, pod_affinity_term=api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"app": "web"}),
                        topology_key=api.LABEL_ZONE)))
        hollow.with_spread(p, api.LABEL_ZONE, max_skew=2,
                           match={"color": "blue"})
        hollow.with_spread(p, api.LABEL_HOSTNAME, max_skew=1,
                           when="ScheduleAnyway", match={"app": "web"})
        pending.append(p)
    sb = SnapshotBuilder()
    pinfos = [PodInfo(p) for p in pending]
    sb.intern_pending(pinfos)
    cluster = sb.build(infos).to_device()
    batch = jax.tree.map(np.asarray, PodBatchBuilder(sb.table).build(
        pinfos, spread_selectors=[api.LabelSelector(
            match_labels={"app": "web"})] * len(pending)))
    cfg = programs.ProgramConfig(
        hostname_topokey=sb.table.topokey.get(api.LABEL_HOSTNAME))
    return cluster, batch, cfg


@pytest.fixture(scope="module")
def gate_world():
    return _gate_world()


def _mask_set(batch, name, rows):
    """``batch`` with set ``name`` valid in ``rows`` only ("none", "one",
    "all"); every other set stays valid on every pod."""
    B = batch.valid.shape[0]
    keep = {"none": np.zeros(B, bool), "one": np.arange(B) == 5,
            "all": np.ones(B, bool)}[rows]
    if name == "default_spread":
        # a pod with explicit constraints skips the plugin; the world's
        # pods all carry them, so the rows that count are un-skipped here
        return batch._replace(spread_skip=~keep)
    terms = getattr(batch, name)
    return batch._replace(**{name: terms._replace(
        valid=terms.valid & keep[:, None])})


def _gate_outputs(name, intra):
    """jit of the kernel whose pod-axis work set ``name`` gates, every
    output; ``intra``: over the batch-extended pod axis with hoisted pres
    (the round body's call), else on the plain cluster with none."""
    import jax

    from kubetpu.models import gang
    from kubetpu.models.batch import densify_for
    from kubetpu.ops import kernels as K

    def run(cluster, batch, feasible):
        batch = densify_for(cluster, batch)
        cl = cluster
        if intra:
            B = batch.valid.shape[0]
            cl = gang._extend_cluster(cluster, batch)
            # three batch pods already admitted, as in a later round
            placed = jnp.arange(B) < 3
            cl = cl._replace(
                pod_node=jnp.concatenate(
                    [cluster.pod_node,
                     jnp.where(placed, jnp.arange(B, dtype=jnp.int32), -1)]),
                pod_valid=jnp.concatenate([cluster.pod_valid, placed]))
        aff_ok = K.node_affinity_filter(cl, batch)
        if name in ("ra", "raa"):
            return K.interpod_filter(
                cl, batch, return_no_matches=True,
                pre=K.interpod_filter_pre(cl, batch) if intra else None)
        if name == "pref":
            pre = K.interpod_score_pre(cl, batch) if intra else None
            return (K.interpod_score_raw(cl, batch, pre=pre),
                    K.interpod_score(cl, batch, feasible, pre=pre))
        if name == "spread":
            return K.spread_filter(
                cl, batch, aff_ok, return_slack=True,
                match_ns=K.spread_match_ns(cl, batch, batch.spread)
                if intra else None)
        if name == "spread_soft":
            return K.spread_soft_score(
                cl, batch, feasible, aff_ok, 0,
                match_ns=K.spread_match_ns(cl, batch, batch.spread_soft)
                if intra else None)
        return K.default_spread_score(
            cl, batch,
            match_ns=K.default_spread_match_ns(cl, batch) if intra else None)
    return jax.jit(run)


@pytest.fixture(scope="module")
def gate_programs():
    """(set, intra) -> (gated program, the same traced with the gate forced
    live): one compile each serves the three row cases, because the
    predicate is a runtime value."""
    from kubetpu.ops import kernels as K
    progs = {}

    def get(name, intra):
        if (name, intra) not in progs:
            gated = _gate_outputs(name, intra)
            forced = _gate_outputs(name, intra)

            def forced_live(*a, _f=forced):
                real = K._if_live
                K._if_live = lambda live, live_fn, dead_fn: live_fn()
                try:
                    return _f(*a)       # traces on its first call
                finally:
                    K._if_live = real
            progs[name, intra] = (gated, forced_live)
        return progs[name, intra]
    return get


@pytest.mark.parametrize("intra", [True, False],
                         ids=["intra-batch", "static"])
@pytest.mark.parametrize("rows", ["none", "one", "all"])
@pytest.mark.parametrize("name", GATE_SETS)
def test_a_gated_kernel_equals_itself_forced_live(gate_world, gate_programs,
                                                  name, rows, intra):
    import jax
    cluster, batch, _ = gate_world
    masked = _mask_set(batch, name, rows)
    B, N = batch.valid.shape[0], cluster.allocatable.shape[0]
    feasible = jnp.asarray((np.arange(B * N).reshape(B, N) % 5) != 0)
    gated, forced = gate_programs(name, intra)
    got = jax.tree.leaves(gated(cluster, masked, feasible))
    want = jax.tree.leaves(forced(cluster, masked, feasible))
    assert len(got) == len(want) > 0
    for g, w in zip(got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape
        assert g.tobytes() == w.tobytes()
    if rows == "all" and name in ("ra", "raa", "spread"):
        # the world is not vacuous: the live set does fail some node
        assert not np.asarray(got[0]).all()


def _pod_axis_dots_outside_cond(jaxpr, P):
    """(outside, inside): dot_generals with an operand or result dimension
    of size P, split by whether a ``cond`` encloses them."""
    outside, inside = [], []

    def walk(jp, in_cond):
        for eqn in jp.eqns:
            if eqn.primitive.name == "dot_general":
                shapes = [v.aval.shape for v in eqn.invars + eqn.outvars]
                if any(P in s for s in shapes):
                    (inside if in_cond else outside).append(shapes)
            for v in eqn.params.values():
                subs = v if isinstance(v, (list, tuple)) else [v]
                for sub in subs:
                    sub = getattr(sub, "jaxpr", sub)
                    if hasattr(sub, "eqns"):
                        walk(sub, in_cond or eqn.primitive.name == "cond")
    walk(jaxpr.jaxpr, False)
    return outside, inside


def _gated_calls(cluster, batch):
    """name -> thunk of the five kernels and the five hoisted pres, with no
    pre handed in (so the match itself is traced too)."""
    from kubetpu.models.batch import densify_for
    from kubetpu.ops import kernels as K
    b = densify_for(cluster, batch)
    B, N = b.valid.shape[0], cluster.allocatable.shape[0]
    feas = jnp.ones((B, N), bool)
    return {
        "interpod_filter": lambda c: K.interpod_filter(c, b),
        "interpod_score_raw": lambda c: K.interpod_score_raw(c, b),
        "spread_filter": lambda c: K.spread_filter(c, b, feas),
        "spread_soft_score":
            lambda c: K.spread_soft_score(c, b, feas, feas, 0),
        "default_spread_score": lambda c: K.default_spread_score(c, b),
        "interpod_filter_pre": lambda c: K.interpod_filter_pre(c, b),
        "interpod_score_pre": lambda c: K.interpod_score_pre(c, b),
        "spread_match_ns(hard)":
            lambda c: K.spread_match_ns(c, b, b.spread),
        "spread_match_ns(soft)":
            lambda c: K.spread_match_ns(c, b, b.spread_soft),
        "default_spread_match_ns":
            lambda c: K.default_spread_match_ns(c, b),
    }


@pytest.mark.parametrize("name", [
    "interpod_filter", "interpod_score_raw", "spread_filter",
    "spread_soft_score", "default_spread_score", "interpod_filter_pre",
    "interpod_score_pre", "spread_match_ns(hard)", "spread_match_ns(soft)",
    "default_spread_match_ns"])
def test_no_pod_axis_product_sits_outside_a_gate(gate_world, name):
    import jax
    cluster, batch, _ = gate_world
    P = cluster.pod_valid.shape[0]
    # the pod axis must be told apart by its size alone
    other = {d for x in jax.tree.leaves((cluster, batch))
             for d in np.shape(x)} - {P}
    assert P == 64 and P not in (cluster.allocatable.shape[0],
                                 batch.valid.shape[0])
    assert all(x.shape[0] == P for x in (cluster.pod_kv, cluster.pod_node))
    assert P not in {cluster.kv.shape[1], cluster.keymask.shape[1],
                     cluster.pod_ns_hot.shape[1]}, other
    jaxpr = jax.make_jaxpr(_gated_calls(cluster, batch)[name])(cluster)
    outside, inside = _pod_axis_dots_outside_cond(jaxpr, P)
    assert outside == []
    assert inside                 # and the gate does hold such products


def test_the_gate_stays_a_cond_under_preemptions_candidate_vmap(gate_world):
    """preemption._whatif_reprieve maps run_filters over candidates'
    pod_valid; the batch is not mapped, so each gate's predicate is not,
    and the cond is not lowered to a select over both branches."""
    import jax

    from kubetpu.models import programs
    from kubetpu.models.batch import densify_for
    cluster, batch, cfg = gate_world
    b = densify_for(cluster, batch)
    P = cluster.pod_valid.shape[0]

    def one(pod_valid):
        return programs.run_filters(
            cluster._replace(pod_valid=pod_valid), b, cfg)[0]

    def conds(jaxpr):
        n = 0

        def walk(jp):
            nonlocal n
            for eqn in jp.eqns:
                n += eqn.primitive.name == "cond"
                for v in eqn.params.values():
                    for sub in (v if isinstance(v, (list, tuple)) else [v]):
                        sub = getattr(sub, "jaxpr", sub)
                        if hasattr(sub, "eqns"):
                            walk(sub)
        walk(jaxpr.jaxpr)
        return n

    plain = jax.make_jaxpr(one)(cluster.pod_valid)
    mapped = jax.make_jaxpr(jax.vmap(one))(
        jnp.stack([cluster.pod_valid] * 3))
    # required affinity, required anti-affinity, the hard spread filter
    assert conds(plain) == 3
    assert conds(mapped) == 3
    outside, inside = _pod_axis_dots_outside_cond(mapped, P)
    assert outside == [] and inside

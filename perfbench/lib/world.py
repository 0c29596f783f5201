"""A configuration file turned into a cluster: plain records for the
reference, API objects for the program, both from the same template data.

Pod templates are data (``templates`` in ``perfbench/configs/<name>.json``):
cpu, memory, priority, ``group_labels`` and a list of ``features`` by the
names ``kubetpu/harness/perf.py:_make_pod`` switches on.  Pod ``i`` of a
role ("init", "measured", "sample") gets the labels
``app=app-<i % group_labels>`` and ``group=<role>``, as ``_make_pod`` has it.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Tuple

import numpy as np

HOSTNAME = "kubernetes.io/hostname"
ZONE = "topology.kubernetes.io/zone"
REGION = "topology.kubernetes.io/region"
# feature name -> what it adds (the selector is the pod's own app group
# for anti/panti/paff, its role group for aff/spread, as in _make_pod)
FEATURES = ("anti", "aff", "panti", "paff", "spread")


@dataclass(frozen=True)
class NodeRec:
    name: str
    cpu_milli: int
    mem_bytes: int
    pods: int
    labels: Dict[str, str]


@dataclass(frozen=True)
class PodRec:
    name: str
    cpu_milli: int
    mem_bytes: int
    priority: int
    labels: Dict[str, str]
    features: Tuple[str, ...] = ()
    # required terms as (topology key, ((label, value),))
    anti_required: Tuple[Tuple[str, tuple], ...] = ()
    aff_required: Tuple[Tuple[str, tuple], ...] = ()


def node_records(config: Dict[str, Any]) -> List[NodeRec]:
    c = config["cluster"]
    shape = c["node"]
    zones = int(c.get("zones", 0))
    out = []
    for i in range(int(c["nodes"])):
        name = f"node-{i}"
        labels = {HOSTNAME: name}
        if zones:
            labels[ZONE] = f"zone-{i % zones}"
            labels[REGION] = "region-0"
        out.append(NodeRec(name, int(shape["cpu_milli"]),
                           int(shape["memory_bytes"]), int(shape["pods"]),
                           labels))
    return out


def pod_record(config: Dict[str, Any], template: str, role: str,
               i: int) -> PodRec:
    t = config["templates"][template]
    groups = int(t.get("group_labels", 10))
    labels = {"app": f"app-{i % groups}", "group": role}
    features = tuple(t.get("features", ()))
    unknown = [f for f in features if f not in FEATURES]
    if unknown:
        raise ValueError(f"template {template}: unknown features {unknown}; "
                         f"known: {FEATURES}")
    app = (("app", labels["app"]),)
    grp = (("group", role),)
    return PodRec(
        name=f"{role}-{i}", cpu_milli=int(t["cpu_milli"]),
        mem_bytes=int(t["memory_bytes"]), priority=int(t.get("priority", 0)),
        labels=labels, features=features,
        anti_required=((HOSTNAME, app),) if "anti" in features else (),
        aff_required=((ZONE, grp),) if "aff" in features else ())


def init_placement(config: Dict[str, Any], seed: int) -> List[str]:
    """Node name per init pod: round-robin over a seeded order of the
    nodes, which is what scheduling them with LeastAllocated ends in."""
    n = int(config["cluster"]["nodes"])
    count = int(config["init_pods"]["count"])
    order = np.random.default_rng([int(seed), 0x1217]).permutation(n)
    return [f"node-{int(order[j % n])}" for j in range(count)]


def init_records(config: Dict[str, Any], seed: int
                 ) -> List[Tuple[PodRec, str]]:
    tmpl = config["init_pods"]["template"]
    return [(pod_record(config, tmpl, "init", j), node)
            for j, node in enumerate(init_placement(config, seed))]


def measured_record(config: Dict[str, Any], role: str, i: int) -> PodRec:
    return pod_record(config, config["measured_pods"]["template"], role, i)


# ---------------------------------------------------------------- API objects


def api_node(rec: NodeRec):
    from kubetpu.api import types as api
    alloc = {"cpu": f"{rec.cpu_milli}m", "memory": str(rec.mem_bytes),
             "pods": str(rec.pods)}
    return api.Node(
        metadata=api.ObjectMeta(name=rec.name, labels=dict(rec.labels)),
        status=api.NodeStatus(allocatable=dict(alloc), capacity=dict(alloc)))


def _term(api, topo: str, sel: tuple):
    return api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(sel)),
        topology_key=topo)


def api_pod(rec: PodRec, node: str = ""):
    from kubetpu.api import types as api
    req = {"cpu": f"{rec.cpu_milli}m", "memory": str(rec.mem_bytes)}
    pod = api.Pod(
        metadata=api.ObjectMeta(name=rec.name, labels=dict(rec.labels)),
        spec=api.PodSpec(
            priority=rec.priority,
            containers=[api.Container(
                name="c", image="k8s.gcr.io/pause:3.2",
                resources=api.ResourceRequirements(requests=req))]))
    f = rec.features
    if f:
        app = {"app": rec.labels["app"]}
        grp = {"group": rec.labels["group"]}
        aff = api.Affinity()
        if "anti" in f or "panti" in f:
            aff.pod_anti_affinity = api.PodAntiAffinity()
        if "aff" in f or "paff" in f:
            aff.pod_affinity = api.PodAffinity()
        for topo, sel in rec.anti_required:
            aff.pod_anti_affinity \
                .required_during_scheduling_ignored_during_execution \
                .append(_term(api, topo, sel))
        for topo, sel in rec.aff_required:
            aff.pod_affinity \
                .required_during_scheduling_ignored_during_execution \
                .append(_term(api, topo, sel))
        for name, side in (("panti", aff.pod_anti_affinity),
                           ("paff", aff.pod_affinity)):
            if name in f:
                side.preferred_during_scheduling_ignored_during_execution \
                    .append(api.WeightedPodAffinityTerm(
                        weight=10, pod_affinity_term=_term(
                            api, ZONE, tuple(app.items()))))
        if aff.pod_affinity or aff.pod_anti_affinity:
            pod.spec.affinity = aff
        if "spread" in f:
            pod.spec.topology_spread_constraints.append(
                api.TopologySpreadConstraint(
                    max_skew=2, topology_key=ZONE,
                    when_unsatisfiable="DoNotSchedule",
                    label_selector=api.LabelSelector(match_labels=grp)))
    if node:
        pod.spec.node_name = node
    return pod


def build_store(nodes: List[NodeRec], bound: List[Tuple[PodRec, str]]):
    """A ClusterStore holding the nodes and the already-bound pods."""
    from kubetpu.client.store import ClusterStore
    store = ClusterStore()
    for n in nodes:
        store.add(api_node(n))
    for rec, node in bound:
        store.add(api_pod(rec, node))
    return store


def scheduler_config(section: Dict[str, Any], mesh_shape=None):
    """``scheduler`` (or ``check.scheduler``) of a configuration file as
    the program's component config: every key is a field of
    KubeSchedulerConfiguration, nothing else is set."""
    from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                     KubeSchedulerProfile)
    kw = dict(section)
    if mesh_shape:
        kw["mesh_shape"] = tuple(mesh_shape)
    return KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                      **kw)

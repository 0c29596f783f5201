"""Hollow cluster generation: synthetic node fleets and pod workloads.

The TPU-native analog of kubemark's hollow nodes (reference:
cmd/kubemark/hollow-node.go, pkg/kubemark/hollow_kubelet.go:35) and the
scheduler_perf node-prepare strategies (reference:
test/utils/runners.go:951-1121 TrivialNodePrepareStrategy/LabelNodeStrategy)
plus the benchmark node shape (reference:
test/integration/scheduler_perf/scheduler_test.go:52-66 — 110 pods, 4 CPU,
32 Gi per fake node).  Used by chip_smoke.py, __graft_entry__.py and the
perf harness to synthesize clusters without machines.
"""

from __future__ import annotations

import random
from typing import Dict, List, Optional

from ..api import types as api


HOLLOW_NODE_CPU_MILLI = 4000          # scheduler_test.go:57 "4" cpu
HOLLOW_NODE_MEM_BYTES = 32 * (1 << 30)  # "32Gi"
HOLLOW_NODE_PODS = 110                # "110" pods


def make_node(name: str, zone: Optional[str] = None,
              region: Optional[str] = None,
              cpu_milli: int = HOLLOW_NODE_CPU_MILLI,
              mem: int = HOLLOW_NODE_MEM_BYTES,
              pods: int = HOLLOW_NODE_PODS,
              labels: Optional[Dict[str, str]] = None) -> api.Node:
    lab = {api.LABEL_HOSTNAME: name}
    if zone:
        lab[api.LABEL_ZONE] = zone
    if region:
        lab[api.LABEL_REGION] = region
    if labels:
        lab.update(labels)
    alloc = {"cpu": f"{cpu_milli}m", "memory": str(mem), "pods": str(pods)}
    return api.Node(
        metadata=api.ObjectMeta(name=name, labels=lab),
        status=api.NodeStatus(allocatable=dict(alloc), capacity=dict(alloc)))


def make_nodes(n: int, zones: int = 0, prefix: str = "node-",
               **kw) -> List[api.Node]:
    out = []
    for i in range(n):
        zone = f"zone-{i % zones}" if zones else None
        region = "region-0" if zones else None
        out.append(make_node(f"{prefix}{i}", zone=zone, region=region, **kw))
    return out


def make_pod(name: str, namespace: str = "default",
             cpu_milli: int = 100, mem: int = 256 << 20,
             labels: Optional[Dict[str, str]] = None,
             priority: int = 0) -> api.Pod:
    req = {"cpu": f"{cpu_milli}m", "memory": str(mem)}
    return api.Pod(
        metadata=api.ObjectMeta(name=name, namespace=namespace,
                                labels=dict(labels or {})),
        spec=api.PodSpec(
            priority=priority,
            containers=[api.Container(
                name="c", image="k8s.gcr.io/pause:3.2",
                resources=api.ResourceRequirements(requests=req))]))


def make_pods(n: int, prefix: str = "pod-", namespace: str = "default",
              group_labels: int = 0, rng: Optional[random.Random] = None,
              **kw) -> List[api.Pod]:
    """group_labels > 0 assigns each pod a label app=app-<i%groups> so
    affinity/spread workloads have selector targets."""
    rng = rng or random.Random(0)
    out = []
    for i in range(n):
        labels = {}
        if group_labels:
            labels["app"] = f"app-{i % group_labels}"
        out.append(make_pod(f"{prefix}{i}", namespace=namespace,
                            labels=labels, **kw))
    return out


def restart_world(n_nodes: int, existing_per_node: int = 2,
                  zones: int = 8):
    """The deterministic warm-restart world: n_nodes zoned nodes, each
    carrying existing_per_node bound pods with 16 app-group labels.
    SHARED by tools/kubeaot build_shape, chip_smoke.py and the restart
    tests —
    a restart of shape (n_nodes, wave) dispatches byte-identical call
    forms to a capture of the same shape only because both sides build
    the world through this one function (same store insertion order,
    same label vocab, same selector diversity)."""
    from ..client.store import ClusterStore
    store = ClusterStore()
    for i, n in enumerate(make_nodes(n_nodes, zones=zones)):
        store.add(n)
        for p in make_pods(existing_per_node, prefix=f"ex-{i}-",
                           group_labels=16):
            p.spec.node_name = n.name
            store.add(p)
    return store


def restart_wave(wave: int, prefix: str = "restart-") -> List[api.Pod]:
    """The arriving wave of the warm-restart case: 16 app groups, 1/3
    soft zone spread, 1/5 hostname anti-affinity (the blended
    scheduler_perf topology mix).  Shared with tools/kubeaot build_shape
    for the same reason as restart_world."""
    pods = make_pods(wave, prefix=prefix, group_labels=16)
    for i, p in enumerate(pods):
        if i % 3 == 0:
            with_spread(p, api.LABEL_ZONE, when="ScheduleAnyway")
        if i % 5 == 0:
            with_anti_affinity(p)
    return pods


def with_spread(pod: api.Pod, topo_key: str, max_skew: int = 1,
                when: str = "DoNotSchedule",
                match: Optional[Dict[str, str]] = None) -> api.Pod:
    pod.spec.topology_spread_constraints.append(api.TopologySpreadConstraint(
        max_skew=max_skew, topology_key=topo_key, when_unsatisfiable=when,
        label_selector=api.LabelSelector(match_labels=dict(
            match or pod.metadata.labels))))
    return pod


def with_anti_affinity(pod: api.Pod, topo_key: str = api.LABEL_HOSTNAME,
                       match: Optional[Dict[str, str]] = None) -> api.Pod:
    term = api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(
            match or pod.metadata.labels)),
        topology_key=topo_key)
    aff = pod.spec.affinity or api.Affinity()
    if aff.pod_anti_affinity is None:
        aff.pod_anti_affinity = api.PodAntiAffinity()
    aff.pod_anti_affinity.required_during_scheduling_ignored_during_execution \
        .append(term)
    pod.spec.affinity = aff
    return pod


def with_affinity(pod: api.Pod, topo_key: str = api.LABEL_ZONE,
                  match: Optional[Dict[str, str]] = None) -> api.Pod:
    term = api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels=dict(
            match or pod.metadata.labels)),
        topology_key=topo_key)
    aff = pod.spec.affinity or api.Affinity()
    if aff.pod_affinity is None:
        aff.pod_affinity = api.PodAffinity()
    aff.pod_affinity.required_during_scheduling_ignored_during_execution \
        .append(term)
    pod.spec.affinity = aff
    return pod

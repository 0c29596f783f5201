"""Pod classes (PR 48): prepare does once a CLASS of pending pods what it did
once a pod.  Held here: the grouping itself (framework/types.py
classify_pods), that what is shared is what the per-pod code gives -- the
PodInfos, the batch (models/batch.py: leaf for leaf, dtype and shape, against
the row builder called on every pod), the default spread selectors, the
batch's topology keys and the host plugins' relevance map -- and the
contract that lets ``relevant(pod)`` be asked once a class.
"""

import copy
import json
import os
import random
import sys
from types import SimpleNamespace

import jax
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from kubetpu.api import types as api  # noqa: E402
from kubetpu.apis.config import (KubeSchedulerConfiguration,  # noqa: E402
                                 KubeSchedulerProfile, Plugin, Plugins,
                                 PluginSet)
from kubetpu.client.store import ClusterStore  # noqa: E402
from kubetpu.framework import interface as fw  # noqa: E402
from kubetpu.framework.types import (NodeInfo, PodInfo,  # noqa: E402
                                     QueuedPodInfo, class_pod_infos,
                                     classify_pods)
from kubetpu.harness import hollow  # noqa: E402
from kubetpu.models.batch import PodBatchBuilder  # noqa: E402
from kubetpu.plugins.intree import new_in_tree_registry  # noqa: E402
from kubetpu.scheduler import Scheduler  # noqa: E402
from kubetpu.state.tensors import SnapshotBuilder  # noqa: E402
from kubetpu.state.volumes import DEVICE_COVERED_PLUGINS  # noqa: E402
from perfbench.lib import world  # noqa: E402

ZONE, HOSTNAME = api.LABEL_ZONE, api.LABEL_HOSTNAME

with open(os.path.join(REPO, "BENCHMARK.json")) as _f:
    CONFIGS = [c["name"] for c in json.load(_f)["configs"]]


def _config(name):
    with open(os.path.join(REPO, "perfbench", "configs",
                           name + ".json")) as f:
        return json.load(f)


def template_pod(config, i):
    """The i-th measured pod of a configuration, as the benchmark's
    client makes it."""
    return world.api_pod(world.measured_record(config, "measured", i))


# ------------------------------------------------- one field at a time


def _term(color="blue", topo=ZONE):
    return api.PodAffinityTerm(
        label_selector=api.LabelSelector(match_labels={"color": color}),
        topology_key=topo)


def _affinity(pod):
    if pod.spec.affinity is None:
        pod.spec.affinity = api.Affinity()
    return pod.spec.affinity


def _pod_affinity(pod):
    aff = _affinity(pod)
    if aff.pod_affinity is None:
        aff.pod_affinity = api.PodAffinity()
    return aff.pod_affinity


def _pod_anti(pod):
    aff = _affinity(pod)
    if aff.pod_anti_affinity is None:
        aff.pod_anti_affinity = api.PodAntiAffinity()
    return aff.pod_anti_affinity


def _node_affinity(pod):
    _affinity(pod).node_affinity = api.NodeAffinity(
        required_during_scheduling_ignored_during_execution=api.NodeSelector(
            node_selector_terms=[api.NodeSelectorTerm(match_expressions=[
                api.NodeSelectorRequirement(ZONE, "In", ["zone-1"])])]),
        preferred_during_scheduling_ignored_during_execution=[
            api.PreferredSchedulingTerm(weight=3, preference=(
                api.NodeSelectorTerm(match_expressions=[
                    api.NodeSelectorRequirement(ZONE, "In", ["zone-2"])])))])


def _first_label_last(pod):
    labels = pod.metadata.labels
    if labels:
        k = next(iter(labels))
        labels[k] = labels.pop(k)


def _other_value(pod):
    labels = pod.metadata.labels
    if labels:
        labels[next(iter(labels))] = "other"


# each changes ONE field of a pod, in place
VARIANTS = {
    "label": lambda p: p.metadata.labels.update(tier="web"),
    "label-value": _other_value,
    "label-order": _first_label_last,
    "annotation": lambda p: p.metadata.annotations.update(note="x"),
    "owner-reference": lambda p: p.metadata.owner_references.append(
        api.OwnerReference(kind="ReplicaSet", name="rs", uid="rs-uid-1",
                           controller=True)),
    "toleration": lambda p: p.spec.tolerations.append(
        api.Toleration(key="dedicated", operator="Exists")),
    "host-port": lambda p: p.spec.containers[0].ports.append(
        api.ContainerPort(host_port=8080, container_port=80)),
    "image": lambda p: setattr(p.spec.containers[0], "image",
                               "registry/app:v2"),
    "volume": lambda p: p.spec.volumes.append(
        api.Volume(name="data", persistent_volume_claim="claim")),
    "node-selector": lambda p: p.spec.node_selector.update(
        {ZONE: "zone-1"}),
    "node-affinity-term": _node_affinity,
    "spread-constraint": lambda p: p.spec.topology_spread_constraints.append(
        api.TopologySpreadConstraint(
            max_skew=2, topology_key=ZONE,
            label_selector=api.LabelSelector(match_labels={"color": "b"}))),
    "soft-spread-constraint":
        lambda p: p.spec.topology_spread_constraints.append(
            api.TopologySpreadConstraint(
                max_skew=1, topology_key=HOSTNAME,
                when_unsatisfiable="ScheduleAnyway",
                label_selector=api.LabelSelector(
                    match_labels={"color": "b"}))),
    "required-affinity-term": lambda p: _pod_affinity(p)
    .required_during_scheduling_ignored_during_execution.append(_term()),
    "preferred-affinity-term": lambda p: _pod_affinity(p)
    .preferred_during_scheduling_ignored_during_execution.append(
        api.WeightedPodAffinityTerm(weight=7, pod_affinity_term=_term("r"))),
    "required-anti-affinity-term": lambda p: _pod_anti(p)
    .required_during_scheduling_ignored_during_execution.append(
        _term("green", HOSTNAME)),
    "preferred-anti-affinity-term": lambda p: _pod_anti(p)
    .preferred_during_scheduling_ignored_during_execution.append(
        api.WeightedPodAffinityTerm(weight=2,
                                    pod_affinity_term=_term("y", HOSTNAME))),
    "priority": lambda p: setattr(p.spec, "priority", 100),
    "container-request": lambda p: p.spec.containers[0].resources.requests
    .update(cpu="250m"),
    "container-limit": lambda p: p.spec.containers[0].resources.limits
    .update(cpu="500m"),
    "init-container": lambda p: p.spec.init_containers.append(
        api.Container(name="init", image="busybox", resources=(
            api.ResourceRequirements(requests={"cpu": "2"})))),
    "overhead": lambda p: p.spec.overhead.update(cpu="10m"),
    "namespace": lambda p: setattr(p.metadata, "namespace", "other"),
}


def variant_pod(base, name):
    pod = copy.deepcopy(base)
    VARIANTS[name](pod)
    return pod


_fresh = iter(range(1, 1 << 30))


def stamp(shape):
    """A pod of that shape with an identity and a status of its own, as
    a controller stamps them out."""
    pod = copy.deepcopy(shape)
    i = next(_fresh)
    pod.metadata.name = f"pod-{i}"
    pod.metadata.uid = f"uid-stamped-{i}"
    pod.metadata.resource_version = i
    pod.metadata.creation_timestamp = 1000.0 + i
    pod.status = api.PodStatus(conditions=[api.PodCondition(
        type="PodScheduled", status="False", message=f"attempt {i}")])
    return pod


def class_key(pod):
    """What two pods of one class share, written down independently of
    classify_pods: the dataclass reprs (dict order and all)."""
    m = pod.metadata
    return repr((m.namespace, m.labels, m.annotations, m.owner_references,
                 pod.spec))


# ------------------------------------------------------------ the world


def _nodes():
    """Nodes whose strings fill every vocabulary a batch row reads:
    zones, taints, images, an avoid annotation."""
    nodes = hollow.make_nodes(12, zones=3)
    nodes[0].spec.taints.append(api.Taint("dedicated", "gpu", "NoSchedule"))
    nodes[1].spec.taints.append(api.Taint("flaky", "", "PreferNoSchedule"))
    nodes[2].status.images.append(api.ContainerImage(
        names=["registry/app:v2", "k8s.gcr.io/pause:3.2"], size_bytes=1 << 20))
    nodes[3].metadata.annotations[api.PREFER_AVOID_PODS_ANNOTATION_KEY] = (
        json.dumps({"preferAvoidPods": [{"podSignature": {"podController": {
            "kind": "ReplicaSet", "uid": "rs-uid-1"}}}]}))
    return nodes


def _store():
    """A store with a Service and a ReplicaSet that select some of the
    shapes: DefaultPodTopologySpread's selector is live."""
    store = ClusterStore()
    store.add(api.Service(metadata=api.ObjectMeta(name="blue"),
                          selector={"color": "blue"}))
    store.add(api.Service(metadata=api.ObjectMeta(name="app0"),
                          selector={"app": "app-0"}))
    store.add(api.ReplicaSet(
        metadata=api.ObjectMeta(name="web"),
        selector=api.LabelSelector(match_labels={"tier": "web"})))
    return store


def _table_for(pinfos):
    sb = SnapshotBuilder()
    sb.intern_pending(pinfos)
    sb.build([NodeInfo(n) for n in _nodes()])
    return sb.table


def assert_same_batch(got, want):
    la, ta = jax.tree.flatten(got)
    lb, tb = jax.tree.flatten(want)
    assert ta == tb
    for i, (x, y) in enumerate(zip(la, lb)):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape, (i, x.dtype,
                                                           y.dtype, x.shape,
                                                           y.shape)
        assert np.array_equal(x, y), f"leaf {i} differs"


def build_both(pods, pad_b=None, store=None):
    """(the batch build() gives, the batch of the row builder called on
    every pod, the builder)."""
    pinfos = [PodInfo(p) for p in pods]
    store = store or _store()
    sels = [store.default_spread_selector(p) for p in pods]
    pb = PodBatchBuilder(_table_for(pinfos))
    got = pb.build(pinfos, pad_b=pad_b, spread_selectors=sels)
    want = pb._build_rows(pinfos, got.valid.shape[0], sels)
    return got, want, pb


def mixed_batch(seed, n):
    """n pods in a seeded order: the eight configurations' templates, and
    beside one of them its copies that differ from it in one field each;
    every shape stamped out several times."""
    rng = random.Random(seed)
    shapes = [template_pod(_config(name), rng.randrange(10))
              for name in CONFIGS]
    base = shapes[rng.randrange(len(shapes))]
    shapes += [variant_pod(base, v)
               for v in rng.sample(sorted(VARIANTS), 12)]
    pods = [stamp(s) for s in shapes]
    pods += [stamp(rng.choice(shapes)) for _ in range(n - len(pods))]
    rng.shuffle(pods)
    return pods


# ------------------------------------------------------------ (i) equal


@pytest.mark.parametrize("n", [200, 256], ids=["padded", "full-bucket"])
@pytest.mark.parametrize("seed", range(6))
def test_a_mixed_batch_equals_the_batch_built_a_pod_at_a_time(seed, n):
    pods = mixed_batch(seed, n)
    distinct = len({class_key(p) for p in pods})
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)
    assert got.valid.shape[0] == 256 and int(got.valid.sum()) == n
    assert pb.pod_classes == pb.rows_built == distinct < n // 2
    classes = classify_pods(pods)
    # pods of one class share the key, and no two classes do
    keys = [class_key(pods[r]) for r in classes.reps]
    assert len(set(keys)) == len(keys)
    assert all(class_key(p) == keys[k]
               for p, k in zip(pods, classes.class_of))
    # classes are numbered as first met, their first pod the representative
    assert classes.reps == sorted(classes.reps)
    assert [classes.class_of[r] for r in classes.reps] == list(
        range(len(classes.reps)))


def _one_field_apart():
    """(configuration, variant) wherever the variant changes the
    template (one without labels has no label to reorder)."""
    out = []
    for config in CONFIGS:
        base = template_pod(_config(config), 0)
        out += [(config, v) for v in sorted(VARIANTS)
                if class_key(variant_pod(base, v)) != class_key(base)]
    return out


@pytest.mark.parametrize("config,variant", _one_field_apart())
def test_one_field_apart_is_another_class(config, variant):
    base = template_pod(_config(config), 0)
    other = variant_pod(base, variant)
    pods = [stamp(base) for _ in range(5)] + [stamp(other) for _ in range(5)]
    random.Random(variant).shuffle(pods)
    classes = classify_pods(pods)
    assert len(classes.reps) == 2
    assert [class_key(p) == class_key(pods[0]) for p in pods] == [
        k == 0 for k in classes.class_of]
    got, want, pb = build_both(pods, pad_b=16)
    assert_same_batch(got, want)
    assert (pb.pod_classes, pb.rows_built) == (2, 2)


def test_equal_selectors_on_unequal_objects_share_a_class_and_unequal_none():
    """build() holds a pod's spread selector equal within a class too: a
    caller may hand it any selector a pod."""
    pods = [stamp(template_pod(_config(CONFIGS[0]), 0)) for _ in range(8)]
    pinfos = [PodInfo(p) for p in pods]
    pb = PodBatchBuilder(_table_for(pinfos))
    sel = [api.LabelSelector(match_labels={"app": "app-0"}) for _ in pods]
    got = pb.build(pinfos, spread_selectors=sel)
    assert pb.rows_built == 1
    assert_same_batch(got, pb._build_rows(pinfos, 8, sel))
    sel[5] = None
    sel[6] = api.LabelSelector(match_labels={"app": "app-1"})
    got = pb.build(pinfos, spread_selectors=sel)
    assert pb.rows_built == 3
    assert_same_batch(got, pb._build_rows(pinfos, 8, sel))


# --------------------------------------------------------- (ii) fallback


def _all_distinct(how, n=1024):
    base = template_pod(_config(CONFIGS[0]), 0)
    pods = []
    for i in range(n):
        pod = stamp(base)
        if how == "labels":
            pod.metadata.labels["job"] = f"job-{i}"
        else:       # alike in namespace and labels: told apart by == alone
            pod.spec.containers[0].resources.requests["cpu"] = f"{i + 1}m"
        pods.append(pod)
    return pods


@pytest.mark.parametrize("how", ["labels", "requests"])
def test_a_batch_of_all_distinct_pods_builds_a_row_a_pod(how):
    pods = _all_distinct(how)
    classes = classify_pods(pods)
    assert classes.class_of == classes.reps == list(range(1024))
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)
    assert (pb.pod_classes, pb.rows_built) == (1024, 1024)


@pytest.mark.parametrize("distinct,shares", [(512, True), (513, False)])
def test_more_classes_than_half_the_pods_is_every_pod_its_own(distinct,
                                                              shares):
    pods = _all_distinct("labels", distinct)
    pods += [stamp(pods[0]) for _ in range(1024 - distinct)]
    classes = classify_pods(pods)
    assert len(classes.reps) == (distinct if shares else 1024)
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)
    assert pb.rows_built == len(classes.reps)


def test_alike_pods_that_make_the_search_long_are_taken_for_distinct():
    """100 shapes that share namespace and labels, told apart by == of
    the spec alone: the search gives up (its compares are bounded) and
    every pod is its own class, which shares nothing and is never wrong."""
    shapes = _all_distinct("requests", 100)
    pods = [stamp(shapes[i % 100]) for i in range(1000)]
    classes = classify_pods(pods)
    assert classes.reps == list(range(1000))
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)


@pytest.mark.parametrize("n", [0, 1, 2, 3])
def test_tiny_batches(n):
    pods = [stamp(template_pod(_config(CONFIGS[0]), 0)) for _ in range(n)]
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)
    assert pb.rows_built == (n if n < 2 else 1)


# ---------------------------------------------- (iii) updated in place


def test_a_pod_updated_in_place_lands_in_another_class_the_next_time():
    pods = [stamp(template_pod(_config(CONFIGS[-1]), 0)) for _ in range(8)]
    first = classify_pods(pods)
    assert first.reps == [0] and set(first.class_of) == {0}
    before, _, _ = build_both(pods)
    pods[3].metadata.labels["color"] = "red"        # the same object
    second = classify_pods(pods)
    assert second.reps == [0, 3]
    assert second.class_of == [0, 0, 0, 1, 0, 0, 0, 0]
    got, want, pb = build_both(pods)
    assert_same_batch(got, want)
    assert pb.rows_built == 2
    # ...and its row says so: the rows differ where the label is read
    assert not np.array_equal(got.kv_ids[3], before.kv_ids[3])
    assert np.array_equal(got.kv_ids[2], got.kv_ids[0])
    # the spec's inside, too: a request edited in place
    pods[5].spec.containers[0].resources.requests["cpu"] = "3"
    assert classify_pods(pods).class_of == [0, 0, 0, 1, 0, 2, 0, 0]


# ------------------------------------------ (iv) what prepare shares


def _same_info(a, b):
    for slot in PodInfo.__slots__:
        x, y = getattr(a, slot), getattr(b, slot)
        if slot == "pod":
            assert x is y
        elif slot == "resource":
            assert all(getattr(x, f) == getattr(y, f) for f in x.__slots__)
        else:
            assert x == y, slot


@pytest.mark.parametrize("seed", range(4))
def test_the_shared_pod_infos_equal_a_parse_a_pod(seed):
    pods = mixed_batch(seed, 120)
    infos = class_pod_infos(pods, classify_pods(pods))
    assert len(infos) == len(pods)
    for pod, info in zip(pods, infos):
        assert info.pod is pod
        _same_info(info, PodInfo(pod))


class LabelGate(fw.FilterPlugin):
    """An out-of-tree host filter that cares about pods labelled for it:
    not covered by the device's volume mask."""
    NAME = "LabelGate"

    def relevant(self, pod):
        return "tier" in pod.metadata.labels

    def filter(self, state, pod, node_info):
        return fw.Status.success()


@pytest.fixture(scope="module")
def sched():
    store = _store()
    for n in _nodes():
        store.add(n)
    registry = dict(new_in_tree_registry())
    registry[LabelGate.NAME] = lambda args, handle: LabelGate()
    cfg = KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile(plugins=Plugins(
            filter=PluginSet(enabled=[Plugin(LabelGate.NAME)])))],
        batch_size=256, mode="gang", prewarm=False)
    s = Scheduler(store, config=cfg, registry=registry, async_binding=False)
    yield s
    s.close()


def _relevance_a_pod(fwk, qpods):
    """The walk the parent of PR 48 made: a (pod, plugin) at a time."""
    out = {}
    for qp in qpods:
        rel = unc = False
        for p in fwk.host_filter_plugins:
            if fwk._relevant(p, qp.pod):
                rel = True
                if p.name() not in DEVICE_COVERED_PLUGINS:
                    unc = True
                    break
        out[qp.pod.uid] = (rel, unc)
    return out


@pytest.mark.parametrize("seed", range(4))
def test_selectors_topology_keys_and_relevance_equal_the_walks_a_pod(
        sched, seed):
    fwk = next(iter(sched.profiles.values()))
    assert any(p.name() in DEVICE_COVERED_PLUGINS
               for p in fwk.host_filter_plugins)
    assert any(p.name() == LabelGate.NAME for p in fwk.host_filter_plugins)
    pods = mixed_batch(seed, 150)
    qpods = [QueuedPodInfo(pod=p) for p in pods]
    classes = classify_pods(pods)
    # the relevance map, with and without the grouping handed in
    want = _relevance_a_pod(fwk, qpods)
    assert sched._host_relevance(fwk, qpods, classes) == want
    assert sched._host_relevance(fwk, qpods) == want
    verdicts = set(want.values())
    if any(p.spec.volumes for p in pods):
        assert (True, False) in verdicts
    assert (False, False) in verdicts
    # PreFilter's and the host scorers' relevant plugins, once a class
    for plugins in (fwk.host_pre_filter_plugins, fwk.host_score_plugins,
                    fwk.host_filter_plugins):
        per_class = [fwk.relevant_plugins(plugins, pods[r])
                     for r in classes.reps]
        for pod, k in zip(pods, classes.class_of):
            assert per_class[k] == [p for p in plugins
                                    if fwk._relevant(p, pod)]
    # the default spread selector: computed for the representative
    store = sched.store
    rep_sels = [store.default_spread_selector(pods[r]) for r in classes.reps]
    assert any(s is not None for s in rep_sels)
    for pod, k in zip(pods, classes.class_of):
        assert store.default_spread_selector(pod) == rep_sels[k]
    # the batch's topology keys: the representatives' are the batch's
    infos = class_pod_infos(pods, classes)
    table = _table_for(infos)
    assert sched._batch_topo_keys(
        table, [infos[r] for r in classes.reps]) == sched._batch_topo_keys(
            table, [PodInfo(p) for p in pods])
    # what the representatives intern is what every pod would
    a, b = SnapshotBuilder(), SnapshotBuilder()
    a.intern_pending([infos[r] for r in classes.reps])
    b.intern_pending([PodInfo(p) for p in pods])
    for vocab in ("kv", "key", "ns", "port", "topokey"):
        va, vb = getattr(a.table, vocab), getattr(b.table, vocab)
        assert len(va) == len(vb)
        assert [va.key(i) for i in range(len(va))] == [
            vb.key(i) for i in range(len(vb))]


def test_a_cycle_places_a_mixed_batch_and_says_what_it_shared(sched):
    """Through _prepare_group itself: the cycle's batch is the batch a pod
    at a time would give, and its record says the classes (no pod of it
    has a volume: the store knows no claim)."""
    from kubetpu.utils import trace as utrace
    pods = [p for p in mixed_batch(7, 100) if not p.spec.volumes
            and p.metadata.namespace == "default"
            and not p.spec.node_selector]
    for p in pods:
        sched.store.add(p)
    utrace.disarm_flight_recorder()
    flight = utrace.arm_flight_recorder(capacity=8, max_spans_per_cycle=64)
    try:
        fwk = next(iter(sched.profiles.values()))
        qpods = sched.queue.pop_batch(256, timeout=0.5)
        assert len(qpods) == len(pods)
        prep, early = sched._prepare_group(fwk, qpods)
        assert prep is not None and not early
        prep.trace.finish()
        (rec,) = [c.to_dict() for c in flight.cycles()]
    finally:
        utrace.disarm_flight_recorder()
    live = [qp.pod for qp in prep.live]
    distinct = len({class_key(p) for p in live})
    assert rec["meta"]["pod_classes"] == rec["meta"]["rows_built"] == distinct
    (build,) = [s for s in rec["spans"] if s["name"] == "batch-build"]
    assert build["args"]["pods"] == len(live)
    assert build["args"]["rows_built"] == distinct
    assert [s["name"] for s in rec["spans"]].count("classify") == 2
    # the batch the cycle dispatches is the one built a pod at a time
    pb = PodBatchBuilder(prep.builder.table)
    pinfos = [PodInfo(p) for p in live]
    sels = [sched.store.default_spread_selector(p) for p in live]
    assert_same_batch(prep.batch, jax.tree.map(np.asarray, pb._build_rows(
        pinfos, prep.batch.valid.shape[0], sels)))
    for got, pod in zip(prep.pinfos, live):
        _same_info(got, PodInfo(pod))
    assert prep.relevance == _relevance_a_pod(fwk, prep.live)


# ------------------------------------- the contract of relevant(pod)


def _plugins_with_relevant():
    handle = SimpleNamespace(client=ClusterStore())
    args = {"ServiceAffinity": {"affinityLabels": ["zone"]},
            "NodeLabel": {"presentLabels": ["zone"]}}
    out = {}
    for name, factory in new_in_tree_registry().items():
        try:
            plugin = factory(args.get(name), handle)
        except Exception:   # a plugin that needs arguments this test lacks
            plugin = None
        if plugin is not None and hasattr(plugin, "relevant"):
            out[name] = plugin
    return out


RELEVANT = _plugins_with_relevant()


def test_the_in_tree_plugins_that_define_relevant_are_the_ones_held():
    assert set(RELEVANT) == {
        "ServiceAffinity", "VolumeBinding", "VolumeRestrictions",
        "VolumeZone", "NodeVolumeLimits", "EBSLimits", "GCEPDLimits",
        "AzureDiskLimits", "CinderLimits"}


class _ClassOnly:
    """A pod's metadata that gives out the class key's fields and
    nothing else."""

    def __init__(self, meta):
        object.__setattr__(self, "_given", {
            "namespace": meta.namespace, "labels": meta.labels,
            "annotations": meta.annotations,
            "owner_references": meta.owner_references})

    def __getattr__(self, name):
        given = object.__getattribute__(self, "_given")
        if name not in given:
            raise AssertionError(f"relevant() read metadata.{name}")
        return given[name]


def _volume_shapes():
    base = template_pod(_config(CONFIGS[0]), 0)
    shapes = [base]
    for vol in (api.Volume(name="a", persistent_volume_claim="claim"),
                api.Volume(name="b", gce_persistent_disk="pd"),
                api.Volume(name="c", aws_elastic_block_store="vol"),
                api.Volume(name="d", azure_disk="disk"),
                api.Volume(name="e", cinder="cv"),
                api.Volume(name="f", iscsi=("portal", 0, "iqn")),
                api.Volume(name="g", rbd=("mon", "pool", "img")),
                api.Volume(name="h", host_path="/tmp"),
                api.Volume(name="i", empty_dir=True)):
        pod = copy.deepcopy(base)
        pod.spec.volumes.append(vol)
        shapes.append(pod)
    return shapes


@pytest.mark.parametrize("name", sorted(RELEVANT))
def test_relevant_reads_the_class_key_and_nothing_else(name):
    plugin = RELEVANT[name]
    answers = set()
    for shape in _volume_shapes():
        a, b = stamp(shape), stamp(shape)
        assert (a.metadata.name, a.metadata.uid, a.status) != (
            b.metadata.name, b.metadata.uid, b.status)
        assert classify_pods([a, b, stamp(shape), stamp(shape)]).reps == [0]
        want = plugin.relevant(a)
        assert plugin.relevant(b) is want or plugin.relevant(b) == want
        # ...and it cannot tell: identity and status are not there to read
        bare = stamp(shape)
        bare.metadata = _ClassOnly(bare.metadata)
        del bare.status
        assert bool(plugin.relevant(bare)) == bool(want)
        answers.add(bool(want))
    if name != "ServiceAffinity":       # that one reads its own arguments
        assert answers == {True, False}

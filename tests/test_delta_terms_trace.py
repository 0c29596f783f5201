"""What the term refresh says about itself (PR 29): the ``delta-terms``
span's args (rows rebuilt, their buckets, pods walked, owners changed),
the ``delta-terms-upload`` span around the wholesale replacement on the
device, and the ``(Et, Es)`` the auction ran with on the cycle's meta."""

import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.state.cache import SchedulerCache, Snapshot
from kubetpu.state.delta import DeltaTensorizer
from kubetpu.utils import trace as utrace

ARGS = {"filter_rows", "score_rows", "Et", "Es", "pods_walked",
        "owners_changed"}


def _owner(name, node="", preferred=False):
    """A pod that owns one term: required hostname anti-affinity to its
    own label, or (``preferred``) a weight-1 preferred affinity."""
    p = hollow.make_pod(name)
    p.metadata.labels = {"color": "red" if preferred else "green"}
    if preferred:
        p.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity())
        p.spec.affinity.pod_affinity \
            .preferred_during_scheduling_ignored_during_execution.append(
                api.WeightedPodAffinityTerm(
                    weight=1, pod_affinity_term=api.PodAffinityTerm(
                        label_selector=api.LabelSelector(
                            match_labels={"color": "red"}),
                        topology_key=api.LABEL_HOSTNAME)))
    else:
        hollow.with_anti_affinity(p)
    p.spec.node_name = node
    return p


def _snapshot(cache):
    snap = Snapshot()
    cache.update_snapshot(snap)
    return snap.node_info_list


def test_a_refresh_says_what_it_rebuilt_and_how_many_owners_changed():
    cache = SchedulerCache()
    nodes = hollow.make_nodes(6, zones=3)
    for i, n in enumerate(nodes):
        cache.add_node(n)
        cache.add_pod(_owner(f"green-{i}", n.name))
        if i % 2:
            cache.add_pod(_owner(f"red-{i}", n.name, preferred=True))
    dt = DeltaTensorizer()
    _, st = dt.refresh(_snapshot(cache))
    assert st.resync and st.span_args == {}

    def refresh():
        _, st = dt.refresh(_snapshot(cache))
        assert not st.resync, st.reason
        names = [n for n, _, _ in st.spans]
        assert names == ["delta-build", "delta-terms", "delta-terms-upload",
                         "delta-apply"]
        # the upload lies inside the apply, after the refresh
        at = {n: (t0, t1) for n, t0, t1 in st.spans}
        assert at["delta-terms"][1] <= at["delta-terms-upload"][0] \
            <= at["delta-terms-upload"][1] <= at["delta-apply"][1]
        assert set(st.span_args) == {"delta-terms"}
        assert set(st.span_args["delta-terms"]) == ARGS
        return st.span_args["delta-terms"]

    # a PLAIN pod lands on a node that holds a term owner: the tables are
    # rebuilt whole, and no owner had changed
    plain = hollow.make_pod("plain-0")
    plain.spec.node_name = nodes[0].name
    cache.add_pod(plain)
    assert refresh() == {"filter_rows": 6, "score_rows": 3, "Et": 8, "Es": 4,
                         "pods_walked": 10, "owners_changed": 0}
    # an owner arrives; an owner moves (one uid: counted once); one leaves
    extra = _owner("green-extra", nodes[1].name)
    cache.add_pod(extra)
    got = refresh()
    assert (got["filter_rows"], got["pods_walked"],
            got["owners_changed"]) == (7, 11, 1)
    cache.remove_pod(extra)
    extra.spec.node_name = nodes[2].name
    cache.add_pod(extra)
    assert refresh()["owners_changed"] == 1
    cache.remove_pod(extra)
    got = refresh()
    assert (got["filter_rows"], got["owners_changed"]) == (6, 1)
    # nothing dirty: no refresh, nothing to say
    _, st = dt.refresh(_snapshot(cache))
    assert st.delta_rows == 0 and st.span_args == {}


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def test_the_cycle_record_carries_the_refreshs_args_and_the_term_buckets(
        flight):
    """Upstream's Mixed row in small: every node holds a term owner, so
    every cycle's dirty nodes mark the terms dirty.  Plain traffic (a
    batch binds, an older pod leaves: the departure is what takes the
    cycle off the chain and onto the delta path, as the benchmark's
    client does) reads ``owners_changed`` 0; the cycle after a labelled
    owner bound reads 1."""
    store = ClusterStore()
    nodes = hollow.make_nodes(12, zones=1)
    for i, n in enumerate(nodes):
        store.add(n)
        store.add(_owner(f"green-{i}", n.name))
        store.add(_owner(f"red-{i}", n.name, preferred=True))
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang"),
        async_binding=False)

    def cycle(pods, leaves=None):
        before = len(flight.cycles())
        if leaves is not None:
            store.delete(store.get_pod("default", leaves))
        for p in pods:
            store.add(p)
        while sched.schedule_pending(timeout=0.0):
            pass
        return [c.to_dict() for c in flight.cycles()][before:]
    try:
        cycle(hollow.make_pods(8, prefix="warm-"))          # the resync
        plain = cycle(hollow.make_pods(8, prefix="plain-"), leaves="warm-0")
        cycle([_owner("red-new", preferred=True)], leaves="warm-1")  # binds
        after = cycle(hollow.make_pods(8, prefix="later-"), leaves="warm-2")
    finally:
        sched.close()

    def refresh_of(records):
        assert len(records) == 1 and records[0]["meta"]["resync"] is False
        spans = {s["name"]: s for s in records[0]["spans"]}
        assert {"delta-terms", "delta-terms-upload"} <= set(spans)
        # beside the tensorizer's other spans in the tree, inside the phase
        for name in ("delta-terms", "delta-terms-upload"):
            assert spans[name]["parent"] == spans["delta-build"]["parent"]
            assert spans["tensorize"]["t0"] <= spans[name]["t0"] \
                <= spans[name]["t1"] <= spans["tensorize"]["t1"]
        args = spans["delta-terms"]["args"]
        assert ARGS <= set(args)
        assert records[0]["meta"]["term_buckets"] == [args["Et"], args["Es"]]
        return args
    first, second = refresh_of(plain), refresh_of(after)
    assert (first["filter_rows"], first["score_rows"], first["Et"],
            first["Es"], first["owners_changed"]) == (12, 12, 16, 16, 0)
    assert first["pods_walked"] == 24 + 8 - 1
    assert (second["score_rows"], second["owners_changed"]) == (13, 1)

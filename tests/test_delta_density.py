"""The delta path and its counters at the density of a full cluster
(PR 35, ``sigscale-150k``: 29-30 bound pods a node where the older rows
hold one or two).  Arrivals and departures on a cluster of 1 and of 30
pods a node are held to a fresh build (``test_delta.assert_matches_fresh``),
and the four things the flight recorder says since PR 35 are counted by
hand: the ``delta-build`` span's ``pods_walked``, the ``snapshot`` span's
``pods_copied``, and the cycle meta ``pod_rows_live`` and
``cluster_device_bytes`` (``ClusterTensors.nbytes``).  Since PR 44 the
refresh visits and sends what CHANGED: ``pods_walked`` is the arrivals
(no pod here owns a term), ``pod_rows_seen`` the rows refilled or
cleared, at 1 and at 30 pods a node alike; the snapshot still copies the
dirty nodes whole."""

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.state.cache import Snapshot
from kubetpu.state.delta import DeltaTensorizer
from kubetpu.utils import trace as utrace

from test_delta import assert_matches_fresh, build_cache

N_NODES = 8


def _shape_bytes(tree) -> int:
    """A pytree's bytes from its leaves' shapes and dtypes alone: the
    count ``ClusterTensors.nbytes`` is held to."""
    import jax
    import numpy as np
    return sum(int(np.prod(x.shape)) * np.dtype(x.dtype).itemsize
               for x in jax.tree.leaves(tree))


def _arrive(cache, node, name):
    p = hollow.make_pod(name)
    p.metadata.labels = {"app": "app-1", "group": "measured"}
    p.spec.node_name = node.name
    cache.add_pod(p)
    return p


@pytest.mark.parametrize("per_node", [1, 30])
def test_arrivals_and_departures_stay_golden_and_say_what_they_walked(
        per_node):
    """Six refreshes: on k of the 8 nodes one pod arrives and (from the
    second refresh on) the one that arrived a refresh earlier leaves, as
    the benchmark's client does.  After every one the device tensors are
    a fresh build's, the refresh walked the arrivals and no other pod,
    sent the rows it refilled or cleared and no other, and the snapshot
    copied the pods of the dirty nodes."""
    cache, nodes, _ = build_cache(n_nodes=N_NODES, pods_per_node=per_node)
    snap, dt = Snapshot(), DeltaTensorizer()
    cache.update_snapshot(snap)
    # the first snapshot clones every node: every pod-list entry
    assert snap.pods_copied == N_NODES * per_node
    dt.refresh(snap.node_info_list)
    assert_matches_fresh(dt, snap.node_info_list)
    assert dt.cluster.nbytes == _shape_bytes(dt.cluster) > 0
    bytes0 = dt.cluster.nbytes
    last = []
    for cycle in range(6):
        k = 1 + cycle % 3
        dirty = [nodes[(cycle + 2 * j) % N_NODES] for j in range(k)]
        cleared = {dt.pod_row[p.uid] for p in last}
        for p in last:
            cache.remove_pod(p)
        gone = {p.spec.node_name for p in last}
        last = [_arrive(cache, n, f"new-{cycle}-{j}")
                for j, n in enumerate(dirty)]
        cache.update_snapshot(snap)
        infos = snap.node_info_list
        _, st = dt.refresh(infos)
        assert_matches_fresh(dt, infos)
        names = gone | {n.name for n in dirty}
        on_dirty = sum(len(ni.pods) for ni in infos
                       if ni.node.metadata.name in names)
        # a node an arrival left and none reached holds per_node pods, a
        # node one reached per_node + 1
        assert on_dirty == len(names) * per_node + len(dirty)
        assert snap.pods_copied == on_dirty
        if st.resync:       # the pod axis grew past its bucket: re-upload
            assert st.reason == "pod-axis-growth"
            assert dt.cluster.nbytes > bytes0
        args = st.span_args["delta-build"]
        assert args["node_rows_dirty"] == len(names)
        assert args["pods_walked"] == args["pod_rows_refilled"] == len(dirty)
        # an arrival takes the lowest free row: a row just cleared counts
        # once
        rows = cleared | {dt.pod_row[p.uid] for p in last}
        assert args["pod_rows_seen"] == len(rows) <= len(dirty) + len(gone)
        assert st.delta_rows == len(names) + len(rows)
    # an unchanged cache copies and walks nothing
    cache.update_snapshot(snap)
    assert snap.pods_copied == 0
    assert dt.refresh(snap.node_info_list)[1].delta_rows == 0


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


@pytest.mark.parametrize("per_node", [1, 29])
def test_the_cycle_record_says_rows_live_bytes_walked_and_copied(
        flight, per_node):
    """A toy of the ``sigscale-150k`` shape through the scheduler: 12
    nodes of ``per_node`` bound pods, batches of 8 with an older pod
    leaving before each (which takes the cycle onto the delta path)."""
    store = ClusterStore()
    nodes = hollow.make_nodes(12, zones=2)
    for i, n in enumerate(nodes):
        store.add(n)
        for p in hollow.make_pods(per_node, prefix=f"init-{i}-"):
            p.spec.node_name = n.name
            store.add(p)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang"),
        async_binding=False)
    bound = 12 * per_node
    try:
        for cycle in range(3):
            if cycle:
                store.delete(store.get_pod("default", f"c{cycle - 1}-0"))
            for p in hollow.make_pods(8, prefix=f"c{cycle}-"):
                store.add(p)
            while sched.schedule_pending(timeout=0.0):
                pass
    finally:
        sched.close()
    records = [c.to_dict() for c in flight.cycles()]
    assert len(records) == 3
    for cycle, rec in enumerate(records):
        meta = rec["meta"]
        spans = {s["name"]: s for s in rec["spans"]}
        # the pods bound when the cycle was prepared: the init pods and
        # the earlier batches, less one departure before each cycle but
        # the first
        assert meta["pod_rows_live"] == bound + 8 * cycle - cycle
        assert meta["pod_rows_live"] <= meta["pod_bucket"]
        assert meta["cluster_device_bytes"] > 0
        copied = spans["snapshot"]["args"]["pods_copied"]
        if cycle == 0:
            assert copied == bound            # the first snapshot: all
            continue
        # the snapshot copied the nodes the last batch and the departure
        # touched, whole; the build visited and sent the seven of the
        # batch's eight that are still bound (the one that left never had
        # a row: nothing is cleared)
        build = spans["delta-build"]["args"]
        assert build["node_rows_dirty"] * per_node <= copied \
            <= build["node_rows_dirty"] * (per_node + 8)
        assert build["pods_walked"] == build["pod_rows_refilled"] == 7
        assert build["pod_rows_seen"] == 7
        assert meta["delta_rows"] \
            == build["node_rows_dirty"] + build["pod_rows_seen"]
    # bytes are the resident cluster's leaves, from shapes
    assert records[-1]["meta"]["cluster_device_bytes"] \
        == _shape_bytes(sched._delta["default-scheduler"].cluster)

"""The binder lane applies a job column-wise (PR 41): one store
transaction, one confirmation in the cache and the queue, one write of the
job's events and one fold of its histograms a job -- and leaves what the
binding cycle a pod at a time leaves."""
import threading
import time

import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile, Plugin, Plugins,
                                 PluginSet)
from kubetpu.client.store import ClusterStore, Conflict, NotFound
from kubetpu.framework import interface as fw
from kubetpu.framework.interface import Code, Status, WaitingPod
from kubetpu.framework.runtime import Framework
from kubetpu.harness import hollow
from kubetpu.plugins.intree import new_in_tree_registry
from kubetpu.schedqueue.queue import SchedulingQueue
from kubetpu.scheduler import Scheduler
from kubetpu.state.cache import SchedulerCache
from kubetpu.utils import trace as utrace
from kubetpu.utils.events import EventBroadcaster
from kubetpu.utils.metrics import SchedulerMetrics

LANE = "binder-lane"
WAIT = 10.0


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


@pytest.fixture(params=["batch", "a-pod-at-a-time"])
def how(request, monkeypatch):
    """The same job both ways: as the lane runs it, and with every row
    forced through ``_bind_cycle`` (no profile binds a list)."""
    if request.param != "batch":
        monkeypatch.setattr(Framework, "batch_binder", lambda self: None)
    return request.param


class Watcher:
    """A per-event subscriber: what it saw, in the order it saw it."""

    def __init__(self, store):
        self.seen = []
        store.subscribe("Pod", self)

    def __call__(self, event, old, new):
        pod = new if new is not None else old
        self.seen.append((event, pod.metadata.name,
                          old.spec.node_name if old is not None else None,
                          new.spec.node_name if new is not None else None,
                          pod.metadata.resource_version,
                          threading.current_thread().name))

    def binds(self):
        return [s[1] for s in self.seen if s[0] == "update" and s[3]
                and not s[2]]


def _world(store=None, nodes=16, pods=24, batch=8, registry=None,
           plugins=None, metrics=None, names=None, **cfg):
    store = store if store is not None else ClusterStore()
    for n in hollow.make_nodes(nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile(plugins=plugins)], batch_size=batch,
        mode="gang", **cfg), registry=registry, metrics=metrics)
    for p in (hollow.make_pods(pods) if names is None
              else [hollow.make_pod(n) for n in names]):
        store.add(p)
    return store, sched


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            return outs
        outs.extend(got)


def _until(cond, what):
    deadline = time.monotonic() + WAIT
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def _jobs(flight):
    return [s["args"] for c in flight.cycles()
            for s in c.to_dict()["spans"] if s["name"] == "bind-job"]


def _state(store, sched, watchers, metrics=None):
    """Everything the binding cycle leaves behind, by name (uids come
    from a process-wide counter and differ between two worlds)."""
    cache = sched.cache
    with cache._lock:
        cached = {st.pod.metadata.name: (
            st.pod.spec.node_name, uid in cache.assumed_pods,
            st.deadline is not None, st.binding_finished)
            for uid, st in cache.pod_states.items()}
    q = sched.queue
    out = {
        "pods": {p.metadata.name: (p.spec.node_name, p.status.phase,
                                   p.metadata.resource_version,
                                   [(c.type, c.status, c.message)
                                    for c in p.status.conditions])
                 for p in store.list("Pod")},
        "events": [(e.metadata.name, e.metadata.namespace,
                    e.metadata.resource_version, e.involved_name, e.type,
                    e.reason, e.message, e.count)
                   for e in store.list("Event")],
        "watch": [[s[:5] for s in w.seen] for w in watchers],
        "cache": cached,
        "queue": (q.depths(), sorted(p.metadata.name
                                     for p in q.pending_pods()),
                  q.scheduling_cycle, q.move_request_cycle),
    }
    if metrics is not None:
        # without the gauges a cycle samples while the job before it may
        # or may not have landed
        out["metrics"] = "\n".join(
            line for line in metrics.expose_text().splitlines()
            if not line.startswith(("scheduler_scheduler_cache_size",
                                    "scheduler_pending_pods")))
    return out


# ------------------------------------- (a) the same state, either way


def _run_plain_job(frozen_clock, monkeypatch):
    if frozen_clock:
        # every duration reads 0.0 either way, a pod's queue stamps
        # included: the sums are compared too (utrace.wallclock reads
        # perf_counter)
        monkeypatch.setattr(time, "time", lambda: 1e9)
        monkeypatch.setattr(time, "perf_counter", lambda: 1e6)
    m = SchedulerMetrics()
    store = ClusterStore()
    early = Watcher(store)              # subscribed before the scheduler
    store, sched = _world(store=store, metrics=m)
    late = Watcher(store)
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        monkeypatch.undo()
        assert sum(1 for o in outs if o.node) == 24
        return _state(store, sched, [early, late], m), m
    finally:
        monkeypatch.undo()
        sched.close()


def test_a_job_through_the_batch_leaves_what_a_pod_at_a_time_leaves(
        monkeypatch):
    batch, mb = _run_plain_job(True, monkeypatch)
    monkeypatch.setattr(Framework, "batch_binder", lambda self: None)
    single, ms = _run_plain_job(True, monkeypatch)
    for part in batch:
        assert batch[part] == single[part], part
    # and it is the real thing that was compared
    assert len(batch["events"]) == 24
    assert all(e[5] == "Scheduled" and e[7] == 1 for e in batch["events"])
    assert [e[0].rsplit(".", 1)[1] for e in batch["events"]] == [
        format(i, "x") for i in range(1, 25)]
    assert all(node and rv == 2 for node, _, rv, _ in batch["pods"].values())
    assert all(c == (c[0], False, False, False)
               for c in batch["cache"].values()) and len(batch["cache"]) == 24
    assert batch["queue"][0] == {"active": 0, "backoff": 0,
                                 "unschedulable": 0}
    for m in (mb, ms):
        for point in ("PreBind", "Bind", "PostBind"):
            assert m.framework_extension_point_duration.count(
                point, "Success") == 24
        assert m.binding_duration.count() == 24
        assert m.schedule_attempts.value("scheduled") == 24.0


def test_with_real_clocks_the_counts_and_label_sets_are_the_same(
        monkeypatch, flight):
    def series(text):
        return sorted(line.rsplit(" ", 1) for line in text.splitlines()
                      if line and not line.startswith("#")
                      and "_sum" not in line and "le=" not in line)
    batch, _ = _run_plain_job(False, monkeypatch)
    assert [(a["pods"], a["batched"]) for a in _jobs(flight)] == [(8, 8)] * 3
    monkeypatch.setattr(Framework, "batch_binder", lambda self: None)
    single, _ = _run_plain_job(False, monkeypatch)
    assert [(a["pods"], a["batched"]) for a in _jobs(flight)][3:] \
        == [(8, 0)] * 3
    assert series(batch.pop("metrics")) == series(single.pop("metrics"))
    assert batch == single


def test_a_batch_is_one_transaction_seen_whole_then_event_by_event():
    """Every bind of a job is in the store before its first watch event;
    the scheduler (a list-taking subscriber) has confirmed them all by
    then; each per-event subscriber sees them in store order."""
    store = ClusterStore()
    whole, seen_at = [], []
    store.subscribe("Pod", lambda evs: whole.append(
        [(e, new.metadata.name) for e, _, new in evs if e == "update"]),
        batched=True)
    store, sched = _world(store=store, pods=8)

    def on_pod(event, old, new):
        if event == "update" and new.spec.node_name:
            seen_at.append((new.metadata.name,
                            sum(1 for p in store.list("Pod")
                                if p.spec.node_name),
                            len(sched.cache.assumed_pods)))
    store.subscribe("Pod", on_pod)
    try:
        outs = sched.schedule_pending(timeout=0.0)
        sched.wait_for_inflight_binds(timeout=WAIT)
        order = [o.pod.metadata.name for o in outs]
        assert [n for n, _, _ in seen_at] == order
        assert all(bound == 8 and assumed == 0
                   for _, bound, assumed in seen_at)
        assert [w for w in whole if w] == [[("update", n) for n in order]]
    finally:
        sched.close()


# --------------------------- (b) a bind the store rejects inside a batch


class _Tampered(ClusterStore):
    """A foreign writer gets in between the commit and the job: ``gone``
    is deleted and ``taken`` bound elsewhere before the transaction."""

    def bind_many(self, pairs):
        names = {pod.metadata.name for pod, _ in pairs}
        if {"gone", "taken"} <= names:
            self.delete(self.get_pod("default", "gone"))
            ClusterStore.bind(self, self.get_pod("default", "taken"),
                              "node-15")
        return super().bind_many(pairs)

    def bind(self, pod, node_name):     # the same, a pod at a time
        if pod.metadata.name == "p0" and self.get_pod("default", "gone"):
            self.delete(self.get_pod("default", "gone"))
            ClusterStore.bind(self, self.get_pod("default", "taken"),
                              "node-15")
        ClusterStore.bind(self, pod, node_name)


class _Unreserves(fw.ReservePlugin, fw.UnreservePlugin):
    NAME = "Unreserves"
    log = []

    def name(self):
        return self.NAME

    def reserve(self, state, pod, node_name):
        return Status.success()

    def unreserve(self, state, pod, node_name):
        _Unreserves.log.append(pod.metadata.name)


def test_a_rejected_bind_mid_batch_requeues_its_pod_and_the_rest_bind(how):
    _Unreserves.log = []
    registry = dict(new_in_tree_registry())
    registry[_Unreserves.NAME] = lambda args, handle: _Unreserves()
    names = ["p0", "p1", "gone", "p2", "taken", "p3", "p4", "p5"]
    store = _Tampered()
    if how == "batch":                  # its own bind has no say then
        del _Tampered.bind
    try:
        store, sched = _world(
            store=store, registry=registry, names=names, bind_retries=0,
            plugins=Plugins(
                reserve=PluginSet(enabled=[Plugin(_Unreserves.NAME)]),
                unreserve=PluginSet(enabled=[Plugin(_Unreserves.NAME)])))
        watch = Watcher(store)
        try:
            outs = sched.schedule_pending(timeout=0.0)
            assert len(outs) == 8 and all(o.node for o in outs)
            sched.wait_for_inflight_binds(timeout=WAIT)
            placed = {o.pod.metadata.name: o.node for o in outs}
            rest = set(names) - {"gone", "taken"}
            bound = {p.metadata.name: p.spec.node_name
                     for p in store.list("Pod")}
            assert {n: bound[n] for n in rest} == {n: placed[n]
                                                  for n in rest}
            assert "gone" not in bound and bound["taken"] == "node-15"
            # the batch's binds reached the store in batch order
            assert [n for n in watch.binds() if n in rest] == [
                n for n in names if n in rest]
            # both forgotten, unreserved, requeued; the cache holds what
            # the store holds
            assert sorted(_Unreserves.log) == ["gone", "taken"]
            assert sorted(p.metadata.name
                          for p in sched.queue.pending_pods()) == [
                              "gone", "taken"]
            assert not sched.cache.assumed_pods
            with sched.cache._lock:
                cached = {st.pod.metadata.name: st.pod.spec.node_name
                          for st in sched.cache.pod_states.values()}
            assert cached == bound
            cond = {c.type: c for c in store.get_pod(
                "default", "taken").status.conditions}[api.POD_SCHEDULED]
            assert cond.status == "False"
            assert "already assigned to node node-15" in cond.message
        finally:
            sched.close()
    finally:
        _Tampered.bind = _TAMPERED_BIND


_TAMPERED_BIND = _Tampered.__dict__["bind"]


class _ResetsOnce(ClusterStore):
    """The transaction's answer for ``flaky`` dies on the wire, its bind
    unapplied; whoever retries it binds a pod at a time."""

    def __init__(self):
        super().__init__()
        self.dropped = []

    def bind_many(self, pairs):
        keep = [pr for pr in pairs if pr[0].metadata.name != "flaky"]
        if len(keep) == len(pairs):
            return super().bind_many(pairs)
        self.dropped.append(threading.current_thread().name)
        got = iter(super().bind_many(keep))
        return [OSError("connection reset by peer")
                if pod.metadata.name == "flaky" else next(got)
                for pod, _ in pairs]


def test_a_rejected_row_with_retries_left_goes_to_the_pool(flight):
    names = ["p0", "p1", "flaky", "p2", "p3", "p4", "p5", "p6"]
    store, sched = _world(store=_ResetsOnce(), names=names, bind_retries=2,
                          pod_initial_backoff_seconds=0.3,
                          pod_max_backoff_seconds=0.3)
    watch = Watcher(store)
    try:
        t0 = time.monotonic()
        sched.schedule_pending(timeout=0.0)
        rest = [n for n in names if n != "flaky"]
        # the lane's job is over while the ladder still sleeps
        _until(lambda: _jobs(flight), "the job's span")
        lane_s = time.monotonic() - t0
        assert watch.binds() == rest
        (job,) = _jobs(flight)
        assert (job["pods"], job["batched"], job["pooled"]) == (8, 8, 1)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert time.monotonic() - t0 >= 0.3 > lane_s
        assert watch.binds() == rest + ["flaky"]
        by = {s[1]: s[5] for s in watch.seen if s[0] == "update" and s[3]}
        assert {by[n] for n in rest} == {LANE}
        assert by["flaky"].startswith("binder_")        # a pool thread
        assert store.dropped == [LANE]
        (c,) = [c.to_dict() for c in flight.cycles()]
        (commit,) = [s for s in c["spans"] if s["name"] == "commit"]
        assert commit["args"]["binds_pooled"] == 1
        row = c["binds"][c["meta"]["batch_pods"].index("flaky")]
        assert 0.0 < row[0] <= row[1] <= row[2]
        assert not sched.cache.assumed_pods and len(sched.queue) == 0
    finally:
        sched.close()


# ------------------------ (c) rows that cannot ride a batch keep their place


class _WaitOnSlow(fw.PermitPlugin):
    NAME = "WaitOnSlow"

    def name(self):
        return self.NAME

    def permit(self, state, pod, node_name):
        if pod.metadata.name == "slow":
            return Status(Code.WAIT), WAIT
        return Status.success(), 0.0


class _PreBindsVol(fw.PreBindPlugin):
    NAME = "PreBindsVol"
    log = []

    def name(self):
        return self.NAME

    def relevant(self, pod):
        return pod.metadata.name == "vol"

    def pre_bind(self, state, pod, node_name):
        _PreBindsVol.log.append((pod.metadata.name,
                                 threading.current_thread().name))
        return Status.success()


def test_a_mixed_job_keeps_batch_order_in_the_store(flight):
    _PreBindsVol.log = []
    registry = dict(new_in_tree_registry())
    registry[_WaitOnSlow.NAME] = lambda args, handle: _WaitOnSlow()
    registry[_PreBindsVol.NAME] = lambda args, handle: _PreBindsVol()
    names = ["p0", "p1", "slow", "p2", "vol", "p3", "p4", "p5"]
    m = SchedulerMetrics()
    store, sched = _world(
        registry=registry, names=names, metrics=m,
        plugins=Plugins(
            permit=PluginSet(enabled=[Plugin(_WaitOnSlow.NAME)]),
            pre_bind=PluginSet(enabled=[Plugin(_PreBindsVol.NAME)])))
    watch = Watcher(store)
    fwk = next(iter(sched.profiles.values()))
    try:
        sched.schedule_pending(timeout=0.0)
        lane = [n for n in names if n != "slow"]
        _until(lambda: len(watch.binds()) == 7, "the lane's binds")
        # p0 p1 | vol a pod at a time, in its place | p3 p4 p5
        assert watch.binds() == lane
        assert _PreBindsVol.log == [("vol", LANE)]
        fwk.get_waiting_pod(store.get_pod("default", "slow").uid).allow(
            _WaitOnSlow.NAME)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert watch.binds() == lane + ["slow"]
        (job,) = _jobs(flight)
        assert (job["pods"], job["batched"], job["pooled"]) == (7, 6, 0)
        (c,) = [c.to_dict() for c in flight.cycles()]
        rows = dict(zip(c["meta"]["batch_pods"], c["binds"]))
        # two batches around the row that is none: each starts and ends
        # as one, in batch order
        assert rows["p0"][1:3] == rows["p1"][1:3] == rows["p2"][1:3]
        assert rows["p3"][1:3] == rows["p4"][1:3] == rows["p5"][1:3]
        assert rows["p0"][2] <= rows["vol"][1] <= rows["vol"][2] \
            <= rows["p3"][1]
        assert rows["slow"][3].startswith("binder_")
        for point in ("PreBind", "Bind", "PostBind"):
            assert m.framework_extension_point_duration.count(
                point, "Success") == 8
    finally:
        sched.close()


def test_binds_bare_says_no_to_a_waiting_pod_and_to_a_relevant_hook():
    registry = dict(new_in_tree_registry())
    registry[_PreBindsVol.NAME] = lambda args, handle: _PreBindsVol()
    fwk = Framework(registry, KubeSchedulerProfile(plugins=Plugins(
        pre_bind=PluginSet(enabled=[Plugin(_PreBindsVol.NAME)]))),
        client=ClusterStore())
    pods = [hollow.make_pod(n) for n in ("a", "vol", "waits", "b")]
    fwk.waiting_pods.add(WaitingPod(pods[2], {"x": 1.0}))
    flags, (pre_s, post_s) = fwk.binds_bare(pods)
    assert flags == [True, False, False, True]
    assert pre_s >= 0.0 and post_s >= 0.0
    assert fwk.batch_binder() is fwk.bind_plugins[0]
    # a hook without ``relevant`` runs for every pod
    del _PreBindsVol.relevant
    try:
        assert fwk.binds_bare(pods)[0] == [False] * 4
    finally:
        _PreBindsVol.relevant = lambda self, pod: pod.metadata.name == "vol"
    # two binders decide pod by pod who binds: no batch
    fwk.bind_plugins.append(fwk.bind_plugins[0])
    assert fwk.batch_binder() is None


# ------------------------------------ (d) O(1) lock takings a job, not O(pods)


class CountingLock:
    """Wraps a lock (or a Condition's); counts the takings a thread."""

    def __init__(self, inner):
        self._inner = inner
        self.taken = {}

    def acquire(self, *a, **kw):
        got = self._inner.acquire(*a, **kw)
        if got:
            name = threading.current_thread().name
            self.taken[name] = self.taken.get(name, 0) + 1
        return got

    def release(self):
        self._inner.release()

    __enter__ = acquire

    def __exit__(self, *exc):
        self.release()

    def __getattr__(self, name):        # _is_owned & co. for a Condition
        return getattr(self._inner, name)


@pytest.mark.parametrize("batched", [True, False],
                         ids=["batch", "a-pod-at-a-time"])
def test_the_lane_takes_each_lock_o1_times_a_job(batched, monkeypatch):
    if not batched:
        monkeypatch.setattr(Framework, "batch_binder", lambda self: None)
    taken = {}
    for pods in (8, 64):
        store, sched = _world(nodes=64, pods=pods, batch=64,
                              metrics=SchedulerMetrics())
        locks = {"store": CountingLock(store._lock),
                 "cache": CountingLock(sched.cache._lock),
                 "nominator": CountingLock(sched.queue._lock),
                 "events": CountingLock(sched.broadcaster._lock),
                 "points": CountingLock(
                     sched.metrics.framework_extension_point_duration._lock)}
        store._lock = locks["store"]
        sched.cache._lock = locks["cache"]
        sched.queue._lock = locks["nominator"]
        sched.broadcaster._lock = locks["events"]
        sched.metrics.framework_extension_point_duration._lock = \
            locks["points"]
        queue = CountingLock(threading.RLock())
        sched.queue._cond = threading.Condition(queue)
        locks["queue"] = queue
        try:
            outs = sched.schedule_pending(timeout=0.0)
            sched.wait_for_inflight_binds(timeout=WAIT)
            assert sum(1 for o in outs if o.node) == pods
        finally:
            sched.close()
        taken[pods] = {k: v.taken.get(LANE, 0) for k, v in locks.items()}
    if batched:
        # the same few takings whatever the job's size
        assert taken[8] == taken[64]
        assert taken[64] == {"store": 2, "cache": 2, "queue": 1,
                             "nominator": 1, "events": 1, "points": 1}
    else:
        assert all(taken[64][k] >= 64 for k in ("store", "cache", "queue",
                                                "nominator", "events"))


# ----------------- (e) nobody hears of a bind before the cache confirmed it


def test_a_subscriber_that_deletes_on_bind_leaves_no_ghost_in_the_cache(how):
    """Subscribed BEFORE the scheduler: in subscription order it would
    hear of bind k first, delete the pod, and the scheduler's own
    handling of bind k would then add a pod that is gone."""
    store = ClusterStore()

    def delete_on_bind(event, old, new):
        if (event == "update" and new.spec.node_name
                and not old.spec.node_name
                and new.metadata.name.endswith(("1", "4"))):
            store.delete(new)
    store.subscribe("Pod", delete_on_bind)
    store, sched = _world(store=store, pods=16)
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sum(1 for o in outs if o.node) == 16
        left = {p.metadata.name for p in store.list("Pod")}
        assert len(left) == 12
        with sched.cache._lock:
            cached = {st.pod.metadata.name
                      for st in sched.cache.pod_states.values()}
        assert cached == left
        assert sched.cache.pod_count() == 12
        assert not sched.cache.assumed_pods
    finally:
        sched.close()


# ------------------------------------------------- the pieces on their own


def test_bind_many_is_binds_checks_a_pod_and_stops_at_none():
    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    pods = {n: hollow.make_pod(n) for n in ("a", "b", "c", "d", "e")}
    for n in ("a", "b", "c", "d"):
        store.add(pods[n])
    store.bind(pods["b"], "node-1")
    seen = Watcher(store)
    got = store.bind_many([(pods["a"], "node-0"), (pods["b"], "node-0"),
                           (pods["e"], "node-0"), (pods["c"], "node-9"),
                           (pods["d"], "node-1")])
    assert [type(e) for e in got] == [type(None), Conflict, NotFound,
                                      NotFound, type(None)]
    assert "already assigned to node node-1" in str(got[1])
    assert "pod default/e not found" in str(got[2])
    assert "node node-9 not found" in str(got[3])
    assert seen.binds() == ["a", "d"]
    assert [(s[2], s[3], s[4]) for s in seen.seen[-2:]] == [
        ("", "node-0", 2), ("", "node-1", 2)]
    assert store.get_pod("default", "c").spec.node_name == ""
    assert store.bind_many([]) == []
    with pytest.raises(Conflict):
        store.bind(pods["a"], "node-1")


def test_a_store_with_a_bind_of_its_own_keeps_its_say_in_bind_many():
    class Logs(ClusterStore):
        def __init__(self):
            super().__init__()
            self.log = []

        def bind(self, pod, node_name):
            self.log.append(pod.metadata.name)
            if pod.metadata.name == "b":
                raise OSError("no route to host")
            super().bind(pod, node_name)
    store = Logs()
    store.add(hollow.make_nodes(1)[0])
    pods = [hollow.make_pod(n) for n in "abc"]
    for p in pods:
        store.add(p)
    got = store.bind_many([(p, "node-0") for p in pods])
    assert store.log == ["a", "b", "c"]
    assert [type(e) for e in got] == [type(None), OSError, type(None)]
    assert [p.spec.node_name for p in pods] == ["node-0", "", "node-0"]


def test_a_bind_or_an_add_patched_over_the_class_keeps_its_say_too(
        monkeypatch):
    """What the benchmark's own tests do to break the timed path
    (tests/perfbench/test_perfbench_run.py), and what a remote store does
    to ``add``: the batch forms go through them, a row at a time."""
    from kubetpu.client.rest import RestClusterStore
    from kubetpu.client import store as store_mod
    assert RestClusterStore.add is not store_mod._ADD
    assert RestClusterStore.bind is not store_mod._BIND
    real_bind, real_add, log = ClusterStore.bind, ClusterStore.add, []

    def bind(self, pod, node_name):
        log.append(("bind", pod.metadata.name))
        return real_bind(self, pod, "node-0")

    def add(self, obj):
        log.append(("add", obj.metadata.name))
        return real_add(self, obj)
    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    monkeypatch.setattr(ClusterStore, "bind", bind)
    monkeypatch.setattr(ClusterStore, "add", add)
    pods = [hollow.make_pod(n) for n in "ab"]
    assert store.add_many(pods + pods[:1])[:2] == [None, None]
    assert isinstance(store.add_many(pods[:1])[0], Conflict)
    assert store.bind_many([(p, "node-1") for p in pods]) == [None, None]
    assert log == [("add", "a"), ("add", "b"), ("add", "a"), ("add", "a"),
                   ("bind", "a"), ("bind", "b")]
    assert [p.spec.node_name for p in pods] == ["node-0", "node-0"]


def test_list_takers_hear_first_and_single_writes_come_as_lists_of_one():
    store = ClusterStore()
    heard = []
    store.subscribe("Pod", lambda e, o, n: heard.append(("each", e)))
    store.subscribe("Pod", lambda evs: heard.append(
        ("list", [e for e, _, _ in evs])), batched=True)
    pod = hollow.make_pod("a")
    store.add(pod)
    store.update(pod)
    store.delete(pod)
    assert heard == [("list", ["add"]), ("each", "add"),
                     ("list", ["update"]), ("each", "update"),
                     ("list", ["delete"]), ("each", "delete")]
    # a late list-taker gets the current state as one list of adds
    store.add(hollow.make_pod("b"))
    store.add(hollow.make_pod("c"))
    late = []
    store.subscribe("Pod", late.append, batched=True)
    assert [[(e, n.metadata.name) for e, _, n in evs] for evs in late] == [
        [("add", "b"), ("add", "c")]]


def test_the_caches_batch_forms_are_the_single_ones_n_times():
    def build():
        c = SchedulerCache(ttl=30.0, clock=lambda: 100.0)
        for n in hollow.make_nodes(2):
            c.add_node(n)
        return c
    one, many = build(), build()
    pods = [hollow.make_pod(f"p{i}") for i in range(6)]
    for i, p in enumerate(pods):
        p.spec.node_name = f"node-{i % 2}"
    for c in (one, many):
        for p in pods[:4]:
            c.assume_pod(p)
    for p in pods[:3]:
        one.finish_binding(p)
    many.finish_binding_many(pods[:3])
    moved = api.shallow_copy(pods[1])
    moved.spec = api.shallow_copy(pods[1].spec)
    moved.spec.node_name = "node-0"         # confirmed on another node
    confirmed = [pods[0], moved, pods[4], pods[5], pods[4]]     # one twice
    foreign = 0
    for p in confirmed:
        foreign += not one.is_assumed_pod(p)
        try:
            one.add_pod(p)
        except ValueError:
            pass
    assert many.confirm_pods(confirmed) == foreign == 3

    def dump(c):
        return ({uid: (st.pod.spec.node_name, st.deadline,
                       st.binding_finished)
                 for uid, st in c.pod_states.items()},
                dict(c.assumed_pods), c.dump()["nodes"].keys(),
                {n: sorted(p.pod.metadata.name for p in it.info.pods)
                 for n, it in c.nodes.items()})
    assert dump(one) == dump(many)
    assert many.pod_states[pods[2].uid].deadline == 130.0
    assert many.pod_states[pods[3].uid].deadline is None


def test_pods_bound_is_delete_and_assigned_pod_added_n_times():
    def build():
        q = SchedulingQueue(clock=lambda: 50.0)
        pods = [hollow.make_pod(f"p{i}") for i in range(6)]
        aff = hollow.make_pod("aff")
        aff.spec.affinity = api.Affinity(pod_affinity=api.PodAffinity(
            required_during_scheduling_ignored_during_execution=[
                api.PodAffinityTerm(
                label_selector=api.LabelSelector(match_labels={"a": "b"}),
                topology_key="kubernetes.io/hostname")]))
        for p in pods[:4] + [aff]:
            q.add(p)
        popped = {qp.pod.metadata.name: qp for qp in q.pop_batch(8, 0)}
        # p1: back in backoff; p2 and aff: unschedulable; p3: nominated
        q.move_request_cycle = 99
        q.add_unschedulable_if_not_present(popped["p1"], 1)
        q.move_request_cycle = -1
        q.add_unschedulable_if_not_present(popped["p2"], 2)
        q.add_unschedulable_if_not_present(popped["aff"], 5)
        q.add(pods[4])
        q.add_nominated_pod(pods[3], "node-1")
        return q, pods
    (one, pods1), (many, pods2) = build(), build()
    for p in pods1[:5]:
        one.delete(p)
        one.assigned_pod_added(p)
    many.pods_bound(pods2[:5])

    def dump(q):
        return (q.depths(), sorted(p.metadata.name
                                   for p in q.pending_pods()),
                q.move_request_cycle, q.scheduling_cycle,
                [(p.metadata.name, n) for p, n in q.all_nominated()])
    assert dump(one) == dump(many)
    # AssignedPodAdded moved ``aff`` on (it is still backing off)
    assert dump(many)[0] == {"active": 0, "backoff": 1, "unschedulable": 0}
    assert dump(many)[1] == ["aff"]


def test_a_jobs_events_are_one_write_and_the_same_objects():
    class Sink(ClusterStore):
        def __init__(self):
            super().__init__()
            self.calls = []

        def add_many(self, objs):
            self.calls.append(("add_many", len(objs)))
            return super().add_many(objs)

        def update(self, obj):
            self.calls.append(("update", obj.metadata.name))
            super().update(obj)
    pods = [hollow.make_pod(f"p{i}") for i in range(5)]
    rows = [(p, "Normal", "Scheduled", f"to node-{i}")
            for i, p in enumerate(pods)]
    rows.insert(3, rows[0])             # p0 again: aggregates
    one_sink, many_sink = ClusterStore(), Sink()
    one = EventBroadcaster(sink=one_sink, clock=lambda: 7.0).new_recorder()
    many = EventBroadcaster(sink=many_sink, clock=lambda: 7.0)
    heard = []
    many.watch(lambda ev: heard.append((ev.involved_name, ev.count)))
    for row in rows:
        one.event(*row)
    many.new_recorder().events(rows)

    def dump(store):
        return [(e.metadata.name, e.metadata.namespace,
                 e.metadata.resource_version, e.involved_name,
                 e.involved_uid, e.type, e.reason, e.message, e.count,
                 e.first_timestamp, e.last_timestamp)
                for e in store.list("Event")]
    assert dump(one_sink) == dump(many_sink) and len(dump(one_sink)) == 5
    assert dump(many_sink)[0][2:4] == (2, "p0") and dump(many_sink)[0][8] == 2
    # the sink saw the rows in order: what came before the repeat, the
    # repeat, the rest
    assert many_sink.calls == [("add_many", 3), ("update", "p0.1"),
                               ("add_many", 2)]
    assert heard == [("p0", 1), ("p1", 1), ("p2", 1), ("p0", 2), ("p3", 1),
                     ("p4", 1)]
    # a snapshot handed out is never written again
    first = many_sink.list("Event")[0]
    many.new_recorder().event(pods[0], "Normal", "Scheduled", "again")
    assert first.count == 2 and many_sink.list("Event")[0].count == 3

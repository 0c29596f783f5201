"""The hand-over from the commit loop to the binder (PR 27): one bind job
a cycle on one binder lane (kubetpu/bindlane.py), in batch order; the
pool only for a bind that would block the lane; the per-pod lock takings
folded into one settling a job, with the same metrics."""
import threading
import time

import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile, Plugin, Plugins,
                                 PluginSet)
from kubetpu.client.rest import RestClusterStore
from kubetpu.client.store import ClusterStore
from kubetpu.framework import interface as fw
from kubetpu.framework.interface import Code, Status
from kubetpu.harness import hollow
from kubetpu.plugins.intree import new_in_tree_registry
from kubetpu.scheduler import Scheduler
from kubetpu.utils import chaos
from kubetpu.utils import trace as utrace
from kubetpu.utils.metrics import SchedulerMetrics

LANE = "binder-lane"
WAIT = 10.0         # every wait in this file is bounded; none should near it


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


@pytest.fixture(autouse=True)
def _no_chaos_left_armed():
    yield
    chaos.disarm()


class OrderedStore(ClusterStore):
    """Logs binds in the order the store applied them, with the thread."""

    def __init__(self):
        super().__init__()
        self.bound = []                 # (pod name, thread name)

    def bind(self, pod, node_name):
        self.before_bind(pod)
        super().bind(pod, node_name)
        self.bound.append((pod.metadata.name,
                           threading.current_thread().name))

    def before_bind(self, pod):
        pass


def _world(store=None, nodes=16, pods=24, batch=8, registry=None,
           plugins=None, metrics=None, names=None, **cfg):
    store = store if store is not None else OrderedStore()
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
    deadline = time.time() + WAIT
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.005)


def _bound(store):
    return {p.metadata.name for p in store.list("Pod") if p.spec.node_name}


def _hand_overs(cycle):
    """(bind_jobs, binds_pooled) of one cycle record, wherever its
    hand-overs were counted (the commit phase; in the extender path the
    phase that was open)."""
    args = [s["args"] for s in cycle["spans"] if "bind_jobs" in s["args"]]
    return (sum(a["bind_jobs"] for a in args),
            sum(a["binds_pooled"] for a in args))


def _commit_args(cycle):
    (sp,) = [s for s in cycle["spans"] if s["name"] == "commit"]
    return sp["args"]


# ------------------------------------------------- (a) one job a cycle


def test_a_cycle_of_n_pods_is_one_hand_over_not_n(flight):
    store, sched = _world()
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sum(1 for o in outs if o.node) == 24
        recs = [c.to_dict() for c in flight.cycles()]
        assert len(recs) == 3
        for c in recs:
            a = _commit_args(c)
            assert a["pods"] == 8
            assert (a["bind_jobs"], a["binds_pooled"]) == (1, 0)
            rows = c["binds"]
            assert len(rows) == 8
            for sub, start, done, thread in rows:
                assert 0.0 < sub <= start <= done
                assert thread == LANE
        assert {t for _, t in store.bound} == {LANE}
    finally:
        sched.close()


# ------------------------ (b), (f) batch order, and cycle order of jobs


@pytest.mark.parametrize("pipelined", [False, True])
def test_binds_reach_the_store_in_batch_order_and_jobs_in_cycle_order(
        flight, pipelined):
    cfg = dict(pipeline_cycles=True, chain_cycles=True) if pipelined else {}
    store, sched = _world(pods=40, **cfg)
    try:
        _drain(sched)
        sched.flush_pipeline()
        sched.wait_for_inflight_binds(timeout=WAIT)
        recs = [c.to_dict() for c in flight.cycles()]
        recs = [c for c in recs if any(r[2] > 0.0 for r in c["binds"])]
        assert len(recs) == 5
        recs.sort(key=lambda c: next(
            s["t0"] for s in c["spans"] if s["name"] == "commit"))
        expected = [name for c in recs
                    for name, row in zip(c["meta"]["batch_pods"],
                                         c["binds"]) if row[2] > 0.0]
        assert [n for n, _ in store.bound] == expected
        assert len(expected) == 40
        assert all(_hand_overs(c) == (1, 0) for c in recs)
    finally:
        sched.close()


# -------------------------- (c) what would block the lane takes the pool


class _Gate:
    """A bind that blocks until the test lets it go."""

    def __init__(self):
        self.entered = threading.Event()
        self.release = threading.Event()

    def block(self):
        self.entered.set()
        assert self.release.wait(WAIT)


class _WaitOnSlow(fw.PermitPlugin):
    NAME = "WaitOnSlow"

    def __init__(self, handle):
        self.handle = handle

    def name(self):
        return self.NAME

    def permit(self, state, pod, node_name):
        if pod.metadata.name == "slow":
            return Status(Code.WAIT), WAIT
        return Status.success(), 0.0


def _permit_wait():
    registry = dict(new_in_tree_registry())
    registry[_WaitOnSlow.NAME] = lambda args, handle: _WaitOnSlow(handle)
    store, sched = _world(
        registry=registry, names=["slow"] + [f"p{i}" for i in range(7)],
        plugins=Plugins(permit=PluginSet(enabled=[Plugin(_WaitOnSlow.NAME)])))
    fwk = next(iter(sched.profiles.values()))

    def blocked():
        return fwk.get_waiting_pod(
            store.get_pod("default", "slow").uid) is not None

    def release():
        wp = fwk.get_waiting_pod(store.get_pod("default", "slow").uid)
        if wp is not None:
            wp.allow(_WaitOnSlow.NAME)
    return store, sched, blocked, release, (1, 1)


class _BinderExtender:
    """An extender that binds the pods it is interested in itself."""
    url_prefix = "fake-binder"

    def __init__(self, gate):
        self.gate = gate

    def is_interested(self, pod):
        return pod.metadata.name == "slow"

    def filter(self, pod, names):
        return names, {}

    def prioritize(self, pod, names):
        return {}

    def is_binder(self):
        return True

    def bind(self, pod, node_name):
        self.gate.block()
        self.store.bind(pod, node_name)


def _binder_override():
    gate = _Gate()
    store, sched = _world(names=["slow"] + [f"p{i}" for i in range(7)])
    ext = _BinderExtender(gate)
    ext.store = store
    sched.extenders = [ext]         # one pod a cycle, commits singly
    # eight cycles of one pod: seven jobs of one on the lane, one pooled
    return store, sched, gate.entered.is_set, gate.release.set, (7, 1)


class _RemoteStore(OrderedStore):
    """Declares itself remote, as RestClusterStore does; its bind of
    ``slow`` takes as long as the test says."""
    in_process = False

    def __init__(self, gate):
        super().__init__()
        self.gate = gate

    def before_bind(self, pod):
        if pod.metadata.name == "slow":
            self.gate.block()


def _remote_client():
    gate = _Gate()
    store, sched = _world(store=_RemoteStore(gate),
                          names=["slow"] + [f"p{i}" for i in range(7)])
    return store, sched, gate.entered.is_set, gate.release.set, (0, 8)


class _RejectOnceStore(OrderedStore):
    """The first bind of ``slow`` dies on the wire, unapplied; the retry
    takes as long as the test says."""

    def __init__(self, gate):
        super().__init__()
        self.gate = gate
        self.attempts = []              # thread of each attempt on slow

    def before_bind(self, pod):
        if pod.metadata.name != "slow":
            return
        self.attempts.append(threading.current_thread().name)
        if len(self.attempts) == 1:
            raise OSError("connection reset by peer")
        self.gate.block()


def _rejected_first_bind():
    gate = _Gate()
    store, sched = _world(store=_RejectOnceStore(gate), bind_retries=2,
                          pod_initial_backoff_seconds=0.01,
                          pod_max_backoff_seconds=0.05,
                          names=["slow"] + [f"p{i}" for i in range(7)])
    return store, sched, gate.entered.is_set, gate.release.set, (1, 1)


@pytest.mark.parametrize("case", [_permit_wait, _binder_override,
                                  _remote_client, _rejected_first_bind],
                         ids=["permit-wait", "binder-override",
                              "remote-client", "rejected-first-bind"])
def test_a_bind_that_would_block_takes_the_pool_and_delays_no_other(
        flight, case):
    store, sched, blocked, release, expected = case()
    others = {f"p{i}" for i in range(7)}
    try:
        _drain(sched)
        # every other bind lands while ``slow`` is still blocked
        _until(blocked, "the blocking bind to start")
        _until(lambda: _bound(store) >= others, "the binds behind it")
        assert "slow" not in _bound(store)
        release()
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert _bound(store) == others | {"slow"}
        recs = [c.to_dict() for c in flight.cycles()]
        assert (sum(_hand_overs(c)[0] for c in recs),
                sum(_hand_overs(c)[1] for c in recs)) == expected
        threads = dict(store.bound)
        assert threads["slow"].startswith("binder_")        # a pool thread
        if expected[0]:                                     # the lane ran
            assert {threads[n] for n in others} == {LANE}
    finally:
        release()
        sched.close()
    if case is _rejected_first_bind:
        # begun on the lane, finished on the pool; its row is complete
        assert store.attempts[0] == LANE
        assert store.attempts[1].startswith("binder_")
        (c,) = recs
        row = c["binds"][c["meta"]["batch_pods"].index("slow")]
        assert 0.0 < row[0] <= row[1] <= row[2]


def test_an_armed_chaos_bind_stall_keeps_binds_off_the_lane(flight):
    store, sched = _world(pods=16)
    try:
        chaos.arm(chaos.ChaosRegistry(seed=3).arm_point(
            "bind", "stall", n=1, delay=0.5))
        first = sched.schedule_pending(timeout=0.0)
        sched.wait_for_inflight_binds(timeout=WAIT)
        # the rule is spent: the next cycle rides the lane again
        second = sched.schedule_pending(timeout=0.0)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert len(first) == len(second) == 8
        c1, c2 = [c.to_dict() for c in flight.cycles()]
        assert _hand_overs(c1) == (0, 8)
        assert _hand_overs(c2) == (1, 0)
        rows = c1["binds"]
        assert all(r[3].startswith("binder_") for r in rows)
        stalled = max(rows, key=lambda r: r[2] - r[1])
        assert stalled[2] - stalled[1] >= 0.5
        # the seven others were done while that one slept
        assert all(r[2] < stalled[2] for r in rows if r is not stalled)
        assert {r[3] for r in c2["binds"]} == {LANE}
        assert len(_bound(store)) == 16
    finally:
        sched.close()


def test_the_stores_say_whether_a_bind_leaves_the_process():
    assert ClusterStore.in_process is True
    assert RestClusterStore.in_process is False


# --------------------------------- (d) a failed bind in the middle of a job


class _Unreserves(fw.ReservePlugin, fw.UnreservePlugin):
    NAME = "Unreserves"
    log = []

    def name(self):
        return self.NAME

    def reserve(self, state, pod, node_name):
        return Status.success()

    def unreserve(self, state, pod, node_name):
        _Unreserves.log.append(pod.metadata.name)


class _RefusesBad(OrderedStore):
    def before_bind(self, pod):
        if pod.metadata.name == "bad":
            raise OSError("no route to host")


def test_a_failed_bind_mid_job_requeues_its_pod_and_the_rest_still_bind():
    _Unreserves.log = []
    registry = dict(new_in_tree_registry())
    registry[_Unreserves.NAME] = lambda args, handle: _Unreserves()
    names = [f"p{i}" for i in range(4)] + ["bad"] + [f"q{i}" for i in range(3)]
    store, sched = _world(
        store=_RefusesBad(), registry=registry, names=names, bind_retries=0,
        plugins=Plugins(
            reserve=PluginSet(enabled=[Plugin(_Unreserves.NAME)]),
            unreserve=PluginSet(enabled=[Plugin(_Unreserves.NAME)])))
    try:
        outs = sched.schedule_pending(timeout=0.0)
        assert len(outs) == 8 and all(o.node for o in outs)    # all assumed
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert _bound(store) == set(names) - {"bad"}
        assert {t for _, t in store.bound} == {LANE}
        bad = store.get_pod("default", "bad")
        # forgotten, unreserved, requeued, and told why
        assert bad.uid not in sched.cache.assumed_pods
        assert sched.cache.get_pod(bad) is None
        assert _Unreserves.log == ["bad"]
        assert len(sched.queue) == 1
        cond = {c.type: c for c in bad.status.conditions}[api.POD_SCHEDULED]
        assert cond.status == "False" and "no route to host" in cond.message
    finally:
        sched.close()


def test_a_bind_that_raises_is_kept_for_the_waiter_and_the_rest_still_bind():
    class Raises(fw.PostBindPlugin):
        def name(self):
            return "Raises"

        def post_bind(self, state, pod, node_name):
            if pod.metadata.name == "pod-3":
                raise RuntimeError("post-bind panicked")

    registry = dict(new_in_tree_registry())
    registry["Raises"] = lambda args, handle: Raises()
    store, sched = _world(
        pods=8, registry=registry,
        plugins=Plugins(post_bind=PluginSet(enabled=[Plugin("Raises")])))
    try:
        sched.schedule_pending(timeout=0.0)
        with pytest.raises(RuntimeError, match="post-bind panicked"):
            sched.wait_for_inflight_binds(timeout=WAIT)
        assert len(_bound(store)) == 8
    finally:
        sched.close()


# ------------------------------------ (e) waiting for, and closing on, jobs


class _SlowStore(OrderedStore):
    def before_bind(self, pod):
        time.sleep(0.01)


@pytest.mark.parametrize("how", ["wait_for_inflight_binds", "close"])
def test_waiting_and_closing_return_once_the_queued_jobs_are_applied(how):
    store, sched = _world(store=_SlowStore(), pods=24)
    try:
        outs = _drain(sched)            # three jobs, ~80 ms of binds each
        assert sum(1 for o in outs if o.node) == 24
        assert len(_bound(store)) < 24  # the serving thread did not wait
        if how == "close":
            sched.close()
        else:
            sched.wait_for_inflight_binds(timeout=WAIT)
        assert len(_bound(store)) == 24
        assert [n for n, _ in store.bound] == [o.pod.metadata.name
                                               for o in outs]
    finally:
        sched.close()
    assert not sched._bind_lane._thread.is_alive()


def test_a_hand_over_waits_until_the_job_before_it_is_applied():
    """The lane holds one job: however slow the binds, the pods assumed
    and not yet bound never pass a cycle's worth plus the cycle at hand."""
    store, sched = _world(store=_SlowStore(), pods=32)
    try:
        for k in range(4):
            outs = sched.schedule_pending(timeout=0.0)
            assert len(outs) == 8
            # cycle k's job is handed over: every job before it is applied
            assert len(store.bound) >= 8 * k
            assert len(sched.cache.assumed_pods) <= 8
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert len(_bound(store)) == 32
    finally:
        sched.close()


def test_close_racing_a_cycle_still_lands_its_placements(flight):
    """close() shuts the lane while the serving loop, past its join bound,
    is still in a cycle: that cycle's job is applied where it is handed
    over, on the serving thread."""
    store, sched = _world(pods=8)
    try:
        sched._bind_lane.close()
        sched._bind_pool.shutdown(wait=False)
        outs = sched.schedule_pending(timeout=0.0)
        assert len(outs) == 8 and all(o.node for o in outs)
        assert len(_bound(store)) == 8          # before anyone waits
        me = threading.current_thread().name
        assert {t for _, t in store.bound} == {me}
        (c,) = [c.to_dict() for c in flight.cycles()]
        assert all(0.0 < r[0] <= r[1] <= r[2] and r[3] == me
                   for r in c["binds"])
        sched.wait_for_inflight_binds(timeout=WAIT)
    finally:
        sched.close()


def test_the_lane_has_no_thread_until_a_job_and_none_under_sync_binding():
    store = ClusterStore()
    for n in hollow.make_nodes(4):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang"),
        async_binding=False)
    try:
        for p in hollow.make_pods(4):
            store.add(p)
        assert len(_drain(sched)) == 4 and len(_bound(store)) == 4
        assert sched._bind_lane._thread is None
    finally:
        sched.close()


# ------------------------------------------------ folded metrics are the same


BIND_SERIES = (
    ("framework_extension_point_duration", ("PreBind", "Success")),
    ("framework_extension_point_duration", ("Bind", "Success")),
    ("framework_extension_point_duration", ("PostBind", "Success")),
    ("binding_duration", ()),
    ("pod_scheduling_attempts", ()),
    ("pod_scheduling_duration", ()),
    ("e2e_scheduling_duration", ()),
)


def _metrics_after_24_binds(store):
    m = SchedulerMetrics()
    store, sched = _world(store=store, metrics=m)
    try:
        _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert len(_bound(store)) == 24
    finally:
        sched.close()
    return m


def _series(text):
    """Every sample of /metrics without its value."""
    return sorted(line.rsplit(" ", 1)[0] for line in text.splitlines()
                  if line and not line.startswith("#"))


def test_binds_through_the_lane_leave_the_metrics_the_pool_leaves():
    lane_store, pool_store = OrderedStore(), _RemoteStore(_Gate())
    pool_store.gate.release.set()
    lane = _metrics_after_24_binds(lane_store)
    pool = _metrics_after_24_binds(pool_store)
    assert {t for _, t in lane_store.bound} == {LANE}
    assert all(t.startswith("binder_") for _, t in pool_store.bound)
    for m in (lane, pool):
        for name, labels in BIND_SERIES:
            h = getattr(m, name)
            assert h.count(*labels) == 24, (name, labels)
            assert h.sum(*labels) > 0.0 or name == "binding_duration"
        assert m.schedule_attempts.value("scheduled") == 24.0
        assert m.pod_scheduling_attempts.sum() == 24.0     # one attempt each
    # the same series, letter for letter, and the same counts
    lane_text, pool_text = lane.expose_text(), pool.expose_text()
    assert _series(lane_text) == _series(pool_text)
    counts = [line for line in lane_text.splitlines()
              if "_count" in line and ("PreBind" in line or "Bind" in line
                                       or "binding_duration" in line
                                       or "pod_scheduling" in line
                                       or "e2e_scheduling" in line)]
    assert len(counts) >= 7
    assert set(counts) <= set(pool_text.splitlines())


BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


@pytest.mark.parametrize("rows", [
    [(0.003, "x"), (0.2, "y"), (7.0, "x"), (0.04, "x")],
    [(0.005, "x"), (0.01, "x"), (10.0, "x"), (1.0, "y")],     # on an edge
    [(0.0, "x"), (-1.0, "x"), (0.0049999, "y")],        # below the first
    [(10.000001, "x"), (1e9, "y"), (float("inf"), "x")],    # above the last
    [(0.1, "x"), (0.1, "y"), (0.2, "x"), (0.3, "y"), (0.1 + 0.2, "x")],
    [(0.1,)] * 1024 + [(0.7,)] * 3,                           # no labels
    [],
], ids=["mixed", "on-an-edge", "below-the-first", "above-the-last",
        "interleaved-labels", "a-job", "empty"])
def test_observe_many_is_observe_many_times(rows):
    from kubetpu.utils.metrics import Histogram
    labels = ("a",) if rows and len(rows[0]) > 1 else ()
    one, many = (Histogram("h", "", labels, BUCKETS) for _ in range(2))
    for row in rows:
        one.observe(*row)
    many.observe_many(rows)
    assert "\n".join(one.expose()) == "\n".join(many.expose())
    for lab in {r[1:] for r in rows}:
        assert one.count(*lab) == many.count(*lab) == sum(
            1 for r in rows if r[1:] == lab)
        assert one.sum(*lab) == many.sum(*lab)
        for q in (0.5, 0.99):
            assert one.percentile(q, *lab) == many.percentile(q, *lab)


def test_the_exposition_is_cumulative_as_ever():
    from kubetpu.utils.metrics import Histogram
    h = Histogram("h", "help", (), (1, 2, 4))
    h.observe_many([(0.5,), (1,), (3,), (9,)])
    assert h.expose() == [
        "# HELP h help", "# TYPE h histogram",
        'h_bucket{le="1"} 2', 'h_bucket{le="2"} 2', 'h_bucket{le="4"} 3',
        'h_bucket{le="+Inf"} 4', "h_sum 13.5", "h_count 4"]
    assert h.count() == 4 and h.percentile(0.5) == 1
    assert h.percentile(0.99) == 4      # past the last edge: clamped


# ------------------------------------------------------------- the lane alone


def test_the_lane_applies_every_job_once_in_each_producers_order():
    """More producers than cores and a 100 us switch interval: no job is
    lost or applied twice, each producer's jobs keep their order, close()
    drains what is queued, and a job handed over after it is refused."""
    import os
    import sys

    from kubetpu.bindlane import BindJob, BindLane
    applied = []

    def run(job):
        applied.extend(job.entries)

    lane = BindLane(run)
    producers, each = 2 * (os.cpu_count() or 4), 200
    jobs = []

    def produce(k):
        for i in range(each):
            job = BindJob()
            job.entries.append((k, i))
            assert lane.submit(job)
            jobs.append(job)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-4)
    try:
        threads = [threading.Thread(target=produce, args=(k,))
                   for k in range(producers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
            assert not t.is_alive()
        lane.close(timeout=WAIT)
    finally:
        sys.setswitchinterval(old)
    assert not lane._thread.is_alive()
    assert len(applied) == producers * each == len(set(applied))
    for k in range(producers):
        assert [i for kk, i in applied if kk == k] == list(range(each))
    assert all(j.done() for j in jobs)
    late = BindJob()
    assert lane.submit(late) is False and not late.done()
    lane.close()                                    # idempotent


def test_waiters_on_other_threads_never_lose_a_hand_over():
    """wait_for_inflight_binds prunes the list of hand-overs the serving
    thread appends to: hammered from three threads while cycles run, the
    last wait still returns only once every pod is bound."""
    import sys
    store, sched = _world(store=_SlowStore(), pods=20, batch=4)
    sched.schedule_pending(timeout=0.0)     # compile before the hammering
    stop = threading.Event()

    def hammer():
        while not stop.is_set():
            sched.wait_for_inflight_binds(timeout=WAIT)
            time.sleep(0.002)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-3)
    threads = [threading.Thread(target=hammer) for _ in range(3)]
    try:
        for t in threads:
            t.start()
        outs = _drain(sched)
        stop.set()
        for t in threads:
            t.join(WAIT)
            assert not t.is_alive()
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sum(1 for o in outs if o.node) == 16
        assert len(_bound(store)) == 20
        assert len(store.bound) == 20               # none twice
    finally:
        stop.set()
        sys.setswitchinterval(old)
        sched.close()

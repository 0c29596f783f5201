"""The commit loop's RUN path (PR 50): consecutive pods whose commit is
their assume alone are committed a step at a time over the run
(``Scheduler._commit_run``: one hold of the cache's lock, one scatter
into the cycle context's overlays, one write of the decision audit), any
other pod by ``_commit`` between the runs.  Held here: the run path
leaves what the per-pod loop leaves, the runs are cut in scan order where
a pod needs more, a pod the cache refuses fails alone, and the three
batch calls equal their per-pod twins."""
import threading
from types import SimpleNamespace

import numpy as np
import pytest

from kubetpu.api import types as api
from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile, Plugin, Plugins,
                                 PluginSet)
from kubetpu.client.store import ClusterStore
from kubetpu.framework import interface as fw
from kubetpu.framework.interface import Code, Status
from kubetpu.harness import hollow
from kubetpu.plugins.intree import new_in_tree_registry
from kubetpu.preemption import CycleContext
from kubetpu.scheduler import Scheduler, _assumed
from kubetpu.state.cache import SchedulerCache
from kubetpu.utils import trace as utrace
from kubetpu.utils.decisions import DecisionLog, PodDecision
from kubetpu.utils.metrics import SchedulerMetrics

WAIT = 10.0
NODES = 6


class EveryPodPermit(fw.PermitPlugin):
    """A Permit plugin with no ``relevant``: it runs for every pod, so no
    pod's commit is bare and the loop takes the per-pod path whole."""

    def __init__(self, verdict=Code.SUCCESS):
        self.verdict = verdict
        self.calls = []

    def name(self):
        return "EveryPodPermit"

    def permit(self, state, pod, node_name):
        self.calls.append(pod.metadata.name)
        if self.verdict == Code.WAIT:
            return Status(Code.WAIT), 5.0
        return Status.success(), 0.0


class GatedPermit(EveryPodPermit):
    """The same, caring only about pods labelled ``gate: yes``."""

    def relevant(self, pod):
        return pod.metadata.labels.get("gate") == "yes"


class HostGate(fw.FilterPlugin):
    """A host filter that cares about pods labelled ``gate: yes``."""

    def name(self):
        return "HostGate"

    def relevant(self, pod):
        return pod.metadata.labels.get("gate") == "yes"

    def filter(self, state, pod, node_info):
        return Status.success()


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _world(plugins=None, extra=(), async_binding=True, store=None):
    """A scheduler over NODES hollow nodes; ``extra``: plugin instances
    to register under their names; ``plugins``: the profile's additions."""
    store = store or ClusterStore()
    for n in hollow.make_nodes(NODES):
        store.add(n)
    registry = dict(new_in_tree_registry())
    for p in extra:
        registry[p.name()] = lambda args, handle, _p=p: _p
    sched = Scheduler(
        store, config=KubeSchedulerConfiguration(
            profiles=[KubeSchedulerProfile(plugins=plugins)],
            batch_size=32, mode="gang"),
        registry=registry, metrics=SchedulerMetrics(),
        async_binding=async_binding)
    return store, sched


def _prepare(store, sched, pods):
    """The pods added, popped and prepared: the cycle as it stands when
    the readback comes back."""
    for p in pods:
        store.add(p)
    by_profile, pop = sched._pop_grouped(len(pods), 0.0)
    (name, qpods), = by_profile.items()
    prep, early = sched._prepare_group(sched.profiles[name], qpods, pop=pop)
    assert prep is not None and not early
    assert [qp.pod.metadata.name for qp in prep.live] == [
        p.metadata.name for p in pods]
    return prep


def _commit(sched, prep, nodes, hold=None):
    """``_commit_group`` on a readback worked out by hand: ``nodes`` a
    pod, a node's name or None for a pod the auction did not place (and
    preemption cannot help).  hold: a list that takes the bind jobs in
    place of the lane, so the state is read as the loop left it."""
    B = prep.batch.valid.shape[0]
    row = {ni.node_name: j for j, ni in enumerate(prep.node_infos)}
    packed = np.zeros(3 * B + 1, np.int32)
    packed[:B] = -1
    for i, node in enumerate(nodes):
        packed[i] = -1 if node is None else row[node]
        packed[B + i] = 0 if node is None else NODES
        packed[2 * B + i] = node is None
    packed[3 * B] = 1
    if hold is not None:
        sched._hand_over = hold.append
    try:
        with prep.trace.phase("commit"):
            out = sched._commit_group(prep, packed)
            prep.trace.finish()
    finally:
        sched.__dict__.pop("_hand_over", None)
    return out


def _pod(name, color="red", **labels):
    return hollow.make_pod(name, labels=dict(labels, color=color))


def _resource(r):
    return {k: getattr(r, k) for k in r.__slots__}


def _cache_state(cache: SchedulerCache):
    with cache._lock:
        per_node = {
            name: ([pi.pod.metadata.name for pi in item.info.pods],
                   _resource(item.info.requested),
                   _resource(item.info.non_zero_requested))
            for name, item in cache.nodes.items()}
        by_generation = sorted(
            cache.nodes, key=lambda n: cache.nodes[n].info.generation)
        head, item = [], cache.head
        while item is not None:
            head.append(item.info.node_name)
            item = item.next
        # by name: a uid is the store's own, a counter a process
        states = [(st.pod.metadata.name, st.pod.spec.node_name,
                   st.binding_finished, st.deadline, uid == st.pod.uid,
                   cache.assumed_pods.get(uid))
                  for uid, st in cache.pod_states.items()]
        return (per_node, by_generation, head, states,
                len(cache.assumed_pods))


def _decisions(log: DecisionLog):
    return ([(d.name, d.namespace, d.outcome, d.node, d.message,
              d.n_feasible, d.cycle) for d in log.recent(10 ** 6)],
            log.evicted())


def _points(sched):
    h = sched.metrics.framework_extension_point_duration
    return {labels: sum(counts) for labels, counts in h._counts.items()
            if labels[0] in ("Reserve", "Permit")}


PLAIN = [("p0", "red"), ("p1", "red"), ("p2", "blue"), ("p3", "red"),
         ("p4", "red"), ("p5", "blue"), ("p6", "red"), ("p7", "blue")]
# two classes; p1 and p5 land on one node; p4 was not placed
PLACED = ["node-0", "node-2", "node-1", "node-3", None, "node-2", "node-4",
          "node-0"]


def _committed(per_pod: bool, flight):
    """PLAIN placed as PLACED by the run path, or (``per_pod``) by the
    per-pod path, forced through the loop's own fallback."""
    plugin = EveryPodPermit()
    store, sched = _world(
        plugins=Plugins(permit=PluginSet(enabled=[Plugin(plugin.name())]))
        if per_pod else None, extra=[plugin])
    try:
        prep = _prepare(store, sched, [_pod(n, c) for n, c in PLAIN])
        jobs = []
        out = _commit(sched, prep, PLACED, hold=jobs)
        rec = flight.cycles()[-1].to_dict()
        (job,) = jobs
        got = SimpleNamespace(
            cache=_cache_state(sched.cache),
            decisions=_decisions(sched.decisions),
            points=_points(sched),
            entries=[(e[0] is prep.fwk, e[1].pod.metadata.name,
                      e[2] is prep.states[e[1].pod.uid],
                      e[3].metadata.name, e[3].spec.node_name, e[4], e[5])
                     for e in job.entries],
            clones=[(e[3] is not e[1].pod, e[3].spec is not e[1].pod.spec,
                     e[1].pod.spec.node_name, e[3].metadata is
                     e[1].pod.metadata, vars(e[3]).keys()
                     == vars(e[1].pod).keys()) for e in job.entries],
            outcomes=[(o.pod.metadata.name, o.node, o.err, o.n_feasible,
                       o.preemption_may_help) for o in out],
            submitted=[r[0] > 0.0 for r in rec["binds"]],
            commit=next(s["args"] for s in rec["spans"]
                        if s["name"] == "commit"),
            queue=sched.queue.depths(), failed=sched._last_commit_failed,
            permit_calls=list(plugin.calls))
        # the binds still land once the job reaches the lane
        sched._hand_over(job)
        sched.wait_for_inflight_binds(timeout=WAIT)
        got.bound = sorted((p.metadata.name, p.spec.node_name)
                           for p in store.list("Pod") if p.spec.node_name)
        return got
    finally:
        sched.close()


@pytest.fixture(scope="module")
def both():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        return _committed(False, fr), _committed(True, fr)
    finally:
        utrace.disarm_flight_recorder()


@pytest.mark.parametrize("what", [
    "cache", "decisions", "points", "entries", "clones", "outcomes",
    "submitted", "queue", "failed", "bound"])
def test_a_run_leaves_what_the_per_pod_loop_leaves(both, what):
    run, per_pod = both
    assert getattr(run, what) == getattr(per_pod, what)


def test_the_two_paths_were_the_two_paths(both):
    run, per_pod = both
    placed = [n for n, node in zip([n for n, _ in PLAIN], PLACED) if node]
    assert run.commit["pods"] == per_pod.commit["pods"] == 7
    assert run.commit["batched"] == 7 and per_pod.commit["batched"] == 0
    assert run.permit_calls == [] and per_pod.permit_calls == placed
    # and what both left is what the readback said
    assert run.outcomes == [
        (n, node or "", None if node else f"0/{NODES} nodes are available",
         NODES if node else 0, bool(node))
        for (n, _), node in zip(PLAIN, PLACED)]
    assert run.cache[0]["node-2"][0] == ["p1", "p5"]
    assert run.cache[0]["node-0"][0] == ["p0", "p7"]
    # the list update_snapshot walks: the node assumed last comes first
    assert run.cache[2][:5] == ["node-0", "node-4", "node-2", "node-3",
                                "node-1"]
    assert run.points == {("Reserve", "Success"): 7,
                          ("Permit", "Success"): 7}
    assert run.entries == [(True, n, True, n, node, node, i)
                           for i, ((n, _), node)
                           in enumerate(zip(PLAIN, PLACED)) if node]
    assert all(c == (True, True, "", True, True) for c in run.clones)
    assert run.submitted == [bool(node) for node in PLACED]
    assert run.decisions[0][0][:4] == ("p4", "default", "unschedulable", "")
    assert [d[0] for d in run.decisions[0][1:]] == placed[::-1]
    assert run.queue["unschedulable"] == 1 and run.failed is False
    assert run.bound == sorted((n, node) for (n, _), node
                               in zip(PLAIN, PLACED) if node)


def test_the_sums_still_cover_the_loop(both):
    for side in both:
        a = side.commit
        parts = [a[k] for k in ("recheck_s", "reserve_s", "assume_s",
                                "permit_s", "submit_s", "records_s")]
        assert all(p >= 0.0 for p in parts)
        assert a["assume_s"] > 0.0 and a["submit_s"] > 0.0
        assert abs(sum(parts) - a["loop_s"]) <= 1e-5
        assert a["loop_cpu_s"] <= a["loop_s"] + 2e-3
        assert "bind_jobs" not in a     # the test held the hand-over


# ---------------------------------------------------------------- the cuts


def _pvc_pod(name):
    pod = _pod(name)
    pod.spec.volumes.append(
        api.Volume(name="data", persistent_volume_claim="claim-1"))
    return pod


def test_runs_are_cut_in_order_where_a_pod_needs_more(flight):
    """Plain pods with one PVC pod (VolumeBinding reserves) and one pod a
    host filter cares about in the middle: both take ``_commit``, each
    re-checks against a cache that holds every earlier pod of the batch
    and none of the later ones, and ``batched`` counts only the rest."""
    store = ClusterStore()
    store.add(api.PersistentVolume(metadata=api.ObjectMeta(name="vol-1")))
    store.add(api.PersistentVolumeClaim(
        metadata=api.ObjectMeta(name="claim-1"), volume_name="vol-1"))
    store, sched = _world(
        plugins=Plugins(filter=PluginSet(enabled=[Plugin("HostGate")])),
        extra=[HostGate()], store=store)
    try:
        pods = ([_pod(f"a{i}") for i in range(3)] + [_pvc_pod("claimed")]
                + [_pod(f"b{i}") for i in range(3)]
                + [_pod("gated", gate="yes")]
                + [_pod(f"c{i}") for i in range(3)])
        # everything on node-1, so each re-check's node shows who came
        prep = _prepare(store, sched, pods)
        assert [prep.host_relevant[qp.pod.uid] for qp in prep.live] == [
            n in ("claimed", "gated") for n in
            [p.metadata.name for p in pods]]
        seen, commits = [], []
        node_info, commit = sched.cache.node_info, sched._commit

        def spy_node_info(name):
            ni = node_info(name)
            seen.append([pi.pod.metadata.name for pi in ni.pods])
            return ni

        def spy_commit(fwk, qp, *a, **kw):
            commits.append(qp.pod.metadata.name)
            return commit(fwk, qp, *a, **kw)
        sched.cache.node_info, sched._commit = spy_node_info, spy_commit
        runs = []
        commit_run = sched._commit_run
        sched._commit_run = lambda prep, rows, *a: (
            runs.append(list(rows)), commit_run(prep, rows, *a))[1]
        out = _commit(sched, prep, ["node-1"] * len(pods))
        assert [o.node for o in out] == ["node-1"] * len(pods)
        assert commits == ["claimed", "gated"]
        assert runs == [[0, 1, 2], [4, 5, 6], [8, 9, 10]]
        assert seen == [["a0", "a1", "a2"],
                        ["a0", "a1", "a2", "claimed", "b0", "b1", "b2"]]
        a = next(s["args"] for s in flight.cycles()[-1].to_dict()["spans"]
                 if s["name"] == "commit")
        assert a["pods"] == 11 and a["batched"] == 9
        assert a["recheck_s"] > 0.0
        # every pod of the batch is assumed in scan order, whoever did it
        sched.wait_for_inflight_binds(timeout=WAIT)
        with sched.cache._lock:
            assert [pi.pod.metadata.name for pi in
                    sched.cache.nodes["node-1"].info.pods] == [
                        p.metadata.name for p in pods]
        assert _points(sched) == {("Reserve", "Success"): 11,
                                  ("Permit", "Success"): 11}
        assert sorted(p.metadata.name for p in store.list("Pod")
                      if p.spec.node_name == "node-1") == sorted(
                          p.metadata.name for p in pods)
    finally:
        sched.close()


def test_a_pod_the_cache_refuses_fails_alone(flight):
    """One pod of a run is in the cache already: it alone is failed and
    requeued, the cycle says a commit failed, the others are assumed and
    bound."""
    store, sched = _world()
    try:
        pods = [_pod(f"p{i}") for i in range(5)]
        prep = _prepare(store, sched, pods)
        twin = _assumed(prep.live[2].pod, "node-5")
        sched.cache.assume_pod(twin)
        out = _commit(sched, prep, [f"node-{i}" for i in range(5)])
        uid = prep.live[2].pod.uid
        assert [(o.node, o.err) for o in out] == [
            (f"node-{i}", None) if i != 2 else
            ("", f"pod {uid} is in the cache, so can't be assumed")
            for i in range(5)]
        assert out[2].preemption_may_help is False
        assert sched._last_commit_failed is True and sched._chain is None
        assert sched.queue.depths()["unschedulable"] == 1
        a = next(s["args"] for s in flight.cycles()[-1].to_dict()["spans"]
                 if s["name"] == "commit")
        assert a["pods"] == a["batched"] == 5
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sorted((p.metadata.name, p.spec.node_name)
                      for p in store.list("Pod") if p.spec.node_name) == [
                          (f"p{i}", f"node-{i}") for i in (0, 1, 3, 4)]
        # the refused pod's first assume stands where it was made
        assert sched.cache.get_pod(twin).spec.node_name == "node-5"
        assert _points(sched) == {("Reserve", "Success"): 5,
                                  ("Permit", "Success"): 4}
        names, _ = _decisions(sched.decisions)
        assert [(d[0], d[2]) for d in names] == [
            ("p4", "scheduled"), ("p3", "scheduled"),
            ("p2", "unschedulable"), ("p1", "scheduled"),
            ("p0", "scheduled")]
        binds = flight.cycles()[-1].to_dict()["binds"]
        assert [r[0] > 0.0 for r in binds] == [True, True, False, True, True]
    finally:
        sched.close()


# ---------------------------------------------------- what keeps the old path


def test_synchronous_binding_commits_a_pod_at_a_time(flight):
    store, sched = _world(async_binding=False)
    try:
        for p in [_pod(f"p{i}") for i in range(6)]:
            store.add(p)
        out = sched.schedule_pending(timeout=0.0)
        # bound as schedule_pending returns: nobody to wait for
        assert len(out) == 6 and all(o.node for o in out)
        assert all(p.spec.node_name for p in store.list("Pod"))
        a = next(s["args"] for s in flight.cycles()[-1].to_dict()["spans"]
                 if s["name"] == "commit")
        assert a["pods"] == 6 and a["batched"] == 0
    finally:
        sched.close()


@pytest.mark.parametrize("gated", [False, True],
                         ids=["every-pod", "its-class-only"])
def test_a_pod_that_waits_on_permit_commits_as_before(flight, gated):
    """A Permit plugin that says WAIT: the pods it cares about are
    assumed by ``_commit`` and their binds wait in the pool until
    allowed; with a ``relevant`` of its own the other pods ride a run."""
    plugin = (GatedPermit if gated else EveryPodPermit)(Code.WAIT)
    store, sched = _world(plugins=Plugins(
        permit=PluginSet(enabled=[Plugin(plugin.name())])), extra=[plugin])
    try:
        pods = [_pod("w0", gate="yes"), _pod("x0"), _pod("x1"),
                _pod("w1", gate="yes"), _pod("x2")]
        for p in pods:
            store.add(p)
        out = sched.schedule_pending(timeout=0.0)
        assert len(out) == 5 and all(o.node for o in out)
        waits = [p.metadata.name for p in pods
                 if not gated or p.metadata.name.startswith("w")]
        assert plugin.calls == waits
        a = next(s["args"] for s in flight.cycles()[-1].to_dict()["spans"]
                 if s["name"] == "commit")
        assert a["pods"] == 5 and a["batched"] == 5 - len(waits)
        assert a["binds_pooled"] == len(waits)
        fwk = sched.profiles["default-scheduler"]
        waiting = []
        fwk.iterate_over_waiting_pods(waiting.append)
        assert sorted(wp.pod.metadata.name for wp in waiting) == sorted(waits)
        # all five are in the cache; the lane may have bound and the
        # watch confirmed the plain ones already, the waiters stay assumed
        with sched.cache._lock:
            assert len(sched.cache.pod_states) == 5
            assert {wp.pod.uid for wp in waiting} <= set(
                sched.cache.assumed_pods)
        for wp in waiting:
            wp.allow(plugin.name())
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert all(p.spec.node_name for p in store.list("Pod"))
        labels = {("Reserve", "Success"): 5}
        if gated:
            labels[("Permit", "Success")] = 3
        labels[("Permit", "Wait")] = len(waits)
        assert _points(sched) == labels
    finally:
        sched.close()


@pytest.mark.parametrize("point", ["reserve", "unreserve", "permit"])
@pytest.mark.parametrize("how", ["no-relevant", "relevant-to-one-class"])
def test_commits_bare_asks_every_point_of_the_three(point, how):
    """``Framework.commits_bare`` over a pod a class: a plugin at any of
    Reserve, Unreserve, Permit that cares (or cannot say) takes the
    class off the run path."""
    _, sched = _world()
    try:
        fwk = sched.profiles["default-scheduler"]
        plain, gate = _pod("a"), _pod("b", gate="yes")
        assert fwk.commits_bare([plain, gate])[0] == [True, True]
        assert fwk.commits_bare([])[0] == []
        plugin = SimpleNamespace(name=lambda: "X")
        if how != "no-relevant":
            plugin.relevant = GatedPermit().relevant
        getattr(fwk, point + "_plugins").append(plugin)
        flags, (reserve_s, permit_s) = fwk.commits_bare([plain, gate])
        assert flags == ([False, False] if how == "no-relevant"
                         else [True, False])
        assert reserve_s >= 0.0 and permit_s >= 0.0
        # a PVC pod: VolumeBinding reserves
        assert fwk.commits_bare([_pvc_pod("c")])[0] == [False]
    finally:
        sched.close()


# ------------------------------------------------ the three calls and twins


def _cache(n=4):
    cache = SchedulerCache()
    for node in hollow.make_nodes(n):
        cache.add_node(node)
    return cache


def _placed(names_nodes):
    out = []
    for name, node in names_nodes:
        pod = hollow.make_pod(name)
        pod.metadata.uid = "uid-" + name
        out.append(_assumed(pod, node))
    return out


@pytest.mark.parametrize("case", [
    [("a", "node-0"), ("b", "node-1"), ("c", "node-0")],
    [("a", "node-3"), ("a", "node-1"), ("b", "node-3")],     # a twice
    [("a", "node-9")],                       # a node the cache never saw
    [],
], ids=["two-on-one-node", "one-refused", "unknown-node", "empty"])
def test_assume_pods_many_is_assume_pod_a_pod(case):
    many, one = _cache(), _cache()
    pods = _placed(case)
    errs = many.assume_pods_many(pods, [None] * len(pods))
    want = []
    for pod in _placed(case):
        try:
            one.assume_pod(pod)
            want.append(None)
        except ValueError as e:
            want.append(str(e))
    assert errs == want
    assert _cache_state(many) == _cache_state(one)
    assert [e is not None for e in errs] == [
        name in [n for n, _ in case[:i]] for i, (name, _) in enumerate(case)]


def test_assume_pods_many_holds_the_lock_once():
    cache = _cache()
    holds = []

    class Counting:
        def __init__(self, lock):
            self.lock = lock

        def __enter__(self):
            holds.append(1)
            return self.lock.__enter__()

        def __exit__(self, *exc):
            return self.lock.__exit__(*exc)
    cache._lock = Counting(cache._lock)
    cache.assume_pods_many(_placed([("a", "node-0"), ("b", "node-1"),
                                    ("c", "node-2")]), [None] * 3)
    assert len(holds) == 1


def _ctx(B=6, N=4, R=3, P=5, seed=0):
    rng = np.random.default_rng(seed)
    batch = SimpleNamespace(
        req=rng.random((B, R), np.float32) * 1e3,
        nonzero_req=rng.random((B, 2), np.float32) * 1e3,
        ports_asnode_hot=(rng.random((B, P)) > 0.6).astype(np.float32))
    cluster = SimpleNamespace(requested=np.zeros((N, R), np.float32),
                              ports=np.zeros((N, P), bool))
    return CycleContext(builder=None, cluster=cluster, cfg=None,
                        node_infos=[], batch=batch)


@pytest.mark.parametrize("rows,node_rows", [
    ([0, 1, 2], [3, 1, 0]),
    ([0, 1, 2, 3, 4, 5], [2, 2, 0, 2, 0, 2]),      # four rows on one node
    ([4], [1]),
    ([], []),
], ids=["apart", "sharing-nodes", "one", "none"])
def test_note_commits_is_note_commit_a_pod(rows, node_rows):
    many, one = _ctx(), _ctx()
    many.note_commits(rows, node_rows)
    for r, n in zip(rows, node_rows):
        one.note_commit(r, n)
    assert many.commits == one.commits == len(rows)
    if not rows:
        assert many.commit_req is None      # nothing noted, nothing built
        return
    # bit for bit: the requests add up in the order given
    assert np.array_equal(many.commit_req, one.commit_req)
    assert np.array_equal(many.commit_nz, one.commit_nz)
    assert np.array_equal(many.commit_ports, one.commit_ports)
    # two rows on one node add up, their ports OR
    b = many.batch
    for n in set(node_rows):
        mine = [r for r, m in zip(rows, node_rows) if m == n]
        assert np.allclose(many.commit_req[n], b.req[mine].sum(0))
        assert np.array_equal(many.commit_ports[n],
                              (b.ports_asnode_hot[mine] > 0.5).any(0))


def test_note_commits_without_a_batch_notes_nothing():
    ctx = _ctx()
    ctx.batch = None
    ctx.note_commits([0, 1], [1, 1])
    assert ctx.commits == 0 and ctx.commit_req is None


@pytest.mark.parametrize("capacity,names", [
    (8, ["a", "b", "c"]),
    (3, ["a", "b", "c", "d", "e"]),              # two evicted
    (3, ["a", "b", "a", "c", "d", "b"]),         # a pod recorded again
    (1, ["a", "a", "b"]),
], ids=["roomy", "evicting", "rerecorded", "one-slot"])
def test_record_many_is_record_a_pod(capacity, names):
    many = DecisionLog(capacity=capacity, enabled=True)
    one = DecisionLog(capacity=capacity, enabled=True)

    def decisions():
        return [PodDecision(n, "default", "uid-" + n, "scheduled",
                            node=f"node-{i}", n_feasible=i, cycle=7)
                for i, n in enumerate(names)]
    many.record_many(decisions())
    for d in decisions():
        one.record(d)
    assert _decisions(many) == _decisions(one)
    assert len(many) == len(one) == min(capacity, len(set(names)))
    assert many.evicted() == one.evicted()


def test_the_assumed_clone_is_copy_copy_field_for_field():
    import copy
    pod = _pvc_pod("p")
    pod.status.nominated_node_name = "node-7"
    want = copy.copy(pod)
    want.spec = copy.copy(pod.spec)
    want.spec.node_name = "node-1"
    got = _assumed(pod, "node-1")
    assert type(got) is api.Pod and type(got.spec) is api.PodSpec
    assert got == want and got is not pod and got.spec is not pod.spec
    assert vars(got).keys() == vars(want).keys()
    assert vars(got.spec).keys() == vars(want.spec).keys()
    for k, v in vars(want).items():
        assert k == "spec" or vars(got)[k] is v
    for k, v in vars(want.spec).items():
        assert k == "node_name" or vars(got.spec)[k] is v
    assert pod.spec.node_name == ""         # the pod itself is untouched


def test_two_threads_assuming_runs_lose_no_pod():
    """Two runs assumed side by side with the informer's confirms: every
    pod ends in the cache once (the lock is held over a whole run)."""
    import sys
    cache = _cache(8)
    runs = [_placed([(f"t{t}-{i}", f"node-{i % 8}") for i in range(200)])
            for t in range(4)]
    errs = [None] * 4

    def work(t):
        errs[t] = cache.assume_pods_many(runs[t], [None] * 200)
        cache.confirm_pods(runs[t][::2])
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=work, args=(t,))
                   for t in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(WAIT)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert all(e == [None] * 200 for e in errs)
    assert cache.pod_count() == 800
    assert len(cache.assumed_pods) == 400
    with cache._lock:
        assert sum(len(it.info.pods) for it in cache.nodes.values()) == 800

"""Metrics, tracing, leader election, cache debugger, serving endpoints
(reference: pkg/scheduler/metrics, utils/trace, client-go leaderelection,
internal/cache/debugger, cmd/kube-scheduler/app/server.go:167-199)."""
import json
import os
import urllib.error
import urllib.request

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.server import SchedulerServer
from kubetpu.state.debugger import CacheComparer, CacheDumper
from kubetpu.utils import journal as ujournal
from kubetpu.utils import trace as utrace
from kubetpu.utils.leaderelection import InMemoryLock, LeaderElector
from kubetpu.utils.metrics import Counter, Histogram, SchedulerMetrics
from kubetpu.utils.trace import Trace


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        outs.extend(got)
    return outs


def _world(n_nodes=2, n_pods=6, batch=8, metrics=None):
    store = ClusterStore()
    for n in hollow.make_nodes(n_nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch),
        async_binding=False, metrics=metrics)
    for p in hollow.make_pods(n_pods):
        store.add(p)
    return store, sched


def test_metrics_through_scheduling():
    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    m = SchedulerMetrics()
    sched = Scheduler(store, async_binding=False, metrics=m)
    for p in hollow.make_pods(3):
        store.add(p)
    big = hollow.make_pod("too-big", cpu_milli=999999)
    store.add(big)
    sched.schedule_pending(timeout=0.0)
    assert m.schedule_attempts.value("scheduled") == 3
    assert m.schedule_attempts.value("unschedulable") == 1
    assert m.pod_scheduling_attempts.count() == 3
    assert m.binding_duration.count() == 3
    assert m.device_batch_size.count() == 1
    assert m.queue_incoming_pods.value("active", "PodAdd") == 4
    # pending gauge: 1 pod waiting again (unschedulable or backoff)
    text = m.expose_text()
    assert "scheduler_schedule_attempts_total" in text
    assert 'result="scheduled"' in text
    assert "scheduler_pending_pods" in text


# every path SchedulerServer serves, with the answer README.md documents
# for a scheduler that has nothing armed: (status, content type, a check
# of the body)
ROUTES = {
    "/healthz": (200, "text/plain", lambda b: b == "ok"),
    "/metrics": (200, "text/plain; version=0.0.4",
                 lambda b: "# TYPE scheduler_e2e_scheduling_duration_seconds"
                 " histogram" in b),
    "/configz": (200, "application/json",
                 lambda b: "profiles" in json.loads(b)),
    "/debug/flightz": (200, "application/json", lambda b: (
        json.loads(b)["armed"] is False
        and "KUBETPU_FLIGHT" in json.loads(b)["hint"])),
    "/debug/explain": (200, "application/json", lambda b: (
        json.loads(b)["enabled"] is True
        and json.loads(b)["decisions"] == [])),
    "/debug/journal": (200, "application/json", lambda b: (
        json.loads(b)["armed"] is False
        and "KUBETPU_JOURNAL" in json.loads(b)["hint"])),
}
# a documented parameter of a served path, and the paths PR 45 removed
OTHERS = {
    "/debug/flightz?format=chrome": (
        200, "application/json", lambda b: json.loads(b)["armed"] is False),
    "/debug/explain?pod=no-such-pod": (
        404, "application/json", lambda b: "no recorded decision" in b),
    "/debug/slo": (404, "text/plain", lambda b: b == "not found"),
    "/debug/loadz": (404, "text/plain", lambda b: b == "not found"),
    "/debug/devicez": (404, "text/plain", lambda b: b == "not found"),
}


@pytest.fixture(scope="module")
def served():
    """One disarmed scheduler behind its server; yields a GET."""
    utrace.disarm_flight_recorder()
    ujournal.disarm_journal()
    store = ClusterStore()
    store.add(hollow.make_node("n1"))
    sched = Scheduler(store, async_binding=False, metrics=SchedulerMetrics())
    srv = SchedulerServer(sched, port=0)
    port = srv.start()

    def get(path):
        try:
            with urllib.request.urlopen(
                    f"http://127.0.0.1:{port}{path}") as r:
                return r.status, r.headers["Content-Type"], r.read().decode()
        except urllib.error.HTTPError as e:
            return e.code, e.headers["Content-Type"], e.read().decode()
    try:
        yield get
    finally:
        srv.stop()
        sched.close()


@pytest.mark.parametrize("path", list(ROUTES) + list(OTHERS))
def test_endpoints_serve(served, path):
    status, ctype, check = {**ROUTES, **OTHERS}[path]
    got_status, got_ctype, body = served(path)
    assert got_status == status, body
    assert got_ctype.startswith(ctype), got_ctype
    assert check(body), body


def test_the_routes_are_the_ones_readme_lists():
    """The paths ``do_GET`` compares against, the table above and
    README.md's table of endpoints are one set."""
    import inspect
    import re

    from kubetpu import server
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    in_code = set(re.findall(r'path == "(/[^"]*)"',
                             inspect.getsource(server)))
    with open(os.path.join(root, "README.md")) as f:
        section = f.read().split("`SchedulerServer` serves", 1)[1]
    table = section.split("\n\n", 2)[1]
    in_readme = set(re.findall(r"^\| `(/[^`]*)` \|", table, re.M))
    assert in_code == set(ROUTES) == in_readme


def test_trace_slow_log():
    t = Trace("Scheduling", pod="x")
    t.step("phase one")
    t.start -= 1.0  # simulate a slow cycle
    out = t.log_if_long(threshold=0.1)
    assert out is not None and "Scheduling" in out and "phase one" in out
    fast = Trace("Scheduling")
    assert fast.log_if_long(threshold=10.0) is None


def test_leader_election_failover():
    lock = InMemoryLock()
    now = [1000.0]
    clock = lambda: now[0]
    events = []
    a = LeaderElector(lock, lambda: events.append("a-start"),
                      lambda: events.append("a-stop"), identity="a",
                      clock=clock)
    b = LeaderElector(lock, lambda: events.append("b-start"),
                      lambda: events.append("b-stop"), identity="b",
                      clock=clock)
    assert a.step() and not b.step()       # a leads, b blocked
    now[0] += 5
    assert a.step() and not b.step()       # renewal holds b off
    now[0] += 100                          # a silent: lease expires
    assert b.step()                        # b takes over
    assert not a.step()                    # a observes loss -> callback
    assert events == ["a-start", "b-start", "a-stop"]


def test_cache_comparer_detects_drift():
    store = ClusterStore()
    store.add(hollow.make_node("n1"))
    sched = Scheduler(store, async_binding=False)
    comparer = CacheComparer(store, sched.cache, sched.queue)
    assert comparer.compare()
    # inject drift: node in store the cache never saw
    from kubetpu.api import types as api
    ghost = hollow.make_node("ghost")
    store._objs["Node"]["ghost"] = ghost   # bypass events deliberately
    missed, redundant = comparer.compare_nodes()
    assert missed == ["ghost"] and redundant == []
    assert not comparer.compare()


def test_cache_dumper():
    store = ClusterStore()
    store.add(hollow.make_node("n1"))
    sched = Scheduler(store, async_binding=False)
    p = hollow.make_pod("p")
    p.spec.node_name = "n1"
    store.add(p)
    out = CacheDumper(sched.cache, sched.queue).dump()
    assert "n1" in out and "'p'" in out


def test_event_broadcaster_aggregates_and_sinks():
    """reference: client-go tools/events — repeats inside the aggregation
    window bump count on ONE Event object; distinct reasons make new
    objects; the scheduler records Scheduled events by default."""
    from kubetpu.utils.events import EventBroadcaster

    now = [1000.0]
    store = ClusterStore()
    b = EventBroadcaster(sink=store, clock=lambda: now[0])
    rec = b.new_recorder("test")
    pod = hollow.make_pod("p1")
    rec.event(pod, "Warning", "FailedScheduling", "0/3 nodes")
    rec.event(pod, "Warning", "FailedScheduling", "0/3 nodes again")
    now[0] += 5
    rec.event(pod, "Warning", "FailedScheduling", "still failing")
    evs = store.list("Event")
    assert len(evs) == 1
    assert evs[0].count == 3
    assert evs[0].message == "still failing"
    rec.event(pod, "Normal", "Scheduled", "bound")
    assert len(store.list("Event")) == 2
    # outside the window -> a fresh Event object
    now[0] += 700
    rec.event(pod, "Warning", "FailedScheduling", "later")
    assert len([e for e in store.list("Event")
                if e.reason == "FailedScheduling"]) == 2

    # the serving path records by default
    store2 = ClusterStore()
    store2.add(hollow.make_node("n1"))
    sched = Scheduler(store2, async_binding=False)
    store2.add(hollow.make_pod("p"))
    out = sched.schedule_pending(timeout=0.0)
    assert out[0].err is None
    evs = store2.list("Event")
    assert any(e.reason == "Scheduled" for e in evs)
    sched.close()


def test_jax_profiler_capture(tmp_path):
    """SURVEY §5: jax.profiler traces wrap the serving cycle — a capture
    produces an XPlane dump with the cycle running inside, and Trace
    phases open TraceAnnotations without disturbing scheduling."""
    import os

    from kubetpu.utils import trace as trace_mod

    store = ClusterStore()
    for n in hollow.make_nodes(2):
        store.add(n)
    cfg = KubeSchedulerConfiguration(profiles=[KubeSchedulerProfile()],
                                     batch_size=4, mode="gang")
    sched = Scheduler(store, config=cfg, async_binding=False)
    for p in hollow.make_pods(3):
        store.add(p)
    log_dir = str(tmp_path / "jaxtrace")
    with trace_mod.capture_device_trace(log_dir):
        out = sched.schedule_pending(timeout=0.2)
    assert sum(1 for o in out if o.node) == 3
    # the capture must have produced profiler artifacts
    found = []
    for root, _dirs, files in os.walk(log_dir):
        found.extend(files)
    assert found, "jax.profiler capture produced no files"
    sched.close()


# -------------------------------------------------- /metrics hardening


def test_metrics_label_escaping_and_histogram_conventions():
    c = Counter("t_total", 'help with "quotes"\nand newline',
                ("reason",))
    c.inc('bad "value" \\ with\nnewline')
    lines = c.expose()
    assert lines[0] == 't_total help with "quotes"\\nand newline' \
        .join(["# HELP ", ""]) or lines[0].startswith("# HELP t_total")
    assert "\n" not in lines[0]
    body = "\n".join(lines)
    assert '\\"value\\"' in body and "\\\\" in body and "\\n" in body
    h = Histogram("d_seconds", "latency", buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(50.0)
    text = "\n".join(h.expose())
    assert 'le="+Inf"} 2' in text
    assert "d_seconds_sum 50.05" in text
    assert "d_seconds_count 2" in text
    assert "# TYPE d_seconds histogram" in text


def test_metrics_content_type_and_exposition():
    m = SchedulerMetrics()
    store, sched = _world(metrics=m)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        _drain(sched)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics") as r:
            assert r.status == 200
            assert r.headers.get("Content-Type").startswith(
                "text/plain; version=0.0.4")
            body = r.read().decode()
        assert "# HELP scheduler_binding_duration_seconds" in body
        assert "# TYPE scheduler_binding_duration_seconds histogram" in body
        assert 'scheduler_binding_duration_seconds_bucket{le="+Inf"} 6' \
            in body
        # the extension-point histogram is now observed on the commit
        # path (Reserve/Permit/PreBind/Bind/PostBind per bound pod)
        for point in ("Reserve", "Permit", "PreBind", "Bind", "PostBind"):
            assert m.framework_extension_point_duration.count(
                point, "Success") == 6, point
        assert m.framework_extension_point_duration.count(
            "PreFilter", "Success") == 6
    finally:
        srv.stop()
        sched.close()


def test_permit_wait_and_preemption_metrics_wired():
    """The previously-dormant metrics observe through the real seams:
    permit_wait via a Wait permit plugin, preemption attempts/victims
    via a priority pod preempting a filler."""
    from kubetpu.framework.interface import Code, PermitPlugin, Status

    class WaitingPermit(PermitPlugin):
        def name(self):
            return "WaitingPermit"

        def permit(self, state, pod, node_name):
            return Status(Code.WAIT), 0.05   # times out -> rejected

    m = SchedulerMetrics()
    store = ClusterStore()
    store.add(hollow.make_node("n1", cpu_milli=1000))
    from kubetpu.plugins.intree import new_in_tree_registry
    registry = new_in_tree_registry()
    registry["WaitingPermit"] = lambda args, fw: WaitingPermit()
    from kubetpu.apis.config import PluginSet, Plugin, Plugins
    prof = KubeSchedulerProfile(plugins=Plugins(
        permit=PluginSet(enabled=[Plugin(name="WaitingPermit")])))
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[prof], batch_size=4), registry=registry,
        async_binding=False, metrics=m)
    try:
        store.add(hollow.make_pod("w1", cpu_milli=100))
        _drain(sched)
        assert m.permit_wait_duration.count("rejected") == 1
    finally:
        sched.close()

    # preemption: fill the node, then a higher-priority pod evicts
    m2 = SchedulerMetrics()
    store2 = ClusterStore()
    store2.add(hollow.make_node("n1", cpu_milli=1000))
    sched2 = Scheduler(store2, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4),
        async_binding=False, metrics=m2)
    try:
        filler = hollow.make_pod("filler", cpu_milli=900)
        store2.add(filler)
        _drain(sched2)
        high = hollow.make_pod("high", cpu_milli=900)
        high.spec.priority = 100
        store2.add(high)
        _drain(sched2)
        assert m2.preemption_attempts.value() >= 1
        assert m2.preemption_victims.count() >= 1
    finally:
        sched2.close()

"""Cycle flight recorder + per-pod decision audit
(kubetpu/utils/trace.py, kubetpu/utils/decisions.py, the /debug
endpoints, and the disarmed-hot-path no-op contract)."""
import json
import time
import urllib.error
import urllib.request

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.server import SchedulerServer
from kubetpu.utils import trace as utrace
from kubetpu.utils.decisions import DecisionLog, PodDecision
from kubetpu.utils.metrics import SchedulerMetrics


@pytest.fixture
def flight():
    """Armed recorder with a tiny ring; always disarmed on exit (the
    recorder is module-global)."""
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=4)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            break
        outs.extend(got)
    return outs


def _world(n_nodes=2, n_pods=6, batch=1, metrics=None, infeasible=True):
    store = ClusterStore()
    for n in hollow.make_nodes(n_nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch),
        async_binding=False, metrics=metrics)
    for p in hollow.make_pods(n_pods):
        store.add(p)
    if infeasible:
        store.add(hollow.make_pod("too-big", cpu_milli=999999))
    return store, sched


# ---------------------------------------------------------------- ring buffer


def test_ring_wraps_and_counts_drops(flight):
    """A multi-cycle run overflows the 4-slot ring: only the last 4 cycle
    records survive, every older one is counted in dropped() (and the
    metric), and each surviving record carries the full span tree."""
    m = SchedulerMetrics()
    store, sched = _world(batch=1, metrics=m)
    try:
        outs = _drain(sched)          # 7 pods x batch 1 => 7 cycles
        assert len(outs) == 7
        cycles = flight.cycles()
        assert len(cycles) == 4
        assert flight.dropped() == 3
        assert m.flight_recorder_dropped.value() == 3
        # ring keeps the LAST cycles (monotonic seq)
        seqs = [c.seq for c in cycles]
        assert seqs == sorted(seqs) and seqs[-1] - seqs[0] == 3
        names = {s.name for c in cycles for s in c.spans()}
        assert {"Scheduling", "dispatch", "packed-readback",
                "commit"} <= names
        # per-span device-wait attribution on the readback
        rb = [s for c in cycles for s in c.spans()
              if s.name == "packed-readback"]
        assert rb and all("device_wait_s" in s.args for s in rb)
        # queue depths stamped at cycle start
        assert all(set(c.queue_depths) == {"active", "backoff",
                                           "unschedulable"}
                   for c in cycles)
    finally:
        sched.close()


def test_span_tree_linkage_and_threads(flight):
    store, sched = _world(batch=8, n_pods=3, infeasible=False)
    try:
        _drain(sched)
        rec = flight.cycles()[-1]
        spans = rec.spans()
        root = [s for s in spans if s.parent_id == 0]
        assert len(root) == 1 and root[0].name == "Scheduling"
        ids = {s.span_id for s in spans}
        assert all(s.parent_id in ids for s in spans if s.parent_id)
        assert all(s.thread for s in spans)
        # binds ride the cycle record too, as rows of its bind table
        # (sync binding: same thread), and render as spans in the exports
        rows = [r for r in rec.bind_rows() if r[0] > 0.0]
        assert len(rows) == 3
        assert all(sub <= start <= done and th == root[0].thread
                   for sub, start, done, th in rows)
        assert sum(1 for s in rec.bind_spans() if s.name == "bind") == 3
    finally:
        sched.close()


# ------------------------------------------------------------- Chrome export


def _validate_chrome(doc):
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    evs = doc["traceEvents"]
    assert evs, "empty traceEvents"
    for e in evs:
        assert e["ph"] in ("X", "M", "C", "i"), e
        assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
        assert isinstance(e["name"], str) and e["name"]
        if e["ph"] == "X":
            assert isinstance(e["ts"], int) and e["ts"] >= 0
            assert isinstance(e["dur"], int) and e["dur"] >= 0
        elif e["ph"] in ("C", "i"):
            assert isinstance(e["ts"], int)
    # metadata names every pid/tid used by X events
    named_pids = {e["pid"] for e in evs
                  if e["ph"] == "M" and e["name"] == "process_name"}
    named_tids = {(e["pid"], e["tid"]) for e in evs
                  if e["ph"] == "M" and e["name"] == "thread_name"}
    for e in evs:
        if e["ph"] == "X":
            assert e["pid"] in named_pids
            assert (e["pid"], e["tid"]) in named_tids
    return [e for e in evs if e["ph"] == "X"]


def test_chrome_trace_schema_and_span_total(flight):
    store, sched = _world(batch=2)
    try:
        _drain(sched)
        chrome = flight.to_chrome_trace()
        json.loads(json.dumps(chrome))   # serializable
        xs = _validate_chrome(chrome)
        pipe = flight.to_pipeline_doc("test")
        # the acceptance contract: Perfetto span count == span_total
        assert len(xs) == pipe["span_total"] == len(pipe["spans"])
        assert pipe["device_wait_s"] >= 0.0
    finally:
        sched.close()


# ------------------------------------------------------- decision audit + HTTP


def test_decision_audit_names_rejecting_plugin(flight):
    """A seeded infeasible pod (cpu beyond every node) must be attributed
    to NodeResourcesFit — blocking plugin, per-plugin failed-node counts,
    and the rejections metric."""
    m = SchedulerMetrics()
    store, sched = _world(batch=8, metrics=m)
    try:
        outs = _drain(sched)
        assert sum(1 for o in outs if not o.node) == 1
        d = sched.decisions.get("too-big")
        assert d is not None and d.outcome == "unschedulable"
        assert d.blocking == ["NodeResourcesFit"]
        assert d.rejections.get("NodeResourcesFit") == 2  # both nodes
        assert "NodeResourcesFit" in d.why()
        assert m.framework_rejections.value("NodeResourcesFit") == 1
        # scheduled pods get decisions too
        ok = sched.decisions.get("pod-0")
        assert ok is not None and ok.outcome == "scheduled" and ok.node
    finally:
        sched.close()


def test_flightz_and_explain_http_roundtrip(flight):
    store, sched = _world(batch=8)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        _drain(sched)

        def get(path):
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}{path}") as r:
                    return r.status, json.loads(r.read().decode())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read().decode())

        code, doc = get("/debug/flightz")
        assert code == 200 and doc["armed"] is True
        assert doc["capacity"] == 4 and len(doc["cycles"]) >= 1
        assert all(c["spans"] for c in doc["cycles"])

        code, chrome = get("/debug/flightz?format=chrome")
        assert code == 200
        _validate_chrome(chrome)

        code, doc = get("/debug/explain?pod=too-big")
        assert code == 200
        assert doc["outcome"] == "unschedulable"
        assert doc["blocking"] == ["NodeResourcesFit"]
        assert "NodeResourcesFit" in doc["why"]

        code, doc = get("/debug/explain?pod=no-such-pod")
        assert code == 404 and "error" in doc

        code, doc = get("/debug/explain?outcome=unschedulable")
        assert code == 200
        assert [d["pod"] for d in doc["decisions"]] == ["too-big"]
    finally:
        srv.stop()
        sched.close()


def test_flightz_reports_disarmed():
    utrace.disarm_flight_recorder()
    store, sched = _world(n_pods=0, infeasible=False)
    srv = SchedulerServer(sched, port=0)
    port = srv.start()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/debug/flightz") as r:
            doc = json.loads(r.read().decode())
        assert doc["armed"] is False
    finally:
        srv.stop()
        sched.close()


# --------------------------------------------------------- disarmed = no-op


def test_disarmed_hot_path_is_noop(monkeypatch):
    """Recorder disarmed + audit off: a scheduling cycle must construct
    no CycleRecord, never read queue depths, and take no DecisionLog
    lock — the new-lock-free hot path contract."""
    utrace.disarm_flight_recorder()

    def boom(*a, **kw):
        raise AssertionError("hot path touched the disarmed recorder")

    monkeypatch.setattr(utrace.FlightRecorder, "begin_cycle", boom)
    monkeypatch.setattr(utrace.CycleRecord, "__init__", boom)
    monkeypatch.setattr(DecisionLog, "record", boom)
    from kubetpu.schedqueue.queue import SchedulingQueue
    monkeypatch.setattr(SchedulingQueue, "depths", boom)
    from kubetpu.models import programs
    monkeypatch.setattr(programs, "explain_verdicts", boom)

    store, sched = _world(batch=8)
    sched.decisions.enabled = False
    try:
        outs = _drain(sched)   # includes a failure -> audit paths skipped
        assert sum(1 for o in outs if o.node) == 6
        assert len(sched.decisions) == 0
    finally:
        sched.close()


# ------------------------------------------------------------- DecisionLog


def test_decision_log_bounded_eviction():
    log = DecisionLog(capacity=3, enabled=True)
    for i in range(5):
        log.record(PodDecision(name=f"p{i}", namespace="default",
                               uid=f"u{i}", outcome="scheduled",
                               node="n1"))
    assert len(log) == 3 and log.evicted() == 2
    assert log.get("p0") is None and log.get("p4") is not None
    # re-recording a pod replaces in place, no eviction
    log.record(PodDecision(name="p4", namespace="default", uid="u4",
                           outcome="unschedulable"))
    assert len(log) == 3 and log.evicted() == 2
    assert log.get("p4").outcome == "unschedulable"
    doc = log.to_dict()
    assert doc["size"] == 3 and doc["evicted"] == 2


def test_contention_loser_reports_best_feasible(flight):
    """A pod that was feasible at cycle start but lost the in-batch
    capacity race reports its best feasible node + score, not a plugin
    rejection."""
    store = ClusterStore()
    store.add(hollow.make_node("n1", cpu_milli=1000))
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4),
        async_binding=False)
    try:
        for i in range(3):
            store.add(hollow.make_pod(f"c{i}", cpu_milli=400))
        outs = _drain(sched)
        losers = [o.pod.metadata.name for o in outs if not o.node]
        assert len(losers) == 1   # 2 x 400m fit in 1000m, third loses
        d = sched.decisions.get(losers[0])
        assert d is not None and d.outcome == "unschedulable"
        assert d.best_node == "n1" and d.best_score is not None
        assert "best feasible score" in d.why()
    finally:
        sched.close()


# -------------------------------------------------- monotonic clock fix


def test_trace_spans_survive_backwards_wall_clock(monkeypatch):
    """The satellite regression: an NTP step that moves time.time()
    BACKWARDS mid-cycle must not produce negative span durations —
    span stamps read trace.wallclock() (perf_counter anchored to the
    import-time wall epoch), which time.time() cannot move."""
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=4)
    try:
        stepped = {"n": 0}
        real_time = time.time

        def ntp_step_backwards():
            stepped["n"] += 1
            return real_time() - 3600.0 * stepped["n"]

        monkeypatch.setattr(time, "time", ntp_step_backwards)
        tr = utrace.Trace("Scheduling", profile="p", pods=1)
        tr.step("first step done")
        with tr.stage("dispatch") as sp:
            assert sp is not None
        tr.step("second step done")
        assert tr.total() >= 0.0
        tr.finish()
        recs = fr.cycles()
        assert recs, "cycle record must commit"
        rec = recs[-1]
        assert rec.t1 is not None and rec.t1 >= rec.t0
        spans = rec.spans()
        assert spans
        for s in spans:
            assert s.t1 is not None and s.t1 >= s.t0, s.name
    finally:
        utrace.disarm_flight_recorder()


def test_wallclock_monotonic_and_wall_anchored():
    a = utrace.wallclock()
    b = utrace.wallclock()
    assert b >= a
    # anchored to the wall epoch: agrees with time.time() closely on a
    # box whose clock has not stepped since import
    assert abs(utrace.wallclock() - time.time()) < 5.0

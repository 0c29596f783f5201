"""The binder lane on its cycle's record (PR 38): ONE ``bind-job`` span a
job, written by the thread that ran it, with that thread's own CPU
seconds; the hand-over's wait on the ``commit`` span; ``loop_s`` as the
six sums' own extent; ``row-maps`` inside ``tensorize``; no annotation
of the lane's in a capture; and the disarmed path that reads no clock."""
import threading
import time

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import trace as utrace

LANE = "binder-lane"
WAIT = 10.0
# two clocks read a few microseconds apart, each rounded to one
SLACK_S = 2e-4
SUMS = ("recheck_s", "reserve_s", "assume_s", "permit_s", "submit_s",
        "records_s")


@pytest.fixture
def flight():
    """The recorder armed as the benchmark arms it."""
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _world(nodes=48, pods=96, batch=32, **cfg):
    store = ClusterStore()
    for n in hollow.make_nodes(nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch, mode="gang",
        **cfg))
    for p in hollow.make_pods(pods):
        store.add(p)
    return store, sched


def _drain(sched):
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            return outs
        outs.extend(got)


@pytest.fixture
def cycles(flight):
    """Three cycles of 32 pods, their binds through the binder lane, read
    after ``wait_for_inflight_binds`` as the benchmark reads them."""
    store, sched = _world()
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sum(1 for o in outs if o.node) == 96
        yield [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()


def _one(cycle, name):
    found = [s for s in cycle["spans"] if s["name"] == name]
    assert len(found) == 1, (name, [s["name"] for s in cycle["spans"]])
    return found[0]


def test_the_lane_writes_one_bind_job_span_a_cycle(cycles):
    assert len(cycles) == 3
    for c in cycles:
        job, commit = _one(c, "bind-job"), _one(c, "commit")
        assert job["thread"] == LANE and commit["thread"] != LANE
        assert job["parent"] == commit["id"]
        a, ext = job["args"], job["t1"] - job["t0"]
        assert 0.0 <= a["cpu_s"] <= ext + SLACK_S
        assert 0.0 <= a["settle_s"] <= ext + SLACK_S
        assert a["wake_s"] >= 0.0 and a["pooled"] == 0
        assert a.get("gc_s", 0.0) <= ext + SLACK_S
        rows = [r for r in c["binds"] if r[3] == LANE]
        assert a["pods"] == len(rows) == 32
        # from outside (lane_busy_ms_per_cycle.sat) the job is its rows'
        # first start to last end: the span holds every one of them
        assert all(job["t0"] - 1e-6 <= r[1] <= r[2] <= job["t1"] + 1e-6
                   for r in rows)
        # the hand-over came first, from inside the commit phase
        assert commit["t0"] <= job["t0"] - a["wake_s"] <= commit["t1"] + 1e-6


def test_the_hand_overs_wait_is_part_of_submit_s(cycles):
    for c in cycles:
        a = _one(c, "commit")["args"]
        assert 0.0 <= a["handover_wait_s"] <= a["submit_s"] + 1e-6
        assert a["bind_jobs"] == 1


def test_loop_s_is_the_extent_of_the_six_sums(cycles):
    """The loop's last stamp less its first: the sums add up to it, to the
    rounding of seven numbers (it used to be closed a clock read later,
    and a toy cycle in ~1,200 fell under the benchmark's 0.9 floor)."""
    for c in cycles:
        a = _one(c, "commit")["args"]
        assert sum(a[k] for k in SUMS) == pytest.approx(a["loop_s"],
                                                        abs=5e-6)


def test_row_maps_is_a_stage_inside_tensorize(cycles):
    """Where the cycle refreshed the resident tensors (meta ``delta_rows``
    says so; a chained cycle reuses the last auction's and copies no
    map), beside its sibling ``batch-build``."""
    copied = []
    for c in cycles:
        maps = [s for s in c["spans"] if s["name"] == "row-maps"]
        assert len(maps) == ("delta_rows" in c["meta"])
        for m in maps:
            tz = _one(c, "tensorize")
            assert tz["t0"] <= m["t0"] <= m["t1"] <= tz["t1"] + 1e-6
            assert m["parent"] == _one(c, "batch-build")["parent"]
            assert m["thread"] == tz["thread"]
            copied.append(m["args"]["pod_rows"])
    assert copied and min(copied) >= 0


def test_the_record_stays_far_under_its_cap(cycles):
    assert max(len(c["spans"]) for c in cycles) < 64
    assert all(c["span_drops"] == 0 and c["event_drops"] == 0
               for c in cycles)


def test_a_job_the_serving_thread_runs_is_tagged_with_that_thread(flight):
    """close() shut the lane under a cycle: its job is applied where it is
    handed over, and says so."""
    store, sched = _world(pods=8)
    try:
        sched._bind_lane.close()
        sched._bind_pool.shutdown(wait=False)
        outs = sched.schedule_pending(timeout=0.0)
        assert len(outs) == 8 and all(o.node for o in outs)
        (c,) = [c.to_dict() for c in flight.cycles()]
        job, commit = _one(c, "bind-job"), _one(c, "commit")
        assert job["thread"] == commit["thread"] \
            == threading.current_thread().name
        assert job["parent"] == commit["id"] and job["args"]["pods"] == 8
        assert "wake_s" not in job["args"]      # no lane took it over
        assert commit["t0"] <= job["t0"] <= job["t1"] <= commit["t1"] + 1e-6
    finally:
        sched.close()


def test_the_exports_show_the_job_on_the_lanes_row(flight):
    """/debug/flightz?format=chrome and tools/traceview key rows by thread
    name: the job lies on ``binder-lane`` beside the ``bind`` spans made
    from the bind table, in both exports."""
    store, sched = _world(pods=32)
    try:
        _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        chrome = flight.to_chrome_trace()["traceEvents"]
        (lane_tid,) = {(e["pid"], e["tid"]) for e in chrome
                       if e["ph"] == "M" and e["name"] == "thread_name"
                       and e["args"]["name"] == LANE}
        (job,) = [e for e in chrome if e["ph"] == "X"
                  and e["name"] == "bind-job"]
        binds = [e for e in chrome if e["ph"] == "X" and e["name"] == "bind"]
        assert len(binds) == 32 and job["cat"] == "binder"
        assert {(e["pid"], e["tid"]) for e in binds + [job]} == {lane_tid}
        assert all(job["ts"] <= e["ts"] and e["ts"] + e["dur"]
                   <= job["ts"] + job["dur"] + 1 for e in binds)
        pipe = flight.to_pipeline_doc("t")
        (row,) = [s for s in pipe["spans"] if s["stage"] == "bind-job"]
        assert row["thread"] == LANE and row["args"]["pods"] == 32
        assert pipe["span_total"] == sum(1 for e in chrome
                                         if e["ph"] == "X")
    finally:
        sched.close()


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""
    log = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name,
                                    threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name,
                                    threading.current_thread().name))
        return False


def test_in_a_capture_the_job_opens_no_annotation(monkeypatch, flight):
    """perfbench/lib/xplane takes every host event named ``Scheduling:*``
    on any thread as a phase of the serving thread, so the lane opens
    none; nor any other (``Binding:bind-job`` had no reader from PR 38 to
    PR 51, which took it out: the job's extent is on the profiler's clock
    through its span and ``kubetpu.clock``, and the stretch it runs in is
    ``Scheduling:teardown``)."""
    import jax
    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(utrace, "_PROFILE_ACTIVE", True)
    store, sched = _world(pods=64)
    try:
        _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
    finally:
        sched.close()
    assert [n for _, n, t in _FakeAnnotation.log if t == LANE] == []
    assert any(n.startswith(utrace.CYCLE_TRACE + ":")
               for _, n, _ in _FakeAnnotation.log)
    jobs = [s for c in flight.cycles() for s in c.spans()
            if s.name == utrace.JOB_SPAN]
    assert len(jobs) == 2 and {s.thread for s in jobs} == {LANE}


def test_disarmed_the_hand_over_and_the_job_read_no_clock(monkeypatch):
    """Recorder disarmed: no JobSpan, no stamp at the lane's queue, no
    thread-CPU clock and no wallclock() on the serving thread's hand-over
    or in the lane's job."""
    utrace.disarm_flight_recorder()

    def boom(*a, **kw):
        raise AssertionError("the disarmed hand-over touched the tracer")

    monkeypatch.setattr(utrace.JobSpan, "__init__", boom)
    monkeypatch.setattr(time, "thread_time", boom)
    # wallclock() is poisoned only inside the two, thread by thread: the
    # cycle's own deadline reads it elsewhere, armed or not.  (Not
    # thread_time: nothing disarmed reads that anywhere.)
    inside = threading.local()
    real_wallclock = utrace.wallclock

    def wallclock():
        if getattr(inside, "depth", 0):
            boom()
        return real_wallclock()
    monkeypatch.setattr(utrace, "wallclock", wallclock)
    errors, ran_on = [], []

    def poisoned(real):
        def call(self, *a, **kw):
            inside.depth = getattr(inside, "depth", 0) + 1
            try:
                return real(self, *a, **kw)
            except AssertionError as e:        # the lane's would be lost
                errors.append(e)
                raise
            finally:
                inside.depth -= 1
                ran_on.append(threading.current_thread().name)
        return call
    real_run, real_over = Scheduler._run_bind_job, Scheduler._hand_over

    def unpoisoned(real):
        def call(self, *a, **kw):
            # a binding cycle (a pod's, a batch's) times itself for the
            # bind metrics, armed or not: the poison is for what the job
            # adds around it
            depth, inside.depth = getattr(inside, "depth", 0), 0
            try:
                return real(self, *a, **kw)
            finally:
                inside.depth = depth
        return call
    monkeypatch.setattr(Scheduler, "_bind_cycle",
                        unpoisoned(Scheduler._bind_cycle))
    monkeypatch.setattr(Scheduler, "_bind_batch",
                        unpoisoned(Scheduler._bind_batch))
    monkeypatch.setattr(Scheduler, "_hand_over", poisoned(real_over))
    monkeypatch.setattr(Scheduler, "_run_bind_job", poisoned(real_run))
    store, sched = _world(pods=64)      # its lane binds the patched job
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds(timeout=WAIT)
        assert sum(1 for o in outs if o.node) == 64
        assert not errors
        assert LANE in ran_on and len(ran_on) == 4   # 2 hand-overs, 2 jobs
    finally:
        sched.close()

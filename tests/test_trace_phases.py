"""The serving cycle's ONE span tree (kubetpu/utils/trace.py, PR 26): a
flat, complete partition into phases named for what is open; the commit
loop's split as sums; the lock-free bind table; the clock event shared
with the profiler; and the disarmed path that reads no clock."""
import json
import threading
import time

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import trace as utrace

PHASES = ("pop", "snapshot", "prefilter", "tensorize", "host-masks",
          "dispatch", "packed-readback", "commit")
ANNOTATIONS = {"Scheduling:pop", "Scheduling:snapshot",
               "Scheduling:prefilter", "Scheduling:tensorize",
               "Scheduling:host-masks", "Scheduling:dispatch",
               "Scheduling:readback", "Scheduling:commit",
               # the stretch of ``pop`` before begin_pop()'s pick-up (PR 51)
               "Scheduling:teardown"}
# what the benchmark of PR 25 reads, letter for letter
LEGACY_STEP = "Tensorizing snapshot and pod batch done"


@pytest.fixture
def flight():
    """The recorder armed as the benchmark arms it."""
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _world(nodes=48, pods=96, batch=32, async_binding=True, **cfg):
    store = ClusterStore()
    for n in hollow.make_nodes(nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=batch, mode="gang",
        **cfg), async_binding=async_binding)
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
    """Three cycles of 32 pods, their binds through the binder lane."""
    store, sched = _world()
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds()
        assert sum(1 for o in outs if o.node) == 96
        yield [c.to_dict() for c in flight.cycles()], store
    finally:
        sched.close()


def _phase(cycle, name):
    found = [s for s in cycle["spans"] if s["name"] == name]
    assert len(found) == 1, (name, [s["name"] for s in cycle["spans"]])
    return found[0]


@pytest.fixture
def quiet_cycles(flight):
    """The same three cycles with binds on the serving thread: no other
    thread takes the interpreter from it between two phases, so what the
    partition leaves uncovered is code, not scheduling noise."""
    store, sched = _world(async_binding=False)
    try:
        _drain(sched)
        yield [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()


def test_the_phases_partition_the_serving_threads_cycle(quiet_cycles):
    recs = quiet_cycles
    assert len(recs) == 3
    for c, nxt in zip(recs, recs[1:] + [None]):
        assert c["span_drops"] == 0 and c["event_drops"] == 0
        assert len(c["spans"]) <= 32
        ph = [_phase(c, n) for n in PHASES]
        # in order, none overlapping the next
        for a, b in zip(ph, ph[1:]):
            assert a["t0"] <= a["t1"] <= b["t0"] + 1e-6, (a, b)
        # all children of the cycle's root, on the serving thread
        root = next(s for s in c["spans"] if s["parent"] == 0)
        assert all(s["parent"] == root["id"] for s in ph)
        assert {s["thread"] for s in ph} == {root["thread"]}
        # they cover the cycle: its own extent plus the pop before it...
        covered = sum(s["t1"] - s["t0"] for s in ph)
        assert ph[0]["t0"] <= c["t0"]
        assert covered >= 0.98 * (c["t1"] - ph[0]["t0"])
        # ...and the serving thread's whole period, pop to pop
        if nxt is not None:
            period = _phase(nxt, "pop")["t0"] - ph[0]["t0"]
            assert covered >= 0.98 * period
        for s in ph:
            assert 0.0 <= s["args"]["cpu_s"] <= s["t1"] - s["t0"] + 2e-3


def test_phase_args_say_what_each_phase_worked_on(flight):
    store, sched = _world(pods=32)
    try:
        _drain(sched)
        sched.wait_for_inflight_binds()
        # churn before each of two more cycles, more pods leaving than
        # coming: the last one runs a delta scatter (no resync, no
        # growth of the pod axis)
        for lo in (32, 40):
            for i in range(3 * (lo - 32) // 2, 3 * (lo - 32) // 2 + 12):
                store.delete(store.get_pod("default", f"pod-{i}"))
            for p in hollow.make_pods(lo + 8)[lo:]:
                store.add(p)
            _drain(sched)
            sched.wait_for_inflight_binds()
    finally:
        sched.close()
    c = flight.cycles()[-1].to_dict()
    pop = _phase(c, "pop")["args"]
    assert pop["popped"] == 8 and pop["skipped"] == 0
    assert pop["wait_s"] == 0.0          # the queue was not empty
    assert _phase(c, "snapshot")["args"]["nodes"] == 48
    assert _phase(c, "prefilter")["args"]["pods"] == 8
    tz = _phase(c, "tensorize")["args"]
    assert tz["pod_bucket"] == c["meta"]["pod_bucket"] > 0
    assert tz["delta_rows"] == c["meta"]["delta_rows"] > 0
    # a delta cycle names the (dirty-node, churned-pod) row buckets
    assert tz["delta_buckets"] == c["meta"]["delta_buckets"]
    assert len(tz["delta_buckets"]) == 2
    assert all(b >= 8 and b & (b - 1) == 0 for b in tz["delta_buckets"])
    kids = {s["name"] for s in c["spans"]}
    assert {"delta-build", "delta-apply", "batch-build"} <= kids
    assert "device_wait_s" in _phase(c, "packed-readback")["args"]


def test_the_names_the_first_benchmark_reads_are_still_there(cycles):
    recs, _ = cycles
    for c in recs:
        assert set(c) >= {"seq", "label", "t0", "t1", "queue_depths",
                          "meta", "span_drops", "event_drops", "spans",
                          "events"}
        names = [s["name"] for s in c["spans"]]
        assert names.count("commit") == 1
        assert names.count("packed-readback") == 1
        step = [s for s in c["spans"] if s["name"] == LEGACY_STEP]
        assert len(step) == 1 and step[0]["t1"] > c["t0"]
        assert c["meta"]["pods"] == 32 and c["meta"]["auction_rounds"] >= 1
    assert callable(utrace.wallclock) and utrace._PROFILE_ACTIVE is False


def test_the_commit_loop_is_split_into_sums_on_the_commit_span(cycles):
    recs, _ = cycles
    for c in recs:
        sp = _phase(c, "commit")
        a = sp["args"]
        parts = [a[k] for k in ("recheck_s", "reserve_s", "assume_s",
                                "permit_s", "submit_s", "records_s")]
        assert all(p >= 0.0 for p in parts)
        assert a["assume_s"] > 0 and a["submit_s"] > 0
        # plain pods through the lane: every one of them rode a run
        assert a["pods"] == a["batched"] == 32
        dur = sp["t1"] - sp["t0"]
        # the sums cover the loop and stay inside the span
        assert sum(parts) <= a["loop_s"] + 1e-4 <= dur + 2e-4
        assert sum(parts) >= 0.9 * a["loop_s"]
        assert a["loop_cpu_s"] <= a["loop_s"] + 2e-3
        assert a["cpu_s"] <= dur + 2e-3


def test_the_bind_table_has_one_complete_row_per_bound_pod(cycles):
    recs, store = cycles
    binders = set()
    for c in recs:
        rows = c["binds"]
        names = c["meta"]["batch_pods"]
        assert len(rows) == len(names) == 32
        rb_end = _phase(c, "packed-readback")["t1"]
        for name, (sub, start, done, thread) in zip(names, rows):
            assert rb_end <= sub <= start <= done, (name, sub, start, done)
            assert thread.startswith("binder")
            binders.add(thread)
            # row i is batch_pods[i]: that pod is bound in the store
            assert store.get_pod("default", name).spec.node_name
    assert binders == {"binder-lane"}    # one lane, not the pool
    json.dumps(recs)


def test_bind_rows_render_as_spans_in_the_exports(flight):
    store, sched = _world(pods=32, async_binding=False)
    try:
        _drain(sched)
        chrome = flight.to_chrome_trace()["traceEvents"]
        binds = [e for e in chrome if e["ph"] == "X" and e["name"] == "bind"]
        assert len(binds) == 32
        assert all(e["args"]["queued_s"] >= 0 for e in binds)
        pipe = flight.to_pipeline_doc("t")
        assert sum(1 for s in pipe["spans"] if s["stage"] == "bind") == 32
        assert pipe["span_total"] == sum(1 for e in chrome
                                         if e["ph"] == "X")
    finally:
        sched.close()


def test_the_snapshot_moment_and_the_batch_ride_the_cycles_meta(cycles):
    """For a later tie-set check on the window's own binds (PERF.md,
    Open questions): when the snapshot was taken, and which pods, in
    batch order, the cycle placed against it."""
    recs, _ = cycles
    seen = []
    for c in recs:
        snap = _phase(c, "snapshot")
        assert snap["t0"] <= c["meta"]["snapshot_t"] <= snap["t1"] + 1e-4
        assert len(c["meta"]["batch_pods"]) == c["meta"]["pods"]
        seen += c["meta"]["batch_pods"]
    assert sorted(seen) == sorted(f"pod-{i}" for i in range(96))


# ------------------------------------------------------------ annotations


class _FakeAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit."""
    log = []

    def __init__(self, name, **kw):
        self.name, self.kw = name, kw

    def __enter__(self):
        _FakeAnnotation.log.append(("enter", self.name, self.kw,
                                    threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        _FakeAnnotation.log.append(("exit", self.name, self.kw,
                                    threading.current_thread().name))
        return False


@pytest.fixture
def annotations(monkeypatch):
    import jax
    _FakeAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _FakeAnnotation)
    monkeypatch.setattr(utrace, "_PROFILE_ACTIVE", True)
    return _FakeAnnotation.log


@pytest.mark.parametrize("armed", [False, True])
@pytest.mark.parametrize("pipelined", [False, True])
def test_annotations_carry_the_open_phase_and_never_nest(
        annotations, armed, pipelined):
    """While a capture is active every phase opens ONE annotation of its
    own extent, named for the phase that is OPEN -- recorder armed or
    not, serial or through the pipelined drain."""
    utrace.disarm_flight_recorder()
    if armed:
        utrace.arm_flight_recorder(capacity=8, max_spans_per_cycle=64)
    cfg = dict(pipeline_cycles=True, chain_cycles=True) if pipelined else {}
    store, sched = _world(pods=64, **cfg)
    try:
        _drain(sched)
        sched.flush_pipeline()
        sched.wait_for_inflight_binds()
    finally:
        sched.close()
        utrace.disarm_flight_recorder()
    open_now = None
    seen = []
    for what, name, _kw, thread in annotations:
        if not name.startswith("Scheduling:"):
            continue
        if what == "enter":
            assert open_now is None, f"{name} opened inside {open_now}"
            open_now = name
            seen.append(name)
        else:
            assert open_now == name
            open_now = None
    assert open_now is None
    assert set(seen) == ANNOTATIONS
    # in a serial cycle they come in the partition's order
    if not pipelined:
        order = [n for n in seen[:8]]
        assert order == ["Scheduling:" + ("readback" if p ==
                                          "packed-readback" else p)
                         for p in PHASES]
        # ...and the next cycle's pop opens with the teardown of this one
        assert seen[8:11] == ["Scheduling:teardown", "Scheduling:pop",
                              "Scheduling:snapshot"]


def test_every_cycle_drops_a_clock_event_into_the_capture(annotations,
                                                          flight):
    store, sched = _world(pods=64)
    try:
        t_before = utrace.wallclock()
        _drain(sched)
        t_after = utrace.wallclock()
    finally:
        sched.close()
    clocks = [(kw["cycle"], kw["wallclock_s"]) for what, name, kw, _
              in annotations
              if what == "enter" and name == utrace.CLOCK_ANNOTATION]
    seqs = [c.seq for c in flight.cycles()]
    assert [c for c, _ in clocks] == seqs and len(seqs) == 2
    assert all(t_before <= t <= t_after for _, t in clocks)
    # the event is shut at once: it never encloses a phase
    idx = [i for i, (w, n, _, _) in enumerate(annotations)
           if n == utrace.CLOCK_ANNOTATION]
    assert all(annotations[i][0] == "enter" and annotations[i + 1][0]
               == "exit" and annotations[i + 1][1]
               == utrace.CLOCK_ANNOTATION for i in idx[::2])


def test_capture_device_trace_hands_profiler_options_through(monkeypatch,
                                                             tmp_path):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d, **kw: calls.append((d, kw)))
    monkeypatch.setattr(jax.profiler, "stop_trace", lambda: None)
    opts = object()
    with utrace.capture_device_trace(str(tmp_path / "a"),
                                     profiler_options=opts):
        assert utrace._PROFILE_ACTIVE is True
    with utrace.capture_device_trace(str(tmp_path / "b")):
        pass
    assert utrace._PROFILE_ACTIVE is False
    assert calls == [(str(tmp_path / "a"), {"profiler_options": opts}),
                     (str(tmp_path / "b"), {"profiler_options": None})]


# --------------------------------------------------------------- disarmed


def test_disarmed_the_cycle_reads_no_clock_and_takes_no_lock(monkeypatch):
    """Recorder disarmed, no capture: no phase object, no bind-table
    stamp, no thread-CPU clock, no queue-wait stamp, no record lock --
    on the serving thread or in the binder pool."""
    utrace.disarm_flight_recorder()

    def boom(*a, **kw):
        raise AssertionError("the disarmed hot path touched the tracer")

    monkeypatch.setattr(utrace, "_open_phase", boom)
    monkeypatch.setattr(utrace, "_emit_clock", boom)
    monkeypatch.setattr(utrace.CycleRecord, "__init__", boom)
    monkeypatch.setattr(utrace.CycleRecord, "alloc_binds", boom)
    monkeypatch.setattr(utrace.CycleRecord, "stamp_bind", boom)
    monkeypatch.setattr(utrace._Phase, "close", boom)
    monkeypatch.setattr(time, "thread_time", boom)
    store, sched = _world(pods=64)
    errors = []
    real = Scheduler._bind_cycle

    def bind_cycle(self, *a, **kw):
        try:
            return real(self, *a, **kw)
        except AssertionError as e:      # a pool thread's would be lost
            errors.append(e)
            raise
    monkeypatch.setattr(Scheduler, "_bind_cycle", bind_cycle)
    try:
        assert utrace.begin_pop() is None
        outs = _drain(sched)
        sched.wait_for_inflight_binds()
        assert sum(1 for o in outs if o.node) == 64
        assert sched.queue.pop_wait_s == 0.0
        assert not errors
    finally:
        sched.close()

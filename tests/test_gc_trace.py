"""The collector's pauses on the cycle's record (PR 38): one function in
``gc.callbacks`` while the flight recorder is armed; a pause lands as
``gc_s`` / ``gc_full`` on the phase or the bind job open on its thread, a
full collection as one ``gc`` event there too; what falls on a thread with
neither lands in the next cycle's meta as ``gc_other_s``."""
import gc
import threading

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import trace as utrace

WAIT = 10.0


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


@pytest.fixture
def no_automatic_passes():
    """Only the collections a test forces: ``gc.collect`` runs, and calls
    the callbacks, with the automatic collector off."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _hooks():
    return [f for f in gc.callbacks if f is utrace._on_gc]


def _named(cycle, name):
    return [s for s in cycle["spans"] if s["name"] == name]


def test_arming_hooks_the_collector_once_and_disarming_unhooks_it():
    utrace.disarm_flight_recorder()
    others = list(gc.callbacks)          # jax keeps one of its own
    assert not _hooks()
    fr = utrace.arm_flight_recorder(capacity=4)
    assert utrace.arm_flight_recorder() is fr
    assert len(_hooks()) == 1
    utrace.disarm_flight_recorder()
    utrace.disarm_flight_recorder()
    assert not _hooks() and gc.callbacks == others


def test_a_collection_inside_a_phase_is_that_phases(flight,
                                                    no_automatic_passes):
    tr = utrace.Trace("Scheduling")
    with tr.phase("snapshot"):
        gc.collect()
    with tr.phase("tensorize"):
        pass
    tr.phase("commit")
    gc.collect(0)
    gc.collect(1)
    tr.finish()                 # closes the phase that is open
    (c,) = [c.to_dict() for c in flight.cycles()]
    (snap,), (tz,), (commit,) = (_named(c, n) for n in
                                 ("snapshot", "tensorize", "commit"))
    assert 0.0 < snap["args"]["gc_s"] <= snap["t1"] - snap["t0"] + 1e-6
    assert snap["args"]["gc_full"] == 1
    # no pause, no key
    assert "gc_s" not in tz["args"] and "gc_full" not in tz["args"]
    # the younger generations are summed and leave no event
    assert 0.0 < commit["args"]["gc_s"] <= commit["t1"] - commit["t0"] + 1e-6
    assert "gc_full" not in commit["args"]
    (ev,) = [e for e in c["events"] if e["name"] == "gc"]
    assert ev["parent"] == snap["id"]
    assert snap["t0"] <= ev["ts"] <= snap["t1"]
    assert ev["args"]["generation"] == 2 and ev["args"]["collected"] >= 0
    assert ev["args"]["seconds"] == pytest.approx(snap["args"]["gc_s"],
                                                  abs=2e-6)
    assert c["meta"]["gc_collections"] == 3 and "gc_other_s" not in c["meta"]


def test_a_collection_on_a_thread_with_nothing_open_is_the_next_cycles(
        flight, no_automatic_passes):
    t = threading.Thread(target=gc.collect, name="perfbench-client")
    t.start()
    t.join(WAIT)
    assert not t.is_alive()
    tr = utrace.Trace("Scheduling")
    with tr.phase("snapshot"):
        pass
    tr.finish()
    utrace.Trace("Scheduling").finish()
    first, second = [c.to_dict() for c in flight.cycles()]
    assert first["meta"]["gc_other_s"] > 0.0
    assert first["meta"]["gc_collections"] == 1
    assert not any(e["name"] == "gc" for e in first["events"])
    assert not any("gc_s" in s["args"] for s in first["spans"])
    # taken once: the cycle after starts from zero
    assert "gc_other_s" not in second["meta"]
    assert "gc_collections" not in second["meta"]


class _CollectingStore(ClusterStore):
    """A full collection inside the first bind of every job."""

    def bind(self, pod, node_name):
        if pod.metadata.name.endswith("-0"):
            gc.collect()
        super().bind(pod, node_name)


def test_a_collection_on_the_lane_is_the_bind_jobs(flight,
                                                   no_automatic_passes):
    store = _CollectingStore()
    for n in hollow.make_nodes(8):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=8, mode="gang"))
    for p in hollow.make_pods(8):
        store.add(p)
    try:
        assert len(sched.schedule_pending(timeout=0.0)) == 8
        sched.wait_for_inflight_binds(timeout=WAIT)
    finally:
        sched.close()
    (c,) = [c.to_dict() for c in flight.cycles()]
    (job,) = _named(c, "bind-job")
    assert job["thread"] == "binder-lane" and job["args"]["gc_full"] == 1
    assert 0.0 < job["args"]["gc_s"] <= job["t1"] - job["t0"] + 1e-6
    (ev,) = [e for e in c["events"] if e["name"] == "gc"]
    assert ev["parent"] == job["id"] and ev["thread"] == "binder-lane"
    assert job["t0"] <= ev["ts"] <= job["t1"]
    # the serving thread's phases had none of their own
    assert not any("gc_s" in s["args"] for s in c["spans"] if s is not job)


def test_disarmed_no_phase_and_no_finish_touches_the_accounting(
        monkeypatch):
    utrace.disarm_flight_recorder()
    assert not _hooks()

    def boom(*a, **kw):
        raise AssertionError("the disarmed cycle touched the accounting")

    monkeypatch.setattr(utrace, "_gc_sums", boom)
    monkeypatch.setattr(utrace, "_on_gc", boom)
    monkeypatch.setattr(utrace, "_read_thread_cpu", boom)
    monkeypatch.setattr(utrace.FlightRecorder, "note_interpreter", boom)
    tr = utrace.Trace("Scheduling")
    with tr.phase("snapshot"):
        gc.collect()
    tr.phase("commit")
    tr.finish()

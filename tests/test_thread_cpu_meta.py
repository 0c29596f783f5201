"""Who held the interpreter, on the cycle's meta (PR 38): ``thread_cpu_s``
= the CPU seconds of every live Python thread between two cycles' ends,
by thread name, and ``thread_cpu_window_s``, the wall seconds between the
two readings -- taken by ``Trace.finish`` from the serving thread, kept on
the ``FlightRecorder``."""
import threading
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import trace as utrace

WAIT = 10.0
PHASES = ("pop", "snapshot", "prefilter", "tensorize", "host-masks",
          "dispatch", "packed-readback", "commit")


@pytest.fixture
def flight():
    utrace.disarm_flight_recorder()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _cycle():
    """One empty cycle's end: a reading of every thread's clock."""
    utrace.Trace("Scheduling").finish()


def _spin(cpu_s, done):
    t0 = time.thread_time()
    while time.thread_time() - t0 < cpu_s:
        pass
    done.set()


def test_a_thread_that_ran_is_there_by_name_and_one_that_slept_is_not(
        flight):
    spun, leave = threading.Event(), threading.Event()
    spinner = threading.Thread(
        target=lambda: (_spin(0.05, spun), leave.wait(WAIT)),
        name="spinner", daemon=True)
    sleeper = threading.Thread(target=leave.wait, args=(WAIT,),
                               name="sleeper", daemon=True)
    try:
        sleeper.start()         # starting up costs a thread ~0.1 ms
        time.sleep(0.01)
        _cycle()                # the first reading: nothing to say
        t_start = time.perf_counter()
        spinner.start()
        assert spun.wait(WAIT)
        _cycle()
        t_end = time.perf_counter()
        _cycle()
    finally:
        leave.set()
        for t in (spinner, sleeper):
            t.join(WAIT)
    first, second, third = [c.to_dict()["meta"] for c in flight.cycles()]
    assert "thread_cpu_s" not in first and "thread_cpu_window_s" not in first
    cpu = second["thread_cpu_s"]
    assert 0.04 <= cpu["spinner"] <= second["thread_cpu_window_s"] + 1e-3
    assert "sleeper" not in cpu
    # the window is the time between the two readings
    assert t_end - t_start - 1e-3 <= second["thread_cpu_window_s"] \
        <= t_end - t_start + 1.0
    # both still alive over the third window, neither ran in it
    assert not {"spinner", "sleeper"} & set(third["thread_cpu_s"])
    assert third["thread_cpu_window_s"] < second["thread_cpu_window_s"]


def test_a_pools_threads_are_one_entry_under_its_prefix(flight):
    _cycle()
    with ThreadPoolExecutor(4, thread_name_prefix="binder") as pool:
        gate = threading.Barrier(4)     # four threads, not one four times

        def work():
            gate.wait(WAIT)
            _spin(0.02, threading.Event())
        for f in [pool.submit(work) for _ in range(4)]:
            f.result(WAIT)
        _cycle()
    cpu = flight.cycles()[-1].to_dict()["meta"]["thread_cpu_s"]
    assert cpu["binder_pool"] >= 0.07
    assert not any(k.startswith("binder_") and k != "binder_pool"
                   for k in cpu)
    assert utrace._fold_name("perfbench-client") == "perfbench-client"
    assert utrace._fold_name("binder-lane") == "binder-lane"
    assert utrace._fold_name("ThreadPoolExecutor-0_12") \
        == "ThreadPoolExecutor-0_pool"


def test_the_phases_cpu_adds_up_to_the_serving_threads_entry(flight):
    """The partition covers the serving thread's period, and both read the
    same per-thread clock: within 5% a cycle (binds on the serving thread,
    so every CPU second of the cycle is inside a phase)."""
    store = ClusterStore()
    for n in hollow.make_nodes(48):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=32, mode="gang"),
        async_binding=False)
    for p in hollow.make_pods(96):
        store.add(p)
    try:
        while sched.schedule_pending(timeout=0.0):
            pass
    finally:
        sched.close()
    recs = [c.to_dict() for c in flight.cycles()]
    assert len(recs) == 3 and "thread_cpu_s" not in recs[0]["meta"]
    me = threading.current_thread().name
    for c in recs[1:]:
        phases = sum(s["args"]["cpu_s"] for s in c["spans"]
                     if s["name"] in PHASES)
        mine = c["meta"]["thread_cpu_s"][me]
        # what lies between two phases is a few hundred microseconds a
        # cycle, which a cycle of a few ms (a warm compile cache) feels
        assert abs(mine - phases) <= 0.05 * mine + 5e-4, (mine, phases)
        assert sum(c["meta"]["thread_cpu_s"].values()) \
            <= 8 * c["meta"]["thread_cpu_window_s"]    # cores, not magic


def test_the_readings_live_on_the_recorder(flight):
    _cycle()
    assert flight._thread_cpu and flight._thread_cpu_t > 0.0
    assert threading.current_thread() in flight._thread_cpu
    # a recorder armed anew starts from nothing
    utrace.disarm_flight_recorder()
    fresh = utrace.arm_flight_recorder(capacity=4)
    assert fresh is not flight and not fresh._thread_cpu
    _cycle()
    assert "thread_cpu_s" not in fresh.cycles()[0].to_dict()["meta"]


def test_a_platform_without_the_clock_says_nothing(monkeypatch, flight):
    monkeypatch.delattr(time, "pthread_getcpuclockid")
    for _ in range(3):
        _cycle()
    for c in flight.cycles():
        meta = c.to_dict()["meta"]
        assert "thread_cpu_s" not in meta
        assert "thread_cpu_window_s" not in meta

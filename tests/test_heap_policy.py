"""The serving process's collector policy (PR 39, kubetpu/utils/heap.py):
between ``Scheduler.run()`` and ``close()`` what survives start-up, and
what survives every HANDOFF_EVERY-th cycle, is handed to the collector's
permanent generation; a sweep is the safety net; ``close()`` gives the
heap back.  A Scheduler that is never ``run()`` touches none of it."""
import gc
import time
import weakref

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import heap as uheap
from kubetpu.utils import trace as utrace

WAIT = 20.0
BATCH = 4


@pytest.fixture(autouse=True)
def _heap_as_found():
    """Every case starts unfrozen (CPython 3.12 itself starts with a few
    hundred objects in the permanent generation; an earlier scheduler's
    close() has released them) and must end so."""
    gc.unfreeze()
    threshold = gc.get_threshold()
    yield
    assert not uheap._serving
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == threshold


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
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()


def _scheduler(nodes=16):
    store = ClusterStore()
    for n in hollow.make_nodes(nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=BATCH, mode="gang",
        prewarm=False), async_binding=True)
    return store, sched


def _parked(sched):
    """``run()``, then the serving loop parked: the policy serves on, and
    the test drives ``schedule_pending`` itself, one cycle a call."""
    t = sched.run()
    sched._stop.set()
    t.join(WAIT)
    assert not t.is_alive() and sched._heap.started
    return sched._heap


class _Feed:
    def __init__(self, store):
        self.store, self.n = store, 0

    def batch(self):
        pods = hollow.make_pods(self.n + BATCH)[self.n:]
        self.n += BATCH
        for p in pods:
            self.store.add(p)


def _cycle(sched, feed):
    feed.batch()
    assert len(sched.schedule_pending(timeout=0.0)) == BATCH
    sched.wait_for_inflight_binds(timeout=WAIT)


def _bound(store):
    return sum(1 for p in store.list("Pod") if p.spec.node_name)


# ------------------------------------------------- (a) run ... close


def test_a_scheduler_that_served_leaves_the_collector_as_it_found_it():
    store, sched = _scheduler()
    threshold = gc.get_threshold()
    try:
        sched.run()
        assert gc.get_freeze_count() > 0        # the start-up hand-off
        feed = _Feed(store)
        for i in range(3):
            feed.batch()
            deadline = time.monotonic() + WAIT
            while _bound(store) < feed.n and time.monotonic() < deadline:
                time.sleep(0.01)
            assert _bound(store) == feed.n
    finally:
        sched.close()
        sched.wait_for_inflight_binds(timeout=WAIT)
    assert gc.get_freeze_count() == 0
    assert gc.get_threshold() == threshold
    assert not sched._heap.started
    sched.close()                               # idempotent


# ------------------------------------------- (b) the hand-offs, while serving


@pytest.mark.parametrize("every", [1, 2, 4])
def test_the_permanent_generation_grows_at_start_up_and_every_kth_cycle(
        monkeypatch, every, no_automatic_passes):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", every)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        at_start = gc.get_freeze_count()
        assert at_start > 1000 and heap.handoffs == 0
        feed = _Feed(store)
        for k in range(1, every + 1):
            # the boundary is the top of the NEXT call: cycles 1..K run
            # before anything is handed over
            _cycle(sched, feed)
            assert heap.handoffs == 0
            assert gc.get_freeze_count() <= at_start
        _cycle(sched, feed)                     # the K+1-th call's top
        assert heap.handoffs == 1
        # what K cycles kept (their pods, their events) is permanent now
        first = gc.get_freeze_count()
        assert first > at_start
        for k in range(every):
            _cycle(sched, feed)
        assert heap.handoffs == 2 and gc.get_freeze_count() > first
        assert heap.sweeps == 0
    finally:
        sched.close()


def test_the_ladders_end_is_a_start_up_hand_off_too(monkeypatch):
    """``run()`` with the prewarm on: the blocking prewarm(0), then the
    background ladder, and each ends in one full pass and a freeze."""
    store, sched = _scheduler()
    sched.config.prewarm, sched.config.prewarm_ladder = True, 1
    calls, full = [], []
    monkeypatch.setattr(sched, "prewarm",
                        lambda ladder_steps=None: calls.append(ladder_steps))
    real = uheap.HeapPolicy.startup_handoff

    def counted(self):
        full.append(gc.get_freeze_count())
        real(self)
    monkeypatch.setattr(uheap.HeapPolicy, "startup_handoff", counted)
    try:
        sched.run()
        deadline = time.monotonic() + WAIT
        while len(calls) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        time.sleep(0.05)
        assert calls == [0, 1]
        # once from run(), once from the ladder thread (a ladder that
        # ends before run() has started the policy is covered by run()'s)
        assert 1 <= len(full) <= 2 and gc.get_freeze_count() > 0
    finally:
        sched.close()


# ------------------------------------------------- (c) never run()


def test_a_scheduler_that_is_never_run_touches_nothing(monkeypatch):
    def refuse(*a):
        raise AssertionError("the collector's state was touched")
    for name in ("freeze", "unfreeze", "set_threshold", "collect"):
        monkeypatch.setattr(uheap.gc, name, refuse)
    store, sched = _scheduler()
    try:
        assert sched._heap is None
        feed = _Feed(store)
        for _ in range(3):
            _cycle(sched, feed)
        assert sched.schedule_pending(timeout=0.0) == []
        assert sched._heap is None
    finally:
        sched.close()


# ----------------------------------------- (d) cyclic garbage still dies


class _Node:
    pass


def _a_cycle():
    a, b = _Node(), _Node()
    a.other, b.other = b, a
    return a, weakref.ref(a)


def test_a_cycle_dropped_while_serving_dies_at_the_next_hand_off(
        monkeypatch, no_automatic_passes):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 1)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        _cycle(sched, feed)
        held, ref = _a_cycle()
        del held                                # garbage, and young
        assert ref() is not None                # no automatic pass ran
        _cycle(sched, feed)                     # its top: collect(1)
        assert heap.handoffs == 1 and ref() is None
        assert heap.sweeps == 0
    finally:
        sched.close()


def test_a_cycle_frozen_first_and_dropped_after_waits_for_the_sweep(
        monkeypatch, no_automatic_passes, flight):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 1)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        _cycle(sched, feed)
        held, ref = _a_cycle()
        _cycle(sched, feed)                     # hand-off: held is frozen
        assert heap.handoffs == 1
        del held
        _cycle(sched, feed)
        gc.collect()                            # not even a full pass
        assert heap.handoffs == 2 and ref() is not None
        # the safety net: asked for, and the gap since the last full pass
        # (start-up's) is over
        monkeypatch.setattr(uheap, "SWEEP_GAP_S", 0.0)
        heap.want_sweep()
        _cycle(sched, feed)
        assert ref() is None
        assert heap.sweeps == 1 and heap.sweep_collected >= 2
        assert heap.handoffs == 3               # a sweep ends in a freeze
        assert gc.get_freeze_count() > 0
        last = flight.cycles()[-1].to_dict()["meta"]
        assert last["heap_sweep_collected"] == heap.sweep_collected
        assert last["heap_handoffs"] == 1
        # taken once, and not asked for again
        _cycle(sched, feed)
        after = flight.cycles()[-1].to_dict()["meta"]
        assert "heap_sweep_collected" not in after
        assert heap.sweeps == 1                 # not asked for again
    finally:
        sched.close()


@pytest.mark.parametrize("gap, sweeps", [(0.0, 1), (3600.0, 0)])
def test_an_empty_queue_is_where_the_sweep_runs_at_most_a_gap_apart(
        monkeypatch, gap, sweeps):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 1)
    monkeypatch.setattr(uheap, "SWEEP_GAP_S", gap)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        # an empty queue before anything was handed over: nothing to do
        assert sched.schedule_pending(timeout=0.0) == []
        assert heap.sweeps == 0
        _cycle(sched, feed)
        _cycle(sched, feed)
        assert heap.handoffs == 1 and heap.sweeps == 0
        assert sched.schedule_pending(timeout=0.0) == []    # a hand-off
        assert heap.handoffs == 2 and heap.sweeps == 0
        assert sched.schedule_pending(timeout=0.0) == []    # idle
        assert heap.sweeps == sweeps
        for _ in range(3):                      # swept once, then quiet
            assert sched.schedule_pending(timeout=0.0) == []
        assert heap.sweeps == sweeps
    finally:
        sched.close()


def test_under_unbroken_load_the_sweep_comes_every_t_seconds(monkeypatch):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 2)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        for _ in range(5):
            _cycle(sched, feed)
        assert (heap.handoffs, heap.sweeps) == (2, 0)
        monkeypatch.setattr(uheap, "SWEEP_EVERY_S", 0.0)
        _cycle(sched, feed)                     # 5 ran: not a boundary's turn
        assert (heap.handoffs, heap.sweeps) == (2, 0)
        _cycle(sched, feed)
        assert (heap.handoffs, heap.sweeps) == (3, 1)
        assert heap.sweep_collected >= 0
    finally:
        sched.close()


def test_a_recovered_cycle_asks_for_a_sweep():
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        feed.batch()
        by_profile, pop = sched._pop_grouped(BATCH, 0.0)
        (name, group), = by_profile.items()
        prep, _ = sched._prepare_group(sched.profiles[name], group, pop=pop)
        assert not heap._sweep_due
        out = sched._recover_cycle(prep, "test", "dispatch-error")
        prep.trace.finish(recovered="dispatch-error")
        assert len(out) == BATCH and heap._sweep_due
    finally:
        sched.close()


# -------------------------------------------------- (e) disarmed, armed


def test_disarmed_the_boundary_reads_no_tracing_clock_and_writes_no_meta(
        monkeypatch):
    utrace.disarm_flight_recorder()
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 1)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        _cycle(sched, feed)
        n = sched.cycle_count

        def refuse(*a):
            raise AssertionError("read while disarmed")
        with monkeypatch.context() as mp:
            # the list walk and the recorder's clocks
            mp.setattr(uheap.gc, "get_freeze_count", refuse)
            mp.setattr(uheap.time, "perf_counter", refuse)
            mp.setattr(uheap.time, "thread_time", refuse)
            mp.setattr(utrace, "wallclock", refuse)
            with monkeypatch.context() as none:
                # no clock at all where nothing is due: an empty pop with
                # nothing frozen since a full pass, a cycle that is not
                # the K-th
                none.setattr(uheap.time, "monotonic", refuse)
                heap.boundary(n - 1)
                none.setattr(uheap, "HANDOFF_EVERY", 4)
                heap.boundary(n)
            assert heap.handoffs == 0
            # time.monotonic is the sweep's cadence, read at a hand-off
            heap.boundary(n + 1)
        assert heap.handoffs == 1
        assert utrace.flight_recorder() is None
    finally:
        sched.close()


def test_armed_every_cycle_says_whether_a_hand_off_came_before_it(
        monkeypatch, flight):
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 2)
    monkeypatch.setattr(uheap, "FROZEN_READ_EVERY", 4)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        for _ in range(7):
            _cycle(sched, feed)
        metas = [c.to_dict()["meta"] for c in flight.cycles()]
        assert [m["heap_handoffs"] for m in metas] == [0, 0, 1, 0, 1, 0, 1]
        frozen = [m["heap_frozen"] for m in metas]
        # read at start-up, then FROZEN_READ_EVERY cycles apart at the
        # least: the first two hand-offs carry start-up's reading on
        assert frozen[0] > 1000 and frozen[:4] == [frozen[0]] * 4
        assert frozen[4] > frozen[0] and frozen[4:] == [frozen[4]] * 3
        assert not any("heap_sweep_collected" in m for m in metas)
        assert heap.handoffs == 3
    finally:
        sched.close()
    # a scheduler that is never run() says nothing
    flight.clear()
    store, sched = _scheduler()
    try:
        fresh = utrace.arm_flight_recorder()
        assert fresh is flight
        flight._heap_handoffs = None            # as a recorder newly armed
        _cycle(sched, _Feed(store))
        (m,) = [c.to_dict()["meta"] for c in flight.cycles()]
        assert "heap_handoffs" not in m and "heap_frozen" not in m
    finally:
        sched.close()


def test_the_explicit_passes_are_charged_like_any_other(
        monkeypatch, flight, no_automatic_passes):
    """``gc.collect(1)`` at the boundary fires ``gc.callbacks``: its pause
    is ``gc_s`` on the ``pop`` phase it falls in, and a sweep is a full
    pass there."""
    monkeypatch.setattr(uheap, "HANDOFF_EVERY", 1)
    store, sched = _scheduler()
    try:
        heap = _parked(sched)
        feed = _Feed(store)
        _cycle(sched, feed)
        _cycle(sched, feed)
        monkeypatch.setattr(uheap, "SWEEP_GAP_S", 0.0)
        heap.want_sweep()
        _cycle(sched, feed)
        second, third = [c.to_dict() for c in flight.cycles()][1:]
        (pop2,) = [s for s in second["spans"] if s["name"] == "pop"]
        (pop3,) = [s for s in third["spans"] if s["name"] == "pop"]
        assert pop2["args"]["gc_s"] > 0 and "gc_full" not in pop2["args"]
        assert pop3["args"]["gc_full"] == 1
        (ev,) = [e for e in third["events"] if e["name"] == "gc"]
        assert ev["args"]["collected"] == third["meta"].get(
            "heap_sweep_collected", 0)
    finally:
        sched.close()


# ------------------------------------------------ (f) two in one process


@pytest.mark.parametrize("first", [0, 1], ids=["oldest-first",
                                               "newest-first"])
def test_two_schedulers_closed_in_either_order_end_unfrozen(first):
    (s0, a), (s1, b) = _scheduler(), _scheduler()
    try:
        ha, hb = _parked(a), _parked(b)
        assert uheap._serving == [ha, hb] and gc.get_freeze_count() > 0
        one, two = (a, b) if first == 0 else (b, a)
        one.close()
        # the other still serves: nothing is given back under it, and its
        # next sweep takes what the closed one left
        assert gc.get_freeze_count() > 0
        assert uheap._serving == [two._heap] and two._heap._sweep_due
        _cycle(two, _Feed(s1 if two is b else s0))
        two.close()
        assert gc.get_freeze_count() == 0 and not uheap._serving
    finally:
        a.close()
        b.close()

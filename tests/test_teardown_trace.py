"""``pop`` lit from the inside (kubetpu/utils/trace.py, PR 51): the span
``teardown`` under ``pop`` with the serving thread's ``cpu_s`` and every
thread's CPU inside it, its children ``teardown-release`` and
``heap-boundary``, ``queue_s`` / ``group_s`` on ``pop``, the two
annotations the phase carries one after the other in a capture, and the
disarmed path that reads no clock."""
import threading
import time

import pytest

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler
from kubetpu.utils import heap as uheap
from kubetpu.utils import trace as utrace

LANE = "binder-lane"
BATCH = 32
# two clocks read a few microseconds apart, each rounded to one
SLACK_S = 2e-4
# how long the patched heap boundary sleeps: the lane's job, handed over
# as commit ended, gets the interpreter INSIDE the teardown
NAP_S = 0.03


@pytest.fixture
def flight():
    """The recorder armed as the benchmark arms it."""
    utrace.disarm_flight_recorder()
    left = getattr(utrace._tls, "phase", None)
    if left is not None:        # an earlier test's last cycle, on this thread
        left.close()
    fr = utrace.arm_flight_recorder(capacity=64, max_spans_per_cycle=64)
    try:
        yield fr
    finally:
        utrace.disarm_flight_recorder()


def _world(nodes=48, pods=5 * BATCH, **kw):
    store = ClusterStore()
    for n in hollow.make_nodes(nodes):
        store.add(n)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=BATCH, mode="gang",
        prewarm=False), **kw)
    made = hollow.make_pods(pods)
    for p in made:
        store.add(p)
    return store, sched, made


def _serve(store, sched, pods, timeout=300.0):
    """Through ``Scheduler.run()``: the serving loop drops the outcomes."""
    sched.run()
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if all(store.get_pod("default", p.metadata.name).spec.node_name
               for p in pods):
            break
        time.sleep(0.02)
    else:
        raise AssertionError("toy run: pods left unbound")
    sched.wait_for_inflight_binds()


def _drain(sched):
    """The caller keeps the outcomes, as tests and tools do."""
    outs = []
    while True:
        got = sched.schedule_pending(timeout=0.0)
        if not got:
            return outs
        outs.extend(got)


def _named(cycle, name):
    return [s for s in cycle["spans"] if s["name"] == name]


@pytest.fixture
def served(flight, monkeypatch):
    """Five cycles of 32 pods, one behind the other, through the serving
    loop; the heap boundary naps so that the lane's job runs inside the
    teardown."""
    real = uheap.HeapPolicy.boundary

    def boundary(self, cycle_count):
        if cycle_count != self._cycle_seen:
            time.sleep(NAP_S)
        return real(self, cycle_count)
    monkeypatch.setattr(uheap.HeapPolicy, "boundary", boundary)
    store, sched, pods = _world()
    try:
        _serve(store, sched, pods)
        recs = [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()
    assert len(recs) == 5 and not sched.recovery_log
    return recs


def test_every_cycle_after_the_first_has_one_teardown_under_its_pop(served):
    first, rest = served[0], served[1:]
    assert not _named(first, "teardown")
    assert "teardown_s" not in _named(first, "pop")[0]["args"]
    for c in rest:
        (pop,) = _named(c, "pop")
        (td,) = _named(c, "teardown")
        assert td["parent"] == pop["id"] and td["thread"] == pop["thread"]
        # ONE extent under two names: the span's and the pop's old arg
        assert td["t0"] == pop["t0"]
        assert (td["t1"] - td["t0"]) == pytest.approx(
            pop["args"]["teardown_s"], abs=2e-6)
        assert td["t1"] <= pop["t1"] + 1e-6
        a = td["args"]
        assert 0.0 <= a["cpu_s"] <= td["t1"] - td["t0"] + SLACK_S
        assert a["cpu_s"] <= pop["args"]["cpu_s"] + SLACK_S
        assert 0.0 <= a["read_s"] < 0.05
        # the collector's pauses, as every phase carries them
        assert a.get("gc_s", 0.0) <= pop["args"].get("gc_s", 0.0) + 1e-6


def test_the_two_children_lie_inside_the_teardown_and_do_not_overlap(served):
    for c in served[1:]:
        (td,) = _named(c, "teardown")
        (rel,) = _named(c, "teardown-release")
        (hb,) = _named(c, "heap-boundary")
        assert rel["parent"] == hb["parent"] == td["id"]
        assert rel["t0"] == td["t0"]
        assert rel["t0"] <= rel["t1"] <= hb["t0"] + 1e-6
        assert hb["t1"] <= td["t1"] + 1e-6
        for s in (rel, hb):
            assert 0.0 <= s["args"]["cpu_s"] <= s["t1"] - s["t0"] + SLACK_S
        assert rel["args"]["outcomes"] == BATCH
        # a cycle ran: the boundary handed off (no sweep is due so soon)
        assert (hb["args"]["handoff"], hb["args"]["sweep"]) == (1, 0)
        assert hb["t1"] - hb["t0"] >= NAP_S - 1e-3
        assert hb["args"].get("gc_s", 0.0) <= hb["t1"] - hb["t0"]
        # the children's CPU is the teardown's, but for the loop between
        assert (rel["args"]["cpu_s"] + hb["args"]["cpu_s"]
                <= td["args"]["cpu_s"] + SLACK_S)
    # what heap_handoffs on the cycle's meta says stays as it is
    assert [c["meta"]["heap_handoffs"] for c in served[1:]] == [1] * 4


def test_the_teardown_names_who_ran_inside_it(served):
    lane_ran = 0
    for c in served[1:]:
        (td,) = _named(c, "teardown")
        cpu = td["args"]["thread_cpu_s"]
        assert all(isinstance(k, str) and v > 1e-4 for k, v in cpu.items())
        # the serving thread's own entry is the clock ``cpu_s`` reads,
        # from Trace.finish()'s reading on (a few stamps earlier)
        mine = cpu.get(td["thread"], 0.0)
        assert mine <= td["args"]["cpu_s"] + 2e-3
        # nobody ran for longer than the teardown lasted
        assert all(v <= td["t1"] - td["t0"] + 2e-3 for v in cpu.values())
        lane_ran += LANE in cpu
    # the lane's job of the cycle before ran while the boundary napped
    assert lane_ran >= 3
    # a whole period's reading keeps its meaning beside it
    for c in served[1:]:
        (td,) = _named(c, "teardown")
        meta = c["meta"]["thread_cpu_s"]
        for k, v in td["args"]["thread_cpu_s"].items():
            # the teardown after cycle k is inside cycle k+1's period
            assert v <= meta.get(k, 0.0) + 2e-3, (k, v, meta)


def test_the_rest_of_the_pop_is_in_its_two_parts(served):
    for c in served:
        (pop,) = _named(c, "pop")
        a = pop["args"]
        assert a["queue_s"] >= a["wait_s"] >= 0.0
        assert a["group_s"] >= 0.0
        rest = pop["t1"] - pop["t0"] - a.get("teardown_s", 0.0)
        assert a["queue_s"] + a["group_s"] <= rest + SLACK_S
    # behind one another the queue is never empty: the parts are the pop
    for c in served[1:]:
        (pop,) = _named(c, "pop")
        assert pop["args"]["wait_s"] == 0.0


def test_a_caller_that_keeps_the_outcomes_gets_no_release(flight):
    """``schedule_pending()`` driven by hand: nothing drops the outcomes
    where the loop would, and a Scheduler never ``run()`` has no heap
    policy -- the teardown has no children, and says who ran all the
    same."""
    store, sched, _pods = _world(pods=3 * BATCH)
    try:
        outs = _drain(sched)
        sched.wait_for_inflight_binds()
        recs = [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()
    assert len(outs) == 3 * BATCH and len(recs) == 3
    for c in recs[1:]:
        (td,) = _named(c, "teardown")
        assert "cpu_s" in td["args"] and "thread_cpu_s" in td["args"]
        assert not _named(c, "teardown-release")
        assert not _named(c, "heap-boundary")
        assert not [s for s in c["spans"] if s["parent"] == td["id"]]
        assert "queue_s" in _named(c, "pop")[0]["args"]


def test_an_empty_pop_closes_its_teardown_unrecorded(flight):
    """The queue came back empty: no cycle follows, so the pop and the
    teardown it opened with go unrecorded, as ``teardown_s`` always did,
    and the next pop starts afresh."""
    store, sched, _pods = _world(pods=BATCH)
    try:
        assert len(_drain(sched)) == BATCH      # ends on an empty pop
        assert getattr(utrace._tls, "phase", None) is None
        for p in hollow.make_pods(2 * BATCH)[BATCH:]:
            store.add(p)
        assert len(_drain(sched)) == BATCH
        sched.wait_for_inflight_binds()
        recs = [c.to_dict() for c in flight.cycles()]
    finally:
        sched.close()
    assert len(recs) == 2
    assert not _named(recs[1], "teardown")
    assert "teardown_s" not in _named(recs[1], "pop")[0]["args"]


def test_the_marks_say_nothing_with_no_teardown_open(flight):
    assert utrace.teardown_mark() is None
    utrace.teardown_released(3)                 # nothing to mark: no-op
    pop = utrace.begin_pop()                    # a thread's first pop
    try:
        assert pop.td is None
        assert utrace.teardown_mark() is None
        utrace.teardown_released(3)
    finally:
        pop.close()
    # a teardown that has ended takes no more children
    tr = utrace.Trace(utrace.CYCLE_TRACE, pods=1)
    tr.phase("commit")
    tr.finish()
    since = utrace.teardown_mark()
    assert since is not None
    utrace.teardown_child("heap-boundary", since, handoff=0, sweep=0)
    pop = utrace.begin_pop()
    try:
        assert pop.td.args is not None and len(pop.td.kids) == 1
        assert utrace.teardown_mark() is None
        utrace.teardown_released(3)
        assert len(pop.td.kids) == 1
    finally:
        pop.close()


def test_the_heap_boundary_says_what_it_did():
    assert (uheap.NOTHING, uheap.HANDED_OFF, uheap.SWEPT) == (0, 1, 2)
    pol = uheap.HeapPolicy()
    assert pol.boundary(1) == uheap.NOTHING     # never started
    pol.start()
    try:
        assert pol.boundary(2) == uheap.HANDED_OFF
        assert pol.boundary(2) == uheap.NOTHING     # idle, nothing due
        pol.want_sweep()
        pol._last_full -= uheap.SWEEP_GAP_S + 1.0
        assert pol.boundary(3) == uheap.SWEPT
        assert (pol.handoffs, pol.sweeps) == (2, 1)
    finally:
        pol.stop()


# ------------------------------------------------------------ annotations


class _StampedAnnotation:
    """Stands in for jax.profiler.TraceAnnotation: logs enter and exit
    with the clock."""
    log = []

    def __init__(self, name, **kw):
        self.name = name

    def __enter__(self):
        _StampedAnnotation.log.append(
            ("enter", self.name, time.perf_counter(),
             threading.current_thread().name))
        return self

    def __exit__(self, *exc):
        _StampedAnnotation.log.append(
            ("exit", self.name, time.perf_counter(),
             threading.current_thread().name))
        return False


@pytest.mark.parametrize("armed", [False, True])
def test_in_a_capture_teardown_and_pop_alternate_and_leave_no_hole(
        monkeypatch, armed):
    """The ``pop`` phase's ANNOTATION splits in two where its span does
    not: ``Scheduling:teardown`` from Trace.finish() to begin_pop()'s
    pick-up, ``Scheduling:pop`` from there.  Never nested, nothing
    between them, and nothing between a cycle's commit and the next
    cycle's snapshot under neither."""
    import jax
    _StampedAnnotation.log = []
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _StampedAnnotation)
    monkeypatch.setattr(utrace, "_PROFILE_ACTIVE", True)
    utrace.disarm_flight_recorder()
    if armed:
        utrace.arm_flight_recorder(capacity=8, max_spans_per_cycle=64)
    store, sched, pods = _world(pods=4 * BATCH)
    try:
        _serve(store, sched, pods)
    finally:
        sched.close()
        utrace.disarm_flight_recorder()
    log = [(w, n.split(":", 1)[1], t) for w, n, t, _ in
           _StampedAnnotation.log if n.startswith("Scheduling:")]
    # never nested: every enter is shut before the next
    for (w0, n0, _), (w1, n1, _) in zip(log[::2], log[1::2]):
        assert (w0, w1) == ("enter", "exit") and n0 == n1, (n0, n1)
    order = [n for w, n, _ in log if w == "enter"]
    stamps = {i: (t_in, t_out) for i, ((_, _, t_in), (_, _, t_out))
              in enumerate(zip(log[::2], log[1::2]))}
    commits = [i for i, n in enumerate(order) if n == "commit"]
    assert len(commits) == 4
    for i in commits[:-1]:
        # commit, then the teardown, then the rest of the pop, then the
        # next cycle: alternating, the one picking up where the other ends
        assert order[i + 1:i + 4] == ["teardown", "pop", "snapshot"]
        # (a bound another thread's turn at the interpreter stays under)
        assert stamps[i + 1][0] - stamps[i][1] < 0.05   # commit -> teardown
        assert stamps[i + 2][0] - stamps[i + 1][1] < 0.05   # teardown -> pop
    assert order.count("teardown") == 4     # the last one met an empty pop
    # every pop but a thread's first (and one after an empty pop, which
    # closed its phase) follows a teardown
    for i, n in enumerate(order):
        if n == "teardown":
            assert order[i + 1] == "pop"
    # the lane opens no annotation of its own (``Binding:bind-job`` went
    # with this PR)
    assert {n for _, n, _, _ in _StampedAnnotation.log
            if not n.startswith("Scheduling:")} <= {utrace.CLOCK_ANNOTATION}


# --------------------------------------------------------------- disarmed


def test_disarmed_the_teardown_reads_no_clock_and_allocates_nothing(
        monkeypatch):
    """Recorder disarmed, no capture, through the serving loop: no
    teardown object, no thread-clock reading, no child, no stamp around
    the queue or the grouping."""
    utrace.disarm_flight_recorder()
    errors = []

    def boom(*a, **kw):
        errors.append(AssertionError("the disarmed path touched the tracer"))
        raise errors[-1]

    for name in ("_Teardown", "_end_teardown", "_read_thread_cpu",
                 "_child", "_open_phase"):
        monkeypatch.setattr(utrace, name, boom)
    monkeypatch.setattr(time, "thread_time", boom)
    store, sched, pods = _world(pods=3 * BATCH)
    in_pop = threading.local()
    reads = []                      # the clock's calls a pop, one entry a pop
    real_clock = utrace.wallclock
    real_pop = Scheduler._pop_grouped

    def pop_grouped(self, *a, **kw):
        in_pop.count = 0
        try:
            return real_pop(self, *a, **kw)
        finally:
            reads.append(in_pop.count)
            in_pop.count = None

    def clock():
        if getattr(in_pop, "count", None) is not None:
            in_pop.count += 1
        return real_clock()
    monkeypatch.setattr(Scheduler, "_pop_grouped", pop_grouped)
    monkeypatch.setattr(utrace, "wallclock", clock)
    try:
        assert utrace.begin_pop() is None
        assert utrace.teardown_mark() is None
        utrace.teardown_released(BATCH)
        _serve(store, sched, pods)
    finally:
        sched.close()
    assert not errors
    # the pop's stamps are the recorder's: disarmed it reads no clock
    assert len(reads) >= 3 and set(reads) == {0}
    assert sched.queue.pop_wait_s == 0.0

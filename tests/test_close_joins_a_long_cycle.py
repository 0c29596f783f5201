"""close() gives a cycle in flight the time the serving loop's cycles have
been taking (PR 33): a deployment whose auction runs for seconds on the
device (upstream's TopologySpreading row: ~370 rounds, 3.6 s a cycle) used
to outlive close()'s fixed 2 s join, and its 1,024 binds then landed after
close(), wait_for_inflight_binds() and the caller's own shutdown had all
returned: the benchmark read them back as missing (`correct` false, one
run in 17)."""

import threading
import time

from kubetpu.apis.config import (KubeSchedulerConfiguration,
                                 KubeSchedulerProfile)
from kubetpu.client.store import ClusterStore
from kubetpu.harness import hollow
from kubetpu.scheduler import Scheduler


def _scheduler(monkeypatch, pass_s, floor_s):
    """A scheduler whose every pass of the serving loop takes ``pass_s``
    (the auction, on the device) and then notes that it ran to its end,
    where a cycle hands its binds over; close()'s floor at ``floor_s``."""
    store = ClusterStore()
    for node in hollow.make_nodes(4):
        store.add(node)
    sched = Scheduler(store, config=KubeSchedulerConfiguration(
        profiles=[KubeSchedulerProfile()], batch_size=4, mode="gang",
        prewarm=False), async_binding=True)
    monkeypatch.setattr(Scheduler, "CLOSE_JOIN_FLOOR_S", floor_s)
    started, ended = threading.Event(), []

    def slow_pass(timeout=0.2):
        started.set()
        time.sleep(pass_s)
        ended.append(time.monotonic())
        return []
    monkeypatch.setattr(sched, "schedule_pending", slow_pass)
    return sched, started, ended


def test_close_waits_for_a_cycle_as_long_as_the_loops_cycles_have_been(
        monkeypatch):
    sched, started, ended = _scheduler(monkeypatch, pass_s=0.5, floor_s=0.1)
    try:
        sched.run()
        time.sleep(0.7)             # one whole pass seen: 0.5 s
        assert sched._longest_pass_s >= 0.5
        started.clear()
        assert started.wait(2.0)    # a pass has just begun
        n = len(ended)
        t0 = time.monotonic()
        sched.close()               # floor 0.1 s, bound 2 x 0.5 s
        took = time.monotonic() - t0
        # the pass in flight ran to its end BEFORE close() returned, and
        # the loop is gone
        assert len(ended) == n + 1 and 0.3 <= took < 2.0
        assert not any(t.name == "kubetpu-scheduler"
                       for t in threading.enumerate())
    finally:
        sched.close()


def test_a_loop_that_outlives_the_bound_is_still_left_behind(monkeypatch):
    """No history of long passes: the bound is the floor, as it was, and
    close() returns without the loop (which then ends on its own)."""
    sched, started, ended = _scheduler(monkeypatch, pass_s=1.0, floor_s=0.1)
    try:
        sched.run()
        assert started.wait(2.0)
        t0 = time.monotonic()
        sched.close()
        assert time.monotonic() - t0 < 0.8 and not ended
        assert sched._longest_pass_s == 0.0
    finally:
        sched.close()
        time.sleep(1.2)             # let the abandoned pass end


def test_the_bound_is_capped():
    assert Scheduler.CLOSE_JOIN_FLOOR_S == 2.0
    assert Scheduler.CLOSE_JOIN_CAP_S == 30.0
